"""``loadgen_stream.run_open_loop`` for a stream whose events carry their
own sequence numbers: the control's loop to the letter, with each sending's
``seqs`` handed to the producer (``StreamProducer.send_many(seqs=)``).

The arrays are in ARRIVAL order, the order they are sent in; a late event
sits further down them than its ``seq`` says.
"""

from __future__ import annotations

from benchmarks.harness import loadgen_stream


class _EventSeqs:
    """The producer as the control's loop calls it (consecutive slices of
    the arrays), giving each slice the ``seqs`` of its place."""

    def __init__(self, producer, seqs) -> None:
        self.producer, self.seqs, self.at = producer, seqs, 0

    def send_many(self, users, items, values):
        lo, self.at = self.at, self.at + len(users)
        return self.producer.send_many(users, items, values,
                                       seqs=self.seqs[lo:self.at])


def run_open_loop(client, server, session, producer, *, ratings, **kw):
    """``ratings`` = (user raw ids, item rows, values, event seqs), sent in
    array order; the rest as ``loadgen_stream.run_open_loop``."""
    r_users, r_items, r_values, r_seqs = ratings
    return loadgen_stream.run_open_loop(
        client, server, session, _EventSeqs(producer, r_seqs),
        ratings=(r_users, r_items, r_values), **kw)
