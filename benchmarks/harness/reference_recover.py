"""Plain reference of a stream whose task was killed and replaced: what
"every rating applied exactly once" and "read committed" mean, from the
ratings the generator sent and the commits the listener saw, in numpy,
importing nothing of the program.

A commit, as the listener saw it, is ``(ordinal, cursor, when, touched rows,
solved rows, cells)`` with ``cells`` the (user row, item row) pairs it
applied; a unit of the reopened store is ``(cursor, touched rows, solved
rows, cells)`` read from the store's own files by the runner.

(i)   ``ordinal_rewritten``: the first commit seen under an ordinal is that
      ordinal; any later event under the same ordinal, equal or not, counts.
(ii)  ``uncommitted_reads``: ordinals the engine was given (answers name
      only those) with no equal unit in the reopened store: cursor, touched
      rows, solved rows bit for bit, cells.
(iii) ``duplicate_cells``: the stream sends each (user, item) once, so the
      cells of all first commits hold each pair once; ``rating_commits``
      maps every rating sent to the ordinal of its commit, for
      ``reference_foldin.list_as_of`` and the row and score checks.
(iv)  ``in_outage``: a rating belongs to the outage if it was sent between
      ``t_kill - visible_within_s`` and the moment the successor had caught
      up; a stale read, or a late rating, of such a rating is a failed
      operation, any other stale read makes the run incorrect.
"""

from __future__ import annotations

import numpy as np


def first_commits(commits):
    """({ordinal: its first commit}, how many events came under an ordinal
    already seen): (i)."""
    first: dict = {}
    rewritten = 0
    for c in commits:
        if c[0] in first:
            rewritten += 1
        else:
            first[c[0]] = c
    return first, rewritten


def uncommitted_reads(first: dict, units: dict) -> int:
    """(ii): first commits with no equal unit in the reopened store."""
    wrong = 0
    for ordinal, (_, cursor, _, touched, rows, cells) in first.items():
        unit = units.get(ordinal)
        if unit is None:
            wrong += 1
            continue
        u_cursor, u_touched, u_rows, u_cells = unit
        same = (int(u_cursor) == int(cursor)
                and np.array_equal(np.asarray(u_touched, np.int64),
                                   np.asarray(touched, np.int64))
                and np.asarray(u_rows).shape == np.asarray(rows).shape
                and np.array_equal(np.asarray(u_rows, np.float32).view(np.uint32),
                                   np.asarray(rows, np.float32).view(np.uint32))
                and np.array_equal(np.asarray(u_cells, np.int64).reshape(-1, 2),
                                   np.asarray(cells, np.int64).reshape(-1, 2)))
        wrong += not same
    return wrong


def duplicate_cells(first: dict) -> tuple[int, int]:
    """(iii): (cells published, how many of them a (user, item) published
    before)."""
    cells = [np.asarray(c[5], np.int64).reshape(-1, 2) for c in first.values()]
    if not cells:
        return 0, 0
    cells = np.concatenate(cells)
    distinct = np.unique(cells, axis=0).shape[0] if cells.shape[0] else 0
    return int(cells.shape[0]), int(cells.shape[0] - distinct)


def rating_commits(first: dict, offset0: int, sent: int):
    """Per rating sent (the log's offsets ``offset0`` on): (the ordinal of
    the first commit whose cursor passed it, -1 where none did; when that
    commit was published, inf where none)."""
    order = sorted(first)
    cursors = np.asarray([first[o][1] for o in order], np.int64)
    # cursors rise with the ordinal: exactly once, in the log's order
    at = np.searchsorted(cursors, offset0 + np.arange(sent), side="right")
    done = at < len(order)
    ordinals = np.where(done, np.asarray(order + [0], np.int64)[
        np.minimum(at, len(order))], -1)
    when = np.asarray([first[o][2] for o in order] + [np.inf])[
        np.minimum(at, len(order))]
    return ordinals, when


def in_outage(rating_sent_s, outages, within_s: float):
    """(iv): per rating, whether it was sent inside an outage ``(t_kill,
    t_caught_up)`` widened by ``within_s`` before the kill."""
    sent = np.asarray(rating_sent_s, np.float64)
    inside = np.zeros(sent.shape[0], bool)
    for t_kill, t_up in outages:
        inside |= (sent >= t_kill - within_s) & (sent <= t_up)
    return inside


def stale_reads(requests, streamed: dict, rating_sent_s, ordinals, inside,
                within_s: float):
    """``requests``: (user, when sent, the ordinal its answer names) each.
    Returns (stale reads outside every outage, requests that read stale
    inside one): a read is stale if a rating of its user sent more than
    ``within_s`` before it was committed after the ordinal named, or never."""
    outside = failed = 0
    for user, t_req, ordinal in requests:
        hit = False
        for j in streamed.get(user, ()):
            if (rating_sent_s[j] + within_s < t_req
                    and not 0 <= ordinals[j] <= ordinal):
                if inside[j]:
                    hit = True
                else:
                    outside += 1
        failed += hit
    return outside, failed
