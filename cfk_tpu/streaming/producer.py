"""Producer side of the streaming-update pipeline: ratings → durable log.

Rating upserts append to the ``rating-updates`` topic as ``RatingUpdate``
frames (``cfk_tpu.transport.serdes``), keyed by user id under the same
mod-N ``PureModPartitioner`` rule as ingest — so a user's updates always
land on ONE partition and per-user ordering is the partition's offset
order.  On a durable transport (``FileBroker``, a TCP broker) the topic IS
the system of record: the consumer's crash recovery replays it from the
committed cursor, and a full retrain can always be rebuilt from base data
plus the whole log.

``seq`` numbers order the EVENTS, not the appends: they make re-rates (two
updates to the same (user, movie) cell), late deliveries and retried
appends idempotent on the consumer — the highest seq of a cell wins,
whatever order the records arrived in, equal-seq drops.  A caller that
knows its events' own sequence numbers hands them over (``send(seq=)``,
``send_many(seqs=)``: strictly increasing per logical update, in any order
per append — a mobile client's rating delivered after a newer one of the
same cell loses to it); a caller that does not gets them assigned in append
order, strictly increasing, as before.  On construction against an
existing topic the producer resumes past the highest seq anywhere in the
log (one pass over it: the last record need not carry the highest; a
single logical producer at a time is assumed, like the reference's one
``NetflixDataFormatProducer``).
"""

from __future__ import annotations

import numpy as np

from cfk_tpu.telemetry import span
from cfk_tpu.transport.broker import Transport, mod_partition
from cfk_tpu.transport.serdes import RatingUpdate, encode_rating_update

UPDATES_TOPIC = "rating-updates"


def ensure_updates_topic(
    transport: Transport, topic: str = UPDATES_TOPIC, num_partitions: int = 1
) -> int:
    """Create the updates topic if absent; returns its partition count.

    An existing topic keeps its own partition count (the cursor layout
    committed with the factors depends on it, so re-partitioning a live
    topic is refused the same way the reference's ``setup.sh`` re-provisions
    out-of-band)."""
    try:
        return transport.num_partitions(topic)
    except KeyError:
        transport.create_topic(topic, num_partitions)
        return num_partitions


class StreamProducer:
    """Append rating upserts to the updates topic, each with its event's
    seq: the caller's, or the next of a strictly increasing run."""

    def __init__(
        self,
        transport: Transport,
        *,
        topic: str = UPDATES_TOPIC,
        num_partitions: int = 1,
    ) -> None:
        self.transport = transport
        self.topic = topic
        self.num_partitions = ensure_updates_topic(
            transport, topic, num_partitions
        )
        self._next_seq = self._resume_seq()

    def _resume_seq(self) -> int:
        """Highest seq in the log + 1 (0 on a fresh topic).

        One pass over every partition: records carry their events' seqs and
        may have been appended out of event order (``send_many(seqs=)``), so
        no record's place says how its seq ranks.
        """
        from cfk_tpu.transport.serdes import decode_rating_update

        high = -1
        for p in range(self.num_partitions):
            if self.transport.end_offset(self.topic, p) == 0:
                continue
            for rec in self.transport.consume(self.topic, p, start_offset=0):
                high = max(high, decode_rating_update(rec.value).seq)
        return high + 1

    @property
    def next_seq(self) -> int:
        return self._next_seq

    def send(self, user: int, movie: int, rating: float, *,
             seq: int | None = None) -> int:
        """Append one upsert; returns its seq: ``seq``, the event's own
        (non-negative; it need not exceed what was sent before), else the
        next of the producer's run."""
        if user < 0 or movie < 0:
            raise ValueError(
                f"user/movie ids must be non-negative raw ids, got "
                f"({user}, {movie})"
            )
        if seq is None:
            seq = self._next_seq
        elif seq < 0:
            raise ValueError(f"an event's seq is non-negative, got {seq}")
        self._next_seq = max(self._next_seq, int(seq) + 1)
        self.transport.produce(
            self.topic,
            key=int(user) % (1 << 31),  # partition key must fit int32
            value=encode_rating_update(
                RatingUpdate(seq=int(seq), user=int(user), movie=int(movie),
                             rating=float(rating))
            ),
            partition=mod_partition(int(user), self.num_partitions),
        )
        return int(seq)

    def send_many(self, users, movies, ratings, *, seqs=None) -> int:
        """Bulk append of parallel (user, movie, rating) arrays, in array
        order (the order of ARRIVAL: a partition's offsets follow it).

        ``seqs``: the events' own sequence numbers, parallel to the arrays
        (non-negative, strictly increasing per logical update, in any order
        down the arrays: the stream's logical time is theirs, and a record
        appended after a newer one of its cell loses to it on the
        consumer).  Without them the run is numbered contiguously in array
        order from ``next_seq`` — the array order is then the stream's
        logical time.  Returns the first record's seq.  Uses
        the transport's bulk frame path per partition when available
        (``FileBroker.produce_frames``), so synthetic bench streams of 100k
        updates don't pay a Python loop of fsync'd appends: one append and
        one fsync a partition a call.  When this returns the run is in the
        log (on a ``FileBroker(fsync=True)``: on disk).  One
        ``stream/log/append`` span a call: ``records``, ``bytes``, and
        ``fsync_ms`` where the transport says how long its sync took.
        """
        users = np.asarray(users, np.int64)
        movies = np.asarray(movies, np.int64)
        ratings = np.asarray(ratings, np.float32)
        n = users.shape[0]
        if movies.shape != (n,) or ratings.shape != (n,):
            raise ValueError(
                f"parallel arrays required, got {users.shape}/"
                f"{movies.shape}/{ratings.shape}"
            )
        if n == 0:
            return self._next_seq
        if users.min() < 0 or movies.min() < 0:
            raise ValueError("user/movie ids must be non-negative raw ids")
        if seqs is None:
            seqs = self._next_seq + np.arange(n, dtype=np.int64)
        else:
            seqs = np.asarray(seqs, np.int64)
            if seqs.shape != (n,) or seqs.min() < 0:
                raise ValueError(
                    f"seqs: {n} non-negative event sequence numbers "
                    f"required, got {seqs.shape}, least {seqs.min()}")
        self._next_seq = max(self._next_seq, int(seqs.max()) + 1)
        with span("stream/log/append", records=n, bytes=28 * n) as sp:
            self._append(users, movies, ratings, seqs)
            fsync_ms = getattr(self.transport, "last_fsync_ms", None)
            if fsync_ms is not None:
                sp.set(fsync_ms=fsync_ms)
        return int(seqs[0])

    def _append(self, users, movies, ratings, seqs) -> None:
        parts = (users % self.num_partitions).astype(np.int64)
        fast = getattr(self.transport, "produce_frames", None)
        for p in range(self.num_partitions):
            sel = np.nonzero(parts == p)[0]  # stable: the order of arrival
            if sel.size == 0:
                continue
            if fast is not None:
                frames = np.zeros((sel.size, 28), np.uint8)
                frames[:, 0:8] = seqs[sel].astype(">i8").view(np.uint8).reshape(-1, 8)
                frames[:, 8:16] = users[sel].astype(">i8").view(np.uint8).reshape(-1, 8)
                frames[:, 16:24] = movies[sel].astype(">i8").view(np.uint8).reshape(-1, 8)
                frames[:, 24:28] = ratings[sel].astype(">f4").view(np.uint8).reshape(-1, 4)
                fast(self.topic, users[sel] % (1 << 31), frames, p)
            else:
                for i in sel.tolist():
                    self.transport.produce(
                        self.topic,
                        key=int(users[i]) % (1 << 31),
                        value=encode_rating_update(RatingUpdate(
                            seq=int(seqs[i]), user=int(users[i]),
                            movie=int(movies[i]), rating=float(ratings[i]),
                        )),
                        partition=p,
                    )
