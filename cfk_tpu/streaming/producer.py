"""Producer side of the streaming-update pipeline: ratings → durable log.

Rating upserts append to the ``rating-updates`` topic as ``RatingUpdate``
frames (``cfk_tpu.transport.serdes``), keyed by user id under the same
mod-N ``PureModPartitioner`` rule as ingest — so a user's updates always
land on ONE partition and per-user ordering is the partition's offset
order.  On a durable transport (``FileBroker``, a TCP broker) the topic IS
the system of record: the consumer's crash recovery replays it from the
committed cursor, and a full retrain can always be rebuilt from base data
plus the whole log.

``seq`` numbers are producer-assigned and strictly increasing; they make
re-rates (two updates to the same (user, movie) cell) and retried appends
idempotent on the consumer — last-seq-wins, equal-seq drops.  On
construction against an existing topic the producer resumes past the
highest seq already in the log (one tail frame per partition; a single
logical producer at a time is assumed, like the reference's one
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
    """Append rating upserts to the updates topic with monotone seq numbers."""

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

        One frame read per partition: a single producer appends seqs in
        order, so each partition's LAST record carries its partition max.
        """
        from cfk_tpu.transport.serdes import decode_rating_update

        high = -1
        for p in range(self.num_partitions):
            end = self.transport.end_offset(self.topic, p)
            if end == 0:
                continue
            for rec in self.transport.consume(self.topic, p, start_offset=end - 1):
                high = max(high, decode_rating_update(rec.value).seq)
        return high + 1

    @property
    def next_seq(self) -> int:
        return self._next_seq

    def send(self, user: int, movie: int, rating: float) -> int:
        """Append one upsert; returns the seq it was assigned."""
        if user < 0 or movie < 0:
            raise ValueError(
                f"user/movie ids must be non-negative raw ids, got "
                f"({user}, {movie})"
            )
        seq = self._next_seq
        self._next_seq += 1
        self.transport.produce(
            self.topic,
            key=int(user) % (1 << 31),  # partition key must fit int32
            value=encode_rating_update(
                RatingUpdate(seq=seq, user=int(user), movie=int(movie),
                             rating=float(rating))
            ),
            partition=mod_partition(int(user), self.num_partitions),
        )
        return seq

    def send_many(self, users, movies, ratings) -> int:
        """Bulk append of parallel (user, movie, rating) arrays.

        Returns the first seq of the run (they are assigned contiguously in
        array order — the array order IS the stream's logical time).  Uses
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
        first = self._next_seq
        seqs = first + np.arange(n, dtype=np.int64)
        self._next_seq = first + n
        with span("stream/log/append", records=n, bytes=28 * n) as sp:
            self._append(users, movies, ratings, seqs)
            fsync_ms = getattr(self.transport, "last_fsync_ms", None)
            if fsync_ms is not None:
                sp.set(fsync_ms=fsync_ms)
        return first

    def _append(self, users, movies, ratings, seqs) -> None:
        parts = (users % self.num_partitions).astype(np.int64)
        fast = getattr(self.transport, "produce_frames", None)
        for p in range(self.num_partitions):
            sel = np.nonzero(parts == p)[0]  # stable: preserves seq order
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
