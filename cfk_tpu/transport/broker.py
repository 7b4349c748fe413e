"""Pluggable transport: the durable-log boundary of the framework.

In the reference, Kafka is the entire communication fabric (SURVEY.md §2.6).
Here the compute-path exchange is XLA collectives; the transport survives as
the *ingest + checkpoint* boundary — a partitioned, offset-addressed record
log.  ``InMemoryBroker`` is the test double (the role the reference's authors
used ``MockProcessorContext`` for, ``apps/ALSApp.java:57``); a real Kafka
client can implement the same protocol for drop-in durable ingest, using the
wire formats in ``cfk_tpu.transport.serdes``.

Partitioning is deterministic mod-N on the integer key — the reference's
``PureModPartitioner`` contract (``producers/PureModPartitioner.java:17``):
no hashing, so a record's partition is reproducible from its key alone.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterator, Protocol


@dataclasses.dataclass(frozen=True)
class Record:
    key: int
    value: bytes
    offset: int
    # when the log took the record, on ``time.perf_counter``, where the
    # transport says (the in-memory log does); 0.0 = not known.  Not part
    # of a record's identity: the same record from two logs is equal.
    appended: float = dataclasses.field(default=0.0, compare=False)


class Transport(Protocol):
    """Minimal partitioned-log protocol used by ingest and checkpointing."""

    def create_topic(self, name: str, num_partitions: int) -> None: ...

    def produce(self, topic: str, key: int, value: bytes,
                partition: int | None = None) -> None: ...

    def consume(self, topic: str, partition: int,
                start_offset: int = 0) -> Iterator[Record]: ...

    def num_partitions(self, topic: str) -> int: ...

    def end_offset(self, topic: str, partition: int) -> int: ...


def mod_partition(key: int, num_partitions: int) -> int:
    """Deterministic mod-N partitioning (PureModPartitioner semantics).

    Keys must be non-negative entity ids (Python and Java ``%`` diverge on
    negatives, so negative keys would partition differently across Transport
    implementations).  Control records like EOF (key −1) must be produced
    with an explicit ``partition=`` instead — which is also how the reference
    routes them (``producers/NetflixDataFormatProducer.java:64-74``).
    """
    if key < 0:
        raise ValueError(
            f"mod_partition requires a non-negative key, got {key}; produce "
            "control records with an explicit partition="
        )
    return key % num_partitions


class InMemoryBroker:
    """In-process Transport: dict of topic → list of append-only partitions."""

    def __init__(self) -> None:
        self._topics: dict[str, list[list[Record]]] = {}

    def create_topic(self, name: str, num_partitions: int) -> None:
        if num_partitions < 1:
            raise ValueError(f"num_partitions must be >= 1, got {num_partitions}")
        if name in self._topics:
            raise ValueError(f"topic {name!r} already exists")
        self._topics[name] = [[] for _ in range(num_partitions)]

    def delete_topic(self, name: str) -> None:
        self._topics.pop(name, None)

    def _partitions(self, topic: str) -> list[list[Record]]:
        try:
            return self._topics[topic]
        except KeyError:
            raise KeyError(
                f"unknown topic {topic!r}; create_topic first (the reference "
                "had the same split: setup.sh provisions topics before the app runs)"
            ) from None

    def produce(
        self, topic: str, key: int, value: bytes, partition: int | None = None
    ) -> None:
        parts = self._partitions(topic)
        if partition is None:
            partition = mod_partition(key, len(parts))
        if not 0 <= partition < len(parts):
            raise IndexError(f"partition {partition} out of range for {topic!r}")
        log = parts[partition]
        log.append(Record(key=key, value=value, offset=len(log),
                          appended=time.perf_counter()))

    def consume(
        self, topic: str, partition: int, start_offset: int = 0
    ) -> Iterator[Record]:
        parts = self._partitions(topic)
        yield from parts[partition][start_offset:]

    def num_partitions(self, topic: str) -> int:
        return len(self._partitions(topic))

    def end_offset(self, topic: str, partition: int) -> int:
        return len(self._partitions(topic)[partition])
