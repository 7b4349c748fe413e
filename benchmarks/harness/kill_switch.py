"""The kill of the stream task, delivered by the benchmark: a wrapper around
the ratings log whose ``consume`` raises once, at the moment the load
generator arms it.  The stream session dies inside its poll, with whatever it
had in flight; everything after that is the program's supervisor.  Every
other call goes to the log untouched."""

from __future__ import annotations

import time


class TaskKilled(RuntimeError):
    """What ``kill -9`` of the stream task looks like from inside the
    process that outlives it."""


class KillSwitch:
    def __init__(self, log, *, clock=time.perf_counter) -> None:
        self._log, self._clock = log, clock
        self.armed = False
        self.fired_at: list = []  # the clock at each kill delivered

    def __getattr__(self, name):
        return getattr(self._log, name)

    def arm(self) -> None:
        self.armed = True

    def consume(self, topic, partition, start_offset=0):
        if self.armed:
            self.armed = False
            self.fired_at.append(self._clock())
            raise TaskKilled(f"stream task killed at its poll of {topic!r} "
                             f"partition {partition} offset {start_offset}")
        return self._log.consume(topic, partition, start_offset)
