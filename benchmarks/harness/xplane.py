"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to what the metrics read.

On this toolchain (jax 0.9.0, libtpu 0.0.34) a TPU's plane is named
``/device:TPU:<n>`` and carries the line ``XLA Modules`` (one event per
executed program: their union is the device's busy time) and the line
``XLA Ops`` (every HLO op, control-flow wrappers included, so events nest).
A Mosaic kernel appears among the ops as ``%<kernel name>.<n> = ... custom-call``;
its name is what ``pallas_call(name=...)`` or the jitted function gave it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

from benchmarks.harness import stats

WINDOW_MARK = "bench/window"


def op_token(event_name: str) -> str:
    """``%_gauss_solve_reg_pallas.16 = f32[...] custom-call(...)`` →
    ``_gauss_solve_reg_pallas.16``: the op's own name in its program."""
    return event_name.split(" ", 1)[0].lstrip("%")


@dataclasses.dataclass
class DeviceTrace:
    """Times in seconds on the trace's own clock."""

    modules: list  # per device: [(start, end, name)]
    ops: list  # device 0: [(start, end, op token, is_custom_call)]
    mark: float | None  # start of the host's WINDOW_MARK annotation
    profile_start_unix_ns: int | None

    @property
    def devices(self) -> int:
        return len(self.modules)

    def busy_s(self) -> float:
        """Seconds in which a program ran on the device, averaged over the
        devices traced."""
        if not self.modules:
            return 0.0
        return sum(stats.union_length([(a, b) for a, b, _ in dev])
                   for dev in self.modules) / len(self.modules)

    def busy_intervals(self):
        return [(a, b) for a, b, _ in self.modules[0]] if self.modules else []

    def kernel_seconds(self, pattern: str, *, exclude: str | None = None):
        """(seconds, events) of device 0's custom-call ops whose token
        matches ``pattern`` (a regex, searched) and not ``exclude``."""
        want = re.compile(pattern)
        skip = re.compile(exclude) if exclude else None
        secs, n = 0.0, 0
        for a, b, name, custom in self.ops:
            if custom and want.search(name) and not (skip and skip.search(name)):
                secs += b - a
                n += 1
        return secs, n

    def self_times(self) -> dict:
        """Seconds per op token with each op's nested ops taken out, so
        a ``while`` wrapper counts only what it does itself."""
        out: dict[str, float] = {}
        stack: list[list] = []  # [end, name, self seconds]

        def close(upto: float) -> None:
            while stack and stack[-1][0] <= upto:
                _, name, self_s = stack.pop()
                out[name] = out.get(name, 0.0) + max(self_s, 0.0)

        for a, b, name, _ in sorted(self.ops, key=lambda e: (e[0], -e[1])):
            close(a)
            if stack:
                stack[-1][2] -= b - a
            stack.append([b, name, b - a])
        close(float("inf"))
        return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def reduce_xplane(path: str) -> DeviceTrace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules, ops, mark, start_ns = [], [], None, None
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            dev_modules = []
            first = not modules
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev_modules = [
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                         e.name) for e in line.events]
                elif line.name == "XLA Ops" and first:
                    for e in line.events:
                        full = e.name
                        ops.append((e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9,
                                    op_token(full),
                                    "custom-call(" in full))
            modules.append(dev_modules)
        elif name == "/host:CPU" and mark is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_MARK:
                        mark = e.start_ns * 1e-9
                        break
                if mark is not None:
                    break
        elif name == "Task Environment":
            for key, value in plane.stats:
                if key == "profile_start_time":
                    start_ns = int(value)
    return DeviceTrace(modules=modules, ops=ops, mark=mark,
                       profile_start_unix_ns=start_ns)


def name_gaps(trace: DeviceTrace, lo: float, hi: float, host_spans, top: int = 5):
    """The ``top`` longest stretches of ``[lo, hi]`` (trace clock) in which no
    program ran on device 0, each named by the deepest host span that covered
    its middle.  ``host_spans`` are ``(start, end, name)`` on the trace's
    clock."""
    out = []
    for a, b in sorted(stats.gaps(trace.busy_intervals(), lo, hi),
                       key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        covering = [s for s in host_spans if s[0] <= mid <= s[1]]
        name = (min(covering, key=lambda s: s[1] - s[0])[2]
                if covering else "no host span")
        out.append([name, b - a])
    return out
