"""Open-loop synthetic load generator for the serve path.

Open-loop (arrivals scheduled by a clock, NOT gated on responses) is the
honest way to measure a server's latency under load: a closed loop slows
its own arrival rate the moment the server falls behind, hiding exactly
the tail it should expose (the coordinated-omission trap).  Here request
i's scheduled send time is ``i / rate``; the generator sends the moment
the clock passes it (never waits for responses to send), polls responses
opportunistically between sends, and reports per-request latency =
response-observed wall − SCHEDULED send — so queueing delay from the
generator itself falling behind counts against the server, as it would
for a real client.

Users are drawn Zipf-ish from the hot end of the row space (traffic skew
is what makes the hot-user cache meaningful); the draw is seeded, so a
bench row is reproducible.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from cfk_tpu.telemetry.metrics import Histogram

# Latency-reservoir size: big enough that the common bench sweeps
# (≤ 4096 requests) record EVERY sample (quantiles exact, bit-for-bit the
# old unbounded-list percentiles), bounded so a day-long soak stays O(1)
# in request count (quantiles become reservoir estimates past this).
LATENCY_RESERVOIR = 4096


@dataclasses.dataclass(frozen=True)
class LoadReport:
    """One open-loop run's measured outcome (the bench row's core)."""

    num_requests: int
    answered: int
    wall_s: float
    qps_target: float
    qps_achieved: float
    p50_ms: float
    p99_ms: float
    max_ms: float
    batches: int
    mean_batch: float

    def as_row(self) -> dict:
        return {
            "requests": self.num_requests,
            "answered": self.answered,
            "wall_s": round(self.wall_s, 4),
            "qps_target": round(self.qps_target, 1),
            "qps": round(self.qps_achieved, 1),
            "p50_ms": round(self.p50_ms, 3),
            "p99_ms": round(self.p99_ms, 3),
            "max_ms": round(self.max_ms, 3),
            "batches": self.batches,
            "mean_batch": round(self.mean_batch, 1),
        }


def zipf_user_rows(num_users: int, n: int, *, seed: int = 0,
                   a: float = 1.2) -> np.ndarray:
    """n user rows with a Zipf(a) popularity skew over the row space."""
    rng = np.random.default_rng(seed)
    draws = rng.zipf(a, size=n)
    return ((draws - 1) % num_users).astype(np.int64)


def warm_serve_programs(client, server, pool, k: int, max_batch: int) -> None:
    """Compile the serve path's batch-size program variants before a
    measured run: every pow2 coalesced size up to ``max_batch``, plus
    ``max_batch`` itself (a non-pow2 cap still pads to its own pow2
    bucket).  Used by the CLI loadgen mode (the benchmark has its own
    copy: ROADMAP D11).  Seen-rectangle widths (W) are data-dependent
    per batch, so a first-seen W can still trace mid-run — warming with
    the hottest pool rows makes the common widths resident."""
    pool = np.asarray(pool, np.int64)
    sizes = []
    warm = 4
    while warm < max_batch:
        sizes.append(warm)
        warm *= 2
    sizes.append(max_batch)
    for s in sizes:
        take = pool[: min(s, pool.shape[0])]
        if take.shape[0]:
            client.ask(take, k, server=server)


def run_open_loop(
    client,
    *,
    rate_qps: float,
    num_requests: int,
    user_rows,
    k: int = 10,
    server=None,
    drive_server: bool = False,
    timeout_s: float = 120.0,
    clock=time.monotonic,
    sleep=time.sleep,
) -> LoadReport:
    """Send ``num_requests`` at ``rate_qps`` open-loop; block for the tail.

    ``drive_server=True`` pumps ``server.step()`` inline between sends —
    the single-process bench mode, where the generator and server share
    one interpreter and a background thread would only serialize on the
    GIL anyway.  With a live server elsewhere, leave it False and pass
    ``server=None``.
    """
    user_rows = np.asarray(user_rows, np.int64)
    if user_rows.shape[0] < num_requests:
        user_rows = np.resize(user_rows, num_requests)
    # Latency accounting is a bounded histogram reservoir (ISSUE 14), not
    # the old per-request lists: outstanding sends are the only O(live)
    # state (entries leave the dict the moment their response arrives),
    # so memory is O(1) in request count while the p50/p99 contract is
    # unchanged (exact while answered <= LATENCY_RESERVOIR).
    outstanding: dict[int, float] = {}  # req_id -> scheduled send wall
    lat_hist = Histogram("serve_request_latency_ms",
                         reservoir=LATENCY_RESERVOIR)
    # warm-up batches before this run must not count against it
    batches_before = getattr(server, "batches", 0)

    def drain():
        for resp in client.poll_responses():
            scheduled = outstanding.pop(resp.req_id, None)
            if scheduled is not None:
                lat_hist.observe((clock() - scheduled) * 1e3)

    t0 = clock()
    for i in range(num_requests):
        scheduled = t0 + i / rate_qps
        while True:
            now = clock()
            if now >= scheduled:
                break
            if drive_server and server is not None and server.step():
                drain()
                continue
            drain()
            sleep(min(scheduled - now, 0.001))
        rid = client.request(int(user_rows[i]), k)
        client.flush()
        # latency clock starts at the SCHEDULED time: generator backlog
        # counts as server latency, not free slack (open-loop contract)
        outstanding[rid] = scheduled
        drain()
    deadline = clock() + timeout_s
    while outstanding:
        if drive_server and server is not None:
            server.step()
        drain()
        if clock() > deadline:
            break
        if not drive_server:
            sleep(0.001)
    wall = max(clock() - t0, 1e-9)
    answered = lat_hist.count
    if answered == 0:
        raise TimeoutError(
            f"no responses within {timeout_s}s — server not draining"
        )
    batches = getattr(server, "batches", 0) - batches_before
    return LoadReport(
        num_requests=num_requests,
        answered=answered,
        wall_s=wall,
        qps_target=rate_qps,
        qps_achieved=answered / wall,
        p50_ms=lat_hist.quantile(0.5),
        p99_ms=lat_hist.quantile(0.99),
        max_ms=lat_hist.max,
        batches=int(batches),
        mean_batch=(answered / batches if batches else 0.0),
    )
