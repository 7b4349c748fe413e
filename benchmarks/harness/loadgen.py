"""Open-loop load for the request server, owned by the benchmark.

Request ``i`` is due at ``t0 + i / rate`` whatever the server does, and its
latency runs from that scheduled send to the moment its response is seen, so
a stall counts against every request it delays.  Generator and server share
one thread on purpose: the server polls its log only between batches, so a
request that falls due mid-batch waits for the next poll either way, and one
thread has no GIL hand-offs to add noise.  How late each send went out
(actual minus scheduled) is reported beside the latencies.

After ``cfk_tpu/serving/loadgen.py::run_open_loop`` (constant rate only,
p50/p99 from a reservoir); this copy keeps every sample.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class LoadResult:
    offered: int  # requests sent (all due inside the window)
    window_s: float  # from the first send to the close (see run_open_loop)
    answered_in_window: int  # every answer seen by the close
    backlog_at_close: int
    unanswered: int  # still missing after the drain: dropped
    latency_ms: np.ndarray  # per answered request, from the scheduled send
    late_ms: np.ndarray  # per sent request, actual minus scheduled send
    responses: dict  # req_id -> response, every answer seen
    users_of: dict  # req_id -> user row
    batch_sizes: list  # requests per server batch inside the window
    batch_ends_s: list  # when each of those batches' answers were seen
    drain_s: float  # how long the backlog took to serve after the close


def run_open_loop(client, server, *, users, rate: float, seconds: float,
                  k: int, drain_s: float, clock=time.perf_counter,
                  sleep=time.sleep) -> LoadResult:
    """Offer ``users[i]`` at ``i / rate`` for ``seconds``.

    The window never cuts a batch: it closes when the batch in flight at
    ``seconds`` has been answered (at ``seconds`` itself where the server is
    idle), and every answer seen by then counts, over all the time to then.
    After the close the server is driven for at most ``drain_s`` more, until
    every request sent has its answer; one still missing then was dropped.
    """
    n_due = min(len(users), int(seconds * rate))
    scheduled: dict[int, float] = {}
    users_of: dict[int, int] = {}
    responses: dict = {}
    latency, late, batch_sizes, batch_ends = [], [], [], []
    t0 = clock()
    t_close = t0 + seconds
    sent = 0

    def collect() -> float:
        now = clock()
        for resp in client.poll_responses():
            due = scheduled.pop(resp.req_id, None)
            if due is not None:
                responses[resp.req_id] = resp
                latency.append((now - due) * 1e3)
        return now

    while True:
        now = clock()
        if now >= t_close:
            break
        due_now = min(n_due, int((now - t0) * rate) + 1)
        if sent < due_now:
            for i in range(sent, due_now):
                rid = client.request(int(users[i]), k)
                due = t0 + i / rate
                scheduled[rid] = due
                users_of[rid] = int(users[i])
                late.append((now - due) * 1e3)
            client.flush()
            sent = due_now
        served = server.step()
        if served:
            batch_sizes.append(served)
            batch_ends.append(collect() - t0)
        elif sent >= n_due:
            sleep(min(0.001, max(t_close - clock(), 0.0)))
        else:
            sleep(max(min(t0 + sent / rate - clock(), 0.001), 0.0))
    t_end = collect()
    window_s = t_end - t0
    answered = len(responses)
    backlog = len(scheduled)
    t_stop = t_end + drain_s
    while scheduled and clock() < t_stop:
        if server.step():
            collect()
        else:
            sleep(0.001)
    return LoadResult(
        offered=sent, window_s=window_s, answered_in_window=answered,
        backlog_at_close=backlog, unanswered=len(scheduled),
        latency_ms=np.asarray(latency, np.float64),
        late_ms=np.asarray(late, np.float64),
        responses=responses, users_of=users_of,
        batch_sizes=batch_sizes, batch_ends_s=batch_ends,
        drain_s=clock() - t_end,
    )
