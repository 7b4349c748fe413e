"""``loadgen.run_open_loop`` with a department on each request, and a count
of what each server step answered together.

The same open loop (request ``i`` due at ``t0 + i / rate``, generator and
server on one thread, every sample kept).  ``departments[i]`` is request
``i``'s department, -1 for none.  The answers collected after one
``server.step()`` are one batch's: a step whose answers name more than one
department is a ``wrong_department_batches``, counted in plain windows too
(spans exist only in traced ones), through the drain as well.

What is kept of every request and answer lives in arrays made before the
first send, a row a request (the client's ids run on from its first), and
no response object outlives the poll that brought it: the generator adds
nothing to the heap the collector walks, so the collector stays on, as it
does in the server this stands in front of.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class DeptLoadResult:
    """As ``loadgen.LoadResult``, by request index where that has dicts by
    ``req_id``: request ``i`` is ``users[i]`` in ``departments[i]``."""

    offered: int  # requests sent (all due inside the window): i < offered
    window_s: float
    answered_in_window: int
    backlog_at_close: int
    unanswered: int  # still missing after the drain: dropped
    latency_ms: np.ndarray  # per answered request, from the scheduled send
    late_ms: np.ndarray  # per sent request, actual minus scheduled send
    answered: np.ndarray  # [offered] bool: its answer was seen
    error: np.ndarray  # [offered] bool: that answer was a refusal
    ids: np.ndarray  # [offered, k] int32 served item rows (-1: none there)
    scores: np.ndarray  # [offered, k] float32 served scores
    id_counts: np.ndarray  # [offered] int32: ids the answer held (k, if sound)
    batch_sizes: list  # requests per server batch inside the window
    batch_ends_s: list  # when each of those batches' answers were seen
    batch_departments: list  # of each of those batches (its first answer's)
    wrong_department_batches: int  # steps whose answers named several
    drain_s: float  # how long the backlog took to serve after the close


def run_open_loop(client, server, *, users, departments, rate: float,
                  seconds: float, k: int, drain_s: float,
                  clock=time.perf_counter, sleep=time.sleep) -> DeptLoadResult:
    """Offer ``users[i]`` in ``departments[i]`` at ``i / rate`` for
    ``seconds``; the window's close and the drain are ``loadgen``'s."""
    n_due = min(len(users), int(seconds * rate))
    users = np.asarray(users[:n_due], np.int64)
    departments = np.asarray(departments[:n_due], np.int64)
    user_list, dept_list = users.tolist(), departments.tolist()
    answered = np.zeros(n_due, bool)
    error = np.zeros(n_due, bool)
    ids = np.full((n_due, k), -1, np.int32)
    scores = np.zeros((n_due, k), np.float32)
    id_counts = np.zeros(n_due, np.int32)
    latency = np.full(n_due, np.nan)
    late = np.zeros(n_due)
    batch_sizes, batch_ends, batch_depts = [], [], []
    wrong = 0
    first_id = None  # request i's req_id is first_id + i
    t0 = clock()
    t_close = t0 + seconds
    sent = 0

    def collect():
        """(now, the departments of the answers seen since the last call)"""
        now = clock()
        named = []
        for resp in client.poll_responses():
            i = resp.req_id - first_id
            if not 0 <= i < sent or answered[i]:
                continue
            answered[i] = True
            latency[i] = (now - t0 - i / rate) * 1e3
            named.append(dept_list[i])
            if resp.error:
                error[i] = True
                continue
            m = min(resp.movie_rows.size, k)
            id_counts[i] = resp.movie_rows.size
            ids[i, :m] = resp.movie_rows[:m]
            scores[i, :m] = resp.scores[:m]
        return now, named

    while True:
        now = clock()
        if now >= t_close:
            break
        due_now = min(n_due, int((now - t0) * rate) + 1)
        if sent < due_now:
            for i in range(sent, due_now):
                dept = dept_list[i]
                rid = client.request(user_list[i], k,
                                     None if dept < 0 else dept)
                if first_id is None:
                    first_id = rid
                if rid != first_id + i:
                    raise RuntimeError(
                        "the client's req_ids do not run on from its first: "
                        "this generator shares its client with no one")
            late[sent:due_now] = (
                now - t0 - np.arange(sent, due_now) / rate) * 1e3
            client.flush()
            sent = due_now
        served = server.step()
        if served:
            now, named = collect()
            batch_sizes.append(served)
            batch_ends.append(now - t0)
            batch_depts.append(named[0] if named else -1)
            wrong += len(set(named)) > 1
        elif sent >= n_due:
            sleep(min(0.001, max(t_close - clock(), 0.0)))
        else:
            sleep(max(min(t0 + sent / rate - clock(), 0.001), 0.0))
    t_end, named = collect()
    wrong += len(set(named)) > 1
    window_s = t_end - t0
    in_window = int(answered.sum())
    backlog = sent - in_window
    t_stop = t_end + drain_s
    left = backlog
    while left and clock() < t_stop:
        if server.step():
            named = collect()[1]
            wrong += len(set(named)) > 1
            left -= len(named)
        else:
            sleep(0.001)
    cut = slice(0, sent)
    return DeptLoadResult(
        offered=sent, window_s=window_s, answered_in_window=in_window,
        backlog_at_close=backlog, unanswered=left,
        latency_ms=latency[cut][answered[cut]], late_ms=late[cut],
        answered=answered[cut], error=error[cut], ids=ids[cut],
        scores=scores[cut], id_counts=id_counts[cut],
        batch_sizes=batch_sizes, batch_ends_s=batch_ends,
        batch_departments=batch_depts, wrong_department_batches=wrong,
        drain_s=clock() - t_end,
    )
