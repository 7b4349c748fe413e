"""Open-loop load for a request server that folds a stream of ratings in:
``loadgen.run_open_loop`` with a second open loop on the same clock.

Request ``i`` is due at ``t0 + i / rate`` and rating ``j`` at ``t0 + j /
rating_rate``, whatever the server does; both are sent by the one thread that
also steps the server (which steps its stream session), as in every cell.
Ratings stop at the close; after it the server is driven until every request
sent has its answer and the session has committed every rating sent, for at
most ``drain_s``.  When each request and each rating really went out is kept:
read-your-writes is counted from the sending.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmarks.harness.loadgen import LoadResult


@dataclasses.dataclass
class StreamLoadResult(LoadResult):
    req_index: dict  # req_id -> i, the request's place in the schedule
    req_sent_s: np.ndarray  # per request sent, when it went out (clock)
    ratings_sent: int
    rating_sent_s: np.ndarray  # per rating sent, when it went out (clock)
    t0: float
    t_close: float  # the clock at the close of the window
    ratings_outstanding: int  # not committed when the drain ended


def run_open_loop(client, server, session, producer, *, users, rate: float,
                  ratings, rating_rate: float, seconds: float, k: int,
                  drain_s: float, clock=time.perf_counter,
                  sleep=time.sleep) -> StreamLoadResult:
    """``ratings`` = (user raw ids, item rows, values), sent in order through
    ``producer.send_many``; the rest as ``loadgen.run_open_loop``."""
    r_users, r_items, r_values = ratings
    n_due = min(len(users), int(seconds * rate))
    n_ratings = min(len(r_users), int(seconds * rating_rate))
    scheduled: dict[int, float] = {}
    users_of: dict[int, int] = {}
    req_index: dict[int, int] = {}
    responses: dict = {}
    latency, late, batch_sizes, batch_ends = [], [], [], []
    req_sent = np.zeros(n_due, np.float64)
    rating_sent = np.zeros(n_ratings, np.float64)
    t0 = clock()
    t_close = t0 + seconds
    sent = rated = 0

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
                req_index[rid] = i
                late.append((now - due) * 1e3)
            client.flush()
            req_sent[sent:due_now] = now
            sent = due_now
        rated_now = min(n_ratings, int((now - t0) * rating_rate) + 1)
        if rated < rated_now:
            producer.send_many(r_users[rated:rated_now],
                               r_items[rated:rated_now],
                               r_values[rated:rated_now])
            rating_sent[rated:rated_now] = clock()
            rated = rated_now
        served = server.step()
        if served:
            batch_sizes.append(served)
            batch_ends.append(collect() - t0)
        elif sent >= n_due and rated >= n_ratings:
            sleep(min(0.001, max(t_close - clock(), 0.0)))
        else:
            nxt = min(t0 + sent / rate if sent < n_due else t_close,
                      t0 + rated / rating_rate if rated < n_ratings
                      else t_close)
            sleep(max(min(nxt - clock(), 0.001), 0.0))
    t_end = collect()
    window_s = t_end - t0
    answered = len(responses)
    backlog = len(scheduled)
    t_stop = t_end + drain_s

    def outstanding() -> int:
        return session.backlog() + (1 if session.in_flight else 0)

    while (scheduled or outstanding()) and clock() < t_stop:
        if server.step():
            collect()
        elif scheduled:
            sleep(0.001)
    return StreamLoadResult(
        offered=sent, window_s=window_s, answered_in_window=answered,
        backlog_at_close=backlog, unanswered=len(scheduled),
        latency_ms=np.asarray(latency, np.float64),
        late_ms=np.asarray(late, np.float64),
        responses=responses, users_of=users_of,
        batch_sizes=batch_sizes, batch_ends_s=batch_ends,
        drain_s=clock() - t_end,
        req_index=req_index, req_sent_s=req_sent[:sent], ratings_sent=rated,
        rating_sent_s=rating_sent[:rated], t0=t0, t_close=t_end,
        ratings_outstanding=outstanding(),
    )
