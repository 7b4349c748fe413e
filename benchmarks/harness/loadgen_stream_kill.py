"""``loadgen_stream.run_open_loop`` for a server whose stream task dies: the
same two open loops on one clock, with the kill armed at ``kill_at_s`` and
the session read from the server at every use, since the server replaces it
(``server.session`` is None while the successor is brought up).  After the
close the server is driven until every request has its answer, the successor
is up and has committed and published every rating sent."""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmarks.harness.loadgen_stream import StreamLoadResult


@dataclasses.dataclass
class KillLoadResult(StreamLoadResult):
    killed_at_s: list  # the clock at each kill delivered
    step_ends_s: np.ndarray  # the clock after every server step of the run


def run_open_loop(client, server, producer, kill, *, users, rate: float,
                  ratings, rating_rate: float, seconds: float, k: int,
                  drain_s: float, kill_at_s, clock=time.perf_counter,
                  sleep=time.sleep) -> KillLoadResult:
    """``kill_at_s``: seconds into the window at which each kill is armed
    (the session dies at its next poll of the log).  The rest as
    ``loadgen_stream.run_open_loop``."""
    r_users, r_items, r_values = ratings
    n_due = min(len(users), int(seconds * rate))
    n_ratings = min(len(r_users), int(seconds * rating_rate))
    scheduled: dict[int, float] = {}
    users_of: dict[int, int] = {}
    req_index: dict[int, int] = {}
    responses: dict = {}
    latency, late, batch_sizes, batch_ends, step_ends = [], [], [], [], []
    req_sent = np.zeros(n_due, np.float64)
    rating_sent = np.zeros(n_ratings, np.float64)
    to_arm = sorted(kill_at_s)
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
        if to_arm and now - t0 >= to_arm[0]:
            to_arm.pop(0)
            kill.arm()
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
        step_ends.append(clock())
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
        session = server.session
        if session is None:  # its successor is not up yet
            return 1
        return session.backlog() + (1 if session.in_flight else 0)

    while (scheduled or outstanding()) and clock() < t_stop:
        got = server.step()
        step_ends.append(clock())
        if got:
            collect()
        elif scheduled or server.session is None:
            sleep(0.001)
    return KillLoadResult(
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
        killed_at_s=list(kill.fired_at),
        step_ends_s=np.asarray(step_ends, np.float64),
    )
