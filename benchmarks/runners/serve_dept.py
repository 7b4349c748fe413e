"""Runner of the department-page mixes: open-loop top-K requests that each
name a department, against the program's request server over a
``ServeEngine`` that was given the department of every item row and a
requests topic keyed by department, at the rate the mix fixes.

Everything but the department is ``runners/serve.py``'s: the deployment's
seen lists (the control's cache file), the factor tables and the request
users from ``--seed``.  A request's department is drawn from the seed in
proportion to the reviews of the departments the mix names; a share of the
requests may name none (``whole_catalogue_share``, 0 in the benchmark's
cell), and is held to the whole-catalogue reference.

What a batch of this deployment has to scan is its department's rows, not
the table: the window's ``table_rows``, which the roofline readers price a
batch by, is the mean over its batches of the rows of the range each had to
scan, padded to tiles (``table_rows_whole`` is the table's).
"""

from __future__ import annotations

import inspect
import sys
import time

import numpy as np

from benchmarks.harness import (
    datagen, loadgen_dept, reference, reference_dept)
from benchmarks.harness.stats import percentile
from benchmarks.runners import serve


class ServeDeptRun:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config, self.mix = ctx.config, ctx.traffic

    def setup(self) -> None:
        from cfk_tpu.serving import (
            RecommendServer, ServeClient, ServeEngine, ensure_serve_topics)
        from cfk_tpu.serving.engine import trace_count
        from cfk_tpu.transport.broker import InMemoryBroker

        if "item_department" not in inspect.signature(
                ServeEngine.__init__).parameters:
            # at once, before any data is made: a program without the
            # read path cannot run this deployment
            sys.exit("FAILED: this program serves no departments "
                     "(ServeEngine takes no item_department)")
        ctx, config, mix = self.ctx, self.config, self.mix
        self.trace_count = trace_count
        self.k = int(mix["k"])
        depts = config["departments"]
        self.ranges = reference_dept.ranges(depts)
        names = [d["name"] for d in depts]
        asked = [names.index(n) for n in mix["departments"]]
        with ctx.phase("setup_data_s"):
            self.seen_items, self.seen_indptr = serve._seen(ctx, config)
            t0 = time.perf_counter()
            scale = config["factor_scale"]
            self.users_tab = datagen.factor_table(
                config["users"], config["rank"], seed=ctx.seed, scale=scale)
            self.items_tab = datagen.factor_table(
                config["items"], config["rank"], seed=ctx.seed + 1, scale=scale)
            ctx.say(f"factor tables from the seed in "
                    f"{time.perf_counter() - t0:.1f} s")
            t0 = time.perf_counter()
            self.engine = ServeEngine(
                self.users_tab, self.items_tab, num_users=config["users"],
                num_movies=config["items"], seen_movies=self.seen_items,
                seen_indptr=self.seen_indptr,
                table_dtype=config["table_dtype"],
                item_department=reference_dept.department_of_rows(depts),
                **config.get("engine", {}))
            ctx.say(f"engine: {config['users']:,} users, {config['items']:,} x "
                    f"{config['rank']} items in {len(depts)} departments "
                    f"(table_dtype={self.engine.table_dtype}, tile_m="
                    f"{self.engine.tile_m}, {self.engine.table_rows} table "
                    f"rows) in {time.perf_counter() - t0:.1f} s")
        broker = InMemoryBroker()
        ensure_serve_topics(broker, departments=len(depts))
        self.server = RecommendServer(self.engine, broker,
                                      max_batch=int(mix["max_batch"]))
        self.client = ServeClient(broker, route="department")
        n = int(max(ctx.seconds, mix.get("trace_seconds", 0)) * mix["rate"]) + 1
        self.users = datagen.zipf_users(config["users"], n, seed=ctx.seed + 2,
                                        a=mix["zipf_a"])
        # each request's department, from the seed, in proportion to the
        # reviews of the departments the mix names; -1 names none
        rng = np.random.default_rng(ctx.seed + 4)
        weight = np.asarray([depts[d]["reviews"] for d in asked], np.float64)
        self.departments = np.asarray(asked)[
            rng.choice(len(asked), size=n, p=weight / weight.sum())]
        whole = float(mix.get("whole_catalogue_share", 0.0))
        if whole:
            self.departments[rng.random(n) < whole] = -1
        with ctx.phase("setup_compile_s"):
            # the server pads k to a power of two (at least 8)
            self.k_pad = max(8, 1 << (self.k - 1).bit_length())
            # the ranged programs of the departments the mix names alone
            warm = self.engine.prewarm(self.k_pad,
                                       max_batch=int(mix["max_batch"]),
                                       user_rows=self.users,
                                       departments=sorted(set(asked)))
        ctx.say(f"prewarm: {warm['programs']} batch programs, "
                f"{warm['new_traces']} traced, {warm['prewarm_s']:.1f} s")

    def _range(self, dept: int):
        return (0, self.config["items"]) if dept < 0 else self.ranges[dept]

    def window(self, seconds: float) -> dict:
        mix, tile = self.mix, self.engine.tile_m
        traces = self.trace_count()
        res = loadgen_dept.run_open_loop(
            self.client, self.server, users=self.users,
            departments=self.departments, rate=float(mix["rate"]),
            seconds=seconds, k=self.k, drain_s=float(mix["drain_seconds"]))
        self.result = res
        new_traces = self.trace_count() - traces
        errors = int(res.error.sum())
        lat = res.latency_ms
        rate = res.answered_in_window / res.window_s
        # the rows each batch of the window had to scan, padded to tiles
        scanned = [(-(-hi // tile) - lo // tile) * tile for lo, hi in
                   map(self._range, res.batch_departments)]
        by_dept = {d: [s for s, b in zip(res.batch_sizes,
                                         res.batch_departments) if b == d]
                   for d in sorted(set(res.batch_departments))}
        self.ctx.say(
            f"window: {res.offered:,} requests offered at {mix['rate']} req/s; "
            f"{res.answered_in_window:,} answered in the {res.window_s:.3f} s "
            f"to its close = {rate:.2f} req/s, backlog then "
            f"{res.backlog_at_close:,}; {len(res.batch_sizes)} batches, "
            f"{new_traces} new program traces")
        self.ctx.say(
            "batches by department (-1: none), count x mean requests: "
            + ", ".join(f"{d}: {len(s)} x {np.mean(s):.1f}"
                        for d, s in by_dept.items())
            + f"; mean rows scanned a batch "
              f"{np.mean(scanned) if scanned else 0:,.0f} of "
              f"{self.engine.table_rows:,}")
        self.ctx.say(
            f"drain: {res.drain_s:.2f} s of at most {mix['drain_seconds']} s, "
            f"{res.unanswered} requests still unanswered, {errors} error "
            "responses")
        ends = np.asarray(res.batch_ends_s)
        if ends.size > 8:
            gaps = np.diff(ends) * 1e3
            self.ctx.say(
                f"batch period p5 {percentile(gaps, 5):.1f} / p50 "
                f"{percentile(gaps, 50):.1f} / p95 {percentile(gaps, 95):.1f}"
                f" / longest {gaps.max():.1f} ms")
        self.ctx.say(
            f"latency from scheduled send, {lat.size:,} samples: p50 "
            f"{percentile(lat, 50):.1f} ms, p95 {percentile(lat, 95):.1f} ms; "
            f"generator lateness p95 {percentile(res.late_ms, 95):.1f} ms")
        return {"window_s": res.window_s, "attempted": res.offered,
                "failed": errors + res.unanswered, "new_traces": new_traces,
                "batch_sizes": res.batch_sizes, "latency_ms": lat,
                "late_ms": res.late_ms,
                "batch_departments": res.batch_departments,
                "end_to_end": {"serve_req_per_s": rate},
                "table_rows": int(np.mean(scanned)) if scanned
                else self.engine.table_rows,
                "table_rows_whole": self.engine.table_rows,
                "k_pad": self.k_pad}

    def check(self, window: dict) -> list:
        config, res = self.config, self.result
        limits, why = config["checks"], config["checks"]["why"]
        # request i of the window is self.users[i] in self.departments[i]
        rids = np.flatnonzero(res.answered & ~res.error).tolist()
        seen_of = lambda u: self.seen_items[
            self.seen_indptr[u]:self.seen_indptr[u + 1]]
        bad = reference_dept.invalid_id_sets(
            [res.ids[r, :res.id_counts[r]] if res.id_counts[r] <= self.k
             else np.empty(0, np.int32) for r in rids],
            [seen_of(self.users[r]) for r in rids],
            [self._range(self.departments[r]) for r in rids], self.k)
        # the sample: so many from each department asked for, the rest from
        # all the answers; a department with fewer answers leaves it short
        rng = np.random.default_rng(self.ctx.seed + 3)
        each = int(limits["sample_per_department"])
        by_dept: dict[int, list] = {}
        for r in rids:
            by_dept.setdefault(int(self.departments[r]), []).append(r)
        named = sorted(set(self.departments.tolist()))
        short = [d for d in named if len(by_dept.get(d, ())) < each]
        take = set()
        for d in named:
            have = by_dept.get(d, [])
            take.update(have[i] for i in rng.choice(
                len(have), size=min(each, len(have)), replace=False))
        rest = [r for r in rids if r not in take]
        more = min(max(limits["sample_responses"] - len(take), 0), len(rest))
        take.update(rest[i] for i in rng.choice(len(rest), size=more,
                                                replace=False))
        rank_gap = score_err = float("inf") if short or not take else 0.0
        by_department = []
        for d in sorted({int(self.departments[r]) for r in take}):
            mine = sorted(r for r in take if self.departments[r] == d)
            users = np.asarray(self.users[mine], np.int64)
            ids, vals = res.ids[mine], res.scores[mine]
            seen = [seen_of(u) for u in users]
            if d < 0:  # the whole catalogue: the control's reference
                best, scores = reference.exact_topk(
                    self.users_tab[users], self.items_tab, seen, self.k)
                gaps = reference.topk_gaps(ids, vals, best, scores)
            else:
                lo, hi = self.ranges[d]
                best, scores = reference_dept.exact_topk(
                    self.users_tab[users], self.items_tab, seen, self.k,
                    lo, hi)
                gaps = reference_dept.topk_gaps(ids, vals, best, scores, lo)
            by_department.append(
                f"{d}: {len(mine)} x ({gaps[0]:.3g}, {gaps[1]:.3g})")
            rank_gap, score_err = (max(rank_gap, gaps[0]),
                                   max(score_err, gaps[1]))
        self.ctx.say(
            f"output check: every one of {len(rids):,} answered id sets held to"
            f" 'K distinct unseen rows of the department named'; {len(take)} "
            f"seeded responses, {each} or more from each of departments "
            f"{named}" + (f" (short: {short})" if short else "")
            + ", against numpy's exact float32 top-K over the department's "
              "rows (ties allowed); by department (-1: none), responses x "
              "(rank_gap, score_err): " + ", ".join(by_department))
        return [
            ("failed_requests", window["failed"], 0, why["failed_requests"]),
            ("invalid_id_sets", bad, 0, why["invalid_id_sets"]),
            ("wrong_department_batches", res.wrong_department_batches, 0,
             why["wrong_department_batches"]),
            ("rank_gap", rank_gap, limits["rank_gap"], why["rank_gap"]),
            ("score_err", score_err, limits["score_err"], why["score_err"]),
        ]


def make(ctx):
    return ServeDeptRun(ctx)
