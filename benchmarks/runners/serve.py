"""Runner of the serve traffic mixes: open-loop top-K requests against the
program's request server (``RecommendServer`` over a ``ServeEngine`` and the
in-memory log), at the rate the mix fixes.

The seen lists are the deployment's data set (from the configuration's
``corpus_seed``, cached under ``benchmarks/.cache/``); the factor tables, the
request users and the sampled responses come from ``--seed``.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

import numpy as np

from benchmarks.harness import datagen, loadgen, reference
from benchmarks.harness.stats import percentile


def _seen(ctx, config: dict):
    s = config["seen_lists"]
    key = hashlib.sha256(json.dumps(
        [config["users"], config["items"], config["corpus_seed"], s],
        sort_keys=True).encode()).hexdigest()[:16]
    base = os.path.join(ctx.cache_dir, f"seen.{key}")
    if os.path.exists(base + ".ok"):
        t0 = time.perf_counter()
        out = np.load(base + ".items.npy"), np.load(base + ".indptr.npy")
        ctx.say(f"seen lists: cache hit, loaded in {time.perf_counter() - t0:.1f} s")
        return out
    t0 = time.perf_counter()
    items, indptr = datagen.seen_lists(
        config["users"], config["items"], s["mean_len"], s["max_len"],
        seed=config["corpus_seed"])
    np.save(base + ".items.npy", items)
    np.save(base + ".indptr.npy", indptr)
    with open(base + ".ok", "w") as f:
        f.write("ok\n")
    ctx.say(f"seen lists: {items.size:,} cells built and cached in "
            f"{time.perf_counter() - t0:.1f} s")
    return items, indptr


class ServeRun:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config, self.mix = ctx.config, ctx.traffic

    def setup(self) -> None:
        from cfk_tpu.serving import (
            RecommendServer, ServeClient, ServeEngine, ensure_serve_topics)
        from cfk_tpu.serving.engine import trace_count
        from cfk_tpu.transport.broker import InMemoryBroker

        ctx, config, mix = self.ctx, self.config, self.mix
        self.trace_count = trace_count
        self.k = int(mix["k"])
        with ctx.phase("setup_data_s"):
            self.seen_items, self.seen_indptr = _seen(ctx, config)
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
                table_dtype=config["table_dtype"], **config.get("engine", {}))
            ctx.say(f"engine: {config['users']:,} users, {config['items']:,} x "
                    f"{config['rank']} items (table_dtype="
                    f"{self.engine.table_dtype}, tile_m={self.engine.tile_m}, "
                    f"{self.engine.table_rows} table rows) in "
                    f"{time.perf_counter() - t0:.1f} s")
        broker = InMemoryBroker()
        ensure_serve_topics(broker)
        self.server = RecommendServer(self.engine, broker,
                                      max_batch=int(mix["max_batch"]))
        self.client = ServeClient(broker)
        n = int(max(ctx.seconds, mix.get("trace_seconds", 0)) * mix["rate"]) + 1
        self.users = datagen.zipf_users(config["users"], n, seed=ctx.seed + 2,
                                        a=mix["zipf_a"])
        with ctx.phase("setup_compile_s"):
            # the server pads k to a power of two (at least 8)
            self.k_pad = max(8, 1 << (self.k - 1).bit_length())
            warm = self.engine.prewarm(self.k_pad,
                                       max_batch=int(mix["max_batch"]),
                                       user_rows=self.users)
        ctx.say(f"prewarm: {warm['programs']} batch programs, "
                f"{warm['new_traces']} traced, {warm['prewarm_s']:.1f} s")

    def window(self, seconds: float) -> dict:
        mix = self.mix
        traces = self.trace_count()
        res = loadgen.run_open_loop(
            self.client, self.server, users=self.users, rate=float(mix["rate"]),
            seconds=seconds, k=self.k, drain_s=float(mix["drain_seconds"]))
        self.result = res
        new_traces = self.trace_count() - traces
        errors = sum(1 for r in res.responses.values() if r.error)
        lat = res.latency_ms
        rate = res.answered_in_window / res.window_s
        self.ctx.say(
            f"window: {res.offered:,} requests offered at {mix['rate']} req/s; "
            f"{res.answered_in_window:,} answered in the {res.window_s:.3f} s "
            f"to its close = {rate:.2f} req/s, backlog then "
            f"{res.backlog_at_close:,}; {len(res.batch_sizes)} batches, "
            f"{new_traces} new program traces")
        self.ctx.say(
            f"drain: {res.drain_s:.2f} s of at most {mix['drain_seconds']} s, "
            f"{res.unanswered} requests still unanswered, {errors} error "
            "responses")
        ends, sizes = np.asarray(res.batch_ends_s), np.cumsum(res.batch_sizes)
        if ends.size > 8:
            # how the same rate reads over shorter windows of this run, and
            # how evenly the batches came: information, not metrics
            parts = []
            for share in (1 / 3, 2 / 3):
                i = int(np.searchsorted(ends, share * seconds, side="right")) - 1
                if i >= 0:
                    parts.append(f"first {ends[i]:.1f} s {sizes[i] / ends[i]:.2f}")
            gaps = np.diff(ends) * 1e3
            self.ctx.say(
                "req/s over " + ", ".join(parts) + "; batch period p5 "
                f"{percentile(gaps, 5):.1f} / p50 {percentile(gaps, 50):.1f} / "
                f"p95 {percentile(gaps, 95):.1f} / longest {gaps.max():.1f} ms")
        self.ctx.say(
            f"latency from scheduled send, {lat.size:,} samples: p50 "
            f"{percentile(lat, 50):.1f} ms, p95 {percentile(lat, 95):.1f} ms; "
            f"generator lateness p95 {percentile(res.late_ms, 95):.1f} ms")
        return {"window_s": res.window_s, "attempted": res.offered,
                "failed": errors + res.unanswered, "new_traces": new_traces,
                "batch_sizes": res.batch_sizes, "latency_ms": lat,
                "late_ms": res.late_ms,
                "end_to_end": {"serve_req_per_s": rate},
                "table_rows": self.engine.table_rows, "k_pad": self.k_pad}

    def check(self, window: dict) -> list:
        config, res = self.config, self.result
        limits, why = config["checks"], config["checks"]["why"]
        ok = {rid: r for rid, r in res.responses.items() if not r.error}
        rids = sorted(ok)
        seen_of = lambda u: self.seen_items[
            self.seen_indptr[u]:self.seen_indptr[u + 1]]
        bad = reference.invalid_id_sets(
            [ok[r].movie_rows for r in rids],
            [seen_of(res.users_of[r]) for r in rids], config["items"], self.k)
        rng = np.random.default_rng(self.ctx.seed + 3)
        take = [rids[i] for i in sorted(rng.choice(
            len(rids), size=min(limits["sample_responses"], len(rids)),
            replace=False))] if rids else []
        users = np.asarray([res.users_of[r] for r in take], np.int64)
        rank_gap = score_err = float("inf")
        if take:
            best, scores = reference.exact_topk(
                self.users_tab[users], self.items_tab,
                [seen_of(u) for u in users], self.k)
            rank_gap, score_err = reference.topk_gaps(
                np.stack([ok[r].movie_rows for r in take]),
                np.stack([ok[r].scores for r in take]), best, scores)
        self.ctx.say(
            f"output check: every one of {len(rids):,} answered id sets held to"
            f" 'K distinct in-range unseen'; {len(take)} seeded responses "
            "against numpy's exact float32 top-K (ties allowed)")
        return [
            ("failed_requests", window["failed"], 0, why["failed_requests"]),
            ("invalid_id_sets", bad, 0, why["invalid_id_sets"]),
            ("rank_gap", rank_gap, limits["rank_gap"], why["rank_gap"]),
            ("score_err", score_err, limits["score_err"], why["score_err"]),
        ]


def make(ctx):
    return ServeRun(ctx)
