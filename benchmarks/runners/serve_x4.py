"""Runner of the serve mixes over an item-sharded engine: ``runners/serve.py``
(same server, same open loop, same window and the same four checks) for a
catalogue that one chip cannot hold.

What differs, and why it is a runner of its own: the seen lists come from
``harness/seen_blocks.py`` (``datagen.seen_lists`` stops at 16.7 M items);
the exact top-K comes from ``harness/reference_blocks.py``, block by block
over the item rows (``reference.exact_topk`` keeps users x items in memory);
the engine shards its table over ``engine.shards`` chips, so a program whose
``ServeEngine`` cannot is refused at once, before any data is made; and in a
traced run the program's tracer is on while the engine is built, for the
``serve/engine/table_upload`` span.
"""

from __future__ import annotations

import hashlib
import inspect
import json
import os
import sys
import time

import numpy as np

from benchmarks.harness import datagen, reference, reference_blocks, seen_blocks
from benchmarks.runners import serve


def _seen(ctx, config: dict):
    s = config["seen_lists"]
    key = hashlib.sha256(json.dumps(
        ["blocks", config["users"], config["items"], config["corpus_seed"], s],
        sort_keys=True).encode()).hexdigest()[:16]
    base = os.path.join(ctx.cache_dir, f"seen.{key}")
    t0 = time.perf_counter()
    if os.path.exists(base + ".ok"):
        out = np.load(base + ".items.npy"), np.load(base + ".indptr.npy")
        ctx.say(f"seen lists: cache hit, loaded in {time.perf_counter() - t0:.1f} s")
        return out
    items, indptr = seen_blocks.seen_lists_blocks(
        config["users"], config["items"], s["mean_len"], s["max_len"],
        seed=config["corpus_seed"], users_per_block=s["users_per_block"])
    np.save(base + ".items.npy", items)
    np.save(base + ".indptr.npy", indptr)
    with open(base + ".ok", "w") as f:
        f.write("ok\n")
    ctx.say(f"seen lists: {items.size:,} cells built and cached in "
            f"{time.perf_counter() - t0:.1f} s")
    return items, indptr


class ShardedServeRun(serve.ServeRun):
    def setup(self) -> None:
        import jax

        from cfk_tpu import telemetry
        from cfk_tpu.serving import (
            RecommendServer, ServeClient, ServeEngine, ensure_serve_topics)
        from cfk_tpu.serving.engine import trace_count
        from cfk_tpu.transport.broker import InMemoryBroker

        ctx, config, mix = self.ctx, self.config, self.mix
        self.shards = int(config["engine"]["shards"])
        if "shards" not in inspect.signature(ServeEngine.__init__).parameters:
            sys.exit("FAILED: this program's ServeEngine takes no `shards`: it "
                     "cannot place a table over several chips, and "
                     f"{config['items']:,} x {config['rank']} "
                     f"{config['table_dtype']} does not fit one")
        if len(jax.devices()) < self.shards:
            sys.exit(f"FAILED: the configuration shards its table over "
                     f"{self.shards} devices, JAX found {len(jax.devices())}")
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
            tracer = telemetry.configure(None) if ctx.trace else None
            try:
                self.engine = ServeEngine(
                    self.users_tab, self.items_tab, num_users=config["users"],
                    num_movies=config["items"], seen_movies=self.seen_items,
                    seen_indptr=self.seen_indptr,
                    table_dtype=config["table_dtype"], **config["engine"])
            finally:
                if tracer is not None:
                    ctx.setup_spans = [e for e in tracer.events()
                                       if e.get("ph") == "X"]
                    telemetry.shutdown(write=False)
            table = self.engine._table[0]
            ctx.say(f"engine: {config['users']:,} users, {config['items']:,} x "
                    f"{config['rank']} items (table_dtype="
                    f"{self.engine.table_dtype}, tile_m={self.engine.tile_m}, "
                    f"{self.engine.table_rows} table rows in "
                    f"{len(table.addressable_shards)} shards of "
                    f"{table.addressable_shards[0].data.shape[0]}) in "
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
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:self.shards]]
        ctx.say("peak bytes per device after warm-up: "
                + ", ".join(f"{p:,}" for p in peaks))

    def window(self, seconds: float) -> dict:
        return dict(super().window(seconds), shards=self.shards)

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
            t0 = time.perf_counter()
            ids = np.stack([ok[r].movie_rows for r in take])
            best, _, at = reference_blocks.exact_topk_blocks(
                self.users_tab[users], self.items_tab,
                [seen_of(u) for u in users], self.k, ids)
            rank_gap, score_err = reference_blocks.topk_gaps(
                np.stack([ok[r].scores for r in take]), best, at)
            self.ctx.say(f"reference: exact float32 top-{self.k} of "
                         f"{len(take)} users over {config['items']:,} rows in "
                         f"{time.perf_counter() - t0:.1f} s")
        self.ctx.say(
            f"output check: every one of {len(rids):,} answered id sets held to"
            f" 'K distinct in-range unseen'; {len(take)} seeded responses "
            "against numpy's exact float32 top-K, block by block (ties allowed)")
        return [
            ("failed_requests", window["failed"], 0, why["failed_requests"]),
            ("invalid_id_sets", bad, 0, why["invalid_id_sets"]),
            ("rank_gap", rank_gap, limits["rank_gap"], why["rank_gap"]),
            ("score_err", score_err, limits["score_err"], why["score_err"]),
        ]


def make(ctx):
    return ShardedServeRun(ctx)
