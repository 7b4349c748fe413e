"""Runner of the serve mixes over an item table that is never whole in
float32: ``runners/serve.py`` (same server, same open loop, same window and
the same four checks) for a catalogue one chip holds only quantized.

What differs, and why it is a runner of its own: the engine is handed its item
factors as a row reader (``harness/reference_q8.py::FactorBlocks``: the seeded
table a row range at a time), so neither this process nor the device holds the
24.7 GB float32 table, and a program whose ``ServeEngine`` takes its table
whole is refused at once, before any data is made; the seen lists are
``serve_x4._seen``'s (``datagen.seen_lists`` stops at 16.7 M items; the same
cache key as the four-chip cell, whose data set this is), built on fewer
threads; the exact top-K
comes from ``harness/reference_blocks.py`` over the dequantized view
(``reference_q8.DequantizedBlocks``: quantized by the written rule in numpy,
block by block); and in a traced run the program's tracer is on while the
engine is built, for the ``serve/engine/table_upload`` span.
"""

from __future__ import annotations

import hashlib
import json
import os
import resource
import sys
import time

import numpy as np

from benchmarks.harness import (
    datagen, reference, reference_blocks, reference_q8, seen_blocks)
from benchmarks.runners import serve


def _seen(ctx, config: dict):
    """``serve_x4._seen``: the same lists under the same cache key (they do
    not depend on the thread count), built on three threads.  A dozen
    threads allocate and free the build's ~84 MB temporaries faster than
    the one-chip machine's host gives the memory back: 8 GiB held beyond
    what the process holds after 6 s of a 16 M-user build, none on three
    threads, and the full build reached the machine's 40 GiB (PERF.md
    section 6, PR 32); three threads build about as fast."""
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
        seed=config["corpus_seed"], users_per_block=s["users_per_block"],
        threads=3)
    np.save(base + ".items.npy", items)
    np.save(base + ".indptr.npy", indptr)
    with open(base + ".ok", "w") as f:
        f.write("ok\n")
    ctx.say(f"seen lists: {items.size:,} cells built and cached in "
            f"{time.perf_counter() - t0:.1f} s")
    return items, indptr


def _rss() -> str:
    """This process's resident set now and at its peak, in GiB."""
    with open("/proc/self/statm") as f:
        now = int(f.read().split()[1]) * resource.getpagesize() / 2**30
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    return f"RSS {now:.2f} GiB now, {peak:.2f} at its peak"


class BlocksServeRun(serve.ServeRun):
    def setup(self) -> None:
        import jax

        from cfk_tpu import telemetry
        from cfk_tpu.serving import (
            RecommendServer, ServeClient, ServeEngine, ensure_serve_topics)
        from cfk_tpu.serving import engine as engine_mod
        from cfk_tpu.transport.broker import InMemoryBroker

        ctx, config, mix = self.ctx, self.config, self.mix
        if not hasattr(engine_mod, "row_reader"):
            sys.exit("FAILED: this program's ServeEngine takes its item table "
                     "whole, as one host array it uploads in float32 before "
                     f"it quantizes: {config['items']:,} x {config['rank']} "
                     "float32 fit neither this host beside the user table "
                     "nor one chip")
        self.trace_count = engine_mod.trace_count
        self.k = int(mix["k"])
        with ctx.phase("setup_data_s"):
            self.seen_items, self.seen_indptr = _seen(ctx, config)
            ctx.say(f"host after the seen lists: {_rss()}")
            t0 = time.perf_counter()
            scale = config["factor_scale"]
            self.users_tab = datagen.factor_table(
                config["users"], config["rank"], seed=ctx.seed, scale=scale)
            ctx.say(f"user table from the seed in "
                    f"{time.perf_counter() - t0:.1f} s; {_rss()}")
            # the item factors are never made whole: the engine reads them a
            # row range at a time, the reference (check) makes them again
            # (one buffer each, made over: the engine has quantized a slice
            # before it reads the next, the reference has scored a block; a
            # float table's slice is still going up when the next is read)
            table = dict(seed=ctx.seed + 1, scale=scale)
            items = reference_q8.FactorBlocks(
                config["items"], config["rank"],
                reuse=config["table_dtype"] == "int8", **table)
            self.items_ref = reference_q8.DequantizedBlocks(
                config["items"], config["rank"], reuse=True, **table)
            t0 = time.perf_counter()
            tracer = telemetry.configure(None) if ctx.trace else None
            try:
                self.engine = ServeEngine(
                    self.users_tab, lambda lo, hi: items[lo:hi],
                    num_users=config["users"], num_movies=config["items"],
                    seen_movies=self.seen_items, seen_indptr=self.seen_indptr,
                    table_dtype=config["table_dtype"], **config["engine"])
            finally:
                if tracer is not None:
                    ctx.setup_spans = [e for e in tracer.events()
                                       if e.get("ph") == "X"]
                    telemetry.shutdown(write=False)
            data, scales = self.engine._table
            stats = jax.devices()[0].memory_stats() or {}
            ctx.say(f"engine: {config['users']:,} users, {config['items']:,} x "
                    f"{config['rank']} items read in row blocks (table_dtype="
                    f"{self.engine.table_dtype}, tile_m={self.engine.tile_m}, "
                    f"{self.engine.table_rows} table rows = {data.nbytes:,} B"
                    + ("" if scales is None
                       else f" of codes + {scales.nbytes:,} B of scales")
                    + f") in {time.perf_counter() - t0:.1f} s; device peak "
                    f"{stats.get('peak_bytes_in_use', 0):,} B; {_rss()}")
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
                f"{warm['new_traces']} traced, {warm['prewarm_s']:.1f} s; "
                f"{_rss()}")

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
            # a block = one 2^18-row piece for each of six threads at most:
            # every piece in the making holds ~0.4 GiB beside the user table
            best, _, at = reference_blocks.exact_topk_blocks(
                self.users_tab[users], self.items_ref,
                [seen_of(u) for u in users], self.k, ids,
                block=min(self.items_ref.threads, 6) * reference_q8.BLOCK)
            rank_gap, score_err = reference_blocks.topk_gaps(
                np.stack([ok[r].scores for r in take]), best, at)
            self.ctx.say(f"reference: item blocks made again from the seed, "
                         f"quantized and dequantized by the rule in numpy, "
                         f"exact float32 top-{self.k} of {len(take)} users "
                         f"over {config['items']:,} rows in "
                         f"{time.perf_counter() - t0:.1f} s; {_rss()}")
        self.ctx.say(
            f"output check: every one of {len(rids):,} answered id sets held to"
            f" 'K distinct in-range unseen'; {len(take)} seeded responses "
            "against numpy's exact float32 top-K over the dequantized table, "
            "block by block (ties allowed)")
        return [
            ("failed_requests", window["failed"], 0, why["failed_requests"]),
            ("invalid_id_sets", bad, 0, why["invalid_id_sets"]),
            ("rank_gap", rank_gap, limits["rank_gap"], why["rank_gap"]),
            ("score_err", score_err, limits["score_err"], why["score_err"]),
        ]


def make(ctx):
    return BlocksServeRun(ctx)
