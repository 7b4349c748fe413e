"""Runner of the skewed serve-while-folding-in mix over an item table one
chip holds only quantized: ``runners/serve_stream_skew.py``'s server,
session, store, event order, window and checks (subclassed), with the item
table handed to the engine as a row reader of blocks, as
``runners/serve_blocks.py`` does, and the session folding in against the
table AS THE ENGINE HOLDS IT (int8 codes and a float32 scale a row): the
float32 table (24.7 GB) is never whole on the host or the chip.

What differs, and why it is a runner of its own: the seen lists are the
tail's law built a block of users at a time (``harness/seen_tail_blocks.py``:
48.19 M items are past ``seen_tail``'s 24 bits and its one sort would not
fit beside the user table); the host holds no item table, so ``check`` reads
the exact top-K block by block over the dequantized view
(``reference_blocks`` over ``reference_q8.DequantizedBlocks``) and solves
the sampled rows' float64 normal equations over the DEQUANTIZED rows of
their lists, made again from the seed (``harness/reference_foldin_q8.py``);
the program's tracer is on while the engine is built in a traced run
(``serve/engine/table_upload``); and the host's used memory is sampled
through set-up and check, the machine's limit being what cuts the users.

``check`` is the skew cell's, check for check and limit for limit.  A
program whose fold-in cannot read a quantized table is refused at once,
before any data is made.
"""

from __future__ import annotations

import gc
import hashlib
import json
import os
import shutil
import sys
import threading
import time
import types

import numpy as np

from benchmarks.harness import (
    datagen, reference_blocks, reference_events, reference_foldin,
    reference_foldin_q8, reference_q8, seen_tail_blocks, stream_gen,
    stream_gen_skew)
from benchmarks.runners import serve_stream_skew
from benchmarks.runners.serve_blocks import _rss


def _require_program() -> None:
    serve_stream_skew._require_program()
    from cfk_tpu.ops import solve
    from cfk_tpu.serving import engine

    lacks = [name for name, there in (
        ("serving.engine.row_reader (an item table read in row blocks)",
         hasattr(engine, "row_reader")),
        ("ops.solve.gather_rows (a fold-in that gathers from the table as "
         "the engine holds it, codes and scales)",
         hasattr(solve, "gather_rows")),
    ) if not there]
    if lacks:
        sys.exit("FAILED: this program cannot run the cell: it lacks "
                 + ", ".join(lacks) + ": its fold-in reads one chip's "
                 "float32 item table only, and 48.19 M x 128 float32 fit "
                 "neither one chip nor this host beside the user table")


def _seen(ctx, config: dict):
    """The tail's seen lists, block by block, and their facts, from the
    corpus seed; cached under a name of their own."""
    s = config["seen_lists"]
    key = hashlib.sha256(json.dumps(
        ["tail-blocks", config["users"], config["items"],
         config["corpus_seed"], s["exponent"], s["max_len"],
         s["users_per_block"], config["engine"]["tile_m"]],
        sort_keys=True).encode()).hexdigest()[:16]
    base = os.path.join(ctx.cache_dir, f"seen_tail_blocks.{key}")
    t0 = time.perf_counter()
    if os.path.exists(base + ".ok"):
        with open(base + ".ok") as f:
            facts = json.load(f)
        out = np.load(base + ".items.npy"), np.load(base + ".indptr.npy")
        ctx.say(f"seen lists: cache hit, loaded in "
                f"{time.perf_counter() - t0:.1f} s")
    else:
        items, indptr, facts = seen_tail_blocks.seen_lists_blocks(
            config["users"], config["items"], exponent=s["exponent"],
            max_len=s["max_len"], seed=config["corpus_seed"],
            tile_m=config["engine"]["tile_m"],
            users_per_block=s["users_per_block"], threads=3)
        np.save(base + ".items.npy", items)
        np.save(base + ".indptr.npy", indptr)
        with open(base + ".ok", "w") as f:
            json.dump(facts, f)
        out = items, indptr
        ctx.say(f"seen lists: {items.size:,} cells built in blocks of "
                f"{s['users_per_block']:,} users and cached in "
                f"{time.perf_counter() - t0:.1f} s")
    ctx.say("seen lists: " + ", ".join(f"{k} {v:.6g}" for k, v in
                                       facts.items()))
    return (*out, facts)


class _HostWatch:
    """The machine's used memory (``MemTotal - MemAvailable``), sampled
    twice a second on a thread of its own between ``start`` and ``stop``:
    the one-chip machine ends a command at 40 GiB, and RSS does not see
    what the allocator holds (PERF.md section 6, PR 32)."""

    def __init__(self) -> None:
        self.peak_gib = 0.0
        self._stop = threading.Event()
        self._thread = None

    @staticmethod
    def used_gib() -> float:
        with open("/proc/meminfo") as f:
            kb = {line.split(":")[0]: int(line.split()[1]) for line in f
                  if line.startswith(("MemTotal", "MemAvailable"))}
        return (kb["MemTotal"] - kb["MemAvailable"]) / 2**20

    def _run(self) -> None:
        while not self._stop.wait(0.5):
            self.peak_gib = max(self.peak_gib, self.used_gib())

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-host-watch")
        self._thread.start()

    def stop(self) -> str:
        self._stop.set()
        self._thread.join()
        self.peak_gib = max(self.peak_gib, self.used_gib())
        return (f"the machine's used memory {self.used_gib():.2f} GiB now, "
                f"{self.peak_gib:.2f} at its peak")


class SkewStreamQ8Run(serve_stream_skew.SkewStreamServeRun):
    def __init__(self, ctx):
        _require_program()
        super().__init__(ctx)
        self.host = _HostWatch()

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
        import jax

        from cfk_tpu import telemetry
        from cfk_tpu.config import ALSConfig
        from cfk_tpu.serving import (
            RecommendServer, ServeClient, ServeEngine, ensure_serve_topics)
        from cfk_tpu.serving.engine import trace_count
        from cfk_tpu.streaming import (
            StreamConfig, StreamProducer, StreamSession, StreamState, foldin)
        from cfk_tpu.transport import CheckpointManager
        from cfk_tpu.transport.broker import InMemoryBroker

        ctx, config, mix = self.ctx, self.config, self.mix
        stream = config["stream"]
        self.trace_count = lambda: trace_count() + foldin.trace_count()
        self.k = int(mix["k"])
        self.host.start()
        with ctx.phase("setup_data_s"):
            self.seen_items, self.seen_indptr, facts = _seen(ctx, config)
            self.seen_width = facts["most_cells_a_user_a_tile"]
            t0 = time.perf_counter()
            self.base_ratings = stream_gen.rating_values(
                self.seen_items.shape[0], seed=config["corpus_seed"] + 1)
            seconds = max(ctx.seconds, mix.get("trace_seconds", 0))
            n_ratings = int(seconds * mix["rating_rate"]) + 1
            self.broker = InMemoryBroker()
            ensure_serve_topics(self.broker)
            self.producer = StreamProducer(
                self.broker, num_partitions=int(stream["partitions"]))
            ev = self.events = stream_gen_skew.stream_events(
                self.seen_indptr, self.seen_items, n_ratings,
                seed=ctx.seed + 4, rating_rate=float(mix["rating_rate"]),
                new_user_share=stream["new_user_share"],
                hot_share=mix["hot_share"], hot_users=int(mix["hot_users"]),
                hot_period_s=mix["hot_period_s"],
                rerate_share=mix["rerate_share"],
                rerate_pair_share=mix["rerate_pair_share"],
                rerate_pair_gap_s=mix["rerate_pair_gap_s"],
                late_share=mix["late_share"], late_by_s=mix["late_by_s"],
                seq0=self.producer.next_seq)
            self.r_users, self.r_items = ev.users, ev.items
            self.r_values, self.r_new = ev.values, ev.new
            ctx.say(f"ratings: {self.base_ratings.size:,} base values from "
                    f"the corpus seed, {n_ratings:,} events to stream from "
                    f"the seed ({int(ev.new.sum()):,} from users not in the "
                    f"base, {int(ev.rerate.sum()):,} re-rate a held cell, "
                    f"{int(ev.late.sum()):,} sent late, "
                    f"{int(reference_events.outranked(ev.users, ev.items, ev.seqs).sum()):,}"
                    f" arrive after a newer event of their cell) in "
                    f"{time.perf_counter() - t0:.1f} s; {_rss()}")
            t0 = time.perf_counter()
            scale = config["factor_scale"]
            self.users_tab = datagen.factor_table(
                config["users"], config["rank"], seed=ctx.seed, scale=scale)
            ctx.say(f"user table from the seed in "
                    f"{time.perf_counter() - t0:.1f} s; {_rss()}")
            # the item factors are never made whole: the engine reads them a
            # row range at a time (one buffer, made over: a slice is
            # quantized before the next is read); the reference (check)
            # makes them again.  ``_reopen_cells`` deletes the attribute
            self.items_tab = None
            self.table_seed = dict(seed=ctx.seed + 1, scale=scale)
            items = reference_q8.FactorBlocks(
                config["items"], config["rank"], reuse=True,
                **self.table_seed)
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
            del items
            data, scales = self.engine.fold_table()
            ctx.say(f"engine: {config['users']:,} users, {config['items']:,} x "
                    f"{config['rank']} items read in row blocks (table_dtype="
                    f"{self.engine.table_dtype}, tile_m={self.engine.tile_m}, "
                    f"{self.engine.table_rows} table rows = {data.nbytes:,} B"
                    f" of codes + {scales.nbytes:,} B of scales) in "
                    f"{time.perf_counter() - t0:.1f} s; {_rss()}")
            t0 = time.perf_counter()
            shutil.rmtree(os.path.join(ctx.cache_dir, "stream"),
                          ignore_errors=True)
            self.store = os.path.join(
                ctx.cache_dir, "stream", f"{ctx.cell['name']}.{ctx.seed}")
            # the solve is float32 whatever the table stores
            self.als = ALSConfig(
                rank=config["rank"], lam=stream["lam"], dtype="float32",
                solver=stream["solver"],
                health_check_every=stream["health_check_every"])
            self.stream_config = StreamConfig(
                batch_records=int(stream["batch_records"]))
            self.session = StreamSession(
                self._state(StreamState), self.als, self.broker,
                CheckpointManager(
                    self.store,
                    max_pending=int(stream["max_pending_commits"])),
                stream=self.stream_config,
                base_model=types.SimpleNamespace(user_factors=self.users_tab),
                engine=self.engine)
            ctx.say(f"session: state from the CSR, bootstrap snapshot under "
                    f"{os.path.relpath(self.store, ctx.cache_dir)} in "
                    f"{time.perf_counter() - t0:.1f} s")
        self.commits: list = []
        self.session.add_commit_listener(self._on_commit)
        self.server = RecommendServer(
            self.engine, self.broker, max_batch=int(mix["max_batch"]),
            session=self.session)
        self.client = ServeClient(self.broker)
        n = int(seconds * mix["rate"]) + 1
        zipf = datagen.zipf_users(config["users"], n, seed=ctx.seed + 2,
                                  a=mix["zipf_a"])
        self.users, self.is_followup = stream_gen.with_followups(
            zipf, rate=float(mix["rate"]), rating_users=self.r_users,
            rating_new=self.r_new, rating_rate=float(mix["rating_rate"]),
            share=float(mix["followup_share"]),
            delay_s=float(mix["followup_delay_s"]), seed=ctx.seed + 5)
        with ctx.phase("setup_compile_s"):
            self.k_pad = max(8, 1 << (self.k - 1).bit_length())
            warm = self.engine.prewarm(self.k_pad,
                                       max_batch=int(mix["max_batch"]),
                                       user_rows=zipf)
            fold = self.session.prewarm()
        gc.freeze()
        gc.disable()
        stats = jax.devices()[0].memory_stats() or {}
        ctx.say(f"prewarm: {warm['programs']} batch programs, "
                f"{warm['new_traces']} traced, {warm['prewarm_s']:.1f} s; "
                f"{fold['programs']} fold-in programs against the table as "
                f"held, {fold['new_traces']} traced, {fold['prewarm_s']:.1f} "
                f"s; the heaviest (user, tile) holds {self.seen_width} cells "
                "of the rectangle's width bucket of 16; the device holds "
                f"{stats.get('bytes_in_use', 0):,} B now, "
                f"{stats.get('peak_bytes_in_use', 0):,} at its peak (codes + "
                f"scales are {data.nbytes + scales.nbytes:,}: no float32 "
                "block of the table beside them)")
        ctx.say("host after set-up: " + self.host.stop())

    # -- the comparison that decides ``correct`` -----------------------------

    def _reference_tables(self, users, vectors, lists, ids, rows_of):
        """(best, exact scores at the served ids) block by block over the
        dequantized view, and the dequantized rows ``rows_of`` names, both
        from item blocks made again from the seed."""
        config = self.config
        view = reference_q8.DequantizedBlocks(
            config["items"], config["rank"], reuse=True, **self.table_seed)
        t0 = time.perf_counter()
        best, _, at = reference_blocks.exact_topk_blocks(
            vectors, view, lists, self.k, ids,
            block=min(view.threads, 6) * reference_q8.BLOCK)
        t1 = time.perf_counter()
        rows = reference_foldin_q8.RowTable(
            view, rows_of, blocks_a_read=min(view.threads, 6))
        self.ctx.say(
            f"reference: item blocks made again from the seed, quantized "
            f"and dequantized by the rule in numpy: exact float32 "
            f"top-{self.k} of {len(users)} users over {config['items']:,} "
            f"rows in {t1 - t0:.1f} s, {rows.ids.shape[0]:,} rows of the "
            f"sampled lists in {time.perf_counter() - t1:.1f} s "
            f"({rows.reads} reads); {_rss()}")
        return best, at, rows

    def check(self, window: dict) -> list:
        gc.enable()
        self.host.start()
        config, res, ev = self.config, self.result, self.events
        limits, why = config["checks"], config["checks"]["why"]
        stream = config["stream"]
        users_n = config["users"]
        rng = np.random.default_rng(self.ctx.seed + 3)
        sent = res.ratings_sent
        r_users, r_items = ev.users[:sent], ev.items[:sent]
        r_values, r_seqs = ev.values[:sent], self.seqs[:sent]
        r_commit = self.rating_commit
        list_len = np.diff(self.seen_indptr)
        heavy_cells = int(limits["heavy_cells"])
        # a user's events in the order they ARRIVED
        streamed: dict[int, list] = {}
        for j in range(sent):
            if r_users[j] < users_n:
                streamed.setdefault(int(r_users[j]), []).append(j)

        def base_of(u):
            lo, hi = self.seen_indptr[u], self.seen_indptr[u + 1]
            return self.seen_items[lo:hi], self.base_ratings[lo:hi]

        def list_of(u, ordinal):
            return reference_events.list_as_of(*base_of(u), [
                (r_items[j], r_values[j], r_seqs[j],
                 r_commit[j] if r_commit[j] >= 0 else np.inf)
                for j in streamed.get(int(u), ())], ordinal)

        def is_heavy(u):
            return u < users_n and list_len[u] > heavy_cells

        ok = {rid: r for rid, r in res.responses.items() if not r.error}
        rids = sorted(ok)
        # 1: K distinct in-range rows, none in the list as of the ordinal
        bad = reference_foldin.invalid_id_sets(
            [ok[r].movie_rows for r in rids],
            [list_of(res.users_of[r], ok[r].ordinal)[0]
             if res.users_of[r] in streamed else base_of(res.users_of[r])[0]
             for r in rids], config["items"], self.k)
        # 4: no request sent visible_within_s after an event of its user
        # names an ordinal before that event's commit
        stale = 0
        within = float(stream["visible_within_s"])
        for rid in rids:
            u = res.users_of[rid]
            if u in streamed:
                t_req = res.req_sent_s[res.req_index[rid]]
                for j in streamed[u]:
                    if (res.rating_sent_s[j] + within < t_req
                            and not 0 <= r_commit[j] <= ok[rid].ordinal):
                        stale += 1
        row_at: dict[int, list] = {}
        for ordinal, _, _, touched, rows in self.commits:
            for i, row in enumerate(touched.tolist()):
                row_at.setdefault(row, []).append((ordinal, rows[i]))

        def vector_of(u, ordinal):
            last = [r for o, r in row_at.get(int(u), ()) if o <= ordinal]
            return last[-1] if last else self.users_tab[u]

        def some(pool, n):
            return [pool[i] for i in rng.choice(len(pool), min(n, len(pool)),
                                                False)]

        # 2's sample: answers against the exact float32 top-K of the vector
        # and the list as of the ordinal each names; sample_heavy of them
        # from users holding more than heavy_cells, half the rest follow-ups
        heavy = [r for r in rids if is_heavy(res.users_of[r])]
        light = [r for r in rids if not is_heavy(res.users_of[r])]
        follow = [r for r in light if self.is_followup[res.req_index[r]]]
        plain = [r for r in light if not self.is_followup[res.req_index[r]]]
        take_heavy = some(heavy, limits["sample_heavy"])
        take_follow = some(follow, limits["sample_followups"])
        take = sorted(take_heavy + take_follow + some(
            plain, limits["sample_responses"] - len(take_heavy)
            - len(take_follow)))
        # 5's sample: folded-in rows against the float64 solve of their own
        # normal equations over the DEQUANTIZED rows of the list as of their
        # commit; sample_heavy of them rows of users over heavy_cells
        pairs = [(row, o) for row, hist in sorted(row_at.items())
                 if row < users_n for o, _ in hist]
        heavy_pairs = [p for p in pairs if is_heavy(p[0])]
        light_pairs = [p for p in pairs if not is_heavy(p[0])]
        pick_heavy = some(heavy_pairs, limits["sample_heavy"])
        picks = sorted(pick_heavy + some(
            light_pairs, limits["sample_rows"] - len(pick_heavy)))
        pick_lists = {p: list_of(*p) for p in picks}
        rank_gap = score_err = row_err = float("inf")
        errs: dict = {}
        if take and picks:
            take_lists = [list_of(res.users_of[r], ok[r].ordinal)[0]
                          for r in take]
            best, at, rows = self._reference_tables(
                [res.users_of[r] for r in take],
                np.stack([vector_of(res.users_of[r], ok[r].ordinal)
                          for r in take]), take_lists,
                np.stack([ok[r].movie_rows for r in take]),
                np.concatenate([mv for mv, _ in pick_lists.values()]))
            if len(take_heavy) >= limits["sample_heavy"]:
                rank_gap, score_err = reference_blocks.topk_gaps(
                    np.stack([ok[r].scores for r in take]), best, at)
            errs = {p: reference_foldin.row_err(
                vector_of(*p), reference_foldin.solve_row(
                    rows, *pick_lists[p], stream["lam"]))
                for p in picks}
            if len(pick_heavy) >= limits["sample_heavy"]:
                row_err = max(errs.values())
        fresh = int(self.session.metrics.counters.get("updates_fresh", 0))
        # 6 and 7: the store reopened
        reopened, misordered = self._reopen_cells(
            [row for row, _ in picks], r_users, r_items, r_values, r_seqs)
        self.ctx.say(
            f"output check: every one of {len(rids):,} answered id sets held "
            "to 'K distinct in-range, none in the list as of the ordinal "
            f"named'; {len(take)} seeded responses ({len(take_heavy)} of "
            f"users holding more than {heavy_cells} cells, "
            f"{len(take_follow)} follow-ups) against numpy's exact float32 "
            f"top-K over the dequantized table as of their ordinals; "
            f"{len(picks)} folded-in rows ({len(pick_heavy)} of such users: "
            f"foldin_row_err "
            f"{max([errs[p] for p in pick_heavy if p in errs], default=float('nan')):.3g}"
            f" on them, "
            f"{max([errs[p] for p in picks if p in errs and p not in set(pick_heavy)], default=float('nan')):.3g}"
            f" on the others) against the float64 solve over the dequantized "
            f"rows; {len(self.commits)} commit units, {fresh:,} fresh cells")
        self.ctx.say("host after the check: " + self.host.stop())
        return [
            ("failed_requests", window["failed_requests"], 0,
             why["failed_requests"]),
            ("invalid_id_sets", bad, 0, why["invalid_id_sets"]),
            ("rank_gap", rank_gap, limits["rank_gap"], why["rank_gap"]),
            ("score_err", score_err, limits["score_err"], why["score_err"]),
            ("lost_ratings", self.lost, 0, why["lost_ratings"]),
            ("stale_reads", stale, 0, why["stale_reads"]),
            ("foldin_row_err", row_err, limits["foldin_row_err"],
             why["foldin_row_err"]),
            ("reopened_store", reopened, 0, why["reopened_store"]),
            ("misordered_cells", misordered, 0, why["misordered_cells"]),
        ]


def make(ctx):
    return SkewStreamQ8Run(ctx)
