"""Runner of the serve-while-folding-in mix under the skew its users have:
``runners/serve_stream.py``'s server, engine, session, store and request
schedule (the control's run, subclassed), over seen lists with a power-law
tail (``harness/seen_tail.py``) and a stream whose events carry their own
sequence numbers (``harness/stream_gen_skew.py``): activity-weighted users,
a hot set that moves, re-ratings of held cells, events sent later than their
place in event order.

``check`` is the control's, check for check and limit for limit, with the
lists and vectors read through ``harness/reference_events.py`` (a cell holds
the rating of its highest event ``seq`` among those committed at the
ordinal, whatever order they arrived in), the samples drawn so that a share
of them are users holding more than ``heavy_cells`` cells, and one more
exact check: ``misordered_cells``.  A program that cannot take the events'
own sequence numbers, or has no fold-in whose work follows the cells, is
refused at once, before any data is made.
"""

from __future__ import annotations

import gc
import hashlib
import inspect
import json
import os
import shutil
import sys
import time
import types

import numpy as np

from benchmarks.harness import (
    datagen, loadgen_stream_skew, reference_events, reference_foldin,
    seen_tail, stream_gen, stream_gen_skew)
from benchmarks.harness.stats import percentile
from benchmarks.runners import serve_stream


def _require_program() -> None:
    serve_stream._require_program()
    from cfk_tpu.streaming import StreamProducer, foldin
    from cfk_tpu.streaming.state import ApplyStats

    lacks = [name for name, there in (
        ("StreamProducer.send_many(seqs=)", "seqs" in inspect.signature(
            StreamProducer.send_many).parameters),
        ("streaming.foldin.fold_route (a fold-in whose work follows the "
         "cells)", hasattr(foldin, "fold_route")),
        ("ApplyStats.rerated", "rerated" in getattr(
            ApplyStats, "__dataclass_fields__", {})),
    ) if not there]
    if lacks:
        sys.exit("FAILED: this program cannot run the cell: it lacks "
                 + ", ".join(lacks) + " (events ordered by their own "
                 "sequence numbers, folded in over lists of 10,000 cells by "
                 "a fixed set of programs)")


def _seen(ctx, config: dict):
    """The tail's seen lists and their facts, from the corpus seed; cached
    under a name of their own (``seen_tail.<key>``: the control's files are
    ``seen.<key>``)."""
    s = config["seen_lists"]
    key = hashlib.sha256(json.dumps(
        [config["users"], config["items"], config["corpus_seed"], s,
         config["engine"]["tile_m"]], sort_keys=True).encode()).hexdigest()[:16]
    base = os.path.join(ctx.cache_dir, f"seen_tail.{key}")
    t0 = time.perf_counter()
    if os.path.exists(base + ".ok"):
        with open(base + ".ok") as f:
            facts = json.load(f)
        out = np.load(base + ".items.npy"), np.load(base + ".indptr.npy")
        ctx.say(f"seen lists: cache hit, loaded in "
                f"{time.perf_counter() - t0:.1f} s")
    else:
        items, indptr, facts = seen_tail.seen_lists(
            config["users"], config["items"], exponent=s["exponent"],
            max_len=s["max_len"], seed=config["corpus_seed"],
            tile_m=config["engine"]["tile_m"])
        np.save(base + ".items.npy", items)
        np.save(base + ".indptr.npy", indptr)
        with open(base + ".ok", "w") as f:
            json.dump(facts, f)
        out = items, indptr
        ctx.say(f"seen lists: {items.size:,} cells built and cached in "
                f"{time.perf_counter() - t0:.1f} s")
    ctx.say("seen lists: " + ", ".join(f"{k} {v:.6g}" for k, v in
                                       facts.items()))
    return (*out, facts)


class SkewStreamServeRun(serve_stream.StreamServeRun):
    def __init__(self, ctx):
        _require_program()
        super().__init__(ctx)

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
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
        with ctx.phase("setup_data_s"):
            self.seen_items, self.seen_indptr, facts = _seen(ctx, config)
            # the width bucket the exclusion rectangle takes from the
            # heaviest (user, tile): what ``ServeEngine.prewarm`` must cover
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
                    f"{time.perf_counter() - t0:.1f} s")
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
            t0 = time.perf_counter()
            # one run at a time in a checkout: what an ended run left goes
            shutil.rmtree(os.path.join(ctx.cache_dir, "stream"),
                          ignore_errors=True)
            self.store = os.path.join(
                ctx.cache_dir, "stream", f"{ctx.cell['name']}.{ctx.seed}")
            self.als = ALSConfig(
                rank=config["rank"], lam=stream["lam"],
                dtype=config["table_dtype"], solver=stream["solver"],
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
        ctx.say(f"prewarm: {warm['programs']} batch programs, "
                f"{warm['new_traces']} traced, {warm['prewarm_s']:.1f} s; "
                f"{fold['programs']} fold-in programs, {fold['new_traces']} "
                f"traced, {fold['prewarm_s']:.1f} s; the heaviest (user, "
                f"tile) holds {self.seen_width} cells of the rectangle's "
                "width bucket of 16")

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float) -> dict:
        mix, stream = self.mix, self.config["stream"]
        ev = self.events
        traces = self.trace_count()
        first_commit = len(self.commits)
        offset0 = self.session.consumer.cursors[0] + self.session.backlog()
        counters = self.session.metrics.counters
        fresh0 = counters.get("updates_fresh", 0)
        stale0 = counters.get("updates_stale", 0)
        # a second window of one process (``tools/sweep.py``) sends the
        # events again as newer ones: their numbers go on from the last
        self.seqs = ev.seqs + (self.producer.next_seq - int(ev.seqs.min()))
        res = loadgen_stream_skew.run_open_loop(
            self.client, self.server, self.session, self.producer,
            users=self.users, rate=float(mix["rate"]),
            ratings=(ev.users, ev.items, ev.values, self.seqs),
            rating_rate=float(mix["rating_rate"]), seconds=seconds, k=self.k,
            drain_s=float(mix["drain_seconds"]))
        self.result = res
        new_traces = self.trace_count() - traces
        errors = sum(1 for r in res.responses.values() if r.error)
        rate = res.answered_in_window / res.window_s
        # each event's commit: the first one whose cursor passed its place
        # in the log (its place in ARRIVAL order)
        commits = self.commits[first_commit:]
        cursors = np.asarray([c[1] for c in commits], np.int64)
        sent = res.ratings_sent
        at = np.searchsorted(cursors, offset0 + np.arange(sent), side="right")
        done = at < len(commits)
        self.rating_commit = np.where(
            done, np.asarray([c[0] for c in commits] + [0])[
                np.minimum(at, len(commits))], -1)
        visible_s = np.asarray([c[2] for c in commits] + [np.inf])[
            np.minimum(at, len(commits))]
        visible_ms = (visible_s - res.rating_sent_s) * 1e3
        late = int(np.sum(~(visible_ms <= stream["visible_within_s"] * 1e3)))
        in_window = [c for c in commits if c[2] <= res.t_close]
        committed_in_window = (int(in_window[-1][1]) - offset0
                               if in_window else 0)
        touched = sum(len(c[3]) for c in in_window)
        fresh = int(counters.get("updates_fresh", 0) - fresh0)
        stale = int(counters.get("updates_stale", 0) - stale0)
        outranked = int(reference_events.outranked(
            ev.users[:sent], ev.items[:sent], self.seqs[:sent]).sum())
        self.ctx.say(
            f"window: {res.offered:,} requests offered at {mix['rate']} req/s; "
            f"{res.answered_in_window:,} answered in the {res.window_s:.3f} s "
            f"to its close = {rate:.2f} req/s, backlog then "
            f"{res.backlog_at_close:,}; {len(res.batch_sizes)} batches, "
            f"{new_traces} new program traces")
        self.ctx.say(
            f"stream: {sent:,} events sent at {mix['rating_rate']} a second "
            f"({int(ev.late[:sent].sum()):,} late, "
            f"{int(ev.rerate[:sent].sum()):,} re-rates); "
            f"{committed_in_window:,} committed by the close in "
            f"{len(in_window)} micro-batches ({touched:,} rows re-solved) = "
            f"{committed_in_window / res.window_s:.2f} events/s; {fresh:,} "
            f"fresh + {stale:,} outranked (the reference counts "
            f"{outranked:,}); visible after p50 "
            f"{percentile(visible_ms[done], 50):.1f} / p95 "
            f"{percentile(visible_ms[done], 95):.1f} / longest "
            f"{visible_ms[done].max():.1f} ms; {late} late, "
            f"{int(np.sum(~done))} never committed, "
            f"{res.ratings_outstanding} outstanding after the drain")
        self.ctx.say(
            f"drain: {res.drain_s:.2f} s of at most {mix['drain_seconds']} s, "
            f"{res.unanswered} requests still unanswered, {errors} error "
            "responses")
        ends, sizes = np.asarray(res.batch_ends_s), np.cumsum(res.batch_sizes)
        if ends.size > 8:
            gaps = np.diff(ends) * 1e3
            third = int(np.searchsorted(ends, seconds / 3, side="right")) - 1
            self.ctx.say(
                f"req/s over the first {ends[third]:.1f} s "
                f"{sizes[third] / ends[third]:.2f}; batch period p5 "
                f"{percentile(gaps, 5):.1f} / p50 {percentile(gaps, 50):.1f}"
                f" / p95 {percentile(gaps, 95):.1f} / longest "
                f"{gaps.max():.1f} ms, {int((gaps > 100).sum())} over 100 "
                f"ms; generator lateness p95 "
                f"{percentile(res.late_ms, 95):.1f} ms; "
                f"{int(self.is_followup[:res.offered].sum()):,} follow-ups")
        # every event committed exactly once, none outstanding: fresh +
        # outranked = events sent = where the cursor stands, and the
        # outranked are the reference's, event for event in number
        cursor = self.session.consumer.cursors[0] - offset0
        self.lost = (int(np.sum(~done)) + res.ratings_outstanding
                     + abs(fresh + stale - sent) + abs(cursor - sent)
                     + abs(stale - outranked))
        return {"window_s": res.window_s,
                "attempted": res.offered + sent,
                "failed": errors + res.unanswered + late,
                "failed_requests": errors + res.unanswered,
                "new_traces": new_traces,
                "batch_sizes": res.batch_sizes,
                "latency_ms": res.latency_ms, "late_ms": res.late_ms,
                "end_to_end": {"serve_req_per_s": rate},
                "table_rows": self.engine.table_rows, "k_pad": self.k_pad,
                "visible_ms": visible_ms[done],
                "ratings_committed_in_window": committed_in_window,
                "rows_solved_in_window": touched,
                "micro_batches_in_window": len(in_window)}

    # -- the comparison that decides ``correct`` -----------------------------

    def check(self, window: dict) -> list:
        gc.enable()
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

        # 2: sampled answers against the exact float32 top-K of the vector
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
        rank_gap = score_err = float("inf")
        if take and len(take_heavy) >= limits["sample_heavy"]:
            best, scores = reference_foldin.exact_topk(
                np.stack([vector_of(res.users_of[r], ok[r].ordinal)
                          for r in take]), self.items_tab,
                [list_of(res.users_of[r], ok[r].ordinal)[0] for r in take],
                self.k)
            rank_gap, score_err = reference_foldin.topk_gaps(
                np.stack([ok[r].movie_rows for r in take]),
                np.stack([ok[r].scores for r in take]), best, scores)
            del scores
        # 5: sampled folded-in rows against the float64 solve of their own
        # normal equations over the list as of their commit; sample_heavy
        # of them rows of users holding more than heavy_cells
        pairs = [(row, o) for row, hist in sorted(row_at.items())
                 if row < users_n for o, _ in hist]
        heavy_pairs = [p for p in pairs if is_heavy(p[0])]
        light_pairs = [p for p in pairs if not is_heavy(p[0])]
        pick_heavy = some(heavy_pairs, limits["sample_heavy"])
        picks = sorted(pick_heavy + some(
            light_pairs, limits["sample_rows"] - len(pick_heavy)))

        def err_of(row, o):
            return reference_foldin.row_err(
                vector_of(row, o), reference_foldin.solve_row(
                    self.items_tab, *list_of(row, o), stream["lam"]))

        errs = {p: err_of(*p) for p in picks}
        row_err = (max(errs.values())
                   if len(pick_heavy) >= limits["sample_heavy"]
                   else float("inf"))
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
            f"top-K as of their ordinals; {len(picks)} folded-in rows "
            f"({len(pick_heavy)} of such users: foldin_row_err "
            f"{max([errs[p] for p in pick_heavy], default=float('nan')):.3g}"
            f" on them, "
            f"{max([errs[p] for p in picks if p not in set(pick_heavy)], default=float('nan')):.3g}"
            f" on the others) against the float64 solve; "
            f"{len(self.commits)} commit units, {fresh:,} fresh cells")
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

    def _reopen_cells(self, rows, users, items, values, seqs):
        """(``_reopen``'s count, the written cells whose value in the
        reopened store is not that of their highest event ``seq``)."""
        from cfk_tpu.streaming import StreamSession, StreamState
        from cfk_tpu.transport import CheckpointManager
        from cfk_tpu.resilience.loop import drain_checkpoints

        t0 = time.perf_counter()
        drain_checkpoints(self.session.manager)
        live = self.session.user_rows(rows)
        del self.items_tab  # room for the snapshot's table on a 40 GiB host
        again = StreamSession(
            self._state(StreamState), self.als, self.broker,
            CheckpointManager(self.store), stream=self.stream_config)
        wrong = int(again.consumer.cursors != self.session.consumer.cursors)
        wrong += int(again.stream_step != self.session.stream_step)
        restored = again.user_rows(rows)
        wrong += sum(not np.array_equal(a, b) for a, b in zip(live, restored))
        units = again.metrics.counters.get("replayed_units", 0)
        win = reference_events.winners(users, items, seqs)
        misordered, cells = 0, len(win)
        edges = np.flatnonzero(np.concatenate(
            ([True], users[win][1:] != users[win][:-1], [True])))
        for lo, hi in zip(edges[:-1], edges[1:]):
            mine = win[lo:hi]
            row = again.state.user_row(int(users[mine[0]]))
            mv, rt = (again.state.neighbors(row) if row is not None
                      else (np.zeros(0, np.int32), np.zeros(0, np.float32)))
            if not mv.shape[0]:
                misordered += mine.shape[0]
                continue
            at = np.minimum(np.searchsorted(mv, items[mine]), mv.shape[0] - 1)
            misordered += int(np.sum(~((mv[at] == items[mine])
                                       & (rt[at] == values[mine]))))
        del again
        shutil.rmtree(self.store, ignore_errors=True)
        self.ctx.say(
            f"store reopened in {time.perf_counter() - t0:.1f} s: snapshot + "
            f"{units} units, cursor and {len(rows)} sampled rows compared "
            f"with the live session: {wrong} differ; {cells:,} written cells "
            f"against the value of their highest seq: {misordered} differ")
        return wrong, misordered


def make(ctx):
    return SkewStreamServeRun(ctx)
