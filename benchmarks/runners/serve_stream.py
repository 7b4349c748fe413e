"""Runner of the serve-while-folding-in mix: ``runners/serve.py``'s server,
engine, seen lists and request schedule, with the users' new ratings arriving
on the program's log and folded into the live user factors while it answers.

One ``RecommendServer`` drives both (``session=``): a ``StreamSession`` built
from the seen lists' CSR (``StreamState.from_csr``) folds each micro-batch in
against the engine's own item table (``engine=``), commits it as one unit of
a durable store under ``benchmarks/.cache/stream/`` and publishes it to the
engine; every response names the commit ordinal its batch saw.  A program
without those entry points is refused at once, before any data is made.

The seen lists are the control cell's to the byte (``serve._seen``: the same
cache file); their ratings' values come from ``corpus_seed``; the factor
tables, the request users, the streamed ratings and every sample from
``--seed``.  ``check`` holds the run to the configuration's six guarantees
against ``harness/reference_foldin.py`` and ``harness/reference.py``.
"""

from __future__ import annotations

import gc
import inspect
import os
import shutil
import sys
import time
import types

import numpy as np

from benchmarks.harness import (
    datagen, loadgen_stream, reference_foldin, stream_gen)
from benchmarks.harness.stats import percentile
from benchmarks.runners import serve


def _require_program() -> None:
    from cfk_tpu.serving import RecommendServer, ServeEngine
    from cfk_tpu.streaming import StreamSession, StreamState

    lacks = [name for name, there in (
        ("StreamState.from_csr", hasattr(StreamState, "from_csr")),
        ("ServeEngine.fold_table", hasattr(ServeEngine, "fold_table")),
        ("StreamSession(engine=)", "engine" in inspect.signature(
            StreamSession.__init__).parameters),
        ("StreamSession.pump", hasattr(StreamSession, "pump")),
        ("RecommendServer(session=)", "session" in inspect.signature(
            RecommendServer.__init__).parameters),
    ) if not there]
    if lacks:
        sys.exit("FAILED: this program cannot run the cell: it lacks "
                 + ", ".join(lacks) + " (a stream folded in against the "
                 "serving engine's table, driven by the request server)")


class StreamServeRun:
    def __init__(self, ctx):
        _require_program()
        self.ctx = ctx
        self.config, self.mix = ctx.config, ctx.traffic

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
            self.seen_items, self.seen_indptr = serve._seen(ctx, config)
            t0 = time.perf_counter()
            self.base_ratings = stream_gen.rating_values(
                self.seen_items.shape[0], seed=config["corpus_seed"] + 1)
            seconds = max(ctx.seconds, mix.get("trace_seconds", 0))
            n_ratings = int(seconds * mix["rating_rate"]) + 1
            (self.r_users, self.r_items, self.r_values,
             self.r_new) = stream_gen.stream_ratings(
                self.seen_indptr, self.seen_items, n_ratings,
                seed=ctx.seed + 4, new_user_share=stream["new_user_share"])
            ctx.say(f"ratings: {self.base_ratings.size:,} base values from "
                    f"the corpus seed, {n_ratings:,} to stream from the seed "
                    f"({int(self.r_new.sum()):,} from users not in the base) "
                    f"in {time.perf_counter() - t0:.1f} s")
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
            self.broker = InMemoryBroker()
            ensure_serve_topics(self.broker)
            self.producer = StreamProducer(
                self.broker, num_partitions=int(stream["partitions"]))
            # one run at a time in a checkout: what an ended run left (a
            # snapshot the size of the user table) goes first
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
        # every commit, as the engine's listener left it: which ratings it
        # holds (the cursor after it), when it was visible, the rows solved
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
            # the server pads k to a power of two (at least 8)
            self.k_pad = max(8, 1 << (self.k - 1).bit_length())
            warm = self.engine.prewarm(self.k_pad,
                                       max_batch=int(mix["max_batch"]),
                                       user_rows=zipf)
            fold = self.session.prewarm()
        # a server's collector settings: what set-up built is never garbage
        # (frozen), and the window's objects (responses, overlays, commit
        # events: millions, none of them in a cycle) are freed by their
        # reference counts; the collector's passes over them stall whichever
        # stage allocates for 100-300 ms (PERF.md section 6, PR 34), so it
        # stays off from here to the check
        gc.freeze()
        gc.disable()
        ctx.say(f"prewarm: {warm['programs']} batch programs, "
                f"{warm['new_traces']} traced, {warm['prewarm_s']:.1f} s; "
                f"{fold['programs']} fold-in programs, {fold['new_traces']} "
                f"traced, {fold['prewarm_s']:.1f} s")

    def _state(self, StreamState):
        return StreamState.from_csr(
            self.seen_indptr, self.seen_items, self.base_ratings,
            num_movies=self.config["items"])

    def _on_commit(self, event: dict) -> None:
        if event.get("retrain"):
            return
        self.commits.append((
            int(event["stream_step"]), int(event["cursors"][0]),
            time.perf_counter(),
            np.asarray(event["touched_rows"], np.int64), event["rows"]))

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float) -> dict:
        mix, stream = self.mix, self.config["stream"]
        traces = self.trace_count()
        first_commit = len(self.commits)
        offset0 = self.session.consumer.cursors[0] + self.session.backlog()
        fresh0 = self.session.metrics.counters.get("updates_fresh", 0)
        res = loadgen_stream.run_open_loop(
            self.client, self.server, self.session, self.producer,
            users=self.users, rate=float(mix["rate"]),
            ratings=(self.r_users, self.r_items, self.r_values),
            rating_rate=float(mix["rating_rate"]), seconds=seconds, k=self.k,
            drain_s=float(mix["drain_seconds"]))
        self.result = res
        new_traces = self.trace_count() - traces
        errors = sum(1 for r in res.responses.values() if r.error)
        rate = res.answered_in_window / res.window_s
        # each rating's commit: the first one whose cursor passed it
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
        self.ctx.say(
            f"window: {res.offered:,} requests offered at {mix['rate']} req/s; "
            f"{res.answered_in_window:,} answered in the {res.window_s:.3f} s "
            f"to its close = {rate:.2f} req/s, backlog then "
            f"{res.backlog_at_close:,}; {len(res.batch_sizes)} batches, "
            f"{new_traces} new program traces")
        self.ctx.say(
            f"stream: {sent:,} ratings sent at {mix['rating_rate']} a second; "
            f"{committed_in_window:,} committed by the close in "
            f"{len(in_window)} micro-batches ({touched:,} rows re-solved) = "
            f"{committed_in_window / res.window_s:.2f} ratings/s; visible "
            f"after p50 {percentile(visible_ms[done], 50):.1f} / p95 "
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
            parts = []
            for share in (1 / 3, 2 / 3):
                i = int(np.searchsorted(ends, share * seconds, side="right")) - 1
                if i >= 0:
                    parts.append(f"first {ends[i]:.1f} s {sizes[i] / ends[i]:.2f}")
            gaps = np.diff(ends) * 1e3
            self.ctx.say(
                "req/s over " + ", ".join(parts)
                + f"; {int((gaps > 50).sum())} periods over 50 ms, "
                f"{int((gaps > 100).sum())} over 100 ms")
            self.ctx.say(
                f"batch period p5 {percentile(gaps, 5):.1f} / p50 "
                f"{percentile(gaps, 50):.1f} / p95 {percentile(gaps, 95):.1f}"
                f" / longest {gaps.max():.1f} ms; generator lateness p95 "
                f"{percentile(res.late_ms, 95):.1f} ms; "
                f"{int(self.is_followup[:res.offered].sum()):,} follow-ups")
        # every rating committed exactly once, none outstanding: what the
        # session counted fresh and where its cursor stands, against the
        # ratings sent.  A rating committed, but visible later than
        # visible_within_s after its sending, is a failed operation and not
        # a lost one: a stall of the machine past visible_within_s delays
        # every rating sent just before it, whatever the program does, and
        # breaks no read (``stale_reads`` holds the run to those)
        fresh = self.session.metrics.counters.get("updates_fresh", 0) - fresh0
        cursor = self.session.consumer.cursors[0] - offset0
        self.lost = (int(np.sum(~done)) + res.ratings_outstanding
                     + abs(fresh - sent) + abs(cursor - sent))
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
        config, res = self.config, self.result
        limits, why = config["checks"], config["checks"]["why"]
        stream = config["stream"]
        users_n = config["users"]
        rng = np.random.default_rng(self.ctx.seed + 3)
        sent = res.ratings_sent
        r_users, r_items = self.r_users[:sent], self.r_items[:sent]
        r_values, r_commit = self.r_values[:sent], self.rating_commit
        # a user's streamed cells in the order they were sent
        streamed: dict[int, list] = {}
        for j in range(sent):
            if r_users[j] < users_n:
                streamed.setdefault(int(r_users[j]), []).append(j)

        def base_of(u):
            lo, hi = self.seen_indptr[u], self.seen_indptr[u + 1]
            return self.seen_items[lo:hi], self.base_ratings[lo:hi]

        def cells_of(u):
            return [(r_items[j], r_values[j],
                     r_commit[j] if r_commit[j] >= 0 else np.inf)
                    for j in streamed.get(int(u), ())]

        def list_of(u, ordinal):
            return reference_foldin.list_as_of(*base_of(u), cells_of(u),
                                               ordinal)

        ok = {rid: r for rid, r in res.responses.items() if not r.error}
        rids = sorted(ok)
        # 1: K distinct in-range rows, none in the list as of the ordinal
        bad = reference_foldin.invalid_id_sets(
            [ok[r].movie_rows for r in rids],
            [list_of(res.users_of[r], ok[r].ordinal)[0]
             if res.users_of[r] in streamed else base_of(res.users_of[r])[0]
             for r in rids], config["items"], self.k)
        # 4: no request sent visible_within_s after a rating of its user
        # names an ordinal before that rating's commit
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
        # the rows as committed, per (user row, ordinal)
        row_at: dict[int, list] = {}
        for ordinal, _, _, touched, rows in self.commits:
            for i, row in enumerate(touched.tolist()):
                row_at.setdefault(row, []).append((ordinal, rows[i]))

        def vector_of(u, ordinal):
            last = [r for o, r in row_at.get(int(u), ()) if o <= ordinal]
            return last[-1] if last else self.users_tab[u]

        # 2: sampled answers against the exact float32 top-K of the vector
        # and the list as of the ordinal each names; half are follow-ups
        follow = [r for r in rids if self.is_followup[res.req_index[r]]]
        plain = [r for r in rids if not self.is_followup[res.req_index[r]]]
        n_follow = min(limits["sample_followups"], len(follow))
        n_plain = min(limits["sample_responses"] - n_follow, len(plain))
        take = sorted(
            [follow[i] for i in rng.choice(len(follow), n_follow, False)]
            + [plain[i] for i in rng.choice(len(plain), n_plain, False)])
        rank_gap = score_err = float("inf")
        if take:
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
        # normal equations over the list as of their commit
        pairs = [(row, o) for row, hist in sorted(row_at.items())
                 if row < users_n for o, _ in hist]
        picks = [pairs[i] for i in sorted(rng.choice(
            len(pairs), min(limits["sample_rows"], len(pairs)), False))]
        row_err = float("inf") if not picks else max(
            reference_foldin.row_err(
                vector_of(row, o), reference_foldin.solve_row(
                    self.items_tab, *list_of(row, o), stream["lam"]))
            for row, o in picks)
        fresh = self.session.metrics.counters.get("updates_fresh", 0)
        # 6: the store reopened
        reopened = self._reopen([row for row, _ in picks])
        self.ctx.say(
            f"output check: every one of {len(rids):,} answered id sets held "
            "to 'K distinct in-range, none in the list as of the ordinal "
            f"named'; {len(take)} seeded responses ({n_follow} follow-ups) "
            f"against numpy's exact float32 top-K as of their ordinals; "
            f"{len(picks)} folded-in rows against the float64 solve; "
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
        ]

    def _reopen(self, rows) -> int:
        """How far the reopened store is from the live state: 1 for a cursor
        that is not the ratings sent, 1 for each sampled row that differs
        by a bit.  The store is removed afterwards."""
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
        del again
        shutil.rmtree(self.store, ignore_errors=True)
        self.ctx.say(
            f"store reopened in {time.perf_counter() - t0:.1f} s: snapshot + "
            f"{units} units, cursor and {len(rows)} sampled rows compared "
            f"with the live session: {wrong} differ")
        return wrong


def make(ctx):
    return StreamServeRun(ctx)
