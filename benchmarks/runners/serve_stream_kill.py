"""Runner of the serve-while-folding-in mix with the stream task killed in
the window: ``runners/serve_stream.py``'s catalogue, engine, seen lists,
request schedule and rating stream to the number, with the ratings on a log
on disk (``FileBroker(fsync=True)``) behind the benchmark's kill switch, and
the request server as the stream task's supervisor (``session_factory=``).

At ``kill_at_share`` of the window the log's ``consume`` raises once
(``harness/kill_switch.py``): the session is dead with whatever it had in
flight.  From there on everything is the program's: the server abandons the
session, keeps answering, brings up a successor from the store beside its
loop, and the successor replays from the log what was not durable.  ``check``
holds the run to the configuration's guarantees read across the kill, against
``harness/reference_recover.py``, ``reference_foldin.py`` and
``reference.py``.  A program without the supervisor is refused at once, in
words, before any data is made.
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
    datagen, kill_switch, loadgen_stream_kill, reference_foldin,
    reference_recover, stream_gen)
from benchmarks.harness.stats import percentile
from benchmarks.runners import serve, serve_stream


def _require_program() -> None:
    serve_stream._require_program()
    from cfk_tpu.serving import RecommendServer
    from cfk_tpu.streaming import StreamConfig, StreamSession
    from cfk_tpu.transport import CheckpointManager

    lacks = [name for name, there in (
        ("RecommendServer(session_factory=)", "session_factory"
         in inspect.signature(RecommendServer.__init__).parameters),
        ("StreamSession.abandon", hasattr(StreamSession, "abandon")),
        ("StreamSession(listeners=)", "listeners" in inspect.signature(
            StreamSession.__init__).parameters),
        ("CheckpointManager.abort_pending",
         hasattr(CheckpointManager, "abort_pending")),
        ("StreamConfig.snapshot_every_units",
         "snapshot_every_units" in getattr(
             StreamConfig, "__dataclass_fields__", {})),
    ) if not there]
    if lacks:
        sys.exit("FAILED: this program cannot run the cell: it lacks "
                 + ", ".join(lacks) + " (a request server that outlives "
                 "its stream task and brings up a successor from the store)")


class StreamKillRun(serve_stream.StreamServeRun):
    def __init__(self, ctx):
        _require_program()
        super().__init__(ctx)

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
        from cfk_tpu.config import ALSConfig
        from cfk_tpu.serving import (
            RecommendServer, ServeClient, ServeEngine, ensure_serve_topics)
        from cfk_tpu.serving.engine import trace_count
        from cfk_tpu.streaming import StreamConfig, StreamProducer, foldin
        from cfk_tpu.transport import FileBroker
        from cfk_tpu.transport.broker import InMemoryBroker
        from cfk_tpu.utils.metrics import Metrics

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
            # requests and responses on the in-memory log, as in the control
            self.broker = InMemoryBroker()
            ensure_serve_topics(self.broker)
            # one run at a time in a checkout: what an ended run left goes
            shutil.rmtree(os.path.join(ctx.cache_dir, "stream"),
                          ignore_errors=True)
            stem = os.path.join(ctx.cache_dir, "stream",
                                f"{ctx.cell['name']}.{ctx.seed}")
            self.store = stem
            if stream["log"] != "file":
                sys.exit(f"FAILED: this runner's ratings travel over the log "
                         f"on disk; the configuration states {stream['log']!r}")
            self.log_dir = stem + ".log"
            self.kill = kill_switch.KillSwitch(
                FileBroker(self.log_dir, fsync=True))
            self.producer = StreamProducer(
                self.kill, num_partitions=int(stream["partitions"]))
            self.als = ALSConfig(
                rank=config["rank"], lam=stream["lam"],
                dtype=config["table_dtype"], solver=stream["solver"],
                health_check_every=stream["health_check_every"])
            self.stream_config = StreamConfig(
                batch_records=int(stream["batch_records"]),
                snapshot_every_units=int(stream["snapshot_every_units"]))
            # every commit, as the engine's listener left it, whichever
            # session published it; the sessions share their counters
            self.commits: list = []
            self.stream_metrics = Metrics()
            self.sessions: list = []
            self.session = self._new_session()
            ctx.say(f"session: state from the CSR, bootstrap snapshot under "
                    f"{os.path.relpath(self.store, ctx.cache_dir)} (no copy "
                    f"of either table), ratings log under "
                    f"{os.path.relpath(self.log_dir, ctx.cache_dir)} in "
                    f"{time.perf_counter() - t0:.1f} s")
        self.server = RecommendServer(
            self.engine, self.broker, max_batch=int(mix["max_batch"]),
            session=self.session, session_factory=self._new_session)
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
        # the collector stays off from here to the check, as in the control
        # cell (runners/serve_stream.py has why)
        gc.freeze()
        gc.disable()
        ctx.say(f"prewarm: {warm['programs']} batch programs, "
                f"{warm['new_traces']} traced, {warm['prewarm_s']:.1f} s; "
                f"{fold['programs']} fold-in programs, {fold['new_traces']} "
                f"traced, {fold['prewarm_s']:.1f} s")

    def _new_session(self):
        """A session on the store, the log and the engine: the first one,
        and, as the server's ``session_factory``, every successor."""
        from cfk_tpu.streaming import StreamSession, StreamState
        from cfk_tpu.transport import CheckpointManager

        session = StreamSession(
            self._state(StreamState), self.als, self.kill,
            CheckpointManager(
                self.store,
                max_pending=int(self.config["stream"]["max_pending_commits"])),
            stream=self.stream_config,
            base_model=types.SimpleNamespace(user_factors=self.users_tab),
            engine=self.engine, metrics=self.stream_metrics,
            listeners=[self._on_commit])
        self.sessions.append(session)
        return session

    def _on_commit(self, event: dict) -> None:
        if event.get("retrain"):
            return
        self.commits.append((
            int(event["stream_step"]), int(event["cursors"][0]),
            time.perf_counter(),
            np.asarray(event["touched_rows"], np.int64), event["rows"],
            np.asarray(event["cells"], np.int64).reshape(-1, 2)))

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float) -> dict:
        mix, stream = self.mix, self.config["stream"]
        within = float(stream["visible_within_s"])
        traces = self.trace_count()
        first_commit = len(self.commits)
        offset0 = self.session.consumer.cursors[0] + self.session.backlog()
        kills = [float(mix["kill_at_share"]) * seconds * (i + 1)
                 for i in range(int(mix["kills"]))]
        res = loadgen_stream_kill.run_open_loop(
            self.client, self.server, self.producer, self.kill,
            users=self.users, rate=float(mix["rate"]),
            ratings=(self.r_users, self.r_items, self.r_values),
            rating_rate=float(mix["rating_rate"]), seconds=seconds, k=self.k,
            drain_s=float(mix["drain_seconds"]), kill_at_s=kills)
        self.result = res
        self.session = self.server.session  # the one that lives, or None
        new_traces = self.trace_count() - traces
        errors = sum(1 for r in res.responses.values() if r.error)
        rate = res.answered_in_window / res.window_s
        sent = res.ratings_sent
        # (i), (iii): each ordinal's first commit, every cell once, each
        # rating's commit
        self.first, self.rewritten = reference_recover.first_commits(
            self.commits[first_commit:])
        published, self.duplicates = reference_recover.duplicate_cells(
            self.first)
        self.rating_commit, visible_s = reference_recover.rating_commits(
            self.first, offset0, sent)
        done = self.rating_commit >= 0
        visible_ms = (visible_s - res.rating_sent_s) * 1e3
        late = ~(visible_ms <= within * 1e3)
        # (iv): the outages, from each kill delivered to its successor's
        # catch-up (no end where none has caught up: a task that is still
        # away fails operations, and loses ratings, to the end)
        recs = [r for r in self.server.recoveries
                if r["caught_up_s"] is not None]
        self.outages = [
            (t_kill, recs[i]["killed_at"] + recs[i]["caught_up_s"]
             if i < len(recs) else np.inf)
            for i, t_kill in enumerate(res.killed_at_s)]
        self.inside = reference_recover.in_outage(
            res.rating_sent_s, self.outages, within)
        users_n = self.config["users"]
        self.streamed: dict[int, list] = {}
        for j in range(sent):
            if self.r_users[j] < users_n:
                self.streamed.setdefault(int(self.r_users[j]), []).append(j)
        ok = {rid: r for rid, r in res.responses.items() if not r.error}
        self.stale, stale_failed = reference_recover.stale_reads(
            [(res.users_of[rid], res.req_sent_s[res.req_index[rid]],
              r.ordinal) for rid, r in ok.items()
             if res.users_of[rid] in self.streamed],
            self.streamed, res.rating_sent_s, self.rating_commit,
            self.inside, within)
        late_in = int(np.sum(late & self.inside))
        late_out = int(np.sum(late & ~self.inside))
        commits = [self.first[o] for o in sorted(self.first)]
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
            f"stream: {sent:,} ratings sent at {mix['rating_rate']} a second "
            f"over the log on disk; {committed_in_window:,} committed by the "
            f"close in {len(in_window)} micro-batches ({touched:,} rows "
            f"re-solved) = {committed_in_window / res.window_s:.2f} ratings/s; "
            f"visible after p50 {percentile(visible_ms[done], 50):.1f} / p95 "
            f"{percentile(visible_ms[done], 95):.1f} / longest "
            f"{visible_ms[done].max():.1f} ms; {int(late.sum())} late, "
            f"{int(np.sum(~done))} never committed, "
            f"{res.ratings_outstanding} outstanding after the drain")
        self.ctx.say(
            f"drain: {res.drain_s:.2f} s of at most {mix['drain_seconds']} s, "
            f"{res.unanswered} requests still unanswered, {errors} error "
            "responses")
        ends, sizes = np.asarray(res.batch_ends_s), np.cumsum(res.batch_sizes)
        gaps = np.diff(ends) * 1e3 if ends.size > 1 else np.zeros(0)
        outage_period_ms = 0.0
        for r, (t_kill, t_up) in zip(recs, self.outages):
            inside = (ends[1:] + res.t0 >= t_kill) & (ends[:-1] + res.t0 <= t_up)
            longest = float(gaps[inside].max()) if inside.any() else 0.0
            outage_period_ms = max(outage_period_ms, longest)
            answered = int(np.sum(sizes[1:][inside] - sizes[:-1][inside]))
            self.ctx.say(
                f"recover: killed at {t_kill - res.t0:.3f} s "
                f"({r['cause']}), successor up after {r['up_s']:.3f} s, "
                f"publishing after {r['publishing_s']:.3f} s (restore "
                f"{r['restore_s']:.3f} / state {r['state_s']:.3f} / "
                f"republish {r['republish_s']:.3f}), caught up after "
                f"{r['caught_up_s']:.3f} s; {r['units']} units "
                f"({r['unit_bytes']:,} B) + {r['snapshot_bytes']:,} snapshot "
                f"bytes read, {r['lost_units']} units lost, "
                f"{r['in_flight_batches']} micro-batches in flight dropped, "
                f"{r['replayed_records']:,} records replayed, "
                f"{r['republished']} units published again to the engine; "
                f"catch-up {r.get('catchup_records', 0):,} records in "
                f"{r.get('catchup_micro_batches', 0)} micro-batches; "
                f"{answered:,} requests answered in the outage, longest "
                f"batch period in it {longest:.1f} ms")
        if not len(kills) == len(res.killed_at_s) == len(recs):
            self.ctx.say(f"recover: {len(kills)} kills scheduled, "
                         f"{len(res.killed_at_s)} delivered, {len(recs)} "
                         f"successors caught up")
        if ends.size > 8:
            parts = []
            for share in (1 / 3, 2 / 3):
                i = int(np.searchsorted(ends, share * seconds, side="right")) - 1
                if i >= 0:
                    parts.append(f"first {ends[i]:.1f} s {sizes[i] / ends[i]:.2f}")
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
        failed = errors + res.unanswered + int(late.sum()) + stale_failed
        attempted = res.offered + sent
        self.ctx.say(
            f"failed operations: {failed:,} of {attempted:,} = "
            f"{100.0 * failed / max(attempted, 1):.3f} %: {errors} error "
            f"responses, {res.unanswered} unanswered, {late_in:,} ratings "
            f"late inside the outage, {late_out:,} late outside it, "
            f"{stale_failed:,} stale reads inside the outage "
            f"({self.stale} outside: those decide `correct`)")
        # none lost: each rating has its commit, no fewer cells published
        # than ratings sent (more are ``duplicate_cells``), the live
        # session's cursor = ratings sent, nothing outstanding, every kill
        # was delivered and its successor caught up
        cursor = (self.session.consumer.cursors[0] - offset0
                  if self.session is not None else sent)
        self.lost = (int(np.sum(~done)) + res.ratings_outstanding
                     + max(sent - published, 0) + abs(cursor - sent)
                     + abs(len(kills) - len(res.killed_at_s))
                     + abs(len(kills) - len(recs)))
        return {"window_s": res.window_s,
                "attempted": attempted,
                "failed": failed,
                "failed_requests": errors + res.unanswered,
                "outage_failed": late_in + stale_failed,
                "outage_period_ms": outage_period_ms,
                "recoveries": recs,
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

    def _store_units(self) -> dict:
        """{ordinal: (cursor, touched rows, solved rows, cells)} of every
        commit unit the store holds, read from its files."""
        from cfk_tpu.resilience.loop import drain_checkpoints
        from cfk_tpu.transport import CheckpointManager

        if self.session is not None:
            drain_checkpoints(self.session.manager)
        store = CheckpointManager(self.store)
        units = {}
        for step in store.iterations():
            try:
                st = store.restore(step)
            except Exception:  # a torn step is no unit
                continue
            if st.meta.get("kind") == "unit" and st.arrays["touched"].size:
                cells = st.arrays["cells"]
                units[step] = (
                    st.meta["offsets"]["0"], st.arrays["touched"],
                    np.asarray(st.user_factors, np.float32),
                    np.stack([cells["row"].astype(np.int64),
                              cells["movie"].astype(np.int64)], axis=1))
        return units

    def check(self, window: dict) -> list:
        gc.enable()
        config, res = self.config, self.result
        limits, why = config["checks"], config["checks"]["why"]
        stream = config["stream"]
        users_n = config["users"]
        rng = np.random.default_rng(self.ctx.seed + 3)
        sent = res.ratings_sent
        r_items, r_values = self.r_items[:sent], self.r_values[:sent]
        r_commit, streamed = self.rating_commit, self.streamed
        t0 = time.perf_counter()
        # (ii): every ordinal the engine was given against the store's units
        units = self._store_units()
        uncommitted = reference_recover.uncommitted_reads(self.first, units)
        self.ctx.say(
            f"store: {len(units)} commit units with rows read back in "
            f"{time.perf_counter() - t0:.1f} s; {len(self.first)} ordinals "
            f"published, {uncommitted} without an equal unit, "
            f"{self.rewritten} published twice, {self.duplicates} cells "
            "published twice")
        del units

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
        # K distinct in-range rows, none in the list as of the ordinal
        bad = reference_foldin.invalid_id_sets(
            [ok[r].movie_rows for r in rids],
            [list_of(res.users_of[r], ok[r].ordinal)[0]
             if res.users_of[r] in streamed else base_of(res.users_of[r])[0]
             for r in rids], config["items"], self.k)
        # the rows as committed, per (user row, ordinal)
        row_at: dict[int, list] = {}
        for ordinal in sorted(self.first):
            _, _, _, touched, rows, _ = self.first[ordinal]
            for i, row in enumerate(touched.tolist()):
                row_at.setdefault(row, []).append((ordinal, rows[i]))

        def vector_of(u, ordinal):
            last = [r for o, r in row_at.get(int(u), ()) if o <= ordinal]
            return last[-1] if last else self.users_tab[u]

        # sampled answers and rows from both sides of the kill: at least
        # sample_each_side of each before it and after it
        t_kill = self.outages[0][0] if self.outages else np.inf
        each = int(limits.get("sample_each_side", 0))
        when_req = {r: res.req_sent_s[res.req_index[r]] for r in rids}

        def both_sides(pool, is_before, n, least):
            """``n`` of ``pool`` drawn from the seed, by the sides' own
            shares, with at least ``least`` from each side that has them."""
            before = [p for p in pool if is_before(p)]
            after = [p for p in pool if not is_before(p)]
            n_b = int(round(n * len(before) / max(len(pool), 1)))
            if n >= 2 * least:
                n_b = min(max(n_b, least), n - least)
            n_b = min(n_b, len(before))
            n_a = min(n - n_b, len(after))
            return ([before[i] for i in rng.choice(len(before), n_b, False)]
                    + [after[i] for i in rng.choice(len(after), n_a, False)])

        follow = [r for r in rids if self.is_followup[res.req_index[r]]]
        plain = [r for r in rids if not self.is_followup[res.req_index[r]]]
        n_follow = min(limits["sample_followups"], len(follow))
        n_plain = min(limits["sample_responses"] - n_follow, len(plain))
        take = sorted(
            both_sides(follow, lambda r: when_req[r] < t_kill, n_follow,
                       each // 2)
            + both_sides(plain, lambda r: when_req[r] < t_kill, n_plain,
                         each // 2))
        n_before = sum(when_req[r] < t_kill for r in take)
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
        # sampled folded-in rows against the float64 solve of their own
        # normal equations over the list as of their commit
        pairs = [(row, o) for row, hist in sorted(row_at.items())
                 if row < users_n for o, _ in hist]
        when_commit = {o: c[2] for o, c in self.first.items()}
        picks = both_sides(pairs, lambda p: when_commit[p[1]] < t_kill,
                           min(limits["sample_rows"], len(pairs)), each)
        rows_before = sum(when_commit[o] < t_kill for _, o in picks)
        row_err = float("inf") if not picks else max(
            reference_foldin.row_err(
                vector_of(row, o), reference_foldin.solve_row(
                    self.items_tab, *list_of(row, o), stream["lam"]))
            for row, o in picks)
        # the store reopened a second time equals the live state (the
        # control's ``_reopen``, which opens its session on ``self.broker``:
        # here the ratings' log)
        self.broker = self.kill
        # (with no live session there is nothing to compare it with: the
        # loss is ``lost_ratings``')
        reopened = (self._reopen(sorted({row for row, _ in picks}))
                    if self.session is not None else 0)
        shutil.rmtree(self.log_dir, ignore_errors=True)
        self.ctx.say(
            f"output check: every one of {len(rids):,} answered id sets held "
            "to 'K distinct in-range, none in the list as of the ordinal "
            f"named'; {len(take)} seeded responses ({n_follow} follow-ups; "
            f"{n_before} sent before the kill, {len(take) - n_before} after) "
            f"against numpy's exact float32 top-K as of their ordinals; "
            f"{len(picks)} folded-in rows ({rows_before} committed before the "
            f"kill, {len(picks) - rows_before} after) against the float64 "
            f"solve; {len(self.first)} commit units")
        return [
            ("failed_requests", window["failed_requests"], 0,
             why["failed_requests"]),
            ("invalid_id_sets", bad, 0, why["invalid_id_sets"]),
            ("rank_gap", rank_gap, limits["rank_gap"], why["rank_gap"]),
            ("score_err", score_err, limits["score_err"], why["score_err"]),
            ("lost_ratings", self.lost, 0, why["lost_ratings"]),
            ("duplicate_cells", self.duplicates, 0, why["duplicate_cells"]),
            ("uncommitted_reads", uncommitted, 0, why["uncommitted_reads"]),
            ("ordinal_rewritten", self.rewritten, 0, why["ordinal_rewritten"]),
            ("stale_reads", self.stale, 0, why["stale_reads"]),
            ("foldin_row_err", row_err, limits["foldin_row_err"],
             why["foldin_row_err"]),
            ("reopened_store", reopened, 0, why["reopened_store"]),
        ]


def make(ctx):
    return StreamKillRun(ctx)
