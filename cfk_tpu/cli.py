"""Command-line interface.

Subcommands:

- ``run`` — reference-compatible positional form, mirroring
  ``apps/ALSAppRunner.java:16-28`` / README.md:35 of the reference:
  ``NUM_PARTITIONS NUM_FEATURES LAMBDA NUM_ITERATIONS PATH NUM_MOVIES
  NUM_USERS``.  Entity counts are *derived from the data* here; the passed
  NUM_MOVIES/NUM_USERS are cross-checked and warned about on mismatch
  (the reference trusts them blindly and mis-sizes its collector if wrong).
- ``train`` — full-flag form: explicit or implicit model, sharding,
  exchange strategy, solver backend, checkpointing, profiling.
- ``evaluate`` — offline MSE/RMSE of a prediction CSV against a ratings
  file: the (fixed) replacement for ``scripts/calculate_mse.py`` (which
  reads uninitialized ``np.empty`` memory and can print nan).
- ``recommend`` — top-K serving from checkpointed factors.
- ``predict`` — prediction-CSV dump from checkpointed factors (the
  reference's final-collection phase as a standalone step).
- ``broker`` / ``produce`` — run the native TCP log broker and stream a
  ratings file into it; ``train --data tcp://HOST:PORT[/TOPIC]`` then
  ingests from the broker (the reference's producer → Kafka → app split,
  ``apps/ALSAppRunner.java:30-33``, as separate processes).
- ``stream`` — exactly-once streaming fold-in: consume rating updates
  from a durable topic and fold them into live factors, committing
  factors + offset cursor atomically per micro-batch; ``--produce-csv``
  is the producer side (``cfk_tpu.streaming``; ARCHITECTURE.md
  "Streaming ingest & incremental fold-in").
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _eprint(*args) -> None:
    print(*args, file=sys.stderr)


def _parse_tcp_url(url: str, topic_optional: bool = False) -> tuple[str, int, str]:
    """``tcp://HOST:PORT[/TOPIC]`` → (host, port, topic).

    Without a /TOPIC segment the default ratings topic is returned, or None
    when ``topic_optional`` (admin commands that act on the whole broker).
    """
    from cfk_tpu.transport.ingest import RATINGS_TOPIC

    if not url.startswith("tcp://"):
        raise ValueError(
            f"bad broker url {url!r}; expected tcp://HOST:PORT[/TOPIC]"
        )
    rest = url[len("tcp://"):]
    addr, _, topic = rest.partition("/")
    host, _, port_s = addr.rpartition(":")
    if not host or not port_s.isdigit():
        raise ValueError(f"bad broker url {url!r}; expected tcp://HOST:PORT[/TOPIC]")
    return host, int(port_s), topic or (None if topic_optional else RATINGS_TOPIC)


AUTO_LAYOUT_TILED_NNZ = 2_000_000  # a guess no chip run has tested (PERF.md §8)


def _resolve_auto_layout(coo, algorithm="als", solve_chunk=None) -> str:
    """layout='auto': one padded rectangle for small data (fastest to
    compile, no chunking machinery), the tiled layout once the data is
    big enough for its batched-GEMM Grams to matter.  Constrained by the
    rest of the invocation: an explicit (deprecated) --solve-chunk only
    means anything on the padded layout, and the subspace optimizers
    (als++/ials++) need padded/bucketed — bucketed is their at-scale
    layout."""
    if solve_chunk is not None:
        return "padded"
    big = coo.num_ratings >= AUTO_LAYOUT_TILED_NNZ
    if algorithm != "als":
        return "bucketed" if big else "padded"
    return "tiled" if big else "padded"


def _load_dataset(path, fmt, min_rating, num_shards, pad_multiple, layout="padded",
                  chunk_elems=1 << 20, cache_dir=None, ring=False,
                  auto_resolver=_resolve_auto_layout, auto_key=None,
                  dense_stream=False):
    import os

    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.movielens import parse_movielens_csv
    from cfk_tpu.data.netflix import parse_netflix

    import zipfile

    # Built blocks are deterministic for this tuple; it is stored in the
    # cache's meta.json so a cache built from other data or flags is
    # rebuilt instead of silently reused.  Content fingerprint: size + mtime
    # for files, per-partition end offsets for broker topics (append-only
    # logs — the offsets identify the ingested prefix exactly).
    build_key = {
        "data": path if path.startswith("tcp://") else os.path.abspath(path),
        "format": fmt,
        "min_rating": min_rating,
        "num_shards": num_shards,
        "pad_multiple": pad_multiple,
        "layout": layout,
        "chunk_elems": chunk_elems,
    }
    if ring:  # absent for non-ring keys so existing caches stay valid
        build_key["ring"] = ring
    if dense_stream and layout == "tiled":
        # Same back-compat rule — and only for layouts that can actually
        # consume the flag: recording it for explicit padded/bucketed/
        # segment builds would spuriously invalidate their caches while
        # producing byte-identical blocks.
        build_key["dense_stream"] = True
    if layout == "auto" and auto_key:
        # layout='auto' resolves from the data AND the invocation
        # (algorithm, solve_chunk constrain the choice) — without these in
        # the key, a cache built under `als` would be silently reused by
        # `ials++` with a layout that invocation cannot train on.
        build_key.update(auto_key)

    # For layout='auto' the dense flag only changes the blocks when the
    # resolution lands on tiled — unknowable before the data is parsed, so
    # the flag cannot be keyed up front (keying it on the UNRESOLVED layout
    # spuriously invalidated pre-existing auto caches whose resolution was
    # segment/bucketed — ADVICE r4).  Saves record the flag iff the
    # resolved build consumed it; loads accept the flagless key too, but
    # only when the cached dataset is NOT tiled (a flagless tiled cache is
    # a padded build and must not serve a dense request).
    auto_dense = dense_stream and layout == "auto"

    def cache_or_build(build):
        if cache_dir and os.path.exists(os.path.join(cache_dir, "meta.json")):
            keys = ([{**build_key, "dense_stream": True}, build_key]
                    if auto_dense else [build_key])
            err = None
            for key in keys:
                try:
                    ds = Dataset.load(cache_dir, expect_build_key=key)
                except (ValueError, KeyError, OSError,
                        zipfile.BadZipFile) as e:
                    # mismatched build key, or a missing/corrupt/truncated
                    # cache file: every broken-cache state self-heals via
                    # rebuild
                    err = e
                    continue
                from cfk_tpu.data.blocks import TiledBlocks

                if (auto_dense and "dense_stream" not in key
                        and isinstance(ds.user_blocks, TiledBlocks)):
                    err = ValueError(
                        "cached auto-layout dataset resolved to tiled "
                        "without the dense stream; dense run rebuilds"
                    )
                    continue
                return ds
            _eprint(f"warning: ignoring dataset cache: {err}")
        coo = build()
        resolved = auto_resolver(coo) if layout == "auto" else layout
        use_dense = dense_stream and resolved == "tiled"
        ds = Dataset.from_coo(
            coo, num_shards=num_shards, pad_multiple=pad_multiple,
            layout=resolved, chunk_elems=chunk_elems, ring=ring,
            dense_stream=use_dense,
        )
        if cache_dir:
            key = ({**build_key, "dense_stream": True}
                   if auto_dense and use_dense else build_key)
            ds.save(cache_dir, build_key=key)
        return ds

    if path.startswith("tcp://"):
        from cfk_tpu.transport.ingest import collect_ratings
        from cfk_tpu.transport.tcp import TcpBrokerClient

        if fmt != "netflix" or min_rating:
            # Broker records are already-parsed (movieId, userId, rating)
            # wire frames; file-parse flags have nothing to apply to.
            _eprint(
                "warning: --format/--min-rating are ignored for tcp:// "
                "ingest (records on the broker are already parsed)"
            )
        host, port, topic = _parse_tcp_url(path)
        try:
            client = TcpBrokerClient(host, port)
        except OSError as e:
            # Broker down — a matching cache can still train offline, minus
            # the offset freshness check (which needs the broker).  The
            # non-offset key fields must still match exactly.
            ds = _cache_sans_fingerprint(cache_dir, build_key, Dataset,
                                         ignore=("end_offsets",),
                                         auto_dense=auto_dense)
            if ds is not None:
                _eprint(
                    f"warning: broker unreachable ({e}); using dataset cache "
                    "without the end-offset freshness check"
                )
                return ds
            raise
        with client:
            if cache_dir:
                from cfk_tpu.transport.tcp import BrokerRequestError

                try:
                    build_key["end_offsets"] = [
                        client.end_offset(topic, p)
                        for p in range(client.num_partitions(topic))
                    ]
                except BrokerRequestError as e:
                    # Topic gone (e.g. deleted after caching): a matching
                    # cache is the only way to train; offsets unverifiable.
                    ds = _cache_sans_fingerprint(
                        cache_dir, build_key, Dataset,
                        ignore=("end_offsets",), auto_dense=auto_dense)
                    if ds is not None:
                        _eprint(
                            f"warning: topic unavailable ({e}); using "
                            "dataset cache without the end-offset check"
                        )
                        return ds
                    raise
            return cache_or_build(lambda: collect_ratings(client, topic=topic))
    if os.path.exists(path):
        st = os.stat(path)
        build_key["data_size"] = st.st_size
        build_key["data_mtime_ns"] = st.st_mtime_ns
    else:
        # Source file gone (archived/deleted after caching) — a cache whose
        # key matches on everything but the file fingerprint still trains.
        ds = _cache_sans_fingerprint(cache_dir, build_key, Dataset,
                                     ignore=("data_size", "data_mtime_ns"),
                                     auto_dense=auto_dense)
        if ds is not None:
            _eprint(
                f"warning: data file {path!r} not found; using dataset "
                "cache without the size/mtime freshness check"
            )
            return ds
    if fmt == "netflix":
        return cache_or_build(lambda: parse_netflix(path))
    return cache_or_build(lambda: parse_movielens_csv(path, min_rating=min_rating))


def _cache_sans_fingerprint(cache_dir, build_key, Dataset, ignore,
                            auto_dense=False):
    """Load a cache whose content fingerprint cannot be recomputed (broker
    unreachable, source file deleted), if the stored build key matches ours
    on every field outside ``ignore``.

    ``auto_dense`` applies the same dual-key rule as the online path: a
    layout='auto' + dense_stream run matches a stored key WITH the
    ``dense_stream`` flag (its own prior dense-resolved-tiled save) or one
    without it — but a flagless cache that turns out to be tiled is a
    padded-stream build and must not serve a dense request."""
    import os
    import zipfile

    from cfk_tpu.data.cache import read_build_key

    if not cache_dir or not os.path.exists(os.path.join(cache_dir, "meta.json")):
        return None
    try:
        stored = read_build_key(cache_dir)
        if stored is None:
            return None
        strip = lambda k: {x: v for x, v in k.items() if x not in ignore}
        s, b = strip(stored), strip(build_key)
        flagged_ok = auto_dense and s == {**b, "dense_stream": True}
        if s != b and not flagged_ok:
            return None
        ds = Dataset.load(cache_dir, expect_build_key=stored)
        if auto_dense and not flagged_ok:
            from cfk_tpu.data.blocks import TiledBlocks

            if isinstance(ds.user_blocks, TiledBlocks):
                return None
        return ds
    except (ValueError, KeyError, OSError, zipfile.BadZipFile):
        return None


import contextlib as _contextlib


@_contextlib.contextmanager
def _telemetry_session(args, metrics=None):
    """Wire the telemetry subsystem for one CLI command (ISSUE 14).

    ``--trace-dir`` installs the host span tracer (Chrome-trace JSON
    written at exit, colocated with ``--profile-dir``'s jax-profiler trace
    when both point at the same directory); the flight recorder's dump
    directory resolves to the trace dir, else the checkpoint/stream dir,
    so any trip/escalation/eviction/crash leaves its forensic dump next to
    the run's other artifacts; ``--metrics-jsonl`` streams periodic
    registry snapshots for training dashboards."""
    from cfk_tpu import telemetry

    trace_dir = getattr(args, "trace_dir", None)
    dump_dir = (trace_dir
                or getattr(args, "checkpoint_dir", None)
                or getattr(args, "stream_dir", None))
    tracer = None
    if trace_dir:
        tracer = telemetry.configure(trace_dir=trace_dir)
    if dump_dir:
        telemetry.get_recorder().configure(dump_dir=dump_dir)
        telemetry.install_crash_hooks()
    emitter = None
    jsonl = getattr(args, "metrics_jsonl", None)
    if jsonl and metrics is not None:
        emitter = telemetry.MetricsEmitter(
            metrics, jsonl,
            interval_s=getattr(args, "metrics_interval_s", 10.0),
        ).start()
    try:
        yield
    finally:
        if emitter is not None:
            emitter.stop()
        if tracer is not None:
            path = telemetry.shutdown(write=True)
            if path:
                _eprint(f"host span trace written to {path}")


def _train(args) -> int:
    from cfk_tpu.utils.metrics import Metrics

    metrics = Metrics()
    with _telemetry_session(args, metrics):
        return _train_impl(args, metrics)


def _train_impl(args, metrics) -> int:
    from cfk_tpu.config import ALSConfig, set_async_collective_permute
    from cfk_tpu.eval.metrics import mse_rmse_from_model
    from cfk_tpu.eval.predict import save_prediction_csv
    from cfk_tpu.models.als import train_als
    from cfk_tpu.models.ials import IALSConfig, train_ials, train_ials_sharded
    from cfk_tpu.utils.metrics import maybe_profile

    # Must land in LIBTPU_INIT_ARGS before the first jax computation (the
    # dataset load below initializes the backend, which is when libtpu
    # reads the env on TPU; never XLA_FLAGS — CPU/GPU-only XLA aborts on
    # the unknown TPU flag).
    set_async_collective_permute(args.async_collective_permute)
    if args.layout == "auto" and args.exchange == "auto":
        # The per-half exchange builds on the tiled layout only (config
        # validation says so); resolve up front so ring blocks are built.
        args.layout = "tiled"
    if args.layout == "auto" and args.exchange == "ring":
        # Both ring-capable layouts work; padded needs no build-time ring
        # blocks and has no per-shard accumulator cap — the safe default
        # (pass --layout tiled explicitly for the tiled ring).
        args.layout = "padded"
    if args.layout == "auto" and args.exchange == "hier_ring":
        # The hierarchical exchange runs on the tiled ring blocks only.
        args.layout = "tiled"
    if args.layout == "auto" and args.offload_tier == "host_window":
        # The windowed host-offload driver streams the tiled stream-mode
        # layout; resolve up front so config validation never refuses a
        # flag combination the parser accepted.
        args.layout = "tiled"

    def _resolver(coo):
        return _resolve_auto_layout(coo, args.algorithm, args.solve_chunk)

    with metrics.phase("ingest"):
        ds = _load_dataset(
            args.data, args.format, args.min_rating, args.shards,
            args.pad_multiple, args.layout, args.chunk_elems,
            cache_dir=args.dataset_cache,
            ring=(
                (args.exchange if args.exchange == "auto"
                 else args.exchange in ("ring", "hier_ring"))
                if args.layout == "tiled" else False
            ),
            auto_resolver=_resolver,
            auto_key={
                "algorithm": args.algorithm,
                "solve_chunk": args.solve_chunk,
            },
            # The unpadded dense gather stream is the measured at-scale
            # default for BOTH models (round 5): explicit ALS 0.707 →
            # 0.652 s/iter full Netflix rank 64 (round 4), and — with the
            # sqrt-reparameterized weight stream replacing round 4's
            # premultiplied second stream — iALS ML-25M rank 128 0.662 →
            # 0.630 s/iter (the dense builder always stages the
            # rating_dense channel the weighted path needs).  Subspace
            # optimizers (als++/ials++) use padded/bucketed layouts, and
            # an explicit --exchange ring build carries the accum
            # machinery on both halves — the flag has no half to apply to
            # there, so don't request it (avoids the builder's warning).
            dense_stream=args.exchange not in ("ring", "hier_ring"),
        )
    if args.layout == "auto":
        # Reflect what _resolve_auto_layout (or a cache hit) actually built,
        # so the config matches the blocks.
        from cfk_tpu.data.blocks import (
            BucketedBlocks, SegmentBlocks, TiledBlocks,
        )

        args.layout = {
            BucketedBlocks: "bucketed",
            SegmentBlocks: "segment",
            TiledBlocks: "tiled",
        }.get(type(ds.movie_blocks), "padded")
    common = dict(
        layout=args.layout,
        rank=args.rank,
        lam=args.lam,
        num_iterations=args.iterations,
        seed=args.seed,
        num_shards=args.shards,
        exchange=args.exchange,
        ici_group=args.ici_group,
        offload_tier=args.offload_tier,
        staging=args.staging,
        staging_pool_depth=args.staging_pool_depth,
        hot_rows=args.hot_rows,
        compile_cache_dir=args.compile_cache_dir,
        overlap=not args.no_overlap,
        in_kernel_gather=(
            None if args.in_kernel_gather == "auto"
            else args.in_kernel_gather == "on"
        ),
        reg_solve_algo=args.reg_solve_algo,
        table_dtype=args.table_dtype,
        async_collective_permute=args.async_collective_permute,
        dtype=args.dtype,
        solver=args.solver,
        solve_chunk=args.solve_chunk,
        hbm_chunk_elems=args.chunk_elems,
        pad_multiple=args.pad_multiple,
        algorithm=args.algorithm,
        block_size=args.block_size,
        sweeps=args.sweeps,
        health_check_every=args.health_check_every,
        health_norm_limit=args.health_norm_limit,
        max_recoveries=args.max_recoveries,
        lam_escalation=args.lam_escalation,
        on_unrecoverable=args.on_unrecoverable,
    )
    heldout = train_coo = None
    if args.eval_ranking:
        if not args.implicit:
            _eprint("error: --eval-ranking requires --implicit (it is a "
                    "top-K ranking protocol, not a rating-error one)")
            return 1
        from cfk_tpu.data.blocks import Dataset
        from cfk_tpu.eval.ranking import leave_one_out_split

        d = ds.coo_dense
        train_coo, heldout = leave_one_out_split(
            d.movie_raw, d.user_raw, d.rating, seed=args.seed
        )
        before = (ds.movie_map.num_entities, ds.user_map.num_entities)
        ds = Dataset.from_coo(
            train_coo, num_shards=args.shards, pad_multiple=args.pad_multiple,
            layout=args.layout, chunk_elems=args.chunk_elems,
        )
        if (ds.movie_map.num_entities, ds.user_map.num_entities) != before:
            _eprint(
                "error: the leave-one-out split removed some entity's only "
                "interaction; ranking eval needs every movie to keep >= 1 — "
                "use a denser dataset"
            )
            return 1

    manager = _make_checkpoint_manager(args)
    if isinstance(manager, int):
        return manager
    ck = dict(checkpoint_manager=manager, checkpoint_every=args.checkpoint_every)

    # Preemption tolerance is on whenever a checkpoint store exists: an
    # eviction SIGTERM (or Ctrl-C) drains the async writer, commits one
    # final checkpoint, and the process exits resumable — re-run the same
    # command to continue (cfk_tpu.resilience.preempt).
    import contextlib

    guard_cm = contextlib.nullcontext(None)
    if manager is not None and not getattr(args, "no_preempt_save", False):
        from cfk_tpu.resilience.preempt import PreemptionGuard

        guard_cm = PreemptionGuard()

    with maybe_profile(args.profile_dir), guard_cm as guard:
        ck["preemption_guard"] = guard
        if args.implicit:
            config = IALSConfig(alpha=args.alpha, **common)
            if args.shards > 1:
                from cfk_tpu.parallel.mesh import make_mesh

                model = train_ials_sharded(
                    ds, config, make_mesh(args.shards), metrics=metrics, **ck
                )
            else:
                model = train_ials(ds, config, metrics=metrics, **ck)
        else:
            config = ALSConfig(**common)
            if args.shards > 1:
                from cfk_tpu.parallel.mesh import make_mesh
                from cfk_tpu.parallel.spmd import train_als_sharded

                model = train_als_sharded(
                    ds, config, make_mesh(args.shards), metrics=metrics, **ck
                )
            else:
                model = train_als(ds, config, metrics=metrics, **ck)

    if guard is not None and guard.triggered:
        # Exit inside the platform's SIGTERM grace window: the checkpoint
        # is committed and drained, so evaluation / ranking / the CSV dump
        # on the partial model would only risk a SIGKILL mid-eval.  The
        # metrics line still goes out — it carries the "preempted" note.
        _eprint(
            f"preempted ({guard.signal_name}): a final checkpoint was "
            "committed — re-run this command to resume; skipping "
            "evaluation and output for the partial run"
        )
        print(metrics.json_line() if args.metrics == "json"
              else metrics.logfmt())
        return 0

    # Both evals stream from the factors (never materializing U·Mᵀ), so they
    # run at scales where the dense matrix cannot exist; only the CSV dump
    # still needs dense predictions, and only it is skipped (with a warning)
    # when they're unmaterializable.
    if not args.implicit:
        with metrics.phase("eval_mse"):
            mse, rmse = mse_rmse_from_model(model, ds)
        metrics.gauge("mse", round(mse, 6))
        metrics.gauge("rmse", round(rmse, 6))
        _eprint(f"train MSE={mse:.4f} RMSE={rmse:.4f}")
    if heldout is not None:
        from cfk_tpu.eval.ranking import ranking_metrics_from_model

        with metrics.phase("eval_ranking"):
            rec, mpr = ranking_metrics_from_model(
                model, train_coo, heldout, k=args.eval_ranking
            )
        metrics.gauge(f"recall_at_{args.eval_ranking}", round(rec, 6))
        metrics.gauge("mpr", round(mpr, 6))
        _eprint(
            f"leave-one-out Recall@{args.eval_ranking}={rec:.4f} MPR={mpr:.4f}"
        )
    if args.output != "none":
        with metrics.phase("predict"):
            try:
                preds = model.predict_dense()
            except ValueError as e:
                # At full-Netflix scale the trained model is the deliverable;
                # don't discard it over an unmaterializable side product.
                preds = None
                _eprint(f"warning: skipping the prediction CSV dump: {e}")
        if preds is not None:
            with metrics.phase("dump_csv"):
                path = save_prediction_csv(
                    preds, None if args.output == "auto" else args.output
                )
            _eprint(f"predictions written to {path}")
    print(metrics.json_line() if args.metrics == "json" else metrics.logfmt())
    return 0


def _journal_transport(journal: str, *, fsync: bool):
    """Transport for a --checkpoint-journal target: tcp://HOST:PORT broker
    or a FileBroker directory.  Raises ValueError on a malformed URL and
    OSError when the broker is unreachable — callers turn both into clean
    CLI errors."""
    if journal.startswith("tcp://"):
        from cfk_tpu.transport.tcp import TcpBrokerClient

        host, port, _ = _parse_tcp_url(journal, topic_optional=True)
        return TcpBrokerClient(host, port)
    from cfk_tpu.transport.filelog import FileBroker

    return FileBroker(journal, fsync=fsync)


def _make_checkpoint_manager(args):
    """The checkpoint store the train flags select: the npz directory
    (``--checkpoint-dir``, the fast local default), the transport journal
    (``--checkpoint-journal``, factors as FeatureRecord frames through a
    FileBroker dir or a ``tcp:HOST:PORT`` broker — the reference's
    topics-as-durable-checkpoint design, ``setup.sh:18-21``), or None.
    Returns an int exit code on flag errors."""
    journal = getattr(args, "checkpoint_journal", None)
    if args.checkpoint_dir and journal:
        _eprint("error: --checkpoint-dir and --checkpoint-journal are "
                "mutually exclusive")
        return 2
    if args.checkpoint_dir:
        from cfk_tpu.transport.checkpoint import CheckpointManager

        return CheckpointManager(
            args.checkpoint_dir,
            keep_last_n=getattr(args, "keep_last_n", None),
        )
    if journal:
        from cfk_tpu.transport.journal import JournalCheckpointManager

        try:
            # fsync per append for the training journal: the commit marker
            # must never reach disk before the factor frames it commits.
            transport = _journal_transport(journal, fsync=True)
        except (ValueError, OSError) as e:
            _eprint(f"error: {e}")
            return 2
        return JournalCheckpointManager(
            transport, num_partitions=args.journal_partitions
        )
    return None


def _run_reference_form(args) -> int:
    """The reference's 7-positional-arg invocation."""
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.netflix import parse_netflix
    from cfk_tpu.eval.metrics import mse_rmse_from_model
    from cfk_tpu.eval.predict import save_prediction_csv
    from cfk_tpu.models.als import train_als

    _eprint(f"app started: {time.strftime('%Y-%m-%d %H:%M:%S')}")
    coo = parse_netflix(args.path)
    _eprint(f"producer finished: {time.strftime('%Y-%m-%d %H:%M:%S')}")
    # NUM_PARTITIONS maps to device shards when that many devices exist;
    # otherwise fall back to one shard with a warning (the reference's
    # partitions are Kafka-internal and have no single-device meaning).
    num_shards = args.num_partitions
    mesh = None
    if num_shards > 1:
        try:
            from cfk_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(num_shards)
        except ValueError as e:
            _eprint(f"warning: NUM_PARTITIONS={num_shards} ignored ({e})")
            num_shards = 1
    ds = Dataset.from_coo(coo, num_shards=num_shards)
    if ds.movie_map.num_entities != args.num_movies:
        _eprint(
            f"warning: NUM_MOVIES={args.num_movies} but data has "
            f"{ds.movie_map.num_entities} rated movies (using the data)"
        )
    if ds.user_map.num_entities != args.num_users:
        _eprint(
            f"warning: NUM_USERS={args.num_users} but data has "
            f"{ds.user_map.num_entities} rated users (using the data)"
        )
    config = ALSConfig(
        rank=args.num_features,
        lam=args.lam,
        num_iterations=args.num_iterations,
        num_shards=num_shards,
    )
    if mesh is not None:
        from cfk_tpu.parallel.spmd import train_als_sharded

        model = train_als_sharded(ds, config, mesh)
    else:
        model = train_als(ds, config)
    mse, rmse = mse_rmse_from_model(model, ds)
    try:
        preds = model.predict_dense()
    except ValueError as e:
        # Full-Netflix-scale run of the reference form: the dense CSV is the
        # one unmaterializable artifact; keep the quality numbers.
        preds = path = None
        _eprint(f"warning: skipping the prediction CSV dump: {e}")
    if preds is not None:
        path = save_prediction_csv(preds)
        _eprint(f"prediction matrix written: {time.strftime('%Y-%m-%d %H:%M:%S')}")
    print(f"MSE: {mse}")
    print(f"RMSE: {rmse}")
    if path is not None:
        print(path)
    return 0


def _evaluate(args) -> int:
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.netflix import parse_netflix
    from cfk_tpu.eval.metrics import mse_rmse_from_blocks
    from cfk_tpu.eval.predict import load_prediction_csv

    coo = parse_netflix(args.ratings_file)
    ds = Dataset.from_coo(coo)
    preds = load_prediction_csv(args.prediction_csv)
    want = (ds.user_map.num_entities, ds.movie_map.num_entities)
    if preds.shape != want:
        _eprint(
            f"error: prediction matrix is {preds.shape}, ratings imply {want} "
            "(rows = users ascending id, cols = movies ascending id)"
        )
        return 2
    print(f"#users in ratings_matrix:  {want[0]}")
    print(f"#movies in ratings_matrix:  {want[1]}")
    mse, rmse = mse_rmse_from_blocks(preds, ds)
    print(f"MSE: {mse}")
    print(f"RMSE: {rmse}")
    return 0


def _serving_state(args):
    """Restore factors for the serving subcommands from either store:
    --checkpoint-dir (npz directory) or --checkpoint-journal (transport
    journal — a FileBroker directory or tcp://HOST:PORT broker)."""
    if bool(args.checkpoint_dir) == bool(args.checkpoint_journal):
        _eprint("error: pass exactly one of --checkpoint-dir / "
                "--checkpoint-journal")
        return None
    try:
        if args.checkpoint_dir:
            from cfk_tpu.transport.checkpoint import CheckpointManager

            return CheckpointManager(args.checkpoint_dir).restore()
        from cfk_tpu.transport.journal import JournalCheckpointManager

        transport = _journal_transport(args.checkpoint_journal, fsync=False)
        return JournalCheckpointManager(transport).restore()
    except (ValueError, OSError) as e:
        # Malformed URL, unreachable broker, or an empty/uncommitted store —
        # common operator mistakes; a clean error beats a traceback.
        _eprint(f"error: {e}")
        return None


def _predict(args) -> int:
    """Dump the prediction CSV from checkpointed factors, no retraining.

    The reference's final-collection phase (``processors/FeatureCollector.java``:
    P = U·Mᵀ + CSV dump) as a standalone step over the durable factor store —
    train once with --checkpoint-dir, then regenerate/evaluate predictions at
    any time.
    """
    from cfk_tpu.data.blocks import RatingsIndex
    from cfk_tpu.data.movielens import parse_movielens_csv
    from cfk_tpu.data.netflix import parse_netflix
    from cfk_tpu.eval.predict import save_prediction_csv
    from cfk_tpu.models.als import ALSModel

    if args.format == "netflix":
        coo = parse_netflix(args.data)
    else:
        coo = parse_movielens_csv(args.data, min_rating=args.min_rating)
    ds = RatingsIndex.from_coo(coo)
    state = _serving_state(args)
    if state is None:
        return 2
    if state.user_factors.shape[0] < ds.user_map.num_entities or (
        state.movie_factors.shape[0] < ds.movie_map.num_entities
    ):
        _eprint(
            f"error: checkpoint factors ({state.user_factors.shape[0]} users, "
            f"{state.movie_factors.shape[0]} movies) are smaller than the "
            f"data implies ({ds.user_map.num_entities}, "
            f"{ds.movie_map.num_entities}); wrong --data for this checkpoint?"
        )
        return 1
    model = ALSModel(
        user_factors=state.user_factors,
        movie_factors=state.movie_factors,
        num_users=ds.user_map.num_entities,
        num_movies=ds.movie_map.num_entities,
    )
    path = save_prediction_csv(
        model.predict_dense(), None if args.output == "auto" else args.output
    )
    _eprint(
        f"predictions from iteration-{state.iteration} checkpoint "
        f"written to {path}"
    )
    return 0


def _recommend(args) -> int:
    """Serve top-K from checkpointed factors, printing raw ids."""
    import numpy as np

    from cfk_tpu.data.blocks import RatingsIndex
    from cfk_tpu.data.movielens import parse_movielens_csv
    from cfk_tpu.data.netflix import parse_netflix
    from cfk_tpu.models.als import ALSModel

    # Only the id maps + seen lists are needed — never build solve blocks
    # (a padded rectangle at full-Netflix scale would dwarf serving memory).
    if args.format == "netflix":
        coo = parse_netflix(args.data)
    else:
        coo = parse_movielens_csv(args.data, min_rating=args.min_rating)
    ds = RatingsIndex.from_coo(coo)
    state = _serving_state(args)
    if state is None:
        return 2
    model = ALSModel(
        user_factors=state.user_factors,
        movie_factors=state.movie_factors,
        num_users=ds.user_map.num_entities,
        num_movies=ds.movie_map.num_entities,
    )
    if args.users == "all":
        rows = np.arange(ds.user_map.num_entities)
    else:
        raw = np.asarray([int(u) for u in args.users.split(",")], dtype=np.int64)
        rows = ds.user_map.to_dense(raw).astype(np.int64)
    scores, movie_rows = model.recommend_top_k(
        rows, args.k, dataset=None if args.include_seen else ds
    )
    raw_movies = ds.movie_map.raw_ids[movie_rows]
    raw_users = ds.user_map.raw_ids[rows]
    for i, u in enumerate(raw_users):
        pairs = ",".join(
            f"{mid}:{s:.3f}" for mid, s in zip(raw_movies[i], scores[i])
        )
        print(f"{u}\t{pairs}")
    return 0


def _serve(args) -> int:
    """Top-K request server over the transport log (ISSUE 8).

    Restores factors from the checkpoint store, builds the serving engine
    (quantized table per --table-dtype, exclude-seen from --data's rating
    lists), and serves score requests:

    - with --broker tcp://HOST:PORT, joins the native broker's
      serve-requests/serve-responses topics and answers until killed —
      the cross-process deployment form;
    - without --broker, runs the built-in open-loop load generator
      against an in-memory log (--loadgen-qps/--loadgen-requests) and
      prints the measured QPS/p50/p99 row — the self-contained smoke
      (the chip's numbers are the benchmark's: BENCHMARK.json, PERF.md).

    ``--metrics-port`` makes the server answer ``GET /metrics``
    (Prometheus text) while it serves; ``--trace-dir`` writes the host
    span trace (batch assemble/compute/respond timeline) at exit.

    ``--replicas N`` (ISSUE 18) serves through the replicated fleet
    instead: N replicas behind the request log (one partition each,
    user-keyed routing), each with its own /metrics + /readyz and
    optional admission control (``--admission-queue``).

    ``--item-departments FILE`` (one int an item row: text, or ``.npy``)
    gives every item its department: the table is laid out by department, a
    request that names one (``ServeClient.request(user, k, department=)``)
    is answered with the exact top-K among that department's items, and a
    ``--broker``'s requests topic is keyed by department.  Without the
    file the server serves as it did.
    """
    with _telemetry_session(args):
        return _serve_impl(args)


def _serve_impl(args) -> int:
    import numpy as np

    from cfk_tpu.data.blocks import RatingsIndex
    from cfk_tpu.data.movielens import parse_movielens_csv
    from cfk_tpu.data.netflix import parse_netflix
    from cfk_tpu.models.als import ALSModel
    from cfk_tpu.serving import (
        RecommendServer,
        ServeClient,
        engine_from_model,
        ensure_serve_topics,
        run_open_loop,
        warm_serve_programs,
        zipf_user_rows,
    )

    # Before the first compile (ISSUE 13): warm-start compile caching —
    # a restarted server replays its serve programs from the persistent
    # cache instead of recompiling the whole bucket set.
    from cfk_tpu.config import enable_compile_cache

    enable_compile_cache(args.compile_cache_dir)
    if args.stream_dir and (not args.broker or args.replicas > 1):
        _eprint("error: --stream-dir folds in the ratings of a --broker's "
                "log into ONE server's factors: give --broker, and no "
                "--replicas")
        return 2
    if args.format == "netflix":
        coo = parse_netflix(args.data)
    else:
        coo = parse_movielens_csv(args.data, min_rating=args.min_rating)
    ds = RatingsIndex.from_coo(coo)
    state = _serving_state(args)
    if state is None:
        return 2
    model = ALSModel(
        user_factors=state.user_factors,
        movie_factors=state.movie_factors,
        num_users=ds.user_map.num_entities,
        num_movies=ds.movie_map.num_entities,
    )

    departments = None
    if args.item_departments:
        departments = (np.load(args.item_departments)
                       if args.item_departments.endswith(".npy")
                       else np.loadtxt(args.item_departments, dtype=np.int64,
                                       ndmin=1))

    def build_engine():
        return engine_from_model(
            model, None if args.include_seen else ds,
            table_dtype=args.table_dtype, tile_m=args.tile_m,
            item_department=departments,
        )

    engine = build_engine()
    # Trace/compile the pow2 batch-bucket set before traffic arrives
    # (ISSUE 13): the first real batch then pays zero traces.
    warm = engine.prewarm(args.k, max_batch=args.max_batch)
    _eprint(
        f"prewarmed {warm['programs']} serve programs "
        f"({warm['new_traces']} new traces) in {warm['prewarm_s']:.2f}s"
    )
    # Replicated fleet (ISSUE 18): N replicas behind the request log —
    # user-keyed routing, per-replica /metrics + /readyz, admission
    # control, delta/rollover plumbing ready for a publisher to join.
    def _fleet(transport):
        from cfk_tpu.serving import ServeFleet

        fleet = ServeFleet(
            lambda i: engine if i == 0 else build_engine(),
            transport, replicas=args.replicas, max_batch=args.max_batch,
            admission_max_queue=args.admission_queue or None,
            metrics_ports=args.metrics_port is not None,
        )
        fleet.seed_store(model.user_factors, model.movie_factors,
                         num_users=model.num_users)
        fleet.prewarm(args.k, max_batch=args.max_batch)
        for r in fleet.replicas:
            ms = r.server.metrics_server
            if ms is not None:
                _eprint(f"replica {r.index} metrics endpoint: {ms.url}")
        return fleet

    if args.broker:
        host, port, _ = _parse_tcp_url(args.broker, topic_optional=True)
        from cfk_tpu.transport.tcp import TcpBrokerClient

        transport = TcpBrokerClient(host, port)
        if args.replicas > 1:
            import time as _time

            fleet = _fleet(transport).start()
            _eprint(
                f"serving fleet: {args.replicas} replicas over broker "
                f"{host}:{port} (user-keyed routing; ^C to stop)"
            )
            try:
                while True:
                    _time.sleep(1.0)
            except KeyboardInterrupt:  # pragma: no cover - interactive
                pass
            finally:
                fleet.stop()
            c = fleet.counters()
            _eprint(f"fleet served {c['served']} requests "
                    f"({c['shed']} shed) in {c['batches']} batches")
            return 0
        ensure_serve_topics(
            transport, request_partitions=args.request_partitions,
            response_partitions=args.response_partitions,
            departments=(None if departments is None
                         else int(departments.max()) + 1),
        )
        session, new_session = None, None
        if args.stream_dir:
            # The server folds the broker's rating-updates topic into the
            # factors it serves and is the stream task's supervisor: a
            # session whose pump raises is abandoned and replaced from the
            # store, the server answering all the while.
            from cfk_tpu.config import ALSConfig
            from cfk_tpu.streaming import (
                StreamConfig, StreamSession, StreamState,
                ensure_updates_topic)
            from cfk_tpu.transport.checkpoint import CheckpointManager

            ensure_updates_topic(transport)
            base_state = StreamState(ds)
            als = ALSConfig(rank=int(state.user_factors.shape[-1]),
                            lam=args.stream_lam, health_check_every=1)

            def new_session():
                return StreamSession(
                    base_state.fresh(), als, transport,
                    CheckpointManager(args.stream_dir),
                    stream=StreamConfig(
                        batch_records=args.stream_batch_records),
                    base_model=model, engine=engine)

            session = new_session()
            _eprint(f"folding in {args.stream_dir}: stream step "
                    f"{session.stream_step}, cursor "
                    f"{session.consumer.cursors}")
        server = RecommendServer(engine, transport,
                                 max_batch=args.max_batch,
                                 metrics_port=args.metrics_port,
                                 session=session,
                                 session_factory=new_session)
        if server.metrics_server is not None:
            _eprint(f"metrics endpoint: {server.metrics_server.url}")
        _eprint(
            f"serving {ds.user_map.num_entities} users × "
            f"{ds.movie_map.num_entities} movies (rank "
            f"{state.user_factors.shape[-1]}, table {engine.table_dtype}) "
            f"from broker {host}:{port}; ^C to stop"
        )
        try:
            server.serve_forever()
        except KeyboardInterrupt:  # pragma: no cover - interactive
            pass
        finally:
            server.close()
        _eprint(f"served {server.requests_served} requests "
                f"in {server.batches} batches")
        return 0
    from cfk_tpu.transport import InMemoryBroker

    transport = InMemoryBroker()
    if args.replicas > 1:
        import json

        fleet = _fleet(transport).start()
        client = ServeClient(transport, route="user")
        pool = zipf_user_rows(
            ds.user_map.num_entities, args.loadgen_requests, seed=args.seed
        )
        try:
            report = run_open_loop(
                client, rate_qps=args.loadgen_qps,
                num_requests=args.loadgen_requests, user_rows=pool,
                k=args.k,
            )
        finally:
            fleet.stop()
        c = fleet.counters()
        print(json.dumps({
            "users": ds.user_map.num_entities,
            "movies": ds.movie_map.num_entities,
            "k": args.k,
            "table_dtype": engine.table_dtype,
            "replicas": args.replicas,
            "shed": c["shed"],
            "client_retries": client.retries,
            **report.as_row(),
            # the loadgen can't see the fleet's servers — batch
            # accounting comes from the fleet counters instead
            "batches": c["batches"],
            "mean_batch": (round(c["served"] / c["batches"], 1)
                           if c["batches"] else 0.0),
        }))
        return 0
    ensure_serve_topics(transport)
    server = RecommendServer(engine, transport, max_batch=args.max_batch,
                             metrics_port=args.metrics_port)
    if server.metrics_server is not None:
        _eprint(f"metrics endpoint: {server.metrics_server.url}")
    client = ServeClient(transport)
    pool = zipf_user_rows(
        ds.user_map.num_entities, args.loadgen_requests, seed=args.seed
    )
    try:
        warm_serve_programs(client, server, pool, args.k,
                            min(args.max_batch, pool.shape[0]))
        report = run_open_loop(
            client, rate_qps=args.loadgen_qps,
            num_requests=args.loadgen_requests, user_rows=pool, k=args.k,
            server=server, drive_server=True,
        )
    finally:
        server.close()
    import json

    print(json.dumps({
        "users": ds.user_map.num_entities,
        "movies": ds.movie_map.num_entities,
        "k": args.k,
        "table_dtype": engine.table_dtype,
        **report.as_row(),
    }))
    return 0


def _broker(args) -> int:
    """Run the native broker server in the foreground."""
    import subprocess

    from cfk_tpu.transport.tcp import _BROKER_BIN, build_broker

    if not build_broker(quiet=False):
        _eprint("error: cfk_broker binary unavailable (make -C native failed)")
        return 1
    argv = [_BROKER_BIN, str(args.port)]
    if args.data_dir or args.bind != "127.0.0.1":
        argv.append(args.data_dir or "")
    if args.bind != "127.0.0.1":
        argv.append(args.bind)
    try:
        return subprocess.run(argv).returncode
    except KeyboardInterrupt:
        return 0


def _topics(args) -> int:
    """Topic administration against a running broker — the role of the
    reference's ``setup.sh`` (delete + recreate topics out-of-band,
    ``setup.sh:14-24``), without a second copy of the partition count."""
    from cfk_tpu.transport.tcp import TcpBrokerClient

    host, port, topic = _parse_tcp_url(args.broker, topic_optional=True)
    with TcpBrokerClient(host, port) as client:
        if args.action == "list":
            for name in client.topics():
                nparts = client.num_partitions(name)
                print(
                    f"{name}\tpartitions={nparts}\t"
                    + "\t".join(
                        f"p{p}={client.end_offset(name, p)}"
                        for p in range(nparts)
                    )
                )
            return 0
        if topic is None:
            _eprint(f"error: {args.action} needs tcp://HOST:PORT/TOPIC")
            return 1
        if args.action == "create":
            client.create_topic(topic, args.partitions)
        elif args.action == "delete":
            client.delete_topic(topic)
        elif args.action == "recreate":
            client.delete_topic(topic)
            client.create_topic(topic, args.partitions)
    return 0


def _produce(args) -> int:
    """Stream a Netflix-format ratings file into a broker topic.

    The reference's producer-then-app sequencing (``apps/ALSAppRunner.java:30-33``)
    as two processes: ``cfk_tpu produce`` here, ``cfk_tpu train --data
    tcp://...`` there.
    """
    from cfk_tpu.transport.ingest import produce_ratings_file
    from cfk_tpu.transport.tcp import TcpBrokerClient

    host, port, topic = _parse_tcp_url(args.broker)
    if args.partitions < 1:
        _eprint(f"error: --partitions must be >= 1, got {args.partitions}")
        return 1
    with TcpBrokerClient(host, port) as client:
        try:
            client.create_topic(topic, args.partitions)
        except ValueError as e:
            if "already exists" not in str(e):
                raise
            if not args.append:
                _eprint(
                    f"error: topic {topic!r} already exists (use --append to "
                    "add to a topic produced with --no-eof; a finalized "
                    "topic's EOF records would fail the ingest barrier)"
                )
                return 1
        n = produce_ratings_file(
            client, args.data, topic=topic, send_eof=not args.no_eof
        )
    state = "open (no EOF yet)" if args.no_eof else "finalized"
    _eprint(f"produced {n} ratings to {topic!r} on {host}:{port} [{state}]")
    return 0


def _updates_transport(updates: str, *, fsync: bool = True):
    """Transport for --updates: tcp://HOST:PORT broker or a FileBroker
    directory (the durable default — the updates topic is the system of
    record the crash replay consumes)."""
    if updates.startswith("tcp://"):
        from cfk_tpu.transport.tcp import TcpBrokerClient

        host, port, _ = _parse_tcp_url(updates, topic_optional=True)
        return TcpBrokerClient(host, port)
    from cfk_tpu.transport.filelog import FileBroker

    return FileBroker(updates, fsync=fsync)


def _stream(args) -> int:
    """Streaming fold-in: consume rating updates, fold them into live
    factors, commit factors + offset cursor atomically per micro-batch.

    Bootstrap: with no resumable state in --stream-dir, a base model is
    trained from --data first (same config), then streaming starts from
    offset 0.  Re-running the identical command resumes from the committed
    cursor — including after a crash or an eviction SIGTERM.
    ``--produce-csv`` instead appends "user,movie,rating" lines to the
    updates topic and exits (the producer side of the loop).
    ``--metrics-port`` serves the live registry as Prometheus text on
    ``GET /metrics`` for the duration of the stream."""
    from cfk_tpu.utils.metrics import Metrics

    metrics = Metrics()
    with _telemetry_session(args, metrics):
        http = None
        if getattr(args, "metrics_port", None) is not None:
            from cfk_tpu.telemetry import MetricsHTTPServer

            http = MetricsHTTPServer(
                metrics, port=args.metrics_port
            ).start()
            _eprint(f"metrics endpoint: {http.url}")
        try:
            return _stream_impl(args, metrics)
        finally:
            if http is not None:
                http.stop()


def _stream_impl(args, metrics) -> int:
    from cfk_tpu.config import ALSConfig

    try:
        transport = _updates_transport(args.updates)
    except (ValueError, OSError) as e:
        _eprint(f"error: {e}")
        return 2
    if args.produce_csv:
        from cfk_tpu.streaming import StreamProducer

        prod = StreamProducer(
            transport, num_partitions=args.partitions
        )
        # Parse the whole file first, then one bulk append per partition
        # (send_many → FileBroker.produce_frames): per-line send() pays
        # one fsync'd append each — minutes for a 100k-line file — and
        # parse-before-produce also makes a malformed line all-or-nothing
        # instead of leaving a half-produced file in the log.
        users: list[int] = []
        movies: list[int] = []
        ratings: list[float] = []
        with open(args.produce_csv) as f:
            for lineno, line in enumerate(f, start=1):
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                try:
                    user_s, movie_s, rating_s = line.split(",", 2)
                    users.append(int(user_s))
                    movies.append(int(movie_s))
                    ratings.append(float(rating_s))
                except ValueError as e:
                    _eprint(
                        f"error: {args.produce_csv}:{lineno}: malformed "
                        f"update {line!r} ({e})"
                    )
                    return 1
        prod.send_many(users, movies, ratings)
        n = len(users)
        if hasattr(transport, "flush"):
            transport.flush()
        _eprint(f"produced {n} updates (next seq {prod.next_seq})")
        return 0

    from cfk_tpu.streaming import StreamConfig, StreamSession
    from cfk_tpu.transport.checkpoint import CheckpointManager

    config = ALSConfig(
        rank=args.rank,
        lam=args.lam,
        num_iterations=args.iterations,
        seed=args.seed,
        layout=args.layout,
        solver=args.solver,
        dtype=args.dtype,
        # threaded so retrain()'s merged-dataset rebuild honors the same
        # HBM chunk budget as the base dataset built below
        hbm_chunk_elems=args.chunk_elems,
        health_check_every=args.health_check_every,
        health_norm_limit=args.health_norm_limit,
        max_recoveries=args.max_recoveries,
        lam_escalation=args.lam_escalation,
        on_unrecoverable=args.on_unrecoverable,
        compile_cache_dir=args.compile_cache_dir,
    )
    # Ensure the topic BEFORE the (possibly hours-long) base train: a
    # fresh topic is created empty and followed, instead of training a
    # base model only to crash on an unknown-topic lookup afterwards.
    from cfk_tpu.streaming import ensure_updates_topic

    ensure_updates_topic(transport, num_partitions=args.partitions)
    with metrics.phase("ingest"):
        ds = _load_dataset(
            args.data, args.format, args.min_rating, 1, 8,
            args.layout, args.chunk_elems,
            cache_dir=args.dataset_cache,
            dense_stream=args.layout == "tiled",
        )
    manager = CheckpointManager(
        args.stream_dir, keep_last_n=args.keep_last_n
    )
    base_model = None
    if manager.latest_valid_iteration() is None:
        _eprint("no stream state yet: training the base model first")
        from cfk_tpu.models.als import train_als

        with metrics.phase("base_train"):
            base_model = train_als(ds, config, metrics=metrics)
    stream = StreamConfig(
        batch_records=args.batch_records,
        retrain_every=args.retrain_every,
    )
    import contextlib

    guard_cm = contextlib.nullcontext(None)
    if not args.no_preempt_save:
        from cfk_tpu.resilience.preempt import PreemptionGuard

        guard_cm = PreemptionGuard()
    with guard_cm as guard:
        session = StreamSession(
            ds, config, transport, manager, stream=stream,
            base_model=base_model, metrics=metrics,
            preemption_guard=guard,
        )
        if args.prewarm:
            warm = session.prewarm()
            _eprint(
                f"prewarmed {warm['programs']} fold-in programs "
                f"({warm['new_traces']} new traces) in "
                f"{warm['prewarm_s']:.2f}s"
            )
        model = session.run(
            max_batches=args.max_batches, follow=args.follow
        )
    metrics.gauge("stream_step", session.stream_step)
    metrics.gauge("users", session.state.num_users)
    metrics.gauge("backlog", session.backlog())
    if guard is not None and guard.triggered:
        _eprint(
            f"preempted ({guard.signal_name}): factor+cursor step "
            f"{session.stream_step} is committed — re-run to resume"
        )
    elif not args.no_eval:
        import dataclasses

        from cfk_tpu.eval.metrics import mse_rmse_from_model

        with metrics.phase("eval_mse"):
            # against the merged (base + committed upserts) rating state;
            # the merged dataset re-sorts ALL users ascending by raw id
            # while session rows are base-ascending THEN appended new
            # users, so the factors must be permuted into the merged row
            # order (same perm the warm retrain applies) or every user
            # past a new user's insertion point scores against the wrong
            # row
            from cfk_tpu.data.blocks import Dataset as _DS

            merged = _DS.from_coo(session.state.to_coo())
            perm = merged.user_map.to_dense(session.state.user_raw_ids())
            u_sess = np.asarray(model.user_factors)
            u_eval = np.zeros(
                (merged.user_blocks.padded_entities, u_sess.shape[1]),
                u_sess.dtype,
            )
            u_eval[perm] = u_sess[: session.state.num_users]
            eval_model = dataclasses.replace(
                model, user_factors=u_eval,
                num_users=merged.user_map.num_entities,
            )
            mse, rmse = mse_rmse_from_model(eval_model, merged)
        metrics.gauge("mse", round(mse, 6))
        metrics.gauge("rmse", round(rmse, 6))
        _eprint(f"merged-state MSE={mse:.4f} RMSE={rmse:.4f}")
    print(metrics.json_line() if args.metrics == "json"
          else metrics.logfmt())
    return 0


def _plan_cmd(args) -> int:
    """``cfk_tpu plan``: resolve + print an ExecutionPlan (ISSUE 9).

    The shape/device come from flags (no dataset needed — this is the
    offline side of the planner), 'auto' flags stay free for the
    resolver, anything concrete pins.  ``--explain`` prints the winner's
    cost terms and, per free knob, the estimated cost of flipping it —
    the "why this and not that" record.  ``--autotune`` measures the
    model's top candidates on a trimmed synthetic workload and caches
    the winner keyed by (shape-class, device fingerprint, version).
    """
    import json as _json

    from cfk_tpu.plan import (
        DeviceSpec,
        PlanConstraints,
        ProblemShape,
        plan as resolve_plan,
        plan_cost,
        rank_plans,
    )

    shape = ProblemShape(
        num_users=args.users, num_movies=args.movies,
        nnz=args.ratings, rank=args.rank, num_shards=args.shards,
        implicit=args.implicit, dtype=args.storage_dtype,
        kind="serve" if args.serve else "train", serve_k=args.serve_k,
    )
    tri = {"auto": None, "on": True, "off": False}
    cons = PlanConstraints(
        layout=None if args.layout == "auto" else args.layout,
        exchange=None if args.exchange == "auto" else args.exchange,
        table_dtype=(None if args.table_dtype == "auto"
                     else args.table_dtype),
        fused_epilogue=tri[args.fused],
        in_kernel_gather={"auto": None, "fused": True,
                          "xla": False}[args.gather],
        overlap=tri[args.overlap],
        reg_solve_algo=(None if args.reg_solve_algo == "auto"
                        else args.reg_solve_algo),
        solver=None if args.solver == "auto" else args.solver,
        chunk_elems=args.chunk_elems,
        offload_tier=(None if args.offload_tier == "auto"
                      else args.offload_tier),
        ici_group=args.ici_group,
        staging=None if args.staging == "auto" else args.staging,
        hot_rows=args.hot_rows,
    )
    if args.device == "auto":
        device = DeviceSpec.detect()
    elif args.device == "v5e":
        device = DeviceSpec.nominal("tpu", name="v5e")
    else:
        device = DeviceSpec.nominal("cpu")
    mode = "autotune" if args.autotune else args.mode
    measure = None
    if args.autotune:
        if args.serve:
            raise ValueError(
                "--autotune measures the training iteration; it has no "
                "serve measurement"
            )
        from cfk_tpu.plan.autotune import measure_with_training

        measure = measure_with_training(shape)
    ep, prov = resolve_plan(shape, device, cons, mode=mode,
                            cache_path=args.cache_path, measure=measure)
    print(f"# shape  {shape.shape_class()}")
    print(f"# device {device.fingerprint()}")
    print(f"# plan   {prov.summary()}")
    if args.explain:
        cost = plan_cost(shape, device, ep)
        print("# cost terms:")
        for line in cost.explain_lines():
            print(f"#   {line}")
        for field, value, reason in prov.explain:
            if reason == "cost term (s)":
                continue  # already printed via explain_lines above
            print(f"#   {field}: {value} — {reason}")
        # Per-knob deltas: what would flipping each FREE knob cost?
        try:
            ranked = rank_plans(shape, device, cons)
        except Exception:  # pragma: no cover - pinned-everything case
            ranked = []
        best_knobs = ep.knob_dict()
        seen: set[str] = set()
        for s, alt in ranked[1:]:
            diff = {f: v for f, v in alt.knob_dict().items()
                    if v != best_knobs.get(f)}
            if len(diff) != 1:
                continue
            (f, v), = diff.items()
            tag = f"{f}={v}"
            if tag in seen:
                continue
            seen.add(tag)
            print(f"#   flip {tag}: {s:.6f} s "
                  f"(+{s - (prov.est_cost_s or s):.6f})")
    print(_json.dumps(
        {**ep.as_dict(), **prov.as_row()}, sort_keys=True
    ))
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="cfk_tpu", description=__doc__)
    p.add_argument(
        "--platform",
        choices=["default", "cpu", "tpu"],
        default="default",
        help="force the JAX platform (same effect as JAX_PLATFORMS)",
    )
    sub = p.add_subparsers(dest="command", required=True)

    r = sub.add_parser("run", help="reference-compatible positional form")
    r.add_argument("num_partitions", type=int)
    r.add_argument("num_features", type=int)
    r.add_argument("lam", type=float)
    r.add_argument("num_iterations", type=int)
    r.add_argument("path")
    r.add_argument("num_movies", type=int)
    r.add_argument("num_users", type=int)
    r.set_defaults(fn=_run_reference_form)

    t = sub.add_parser("train", help="full-flag training")
    t.add_argument("--data", required=True)
    t.add_argument("--format", choices=["netflix", "movielens"], default="netflix")
    t.add_argument("--implicit", action="store_true", help="confidence-weighted iALS")
    t.add_argument("--min-rating", type=float, default=0.0)
    t.add_argument("--rank", type=int, default=5)
    t.add_argument("--lam", type=float, default=0.05)
    t.add_argument("--alpha", type=float, default=40.0, help="iALS confidence weight")
    t.add_argument(
        "--algorithm", choices=["als", "als++", "ials++"], default="als",
        help="per-entity optimizer: 'als' = full k-by-k normal-equation "
        "solves (the reference's exact semantics); 'als++' (explicit) / "
        "'ials++' (implicit, Rendle et al.) = warm-started subspace block "
        "coordinate descent — much cheaper per epoch at large rank; "
        "padded/bucketed layouts",
    )
    t.add_argument(
        "--eval-ranking", type=int, default=None, metavar="K",
        help="(implicit only) hold one interaction per user out before "
        "training and report leave-one-out Recall@K and mean percentile "
        "rank after",
    )
    t.add_argument("--block-size", type=int, default=32,
                   help="als++/ials++ coordinate block size (must divide rank)")
    t.add_argument("--sweeps", type=int, default=1,
                   help="als++/ials++ sweeps over all blocks per half-iteration")
    t.add_argument("--iterations", type=int, default=7)
    t.add_argument("--seed", type=int, default=42)
    t.add_argument("--shards", type=int, default=1)
    t.add_argument("--exchange",
                   choices=["all_gather", "ring", "hier_ring", "auto"],
                   default="all_gather",
                   help="fixed-factor exchange; 'auto' (tiled layout) picks "
                   "per half: ring where the Gram accumulator fits, "
                   "all_gather elsewhere")
    t.add_argument(
        "--no-overlap", action="store_true",
        help="pin the serial exchange/compute schedule instead of the "
        "default double-buffered pipelines (A/B measurement; factors are "
        "bit-identical either way — see ARCHITECTURE.md 'Exchange/compute "
        "overlap')",
    )
    t.add_argument(
        "--in-kernel-gather", choices=["auto", "on", "off"], default="auto",
        help="fuse the per-chunk neighbor-factor gather into the pallas "
        "Gram kernels (rows DMA'd straight from the HBM-resident factor "
        "table into the kernel's VMEM double buffer — the materialized "
        "[C, k] gathered stream disappears).  'auto' (default) gathers "
        "in-kernel wherever the kernels' SMEM/alignment gates allow, "
        "falling back to the XLA-gather schedule otherwise; 'off' pins "
        "the XLA gather (A/B measurement; factors are bit-identical "
        "either way — see ARCHITECTURE.md 'In-kernel neighbor gather')",
    )
    t.add_argument(
        "--table-dtype", choices=["float32", "bfloat16", "int8"],
        default="float32",
        help="HBM gather-table dtype (cfk_tpu.ops.quant): quantize the "
        "fixed-side table each half-iteration gathers from — bfloat16 "
        "halves the gather bytes, int8 (+ one f32 scale per row, folded "
        "into the kernels' premultiply) quarters them; Gram/solve "
        "accumulation stays float32 and the solved factors keep --dtype. "
        "float32 (default) is bit-identical to pre-quantization behavior. "
        "int8 needs the tiled/bucketed layouts' weight streams",
    )
    t.add_argument(
        "--reg-solve-algo", choices=["auto", "lu", "gj"], default="auto",
        help="elimination algorithm of the fused reg+solve kernels: "
        "reverse no-pivot LU (rank cap 128) or Gauss-Jordan (cap 64); "
        "'auto' keeps the process default (lu).  Threaded as a real "
        "config parameter — the recovery ladder's GJ rung overrides it "
        "per-step",
    )
    t.add_argument(
        "--async-collective-permute", choices=["auto", "on", "off"],
        default="auto",
        help="force XLA's async collective-permute pass via "
        "LIBTPU_INIT_ARGS "
        "(the escape hatch for the ring overlap's transfer hiding); "
        "'auto' keeps the compiler default",
    )
    t.add_argument(
        "--solver", choices=["auto", "cholesky", "pallas"], default="auto",
        help="batched k-by-k solve backend: auto = pallas Gauss-Jordan "
        "kernel on TPU (rank <= 64), XLA cholesky elsewhere",
    )
    t.add_argument("--dtype", choices=["float32", "bfloat16"], default="float32")
    t.add_argument("--solve-chunk", type=int, default=None,
                   help="DEPRECATED: explicit entities per padded-layout "
                   "solve chunk; --chunk-elems is the one HBM budget for "
                   "every layout")
    t.add_argument("--pad-multiple", type=int, default=8)
    t.add_argument(
        "--layout",
        choices=["auto", "padded", "bucketed", "segment", "tiled"],
        default="auto",
        help="InBlock layout: one rectangle (padded), power-of-two width "
        "buckets (bucketed), flat segment runs with grouped ragged-matmul "
        "Grams (segment; exactly O(nnz) memory for arbitrarily skewed "
        "data), or tile-padded runs with batched-GEMM Grams via the fused "
        "pallas kernel (tiled; the fastest at full-Netflix scale). "
        "Default 'auto': padded below 2M ratings, tiled above",
    )
    t.add_argument(
        "--chunk-elems", type=int, default=1 << 20,
        help="the ONE HBM budget, in gather cells, for every layout: "
        "bucketed/segment/tiled consume it at dataset build time "
        "(ratings per scan chunk); padded derives entities per solve "
        "chunk from it at run time",
    )
    t.add_argument(
        "--offload-tier", choices=["auto", "device", "host_window"],
        default="auto",
        help="where the factor tables live (ISSUE 11/12): 'auto' lets "
        "the planner's PER-SHARD memory-budget predicate decide "
        "(resident while they fit — today's behavior); 'device' pins "
        "resident tables (refused up front when they cannot fit); "
        "'host_window' pins the out-of-core path — host-RAM factor "
        "stores with device_put-pipelined windows, sharded too (per-"
        "shard windows under the all_gather scan or ring/hier_ring "
        "visit schedules, int8 (codes, scales) PCIe staging; explicit "
        "ALS, tiled layout, bit-exact vs the resident paths)",
    )
    t.add_argument(
        "--ici-group", type=int, default=None, metavar="I",
        help="inner-ring size of --exchange hier_ring (devices per ICI "
        "domain); default: local device count when it divides --shards, "
        "else one flat ring",
    )
    t.add_argument(
        "--staging", choices=["auto", "pool", "serial"], default="auto",
        help="host staging engine of the host_window tier (ISSUE 13): "
        "'pool' (= 'auto', the default) overlaps every shard's window "
        "staging — store gather, host quantize, checksum, device_put — "
        "on a bounded thread pool across shards and windows; 'serial' "
        "pins the one-thread double buffer (the A/B baseline).  "
        "Factors are crc-identical across the knob",
    )
    t.add_argument(
        "--hot-rows", type=int, default=None, metavar="F",
        help="skew-aware hot-row device cache of the host_window tier "
        "(ISSUE 15): keep the top-F most-referenced fixed-table rows "
        "(total, both sides) device-resident so windows stage only "
        "their cold delta.  Default: AUTO — the coverage-curve knee of "
        "the window plans' own reference counts, clamped by the budget "
        "headroom (resolves off when either refuses); 0 pins the cache "
        "off (the full-staging engine); an impossible F raises naming "
        "the bytes.  Factors are crc-identical across the knob",
    )
    t.add_argument(
        "--staging-pool-depth", type=int, default=None, metavar="D",
        help="windows staged ahead of consumption in pool mode "
        "(default: offload.staging.DEFAULT_POOL_DEPTH); always clamped "
        "so D+1 worst-case windows fit the per-shard window budget",
    )
    t.add_argument(
        "--compile-cache-dir", default=None, metavar="DIR",
        help="persistent jax compilation cache (ISSUE 13): compiled "
        "programs are reused across process restarts — a warm cache "
        "removes the cold-start compile cost the time_to_first_step/batch "
        "columns measure.  Default: .jax_compile_cache at the checkout "
        "root; JAX_COMPILATION_CACHE_DIR, where set, wins over DIR",
    )
    t.add_argument(
        "--health-check-every", type=int, default=None, metavar="N",
        help="arm the numerical-health sentinel: probe the factor state "
        "(isfinite + norm watchdogs, <2%% overhead at N=1) every N "
        "iterations; a tripped probe rolls back to the last good "
        "checkpoint and escalates (retry, then lam x LAM_ESCALATION, "
        "then split epilogue, then GJ elimination).  Default: off",
    )
    t.add_argument(
        "--health-norm-limit", type=float, default=1e6,
        help="factor-row 2-norm above which the sentinel's watchdog trips "
        "even while values are still finite (catches slow divergence "
        "before overflow)",
    )
    t.add_argument(
        "--max-recoveries", type=int, default=4,
        help="total sentinel trips tolerated before the run stops "
        "retrying (see --on-unrecoverable)",
    )
    t.add_argument(
        "--lam-escalation", type=float, default=10.0,
        help="multiplier applied to lam on the recovery ladder's "
        "regularization rung",
    )
    t.add_argument(
        "--on-unrecoverable", choices=["degrade", "raise"],
        default="degrade",
        help="after max-recoveries trips: 'degrade' returns the last-good "
        "factors with a diagnostic report in the metrics (a stale model "
        "beats no model); 'raise' fails the run",
    )
    t.add_argument("--checkpoint-dir", default=None)
    t.add_argument("--checkpoint-every", type=int, default=1)
    t.add_argument(
        "--keep-last-n", type=int, default=None,
        help="garbage-collect checkpoint steps beyond the newest N after "
        "each save (the last verified-good step the recovery ladder "
        "points at is always pinned); default keeps every step",
    )
    t.add_argument(
        "--no-preempt-save", action="store_true",
        help="disable the SIGTERM/SIGINT preemption guard that is armed "
        "whenever --checkpoint-dir is set: by default an eviction signal "
        "drains the async checkpoint writer, commits one final "
        "checkpoint, and exits resumable instead of dying mid-iteration",
    )
    t.add_argument(
        "--checkpoint-journal", default=None,
        help="journal factor checkpoints through the transport instead of "
        "the npz --checkpoint-dir: a directory (FileBroker journal) or "
        "tcp://HOST:PORT (cfk_broker server); factors travel as "
        "FeatureRecord wire frames on per-iteration topics, resume replays "
        "the latest committed iteration",
    )
    t.add_argument("--journal-partitions", type=int, default=1)
    t.add_argument(
        "--dataset-cache", default=None,
        help="directory for the built-blocks cache: loaded if present and "
        "its stored build key (data path/size/mtime + layout flags) matches, "
        "rebuilt and overwritten otherwise",
    )
    t.add_argument("--profile-dir", default=None, help="write a jax.profiler trace")
    t.add_argument(
        "--trace-dir", default=None, metavar="DIR",
        help="write the host span trace (Chrome-trace JSON) here at exit; "
        "pass the same directory as --profile-dir to line the host "
        "timeline up with the jax-profiler device trace",
    )
    t.add_argument(
        "--metrics-jsonl", default=None, metavar="PATH",
        help="stream periodic metrics-registry snapshots (one JSON line "
        "per interval) for live dashboards",
    )
    t.add_argument(
        "--metrics-interval-s", type=float, default=10.0,
        help="seconds between --metrics-jsonl snapshots",
    )
    t.add_argument(
        "--output", default="auto",
        help="'auto' = predictions/prediction_matrix_<ts>, 'none', or a path",
    )
    t.add_argument("--metrics", choices=["json", "logfmt"], default="logfmt")
    t.set_defaults(fn=_train)

    e = sub.add_parser("evaluate", help="offline MSE/RMSE of a prediction CSV")
    e.add_argument("ratings_file")
    e.add_argument("prediction_csv")
    e.set_defaults(fn=_evaluate)

    rc = sub.add_parser(
        "recommend", help="top-K recommendations from checkpointed factors"
    )
    rc.add_argument("--checkpoint-dir", default=None)
    rc.add_argument("--checkpoint-journal", default=None,
                    help="serve from a transport journal instead "
                    "(directory or tcp://HOST:PORT)")
    rc.add_argument("--data", required=True,
                    help="training data file (raw-id mapping + exclude-seen)")
    rc.add_argument("--format", choices=["netflix", "movielens"], default="netflix")
    rc.add_argument("--min-rating", type=float, default=0.0)
    rc.add_argument("--users", required=True,
                    help="comma-separated raw user ids, or 'all'")
    rc.add_argument("-k", type=int, default=10)
    rc.add_argument("--include-seen", action="store_true",
                    help="do not exclude already-rated movies")
    rc.set_defaults(fn=_recommend)

    sv = sub.add_parser(
        "serve",
        help="top-K request server: score+top-K kernel over the transport "
        "log, batching/coalescing, hot-user cache (ISSUE 8)",
    )
    sv.add_argument("--checkpoint-dir", default=None)
    sv.add_argument("--checkpoint-journal", default=None,
                    help="serve from a transport journal instead "
                    "(directory or tcp://HOST:PORT)")
    sv.add_argument("--data", required=True,
                    help="training data file (raw-id mapping + exclude-seen)")
    sv.add_argument("--format", choices=["netflix", "movielens"],
                    default="netflix")
    sv.add_argument("--min-rating", type=float, default=0.0)
    sv.add_argument("--broker", default=None, metavar="tcp://HOST:PORT",
                    help="join this native broker's serve topics and "
                    "answer until killed; omit for the built-in "
                    "open-loop loadgen against an in-memory log")
    sv.add_argument("-k", type=int, default=10,
                    help="loadgen-mode top-K per request")
    sv.add_argument("--include-seen", action="store_true",
                    help="do not exclude already-rated movies")
    sv.add_argument("--table-dtype",
                    choices=["float32", "bfloat16", "int8"],
                    default="float32",
                    help="item-table quantization (ops.quant): bf16 "
                    "halves the bytes the table holds in HBM and the "
                    "scorer scans per batch, int8 + a float32 scale a "
                    "row quarters them (and is quantized on the host, "
                    "uploaded as codes); that is bytes, not time: the "
                    "float32 scorer is bound by the MXU, and answers "
                    "are exact against the dequantized table (PERF.md "
                    "has the times)")
    sv.add_argument("--tile-m", type=int, default=2048,
                    help="movie-axis tile rows streamed through VMEM")
    sv.add_argument("--max-batch", type=int, default=256,
                    help="max requests coalesced into one scoring batch")
    sv.add_argument("--replicas", type=int, default=1,
                    help="serving fleet size (ISSUE 18): N replicas "
                    "behind the request log with user-keyed routing, "
                    "per-replica /metrics + /readyz, admission control, "
                    "and kill/failover at the committed cursor")
    sv.add_argument("--admission-queue", type=int, default=0,
                    help="fleet admission-control queue depth per poll "
                    "(0 = unbounded); backlog beyond it is answered "
                    "with explicit RETRIABLE rejections, never dropped")
    sv.add_argument("--item-departments", default=None, metavar="FILE",
                    help="one int an item row (text, or .npy): the item's "
                         "department; a request may then name a department "
                         "and is answered among its items alone")
    sv.add_argument("--request-partitions", type=int, default=1)
    sv.add_argument("--response-partitions", type=int, default=1)
    sv.add_argument("--stream-dir", default=None, metavar="DIR",
                    help="with --broker: fold the broker's rating-updates "
                    "topic into the served factors between request "
                    "batches, committing to the stream store DIR "
                    "(resumed if it holds one); the server supervises "
                    "the stream task and replaces one that dies from "
                    "the store, answering all the while (float32 table, "
                    "exact mode)")
    sv.add_argument("--stream-lam", type=float, default=0.05,
                    help="--stream-dir: the fold-in's ALS-WR lambda")
    sv.add_argument("--stream-batch-records", type=int, default=256,
                    help="--stream-dir: log records a micro-batch")
    sv.add_argument("--loadgen-qps", type=float, default=100.0)
    sv.add_argument("--loadgen-requests", type=int, default=256)
    sv.add_argument("--seed", type=int, default=0)
    sv.add_argument("--compile-cache-dir", default=None, metavar="DIR",
                    help="persistent jax compilation cache (ISSUE 13) — a "
                    "restarted server replays its prewarmed serve programs "
                    "instead of recompiling the batch-bucket set (default "
                    ".jax_compile_cache at the checkout root; "
                    "JAX_COMPILATION_CACHE_DIR wins over DIR)")
    sv.add_argument("--metrics-port", type=int, default=None,
                    help="serve GET /metrics (Prometheus text) on this "
                    "port while the server runs (0 = ephemeral)")
    sv.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="write the host span trace (batch assemble/"
                    "compute/respond timeline) here at exit")
    sv.set_defaults(fn=_serve)

    pd = sub.add_parser(
        "predict",
        help="dump the prediction CSV from checkpointed factors "
        "(the reference's final-collection phase as a standalone step)",
    )
    pd.add_argument("--checkpoint-dir", default=None)
    pd.add_argument("--checkpoint-journal", default=None,
                    help="serve from a transport journal instead "
                    "(directory or tcp://HOST:PORT)")
    pd.add_argument("--data", required=True,
                    help="training data file (raw-id mapping / matrix shape)")
    pd.add_argument("--format", choices=["netflix", "movielens"], default="netflix")
    pd.add_argument("--min-rating", type=float, default=0.0)
    pd.add_argument(
        "--output", default="auto",
        help="'auto' = predictions/prediction_matrix_<ts>, or a path",
    )
    pd.set_defaults(fn=_predict)

    b = sub.add_parser(
        "broker", help="run the native TCP log broker (native/cfk_broker)"
    )
    b.add_argument("--port", type=int, default=29092,
                   help="0 picks an ephemeral port (printed on stdout)")
    b.add_argument("--data-dir", default=None,
                   help="persist logs here (FileBroker-compatible format); "
                   "default is memory-only")
    b.add_argument("--bind", default="127.0.0.1",
                   help="listen address; 0.0.0.0 accepts cross-host clients")
    b.set_defaults(fn=_broker)

    tp = sub.add_parser(
        "topics", help="broker topic admin (the reference's setup.sh role)"
    )
    tp.add_argument("action", choices=["list", "create", "delete", "recreate"])
    tp.add_argument("--broker", required=True,
                    help="tcp://HOST:PORT (list) or tcp://HOST:PORT/TOPIC")
    tp.add_argument("--partitions", type=int, default=4)
    tp.set_defaults(fn=_topics)

    pr = sub.add_parser(
        "produce", help="stream a Netflix-format ratings file into a broker"
    )
    pr.add_argument("--broker", required=True, help="tcp://HOST:PORT[/TOPIC]")
    pr.add_argument("--data", required=True)
    pr.add_argument("--partitions", type=int, default=4)
    pr.add_argument("--append", action="store_true",
                    help="produce into an existing topic (only sound if every "
                    "earlier produce used --no-eof; EOF means end-of-ingest)")
    pr.add_argument("--no-eof", action="store_true",
                    help="skip the EOF fan-out, leaving the topic open for "
                    "more files; the final produce must omit this flag")
    pr.set_defaults(fn=_produce)

    st = sub.add_parser(
        "stream",
        help="exactly-once streaming fold-in: consume rating updates and "
        "fold them into live factors (rate → fold-in → resume)",
    )
    st.add_argument("--data", required=True,
                    help="base ratings (the training corpus the stream "
                    "updates; also the crash replay's state seed)")
    st.add_argument("--format", choices=["netflix", "movielens"],
                    default="netflix")
    st.add_argument("--min-rating", type=float, default=0.0)
    st.add_argument("--updates", required=True,
                    help="the durable updates topic's home: a FileBroker "
                    "directory or tcp://HOST:PORT (cfk_broker server)")
    st.add_argument("--stream-dir", required=True,
                    help="checkpoint store for the atomic factor+cursor "
                    "commits; re-run with the same dir to resume")
    st.add_argument("--produce-csv", default=None, metavar="FILE",
                    help="producer mode: append 'user,movie,rating' lines "
                    "from FILE to the updates topic and exit")
    st.add_argument("--partitions", type=int, default=1,
                    help="updates-topic partitions when creating it "
                    "(--produce-csv on a fresh topic)")
    st.add_argument("--rank", type=int, default=5)
    st.add_argument("--lam", type=float, default=0.05)
    st.add_argument("--iterations", type=int, default=7,
                    help="base-train / warm-retrain iteration count")
    st.add_argument("--seed", type=int, default=42)
    st.add_argument("--layout", choices=["padded", "tiled"],
                    default="padded",
                    help="base dataset layout (the fold-in takes its "
                    "route from each micro-batch's lists)")
    st.add_argument("--solver", choices=["auto", "cholesky", "pallas"],
                    default="auto")
    st.add_argument("--dtype", choices=["float32", "bfloat16"],
                    default="float32")
    st.add_argument("--chunk-elems", type=int, default=1 << 20)
    st.add_argument("--batch-records", type=int, default=256,
                    help="log records per partition per micro-batch; part "
                    "of the replay contract (committed with the cursor)")
    st.add_argument("--max-batches", type=int, default=None,
                    help="stop after N micro-batches (default: drain)")
    st.add_argument("--follow", action="store_true",
                    help="keep polling an idle topic instead of exiting "
                    "when caught up")
    st.add_argument("--retrain-every", type=int, default=None, metavar="N",
                    help="warm full retrain (movie side included) every N "
                    "stream commits, current factors as the seed")
    st.add_argument("--health-check-every", type=int, default=1,
                    help="probe every fold-in batch before commit "
                    "(default 1; the ladder escalates on trips and "
                    "quarantines batches that defeat it)")
    st.add_argument("--health-norm-limit", type=float, default=1e6)
    st.add_argument("--max-recoveries", type=int, default=4)
    st.add_argument("--lam-escalation", type=float, default=10.0)
    st.add_argument("--on-unrecoverable", choices=["degrade", "raise"],
                    default="degrade")
    st.add_argument("--keep-last-n", type=int, default=8,
                    help="stream commits retained (per-batch commits grow "
                    "fast; default 8, None-like large values keep more)")
    st.add_argument("--no-preempt-save", action="store_true")
    st.add_argument("--prewarm", action="store_true",
                    help="trace the fold-in pow2 bucket grid before the "
                    "first batch (ISSUE 13): the first real micro-batch "
                    "then pays zero jit traces (padded fold layout; "
                    "pair with --compile-cache-dir so a warm restart "
                    "skips the compiles too)")
    st.add_argument("--compile-cache-dir", default=None, metavar="DIR",
                    help="persistent jax compilation cache — removes the "
                    "cold-process re-compile cost of the fold-in/retrain "
                    "programs (default .jax_compile_cache at the checkout "
                    "root; JAX_COMPILATION_CACHE_DIR wins over DIR)")
    st.add_argument("--metrics-port", type=int, default=None,
                    help="serve GET /metrics (Prometheus text) on this "
                    "port while the stream runs (0 = ephemeral)")
    st.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="write the host span trace (stream batch stage/"
                    "solve/probe/commit timeline) here at exit")
    st.add_argument("--no-eval", action="store_true",
                    help="skip the merged-state RMSE evaluation at exit")
    st.add_argument("--dataset-cache", default=None)
    st.add_argument("--metrics", choices=["json", "logfmt"],
                    default="logfmt")
    st.set_defaults(fn=_stream)

    pl = sub.add_parser(
        "plan",
        help="resolve the execution plan for a shape/device: print the "
        "cost-model choice with per-knob explanations (--explain) or "
        "warm the autotune cache offline (--autotune)",
    )
    pl.add_argument("--users", type=int, default=480_189)
    pl.add_argument("--movies", type=int, default=17_770)
    pl.add_argument("--ratings", type=int, default=100_480_507,
                    help="nnz of the training corpus")
    pl.add_argument("--rank", type=int, default=64)
    pl.add_argument("--shards", type=int, default=1)
    pl.add_argument("--implicit", action="store_true",
                    help="iALS shape (adds the global Gram term)")
    pl.add_argument("--storage-dtype", choices=["float32", "bfloat16"],
                    default="float32",
                    help="factor storage dtype of the run being planned")
    pl.add_argument("--serve", action="store_true",
                    help="plan the top-K serving path instead of training "
                    "(batch quantum + table dtype from the table-scan "
                    "byte model)")
    pl.add_argument("--serve-k", type=int, default=100)
    # Constraint pins — 'auto' leaves the knob to the resolver; anything
    # else pins it exactly like the matching ALSConfig/train flag would.
    pl.add_argument("--layout", default="auto",
                    choices=["auto", "padded", "bucketed", "segment",
                             "tiled"])
    pl.add_argument("--exchange", default="auto",
                    choices=["auto", "all_gather", "ring", "hier_ring"])
    pl.add_argument("--table-dtype", default="auto",
                    choices=["auto", "float32", "bfloat16", "int8"])
    pl.add_argument("--fused", default="auto",
                    choices=["auto", "on", "off"])
    pl.add_argument("--gather", default="auto",
                    choices=["auto", "fused", "xla"])
    pl.add_argument("--overlap", default="auto",
                    choices=["auto", "on", "off"])
    pl.add_argument("--reg-solve-algo", default="auto",
                    choices=["auto", "lu", "gj"])
    pl.add_argument("--solver", default="auto",
                    choices=["auto", "cholesky", "pallas"])
    pl.add_argument("--chunk-elems", type=int, default=None)
    pl.add_argument("--offload-tier", default="auto",
                    choices=["auto", "device", "host_window"],
                    help="out-of-core tier pin (ISSUE 11/12): 'auto' "
                    "lets the PER-SHARD memory-budget predicate decide; "
                    "'device' REFUSES when the resident tables cannot "
                    "fit one device; 'host_window' pins the windowed "
                    "host-offload path (sharded shapes pair it with any "
                    "exchange)")
    pl.add_argument("--ici-group", type=int, default=None, metavar="I",
                    help="inner-ring size pin of the hier_ring exchange "
                    "(a real plan field since ISSUE 12 — the cost model "
                    "prices the pinned hierarchy; default: the device's "
                    "ICI domain)")
    pl.add_argument("--staging", default="auto",
                    choices=["auto", "pool", "serial"],
                    help="host staging engine pin of the host_window "
                    "tier (ISSUE 13): the cost model exposes only the "
                    "PCIe share the chosen engine cannot hide")
    pl.add_argument("--hot-rows", type=int, default=None, metavar="F",
                    help="hot-row device cache pin of the host_window "
                    "tier (ISSUE 15): total top-referenced rows kept "
                    "device-resident (0 = off).  Default: free — the "
                    "resolver picks the ~10%% power-law target when the "
                    "budget headroom admits the reservation, else 0; "
                    "--explain prints the decision (admitted bytes vs "
                    "the coverage target), and a pinned-impossible F "
                    "raises naming the bytes")
    pl.add_argument("--device", default="auto",
                    choices=["auto", "v5e", "cpu"],
                    help="'auto' detects the current jax backend; 'v5e' "
                    "plans for the reference TPU without one attached")
    pl.add_argument("--mode", default="model",
                    choices=["model", "pinned", "autotune"])
    pl.add_argument("--explain", action="store_true",
                    help="per-knob cost-model explanation: every cost "
                    "term plus the estimated delta of flipping each free "
                    "knob away from the chosen value")
    pl.add_argument("--autotune", action="store_true",
                    help="measure the top candidates on a trimmed "
                    "synthetic workload and cache the winner (implies "
                    "--mode autotune)")
    pl.add_argument("--cache-path", default=None,
                    help="autotune cache file (default "
                    "~/.cache/cfk_tpu/plan_cache.json)")
    pl.set_defaults(fn=_plan_cmd)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.platform != "default":
        import jax

        jax.config.update("jax_platforms", args.platform)
    from cfk_tpu.resilience.policy import TrainingDivergedError
    from cfk_tpu.transport.tcp import BrokerRequestError

    try:
        return args.fn(args)
    except (ValueError, OSError, KeyError, BrokerRequestError,
            TrainingDivergedError) as e:
        # User-input errors get one clean line; CFK_TPU_TRACEBACK=1 re-raises
        # for debugging.
        import os

        if os.environ.get("CFK_TPU_TRACEBACK"):
            raise
        _eprint(f"error: {e}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
