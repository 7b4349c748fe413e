"""Perf lab: step-level timing of the full-scale ALS iteration on real TPU.

``bench.py`` measures the user-facing path (fresh trainer per timing, block
upload included) with a two-point fit to cancel the fixed cost — honest for
reporting, but too slow for optimization loops (every timing re-uploads
multi-GB blocks).  This lab uploads once and times ``step()`` calls directly
with a device→host scalar fetch as the barrier, reporting min/median over
repeats.  Datasets are cached on disk per (shape, layout, chunk) key so an
experiment costs seconds, not minutes, after the first run.

Usage:
  python scripts/perf_lab.py --layout segment --chunk-elems 4194304 \
      --solver pallas --iters 3 --repeats 5
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CACHE_ROOT = os.environ.get("CFK_PERF_CACHE", "/tmp/cfk_perf_cache")


def sync(x) -> None:
    np.asarray(x[:1, :1])


def measure_steps(steps_bound, u, m, *, repeats, iters, clock=time.time,
                  on_call=None):
    """min-of-N step timing with a device→host fetch as the barrier.

    ``clock`` is injectable so the scoreboard's timing logic is testable
    without a device (``tests/test_perf_lab.py``)."""
    times = []
    for i in range(repeats):
        t0 = clock()
        u, m = steps_bound(u, m)
        sync(u)
        times.append(clock() - t0)
        print(f"# call {i}: {times[-1]:.3f}s "
              f"({times[-1]/iters:.3f} s/iter)", flush=True)
        if on_call is not None:
            # steps_bound donates its factor arguments; a hook that runs
            # it must hand the fresh buffers back or the next timed call
            # would read donated (deleted) arrays.
            res = on_call(i, u, m)
            if res is not None:
                u, m = res
    return times, u, m


def get_dataset(args):
    from cfk_tpu.data.cache import cached_scale_dataset

    return cached_scale_dataset(
        users=args.users, movies=args.movies, nnz=args.nnz, seed=args.seed,
        layout=args.layout, chunk_elems=args.chunk_elems,
        tile_rows=args.tile_rows, slice_rows=args.slice_rows,
        accum_chunk_elems=args.accum_chunk_elems,
        dense_stream=args.dense_stream, cache_root=CACHE_ROOT,
    )


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--users", type=int, default=480_189)
    p.add_argument("--movies", type=int, default=17_770)
    p.add_argument("--nnz", type=int, default=100_480_507)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rank", type=int, default=64)
    p.add_argument("--layout", default="segment",
                   choices=["padded", "bucketed", "segment", "tiled"])
    p.add_argument("--chunk-elems", type=int, default=1 << 20)
    p.add_argument("--tile-rows", type=int, default=128)
    p.add_argument("--slice-rows", type=int, default=None,
                   help="accum-mode fixed-table gather slice height "
                   "(default: the builder's TILED_SLICE_ROWS_DEFAULT)")
    p.add_argument("--solver", default="pallas",
                   choices=["auto", "cholesky", "pallas"])
    p.add_argument("--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--gram-backend", default=None,
                   choices=[None, "ragged", "segsum"])
    p.add_argument("--tiled-gram-backend", default=None,
                   choices=[None, "xla", "pallas"])
    p.add_argument("--group-tiles", type=int, default=None,
                   help="pallas tiled-gram group size override")
    p.add_argument("--reg-solve-algo", default=None, choices=[None, "gj", "lu"],
                   help="fused reg+solve elimination algorithm override")
    p.add_argument("--table-dtype", default="float32",
                   choices=["float32", "bfloat16", "int8"],
                   help="HBM gather-table dtype axis (cfk_tpu.ops.quant): "
                   "quantize the fixed-side table the half-steps gather "
                   "from — bf16 halves the gather bytes, int8+per-row-"
                   "scale quarters them; accumulation stays f32 and the "
                   "solved factors keep --dtype.  float32 = the identity "
                   "(bit-identical to pre-quantization)")
    p.add_argument("--ials", action="store_true",
                   help="time the implicit-feedback (iALS) iteration body")
    p.add_argument("--alpha", type=float, default=40.0)
    p.add_argument("--dense-stream", action="store_true",
                   help="tiled: unpadded dense gather stream on the "
                   "stream (user) half — kills the ~26%% tile-padding "
                   "gather slots (explicit ALS only)")
    p.add_argument("--accum-chunk-elems", type=int, default=None,
                   help="tiled: separate chunk size for the accum (movie) "
                   "side — its per-chunk VMEM need is tiny, so bigger "
                   "chunks cut scan overheads")
    p.add_argument("--fused", default="on", choices=["on", "off"],
                   help="fused Gram+solve epilogue A/B axis: 'on' "
                   "(default) = solve each chunk's normal equations inside "
                   "the Gram kernel's VMEM residency, 'off' = the split "
                   "Gram→HBM→solve schedule.  The stream/dense chunk "
                   "scans stay bit-exact across the axis (their split "
                   "solve pins the one-pass reg+solve kernel, so only the "
                   "round-trip toggles); the accum/ring final solves swap "
                   "to the split ridge-add + dispatch under 'off'")
    p.add_argument("--gather", default="fused", choices=["fused", "xla"],
                   help="neighbor-gather A/B axis: 'fused' (default) = "
                   "in-kernel DMA gather (the pallas Gram kernels fetch "
                   "the indexed factor rows themselves — no materialized "
                   "[C, k] stream), 'xla' = the XLA gather that "
                   "materializes the stream in HBM.  Factors are "
                   "bit-identical across the axis.  Covers the tiled "
                   "chunk bodies AND the bucketed/subspace ports (same "
                   "process default, ops.tiled.default_in_kernel_gather)")
    p.add_argument("--overlap", default="on", choices=["on", "off"],
                   help="comm/compute overlap A/B axis: 'on' (default) = "
                   "double-buffered chunk/ring pipelines "
                   "(cfk_tpu.ops.pipeline), 'off' = the serial reference "
                   "schedule — same math, bit-identical factors")
    p.add_argument("--health", default="off", choices=["on", "off"],
                   help="health-sentinel A/B axis: 'on' folds the "
                   "resilience probe (isfinite + norm watchdogs, "
                   "cfk_tpu.resilience.sentinel) into the fori_loop "
                   "carry every iteration (health_check_every=1, the "
                   "worst case) — the s/iter delta vs 'off' is the "
                   "sentinel's overhead, budgeted < 2%")
    p.add_argument("--health-norm-limit", type=float, default=1e6)
    p.add_argument("--ckpt", default=None, choices=[None, "sync", "async"],
                   help="checkpoint-writer A/B axis: step per-iteration "
                   "from the host with a save after every iteration — "
                   "'sync' serializes+fsyncs in the step loop, 'async' "
                   "hands the disk work to CheckpointManager's background "
                   "writer (cfk_tpu.transport.checkpoint.save_async).  The "
                   "timed call includes the in-loop save stalls, so the "
                   "sync−async s/iter delta is the save stall removed from "
                   "the step loop; bytes on disk are identical")
    p.add_argument("--foldin", default="off", choices=["off", "on"],
                   help="streaming fold-in throughput axis: instead of the "
                   "step timing, drain a synthetic rating-update stream "
                   "through StreamSession (in-memory broker, per-batch "
                   "atomic factor+cursor commits, health probe per batch) "
                   "and report updates/sec absorbed with the stage/solve/"
                   "commit split (cfk_tpu.streaming; ISSUE 6)")
    p.add_argument("--foldin-updates", type=int, default=4096,
                   help="synthetic stream size for --foldin on")
    p.add_argument("--foldin-batch-records", type=int, default=256,
                   help="log records per micro-batch for --foldin on")
    p.add_argument("--serve", default="off", choices=["off", "on"],
                   help="top-K serving axis (ISSUE 8): drive an open-loop "
                   "synthetic request stream through the full request→"
                   "score→top-K→respond loop (in-memory log, "
                   "RecommendServer batch coalescing, the score+top-K "
                   "kernel with exclude-seen from this dataset's rating "
                   "lists) and report QPS + p50/p99 with the table-scan "
                   "vs_roofline — sweep --serve-batch × --table-dtype × "
                   "--serve-k")
    p.add_argument("--serve-batch", type=int, default=64,
                   help="server max coalesced batch for --serve on")
    p.add_argument("--serve-k", type=int, default=10,
                   help="top-K per request for --serve on")
    p.add_argument("--serve-requests", type=int, default=512,
                   help="open-loop request count for --serve on")
    p.add_argument("--serve-tile-m", type=int, default=512,
                   help="movie-axis tile rows of the serve kernel")
    p.add_argument("--serve-mode", default="exact",
                   choices=["exact", "two_stage"],
                   help="retrieval mode for --serve on (ISSUE 16): "
                   "two_stage runs the clustered candidate -> exact "
                   "rescore path and the row reports measured recall_at_k "
                   "vs the bit-exact scan plus bytes_scanned_per_batch — "
                   "the A/B axis against the default exact scan")
    p.add_argument("--serve-clusters", type=int, default=0,
                   help="two_stage k-means cluster count (0 = auto "
                   "~sqrt(movies); probe count follows the 0.95 recall "
                   "floor)")
    p.add_argument("--offload", default=None,
                   choices=[None, "device", "host_window"],
                   help="out-of-core axis (ISSUE 11): run the SAME "
                   "stream-forced tiled workload with HBM-resident "
                   "tables ('device') or host-RAM stores + windowed "
                   "device_put staging ('host_window'); rows carry a "
                   "factors crc32 so the tier-1 smoke pins windowed == "
                   "resident bit-exactness")
    p.add_argument("--offload-window-chunks", type=int, default=4,
                   help="chunks per staged window on the host_window tier")
    p.add_argument("--optimizer", default="als",
                   choices=["als", "ials", "ialspp"],
                   help="optimizer of the --offload axis (ISSUE 19): "
                   "'als' runs the explicit trainer on the stream-forced "
                   "tiled layout (the original axis); 'ials'/'ialspp' run "
                   "the implicit family on the bucketed width-class "
                   "layout (--layout bucketed) — the host_window arm "
                   "streams width-class windows through the out-of-core "
                   "subspace driver with the global-Gram reduction, and "
                   "crc equality against the resident arm is the "
                   "windowed == resident bit-exactness proof for the "
                   "implicit optimizers")
    p.add_argument("--offload-shards", type=int, default=1,
                   help="shard count of the --offload axis (ISSUE 12): "
                   "the host_window arm runs the sharded windowed "
                   "driver (no mesh needed); the device arm runs the "
                   "real shard_map trainer and needs that many jax "
                   "devices — crc equality between the arms is the "
                   "sharded bit-exactness proof")
    p.add_argument("--offload-budget-mb", type=float, default=None,
                   help="artificial device budget (MB) for window sizing")
    p.add_argument("--staging", default=None,
                   choices=[None, "serial", "pool"],
                   help="host staging engine A/B axis of the "
                   "host_window tier (ISSUE 13): 'pool' (the config "
                   "default) overlaps every shard's window staging — "
                   "store gather, host quantize, checksum, device_put — "
                   "on a bounded thread pool across shards AND windows; "
                   "'serial' pins the PR 10/11 one-thread double buffer "
                   "(the baseline arm).  crc equality across the axis "
                   "is pinned by the tier-1 smoke; the row records pool "
                   "depth, staged MB/s, the overlap-hidden fraction, "
                   "trace_count, and time_to_first_step_s")
    p.add_argument("--staging-pool-depth", type=int, default=None,
                   help="windows staged ahead of consumption (pool "
                   "mode); clamped so depth+1 worst windows fit the "
                   "window budget")
    p.add_argument("--hot-rows", type=int, default=None,
                   help="hot-row device cache axis of the host_window "
                   "tier (ISSUE 15): total top-referenced fixed-table "
                   "rows kept device-resident so windows stage only "
                   "their cold delta.  None = auto (coverage-curve knee "
                   "under the budget headroom), 0 = off (the PR 12 "
                   "full-staging engine — the A/B baseline), N = pinned "
                   "total.  crc equality across the axis is pinned by "
                   "the tier-1 smoke; the row records the resolved "
                   "fraction, reference coverage, and hot/cold staged "
                   "MB")
    p.add_argument("--compile-cache-dir", default=None, metavar="DIR",
                   help="persistent jax compilation cache (ISSUE 13; "
                   "JAX_COMPILATION_CACHE_DIR wins over DIR): a second lab "
                   "run against the same DIR skips the XLA compiles behind "
                   "its traces — compare the rows' "
                   "time_to_first_step_s/compile wall to measure the "
                   "warm-start win")
    p.add_argument("--plan", default=None,
                   choices=[None, "model", "autotune", "pinned"],
                   help="execution-planner axis (cfk_tpu.plan, ISSUE 9): "
                   "'pinned' runs this lab's explicit --fused/--gather/"
                   "--overlap/--reg-solve-algo/--table-dtype flags AS a "
                   "pinned plan (today's behavior, with provenance "
                   "recorded); 'model' FREES those knobs and runs the "
                   "cost-model optimum; 'autotune' measures the model's "
                   "top candidates on this lab's own step timing and "
                   "caches the winner per (shape-class, device, version)."
                   "  The row gains plan/plan_source/plan_est_s/"
                   "plan_cache provenance columns either way")
    p.add_argument("--plan-cache", default=None,
                   help="autotune cache path for --plan autotune "
                   "(default ~/.cache/cfk_tpu/plan_cache.json)")
    p.add_argument("--telemetry", default="off", choices=["off", "on"],
                   help="A/B axis (ISSUE 14): 'on' installs the host span "
                   "tracer for the whole measured run (row gains the "
                   "recorded span count; factors must stay crc-identical "
                   "to the off arm — the overhead smoke pins it)")
    p.add_argument("--trace-dir", default=None, metavar="DIR",
                   help="with --telemetry on, write the Chrome-trace host "
                   "span timeline here")
    p.add_argument("--iters", type=int, default=3,
                   help="steps per timed call (fused per-call overhead "
                   "amortizes over these)")
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of one timed call")
    return p


def run_foldin_lab(args) -> dict:
    """The --foldin axis: streaming fold-in throughput on this dataset.

    Drains a synthetic rating-update stream (drawn from the dataset's own
    id universe — same Zipf-hot users, so neighbor-list widths are
    realistic) through the full ``StreamSession`` loop: exactly-once batch
    assembly, staged dedup, restricted half-iteration solve, health probe,
    and the per-batch atomic factor+cursor commit.  The row reports
    updates/sec absorbed and the stage/solve/commit wall split — the
    stream-freshness counterpart of the step-timing rows.  The base model
    is one training iteration: fold-in cost is independent of factor
    VALUES, and the quality contract lives in ``bench.py --foldin``.
    """
    import tempfile

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.models.als import train_als
    from cfk_tpu.streaming import StreamConfig, StreamProducer, StreamSession
    from cfk_tpu.transport import InMemoryBroker
    from cfk_tpu.transport.checkpoint import CheckpointManager
    from cfk_tpu.utils.metrics import Metrics

    ds = get_dataset(args)
    cfg = ALSConfig(
        rank=args.rank, lam=0.05, num_iterations=1, seed=args.seed,
        layout=args.layout, solver=args.solver, dtype=args.dtype,
        health_check_every=1,
    )
    t0 = time.time()
    base = train_als(ds, cfg)
    base_s = time.time() - t0
    n = args.foldin_updates
    rng = np.random.default_rng(args.seed + 1)
    broker = InMemoryBroker()
    prod = StreamProducer(broker)
    prod.send_many(
        rng.choice(ds.user_map.raw_ids, n),
        rng.choice(ds.movie_map.raw_ids, n),
        rng.integers(1, 6, n).astype(np.float32),
    )
    metrics = Metrics()
    with tempfile.TemporaryDirectory() as d:
        sess = StreamSession(
            ds, cfg, broker, CheckpointManager(d, async_write=True),
            stream=StreamConfig(batch_records=args.foldin_batch_records),
            base_model=base, metrics=metrics,
        )
        t0 = time.time()
        sess.run()
        wall = time.time() - t0
    row = {
        "foldin": "on",
        "updates_per_s": round(n / wall, 1),
        "updates": n,
        "updates_fresh": int(metrics.counters.get("updates_fresh", 0)),
        "batches": int(sess.stream_step),
        "batch_records": args.foldin_batch_records,
        "absorb_wall_s": round(wall, 4),
        "stage_s": round(metrics.phases.get("stage", 0.0), 4),
        "foldin_solve_s": round(metrics.phases.get("foldin_solve", 0.0), 4),
        "health_check_s": round(metrics.phases.get("health_check", 0.0), 4),
        "commit_s": round(metrics.phases.get("commit", 0.0), 4),
        "base_train_s": round(base_s, 4),
        "layout": args.layout, "solver": args.solver, "dtype": args.dtype,
        "rank": args.rank,
        "users": args.users, "movies": args.movies, "nnz": args.nnz,
    }
    print(json.dumps(row))
    return row


def run_serve_lab(args) -> dict:
    """The --serve axis: top-K serving QPS/latency on this dataset.

    The tier-1 in-memory smoke of the WHOLE serve loop (mirroring
    ``--foldin``'s role for streaming): synthetic factors at the dataset's
    entity counts (serving cost is independent of factor values), the
    dataset's real rating lists as the exclude-seen CSR, requests through
    the transport log, ``RecommendServer`` coalescing, the score+top-K
    kernel, responses polled back by the open-loop generator.  The row
    reports achieved QPS, p50/p99, the direct-engine batch floor, and the
    table-scan ``vs_roofline`` (``utils.roofline.serve_batch_cost``).
    """
    import jax

    from cfk_tpu.ops import quant
    from cfk_tpu.serving import (
        RecommendServer,
        ServeClient,
        engine_from_model,
        ensure_serve_topics,
        run_open_loop,
        warm_serve_programs,
        zipf_user_rows,
    )
    from cfk_tpu.transport import InMemoryBroker
    from cfk_tpu.utils.roofline import (
        serve_batch_cost,
        serve_roofline_row,
        this_device_kind,
    )

    quant.resolve_table_dtype(args.table_dtype)
    ds = get_dataset(args)
    num_users = ds.user_map.num_entities
    num_movies = ds.movie_map.num_entities
    rng = np.random.default_rng(args.seed)
    # synthetic factors (serving cost is value-independent); the seen-CSR
    # comes from the dataset's real rating lists via the ONE builder the
    # served path uses (engine_from_model)
    from cfk_tpu.models.als import ALSModel

    model = ALSModel(
        user_factors=rng.standard_normal(
            (num_users, args.rank)).astype(np.float32) * 0.1,
        movie_factors=rng.standard_normal(
            (num_movies, args.rank)).astype(np.float32) * 0.1,
        num_users=num_users, num_movies=num_movies,
    )
    eng = engine_from_model(
        model, ds, table_dtype=args.table_dtype, tile_m=args.serve_tile_m,
        serve_mode=args.serve_mode, clusters=args.serve_clusters or None,
    )
    k = min(args.serve_k, num_movies)
    batch = args.serve_batch
    qrows = zipf_user_rows(num_users, batch, seed=args.seed + 1)
    eng.topk(qrows, k)  # warmup / compile
    times = []
    for _ in range(args.repeats):
        t0 = time.time()
        eng.topk(qrows, k)
        times.append(time.time() - t0)
    batch_s = min(times)
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(eng, broker, max_batch=batch)
    client = ServeClient(broker)
    warm_serve_programs(client, server, qrows, k, batch)
    rate = max(batch / batch_s * 0.7, 1.0)
    report = run_open_loop(
        client, rate_qps=rate, num_requests=args.serve_requests,
        user_rows=zipf_user_rows(num_users, args.serve_requests,
                                 seed=args.seed + 2),
        k=k, server=server, drive_server=True,
    )
    # recall vs the same engine's bit-exact scan + the executed mode's
    # measured scan bytes (ISSUE 16 A/B columns, mirroring bench --serve)
    from cfk_tpu.serving import recall_at_k

    _, ids = eng.topk(qrows, k)
    scan = dict(eng.last_scan)
    if scan.get("serve_mode") == "two_stage":
        _, oracle = eng.topk(qrows, k, force_exact=True)
        recall = float(recall_at_k(ids, oracle))
        cost = serve_batch_cost(
            num_movies, args.rank, batch, k, table_dtype=args.table_dtype,
            serve_mode="two_stage", clusters=scan["clusters"],
            probe_clusters=scan["probe_clusters"],
            shortlist_rows=scan["shortlist_rows_padded"],
        )
    else:
        recall = 1.0
        cost = serve_batch_cost(
            num_movies, args.rank, batch, k,
            table_dtype=args.table_dtype, m_pad=eng.table_rows,
        )
    row = {
        "serve": "on",
        "serve_batch": batch,
        "serve_k": k,
        "serve_mode": scan.get("serve_mode", args.serve_mode),
        "recall_at_k": round(recall, 4),
        **{kk: scan[kk] for kk in ("clusters", "probe_clusters",
                                   "shortlist_rows") if kk in scan},
        "batch_s": round(batch_s, 5),
        "capacity_qps": round(batch / batch_s, 1),
        **report.as_row(),
        **serve_roofline_row(cost, batch_s, args.table_dtype,
                       device_kind=this_device_kind()),
        "layout": args.layout, "rank": args.rank, "dtype": args.dtype,
        "users": args.users, "movies": args.movies, "nnz": args.nnz,
        "tile_m": args.serve_tile_m,
        "backend": jax.default_backend(),
    }
    print(json.dumps(row))
    return row


def _resolve_plan_axis(args, make_steps, mblocks, ublocks, u0, m0):
    """The --plan axis (ISSUE 9): resolve an ExecutionPlan for this lab's
    shape and return (provenance, knobs-for-make_steps).

    'pinned' records provenance for the lab's explicit flags and leaves
    the knob threading EXACTLY as without the axis (bit-identical rows);
    'model' threads the cost-model optimum's knobs concretely; 'autotune'
    measures the model's top candidates with this lab's own steps timing
    (1 timed call after a compile call, per candidate) and caches the
    winner.  Layout/solver/chunk stay pinned to the flags in every mode —
    they are physical properties of the already-built dataset."""
    import functools
    import time as _time

    import jax.numpy as jnp

    from cfk_tpu.plan import (
        DeviceSpec,
        PlanConstraints,
        ProblemShape,
        plan as resolve_plan,
    )

    shape = ProblemShape(
        num_users=args.users, num_movies=args.movies, nnz=args.nnz,
        rank=args.rank, implicit=args.ials, dtype=args.dtype,
        tile_rows=args.tile_rows if args.layout == "tiled" else 16,
    )
    pin = dict(
        layout=args.layout,
        solver=None if args.solver == "auto" else args.solver,
        chunk_elems=args.chunk_elems,
    )
    if args.plan == "pinned":
        pin.update(
            table_dtype=args.table_dtype,
            fused_epilogue=args.fused == "on",
            in_kernel_gather=args.gather == "fused",
            overlap=args.overlap == "on",
            reg_solve_algo=(args.reg_solve_algo
                            if args.reg_solve_algo else None),
        )
    cons = PlanConstraints(**pin)
    device = DeviceSpec.detect()

    def knobs_for(ep):
        return dict(
            overlap=ep.overlap, fused_epilogue=ep.fused_epilogue,
            in_kernel_gather=ep.in_kernel_gather,
            reg_solve_algo=ep.reg_solve_algo,
            table_dtype=ep.table_dtype,
        )

    measure = None
    if args.plan == "autotune":
        def measure(ep):
            steps = make_steps(knobs_for(ep))
            bound = functools.partial(steps, mblk=mblocks, ublk=ublocks)
            uu = jnp.array(u0, copy=True)
            mm = jnp.array(m0, copy=True)
            uu, mm = bound(uu, mm)  # compile + warmup
            sync(uu)
            t0 = _time.time()
            uu, mm = bound(uu, mm)
            sync(uu)
            s = (_time.time() - t0) / args.iters
            print(f"# autotune candidate {ep.summary()}: {s:.4f} s/iter",
                  flush=True)
            return s

    ep, prov = resolve_plan(
        shape, device, cons, mode=args.plan,
        cache_path=args.plan_cache, measure=measure,
    )
    print(f"# plan: {prov.summary()}", flush=True)
    if args.plan == "pinned":
        # Provenance only — the knob threading stays the legacy deferred
        # form, so the row is bit-identical to a --plan-less run.
        return prov, dict(
            overlap=None, fused_epilogue=None, in_kernel_gather=None,
            reg_solve_algo=None, table_dtype=args.table_dtype,
        )
    return prov, knobs_for(ep)


def run_offload_lab(args) -> dict:
    """The ``--offload`` axis (ISSUE 11): time full training iterations on
    one tier — resident tables ('device', the plain trainer) or host-RAM
    stores with windowed staging ('host_window', ``cfk_tpu.offload``) —
    over the SAME stream-forced tiled blocks, so the two rows differ ONLY
    in where the factor tables live.  Each row carries the final factors'
    crc32: the tier-1 smoke (``test_offload_axis_row``) runs both values
    and pins crc equality — the in-memory proof of the windowed ==
    resident bit-exactness contract.

    ``--optimizer ials/ialspp`` (ISSUE 19) swaps in the implicit family
    on the bucketed width-class layout: the host_window arm runs the
    out-of-core subspace driver (width-class windows + the global-Gram
    reduction over the staged table) and the same crc contract holds
    against the resident ``train_ials`` arm
    (``test_offload_axis_optimizer_row``)."""
    import zlib

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synth import synth_coo
    from cfk_tpu.models.als import train_als
    from cfk_tpu.offload.windowed import (
        train_als_host_window,
        train_ials_host_window,
    )
    from cfk_tpu.utils.metrics import Metrics
    from cfk_tpu.utils.roofline import (
        als_iteration_cost,
        roofline_row,
        this_device_kind,
    )

    optimizer = getattr(args, "optimizer", "als") or "als"
    implicit = optimizer in ("ials", "ialspp")
    if implicit:
        if args.layout != "bucketed":
            raise SystemExit(
                "--offload with --optimizer ials/ialspp runs the bucketed "
                "width-class layout; pass --layout bucketed"
            )
    elif args.layout != "tiled":
        raise SystemExit(
            "--offload runs the stream-forced tiled layout; pass "
            "--layout tiled"
        )
    shards = max(int(getattr(args, "offload_shards", 1) or 1), 1)
    coo = synth_coo(args.users, args.movies, args.nnz, seed=args.seed)
    if implicit:
        from cfk_tpu.models.ials import IALSConfig, train_ials

        ds = Dataset.from_coo(
            coo, num_shards=shards, layout="bucketed",
            chunk_elems=args.chunk_elems,
        )
        block_size = max(b for b in (32, 16, 8, 4, 2, 1)
                         if args.rank % b == 0)
        cfg = IALSConfig(
            rank=args.rank, lam=0.1, alpha=args.alpha,
            num_iterations=args.iters, seed=0,
            layout="bucketed", num_shards=shards, dtype=args.dtype,
            table_dtype=args.table_dtype, solver=args.solver,
            overlap=args.overlap == "on",
            fused_epilogue=None if args.fused == "on" else False,
            in_kernel_gather=None if args.gather == "fused" else False,
            algorithm="ials++" if optimizer == "ialspp" else "als",
            block_size=block_size,
            offload_tier=args.offload,
            compile_cache_dir=args.compile_cache_dir,
        )
    else:
        ds = Dataset.from_coo(
            coo, num_shards=shards, layout="tiled",
            chunk_elems=args.chunk_elems,
            tile_rows=args.tile_rows, accum_max_entities=0,
        )
        cfg = ALSConfig(
            rank=args.rank, lam=0.05, num_iterations=args.iters, seed=0,
            layout="tiled", num_shards=shards, dtype=args.dtype,
            table_dtype=args.table_dtype,
            solver=args.solver, overlap=args.overlap == "on",
            fused_epilogue=None if args.fused == "on" else False,
            in_kernel_gather=None if args.gather == "fused" else False,
            hbm_chunk_elems=args.chunk_elems,
            # Pin the axis value into the config so the device arm cannot
            # silently re-plan onto host_window (the same mislabeling
            # guard as bench.py's scale sweep).
            offload_tier=args.offload,
            compile_cache_dir=args.compile_cache_dir,
        )
    metrics = Metrics()
    budget = (args.offload_budget_mb * 1e6
              if args.offload_budget_mb is not None else None)
    mesh = None
    if shards > 1 and args.offload != "host_window":
        if implicit:
            raise SystemExit(
                "--optimizer ials/ialspp resident arm is single-shard; "
                "the host_window arm shards without a mesh"
            )
        # The resident arm of a sharded A/B runs the real shard_map
        # trainer — that is the bit-exactness reference the smoke pins.
        import jax as _jax

        if len(_jax.devices()) < shards:
            raise SystemExit(
                f"--offload device with --offload-shards {shards} needs "
                f"{shards} jax devices (XLA_FLAGS="
                "--xla_force_host_platform_device_count=N on CPU); the "
                "host_window arm needs none"
            )
        from cfk_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(shards)

    def run(cfg_n=None):
        c = cfg if cfg_n is None else cfg_n
        if args.offload == "host_window":
            train_hw = (train_ials_host_window if implicit
                        else train_als_host_window)
            return train_hw(
                ds, c, metrics=metrics,
                chunks_per_window=args.offload_window_chunks,
                device_budget_bytes=budget,
                staging=args.staging,
                pool_depth=args.staging_pool_depth,
                hot_rows=args.hot_rows,
            )
        if implicit:
            return train_ials(ds, c)
        if shards > 1:
            from cfk_tpu.parallel.spmd import train_als_sharded

            return train_als_sharded(ds, c, mesh)
        return train_als(ds, c)

    # Two-point (1 vs N iterations) fit, exactly like bench's scale rows:
    # each trainer call pays a fixed per-call cost — the device arm's
    # block upload, the host_window arm's window PLANNING (window.py is a
    # build-time cost, paid once per dataset in production) — and
    # differencing cancels it, so the per-iteration number compares the
    # tiers on iteration cost alone.
    import dataclasses as _dc

    cfg1 = _dc.replace(cfg, num_iterations=1)
    t0 = time.time()
    model = run()
    compile_s = time.time() - t0
    print(f"# first call (compile+run): {compile_s:.2f}s", flush=True)
    # Cold-start columns from the FIRST call (later calls overwrite the
    # shared metrics with warm numbers): how long until the first full
    # iteration landed, and how many windowed-driver programs it traced.
    cold_first_step_s = metrics.gauges.get("time_to_first_step_s")
    cold_trace_count = metrics.gauges.get("offload_trace_count")
    run(cfg1)
    t_n, t_1 = [], []
    for _ in range(args.repeats):
        t0 = time.time()
        run(cfg1)
        t_1.append(time.time() - t0)
        t0 = time.time()
        model = run()
        np.asarray(model.user_factors[:1])
        t_n.append(time.time() - t0)
    n1 = max(args.iters, 1)
    per_iter = [
        max(tn - t1_, 1e-9) / max(n1 - 1, 1)
        for tn, t1_ in zip(t_n, t_1)
    ] if n1 > 1 else [t / n1 for t in t_n]
    crc = zlib.crc32(
        np.asarray(model.user_factors, np.float32).tobytes()
    ) & 0xFFFFFFFF
    best = min(per_iter)
    cost = als_iteration_cost(
        args.nnz, args.users, args.movies, args.rank,
        factor_bytes=2 if args.dtype == "bfloat16" else 4,
        table_dtype=args.table_dtype,
        implicit=implicit,
        sweeps=cfg.sweeps if optimizer == "ialspp" else 1,
    )
    row = {
        "offload": args.offload,
        "optimizer": optimizer,
        "offload_shards": shards,
        "s_per_iter_min": round(best, 4),
        "s_per_iter_median": round(sorted(per_iter)[len(per_iter) // 2], 4),
        **roofline_row(cost, best, args.table_dtype,
                       device_kind=this_device_kind()),
        "layout": args.layout, "solver": args.solver,
        "chunk_elems": args.chunk_elems, "dtype": args.dtype,
        "rank": args.rank, "iters_per_call": args.iters,
        "overlap": args.overlap, "fused": args.fused,
        "gather": args.gather,
        "factors_crc32": crc,
    }
    if args.offload == "host_window":
        row.update({
            # Staging-engine columns (ISSUE 13) — all read from the
            # driver's HOST-side gauges, never a donated device array
            # (the measure_steps on_call guard, extended to this axis:
            # the windowed driver donates its ring accumulators and, on
            # TPU, the staged table pair, so row assembly must consume
            # only the metrics the driver exported).
            "staging": metrics.notes.get("offload_staging"),
            "pool_depth": metrics.gauges.get("offload_pool_depth"),
            "pool_peak_inflight": metrics.gauges.get(
                "offload_pool_peak_inflight"
            ),
            "stage_busy_s": metrics.gauges.get("offload_stage_busy_s"),
            "stage_stall_s": metrics.gauges.get("offload_stage_stall_s"),
            "staged_mb_per_s": metrics.gauges.get(
                "offload_staged_mb_per_s"
            ),
            "overlap_hidden_fraction": metrics.gauges.get(
                "offload_stage_hidden_frac"
            ),
            "trace_count": cold_trace_count,
            "time_to_first_step_s": cold_first_step_s,
            "windows_m": metrics.gauges.get("offload_windows_m"),
            "windows_u": metrics.gauges.get("offload_windows_u"),
            "window_rows_m": metrics.gauges.get("offload_window_rows_m"),
            "window_rows_u": metrics.gauges.get("offload_window_rows_u"),
            "chunks_per_window": metrics.gauges.get(
                "offload_chunks_per_window"
            ),
            "staged_mb_per_run": metrics.gauges.get("offload_staged_mb"),
            # Split per ISSUE 15: cold = table bytes that crossed PCIe
            # (the whole table share when the hot cache is off), hot =
            # the device-resident partition.
            "staged_cold_mb_per_run": metrics.gauges.get(
                "offload_staged_cold_mb"
            ),
            "hot_resident_mb": metrics.gauges.get(
                "offload_hot_resident_mb"
            ),
            "hot_rows": metrics.gauges.get("offload_hot_rows", 0),
            "hot_coverage": metrics.gauges.get("offload_hot_coverage"),
            "delta_coverage": metrics.gauges.get(
                "offload_delta_coverage"
            ),
            "hot": metrics.notes.get("offload_hot"),
            "plan_held_mb": metrics.gauges.get("offload_plan_held_mb"),
            "staged_rows_local": metrics.gauges.get("offload_rows_local"),
            "staged_rows_ici": metrics.gauges.get("offload_rows_ici"),
            "staged_rows_dcn": metrics.gauges.get("offload_rows_dcn"),
            # Implicit-family columns (ISSUE 19): the global-Gram
            # reduction's own staging meter + its budget reservation.
            "gram_staged_mb_per_run": metrics.gauges.get(
                "offload_gram_staged_mb"
            ),
            "gram_reserved_mb": metrics.gauges.get(
                "offload_gram_reserved_mb"
            ),
        })
    print(json.dumps(row))
    return row


def _telemetry_axis(args):
    """The ``--telemetry {off,on}`` A/B axis (ISSUE 14): ``on`` installs
    the host span tracer for the whole measured run (written to
    ``--trace-dir`` when given, else collected in memory and discarded
    after counting).  Returns a finalize callback that annotates the row
    with the axis value and the recorded span count — the tier-1 smoke
    (``test_telemetry_axis_row``) runs both arms on the same workload and
    pins crc-identical factors plus a bounded on/off timing factor."""
    mode = getattr(args, "telemetry", "off") or "off"
    if mode not in ("off", "on"):
        raise SystemExit(f"--telemetry must be off/on, got {mode!r}")
    if mode == "off":
        # no row annotation: the off arm is byte-for-byte the pre-axis
        # row, which keeps every sub-lab's printed-row == returned-row
        # scoreboard contract untouched
        return lambda row: None
    from cfk_tpu import telemetry

    tracer = telemetry.configure(
        trace_dir=getattr(args, "trace_dir", None)
    )

    def finalize(row):
        row["telemetry"] = "on"
        row["telemetry_spans"] = len(tracer.events())
        path = telemetry.shutdown(write=True)
        if path:
            row["telemetry_trace_path"] = path

    return finalize


def run_lab(args) -> dict:
    """Measure and return the result row (also printed as the last JSON
    line — the scoreboard contract ``tests/test_perf_lab.py`` pins)."""
    finalize_telemetry = _telemetry_axis(args)
    try:
        if args.offload:
            row = run_offload_lab(args)
        elif args.serve == "on":
            row = run_serve_lab(args)
        elif args.foldin == "on":
            row = run_foldin_lab(args)
        else:
            row = _run_train_lab(args)
    except BaseException:
        finalize_telemetry({})
        raise
    finalize_telemetry(row)
    if row.get("telemetry") == "on":
        # re-print so the scoreboard's last-JSON-line contract includes
        # the telemetry columns added after the sub-lab printed
        print(json.dumps(row))
    return row


def _run_train_lab(args) -> dict:
    import jax

    ds = get_dataset(args)

    from cfk_tpu.models import als as als_mod
    from cfk_tpu.utils.roofline import als_iteration_cost

    if args.gram_backend is not None:
        import cfk_tpu.ops.solve as solve_mod

        solve_mod.default_segment_backend = lambda: args.gram_backend
    if args.tiled_gram_backend is not None:
        import cfk_tpu.ops.tiled as tiled_mod

        tiled_mod.default_tiled_gram_backend = (
            lambda: args.tiled_gram_backend
        )
    if args.reg_solve_algo is not None:
        import cfk_tpu.ops.pallas.solve_kernel as sk

        sk.default_reg_solve_algo = lambda: args.reg_solve_algo
    if args.overlap == "off":
        import cfk_tpu.ops.pipeline as pipeline_mod

        pipeline_mod.default_overlap = lambda: False
    if args.gather == "xla":
        import cfk_tpu.ops.tiled as tiled_mod

        tiled_mod.default_in_kernel_gather = lambda: False
    if args.fused == "off":
        import cfk_tpu.ops.solve as solve_mod

        solve_mod.default_fused_epilogue = lambda: False
    if args.group_tiles is not None:
        # Patch EVERY grouped-Gram wrapper — split, fused-solve, and the
        # gather-fused twins: with --fused and --gather on (the defaults)
        # the hot chunk kernel is the gather-fused one, and a partial
        # patch would make this sweep axis silently inert.
        import cfk_tpu.ops.pallas.gram_kernel as gk

        def _with_group(fn):
            def patched(*a, **kw):
                kw.setdefault("group_tiles", args.group_tiles)
                return fn(*a, **kw)

            return patched

        gk.gram_tiles_pallas = _with_group(gk.gram_tiles_pallas)
        gk.gram_solve_tiles_pallas = _with_group(gk.gram_solve_tiles_pallas)
        gk.gram_tiles_gather_pallas = _with_group(gk.gram_tiles_gather_pallas)
        gk.gram_solve_tiles_gather_pallas = _with_group(
            gk.gram_solve_tiles_gather_pallas
        )


    from cfk_tpu.ops import quant

    # Same refusal ALSConfig enforces: int8 on padded/segment would
    # dequantize the whole table up front while the roofline row still
    # charged 1-byte cells — the dishonest-floor artifact this axis
    # exists to measure away.
    quant.validate_table_dtype_layout(args.table_dtype, args.layout)

    segment = args.layout == "segment"
    bucketed = args.layout == "bucketed"
    t0 = time.time()
    if bucketed:
        mblocks, ublocks, u_stats, layout_kw = als_mod._bucketed_device_setup(ds)
    elif segment:
        mblocks, ublocks, u_stats, layout_kw = als_mod._segment_device_setup(ds)
    elif args.layout == "tiled":
        mblocks, ublocks, u_stats, layout_kw = als_mod._tiled_device_setup(
            ds, weighted=args.ials)
    else:
        mblocks = als_mod._blocks_to_device(ds.movie_blocks)
        ublocks = als_mod._blocks_to_device(ds.user_blocks)
        u_stats, layout_kw = None, {}
    # Force the upload now so step timings never include it.
    jax.block_until_ready((mblocks, ublocks))
    sync_leaf = jax.tree.leaves(mblocks)[0]
    np.asarray(sync_leaf.ravel()[:1])
    print(f"# blocks to device in {time.time()-t0:.1f}s", flush=True)

    from cfk_tpu.ops.solve import init_factors_stats

    key = jax.random.PRNGKey(0)
    if u_stats is not None:
        u0 = jax.jit(init_factors_stats, static_argnames="rank")(
            key, u_stats["rating_sum"], u_stats["count"], rank=args.rank
        )
    else:
        u0 = jax.jit(
            lambda k, r, m, c: als_mod.init_factors(k, r, m, c, args.rank)
        )(key, ublocks["rating"], ublocks["mask"], ublocks["count"])
    dt = args.dtype
    u0 = u0.astype(dt)
    m_rows = ds.movie_blocks.padded_entities
    m0 = jax.numpy.zeros((m_rows, args.rank), dt)

    import functools

    # The lab's legacy knob threading: explicit flags pin table_dtype, the
    # other knobs ride the patched process defaults (None = deferred).
    base_knobs = dict(overlap=None, fused_epilogue=None,
                      in_kernel_gather=None, reg_solve_algo=None,
                      table_dtype=args.table_dtype)

    def _iteration(u, m_prev, mblk, ublk, knobs):
        if args.ials:
            from cfk_tpu.models.ials import _ials_iteration_body

            return _ials_iteration_body(
                u, m_prev, mblk, ublk,
                lam=0.05, alpha=args.alpha, dt=jax.numpy.dtype(dt),
                solver=args.solver, algorithm="als", block_size=32,
                sweeps=1, **knobs, **layout_kw,
            )
        return als_mod._iteration_body(
            u, mblk, ublk,
            lam=0.05, solve_chunk=None, dt=jax.numpy.dtype(dt),
            solver=args.solver, m_prev=m_prev, **knobs, **layout_kw,
        )

    def make_steps(knobs):
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def steps(u, m, mblk, ublk):
            # Blocks are jit ARGUMENTS, not closure captures — capturing
            # them would bake 2.4 GB of constants into the executable and
            # blow up compile time (exactly what the real trainers avoid).
            def one(i, u, m_prev):
                return _iteration(u, m_prev, mblk, ublk, knobs)

            if args.health == "off":
                return jax.lax.fori_loop(
                    0, args.iters, lambda i, c: one(i, *c), (u, m)
                )

            # Health on: the in-carry sentinel exactly as the fused
            # trainer loops run it — probe every iteration, word rides
            # the carry.
            from cfk_tpu.resilience import sentinel

            def probed(i, carry):
                u, m_prev, hw = carry
                u2, m2 = one(i, u, m_prev)
                hw = sentinel.fold_probe(
                    hw, i, u2, m2, every=1,
                    norm_limit=args.health_norm_limit, total=args.iters,
                )
                return u2, m2, hw

            u, m, _hw = jax.lax.fori_loop(
                0, args.iters, probed, (u, m, sentinel.carry_init())
            )
            return u, m

        return steps

    plan_prov = None
    if args.plan:
        plan_prov, base_knobs = _resolve_plan_axis(
            args, make_steps, mblocks, ublocks, u0, m0,
        )

    steps = make_steps(base_knobs)
    steps_bound = functools.partial(steps, mblk=mblocks, ublk=ublocks)

    ckpt_mgr = None
    ckpt_save_s = [0.0]
    ckpt_saves = [0]
    if args.ckpt:
        # Checkpoint axis: per-iteration host stepping (the save cadence
        # needs the host between iterations, exactly like the resilient
        # trainer loops) with a save after every iteration.  The timed
        # call therefore INCLUDES the in-loop save stalls — the quantity
        # the sync/async writer axis moves.
        import tempfile

        from cfk_tpu.transport.checkpoint import CheckpointManager

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def one_step(u, m, mblk, ublk):
            return _iteration(u, m, mblk, ublk, base_knobs)

        one_bound = functools.partial(one_step, mblk=mblocks, ublk=ublocks)
        ckpt_dir = tempfile.mkdtemp(prefix="cfk_perf_ckpt_")
        # keep_last_n bounds the disk this sweep burns at full shape
        ckpt_mgr = CheckpointManager(
            ckpt_dir, async_write=args.ckpt == "async", keep_last_n=4,
        )

        def ckpt_steps(u, m):
            for _ in range(args.iters):
                u, m = one_bound(u, m)
                # Drain the device BEFORE the save timer so the per-save
                # stall attributes only host-side checkpoint work, not the
                # async-dispatched compute it would otherwise wait on.
                u.block_until_ready()
                ckpt_saves[0] += 1
                t0 = time.time()
                if args.ckpt == "async":
                    ckpt_mgr.save_async(ckpt_saves[0], u, m)
                else:
                    ckpt_mgr.save(ckpt_saves[0], u, m)
                ckpt_save_s[0] += time.time() - t0
            return u, m

        steps_bound = ckpt_steps

    t0 = time.time()
    u, m = steps_bound(u0, m0)
    sync(u)
    compile_s = time.time() - t0
    print(f"# first call (compile+run): {compile_s:.2f}s", flush=True)

    def profile_hook(i, u, m):
        if args.profile_dir and i == 0:
            with jax.profiler.trace(args.profile_dir):
                u, m = steps_bound(u, m)
                sync(u)
            return u, m
        return None

    times, u, m = measure_steps(
        steps_bound, u, m, repeats=args.repeats, iters=args.iters,
        on_call=profile_hook,
    )
    per_iter = [t / args.iters for t in times]
    gather_rows = None
    if bucketed:
        # Honest bucketed floor: every padded cell of every width class
        # fetches a row (roofline.bucketed_gather_rows).
        from cfk_tpu.utils.roofline import bucketed_gather_rows

        gather_rows = bucketed_gather_rows(ds.movie_blocks, ds.user_blocks)
    # Under --plan model/autotune the EXECUTED table dtype is the plan's
    # choice, and the roofline row must charge what actually ran.
    eff_table_dtype = base_knobs["table_dtype"] or "float32"
    cost = als_iteration_cost(
        args.nnz, args.users, args.movies, args.rank,
        factor_bytes=2 if dt == "bfloat16" else 4,
        table_dtype=eff_table_dtype, gather_rows=gather_rows,
    )
    best = min(per_iter)
    from cfk_tpu.utils.roofline import roofline_row, this_device_kind

    row = {
        "s_per_iter_min": round(best, 4),
        "s_per_iter_median": round(sorted(per_iter)[len(per_iter) // 2], 4),
        **roofline_row(cost, best, eff_table_dtype,
                       device_kind=this_device_kind()),
        "layout": args.layout, "solver": args.solver,
        "chunk_elems": args.chunk_elems, "dtype": dt,
        "gram_backend": args.gram_backend, "rank": args.rank,
        "iters_per_call": args.iters, "overlap": args.overlap,
        "fused": args.fused, "health": args.health,
        "gather": args.gather, "ckpt": args.ckpt,
    }
    if plan_prov is not None:
        row["plan_axis"] = args.plan
        row.update(plan_prov.as_row())
    if ckpt_mgr is not None:
        import shutil

        t0 = time.time()
        ckpt_mgr.wait_pending()
        row["ckpt_drain_s"] = round(time.time() - t0, 4)
        row["ckpt_save_stall_s_per_save"] = round(
            ckpt_save_s[0] / max(ckpt_saves[0], 1), 5
        )
        shutil.rmtree(ckpt_mgr.directory, ignore_errors=True)
    print(json.dumps(row))
    return row


def main() -> None:
    run_lab(make_parser().parse_args())


if __name__ == "__main__":
    main()
