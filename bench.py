"""Benchmark harness: medium Netflix sample at the reference's published config.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...extras}.

Baseline (BASELINE.md): the reference publishes RMSE 0.759 on medium
(3,590 movies × 2,120 users, 108,870 ratings) at k=5, 7 iterations, λ=0.05;
its wall-clock numbers exist only as a chart.  vs_baseline is our RMSE over
the reference's 0.759 (< 1.0 = better quality); wall-clock s/iteration and
ratings/sec are reported as extra fields.

``python bench.py --scale`` instead measures throughput on synthetic
Netflix-Prize-shaped data (BASELINE.md scale targets; no egress, so the real
corpus can't be fetched).  Default scale is 1/10th Netflix Prize at rank 64;
``--full`` runs the real 480k×17.7k×100M dimensions.  vs_baseline there is
s/iteration over the 60 s/iteration BASELINE.json bar.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

MEDIUM = "/root/reference/data/data_sample_medium.txt"
REF_RMSE_MEDIUM = 0.759


def sync(x) -> None:
    """Wait until the device has produced ``x``: the barrier every timing
    here ends on.  ``jax.block_until_ready`` is a true barrier on the v5e
    (chip_smoke.py times a matmul loop against a scalar device→host fetch
    and fails if the two disagree)."""
    import jax

    jax.block_until_ready(x)


def _compact_row(row: dict) -> dict:
    """Strip a headline row to the fields the record must preserve.

    The driver keeps only a ~2000-char tail of bench stdout and parses the
    LAST line; round 4's final line carried every full row and outgrew that
    window, so the flagship number survived only as a comment line
    (VERDICT r4 missing #1).  The full rows stay on the earlier
    ``# name: {...}`` lines; the final line carries just value + the honest
    efficiency field per row and MUST stay well under the tail window
    (tests/test_bench.py asserts the budget)."""
    if "error" in row:
        return {"error": row["error"][:120]}
    keep = ("value", "vs_baseline", "vs_gather_roofline", "s_per_iteration",
            "s_per_iteration_median", "rmse_best_seed", "layout",
            "exchange_s_per_iter", "compute_s_per_iter",
            "factors_bit_exact", "removed_bytes_per_chunk",
            "save_stall_removed_s_per_save", "foldin_rmse_over_retrain",
            "p50_ms", "p99_ms", "vs_roofline", "best_batch",
            "tiers", "crossed_to_host_window", "bytes_cut", "recall_at_k")
    return {k: row[k] for k in keep if k in row}


def _final_summary(rows: dict) -> str:
    """Assemble the final stdout line from the full rows; NEVER oversized
    and never raises — an oversized final line (or a crash after the
    ~50-min measurement) is exactly the round-4 failure this replaces, so
    on budget overflow it degrades to bare values rather than erroring."""
    medium = rows.get("medium", {})
    out = {k: medium[k] for k in ("metric", "value", "unit", "vs_baseline")
           if k in medium}
    out["rows"] = {name: _compact_row(row) for name, row in rows.items()}
    line = json.dumps(out)
    if len(line) > 1800:  # pragma: no cover - headroom is ~2x in practice
        out["rows"] = {
            name: ({"error": row["error"][:60]} if "error" in row
                   else {"value": row.get("value")})
            for name, row in rows.items()
        }
        line = json.dumps(out)
    return line


def main() -> None:
    """Default driver entry: medium-parity RMSE row, a compact at-scale
    tiled row, and the HEADLINE steady-state rows (real full-shape
    rank-64, rank-128, iALS and iALS++ — VERDICT r3 #3: every number
    README/BASELINE quotes must have a driver-artifact counterpart),
    printed as full ``# name: {...}`` lines plus ONE compact final JSON
    summary line (VERDICT r4 #1: the driver preserves/parses only a short
    tail, so the final line must carry every headline value compactly).
    ``CFK_BENCH_HEADLINE=0`` skips the heavy rows (they cost ~10 min
    warm-cache, ~40 min cold).

    One process per chip: every row that computes on the default backend
    runs HERE, in the parent, which holds the chip from its first row on.
    The only children are the virtual-CPU-mesh rows (overlap / fused /
    gather A/Bs and the serve shard sweep), which force the CPU platform
    and never ask for the chip.  A row that errors is recorded as
    ``{"error": …}`` and makes the exit code non-zero."""
    import os

    from cfk_tpu.config import enable_compile_cache

    enable_compile_cache()
    rows = {}
    for name, fn in (("medium", medium_main), ("at_scale", at_scale_quick)):
        try:
            row = fn()
        except Exception as e:  # the row is recorded, the exit code says so
            row = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print(f"# {name}: " + json.dumps(row))
        rows[name] = row
    # The ring-layout overlap A/B + exchange/compute split (subprocess:
    # the virtual mesh flag must precede jax init).  CFK_BENCH_OVERLAP=0
    # skips it.
    if os.environ.get("CFK_BENCH_OVERLAP", "1") != "0":
        try:
            ov = _overlap_ab_row()
        except Exception as e:  # pragma: no cover - subprocess-dependent
            ov = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# overlap_ring: " + json.dumps(ov))
        rows["overlap_ring"] = ov
    # The fused/split Gram+solve epilogue A/B + removed-HBM-traffic
    # estimate (subprocess for the same virtual-mesh reason).
    # CFK_BENCH_FUSED=0 skips it.
    if os.environ.get("CFK_BENCH_FUSED", "1") != "0":
        try:
            fa = _fused_ab_row()
        except Exception as e:  # pragma: no cover - subprocess-dependent
            fa = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# fused_epilogue: " + json.dumps(fa))
        rows["fused_epilogue"] = fa
    # The in-kernel-gather A/B + removed-stream-bytes estimate (subprocess
    # for the same virtual-mesh reason).  CFK_BENCH_GATHER=0 skips it.
    if os.environ.get("CFK_BENCH_GATHER", "1") != "0":
        try:
            ga = _gather_ab_row()
        except Exception as e:  # pragma: no cover - subprocess-dependent
            ga = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# gather_ab: " + json.dumps(ga))
        rows["gather_ab"] = ga
    # Health-sentinel overhead A/B (in-carry probe at every-iteration
    # cadence vs plain loop; < 2% budget).  CFK_BENCH_HEALTH=0 skips it.
    if os.environ.get("CFK_BENCH_HEALTH", "1") != "0":
        try:
            ha = _health_ab_row()
        except Exception as e:  # pragma: no cover - subprocess-dependent
            ha = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# health_sentinel: " + json.dumps(ha))
        rows["health_sentinel"] = ha
    # Async vs sync checkpoint-writer A/B (bit-exact factors + per-save
    # stall removed from the step loop).  CFK_BENCH_CKPT=0 skips it.
    if os.environ.get("CFK_BENCH_CKPT", "1") != "0":
        try:
            ca = _ckpt_ab_row()
        except Exception as e:  # pragma: no cover - subprocess-dependent
            ca = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# ckpt_writer: " + json.dumps(ca))
        rows["ckpt_writer"] = ca
    # Streaming fold-in: updates/sec absorbed + fold-in-vs-retrain RMSE on
    # a held-out time split.  CFK_BENCH_FOLDIN=0 skips it.
    if os.environ.get("CFK_BENCH_FOLDIN", "1") != "0":
        try:
            fi = _foldin_row()
        except Exception as e:  # pragma: no cover - subprocess-dependent
            fi = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# foldin: " + json.dumps(fi))
        rows["foldin"] = fi
    # Top-K serving QPS/p50/p99 at ML-25M scale (ISSUE 8).
    # CFK_BENCH_SERVE=0 skips it.
    if os.environ.get("CFK_BENCH_SERVE", "1") != "0":
        try:
            sv = _serve_row()
        except Exception as e:  # pragma: no cover - subprocess-dependent
            sv = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# serve: " + json.dumps(sv))
        rows["serve"] = sv
    # Replicated serving fleet (ISSUE 18): goodput QPS scaling + admission
    # shed rate vs replica count.  CFK_BENCH_SERVE_FLEET=0 skips it.
    if os.environ.get("CFK_BENCH_SERVE_FLEET", "1") != "0":
        try:
            sf = _serve_fleet_row()
        except Exception as e:  # pragma: no cover - subprocess-dependent
            sf = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# serve_fleet: " + json.dumps(sf))
        rows["serve_fleet"] = sf
    # Execution-planner A/B (ISSUE 9): resolver's serve plan vs the
    # static defaults, measured per request-slot with provenance.
    # CFK_BENCH_PLAN=0 skips it.
    if os.environ.get("CFK_BENCH_PLAN", "1") != "0":
        try:
            pa = run_plan_ab(_plan_ab_args())
        except Exception as e:  # pragma: no cover - device-dependent
            pa = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# plan_ab: " + json.dumps(pa))
        rows["plan_ab"] = pa
    # Out-of-core scale sweep (ISSUE 11): resident->host_window tier
    # crossing under an artificial budget, memory math per point.
    # CFK_BENCH_SCALE_SWEEP=0 skips it.
    if os.environ.get("CFK_BENCH_SCALE_SWEEP", "1") != "0":
        try:
            sw = _scale_sweep_row()
        except Exception as e:  # pragma: no cover - device-dependent
            sw = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# scale_sweep: " + json.dumps(sw))
        rows["scale_sweep"] = sw
    # Host staging engine A/B (ISSUE 13): pooled vs serial window
    # staging on a sharded host_window point, with the engine's own
    # accounting columns.  CFK_BENCH_STAGING=0 skips it.
    if os.environ.get("CFK_BENCH_STAGING", "1") != "0":
        try:
            sa = _staging_ab_row()
        except Exception as e:  # pragma: no cover - device-dependent
            sa = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# staging_ab: " + json.dumps(sa))
        rows["staging_ab"] = sa
    # Hot-row device cache A/B (ISSUE 15): auto hot resolution vs the
    # full-staging engine on a power-law host_window point — resolved
    # hot fraction, reference coverage, hot/cold staged MB, the staged-
    # table-byte cut, crc equality.  CFK_BENCH_HOT=0 skips it.
    if os.environ.get("CFK_BENCH_HOT", "1") != "0":
        try:
            ha = _hot_ab_row()
        except Exception as e:  # pragma: no cover - device-dependent
            ha = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# hot_ab: " + json.dumps(ha))
        rows["hot_ab"] = ha
    # iALS++ resident vs host_window A/B (ISSUE 19): crc equality,
    # s/iter, staged MB/iter with the hot cache on and off.
    # CFK_BENCH_IALS_OFFLOAD=0 skips it.
    if os.environ.get("CFK_BENCH_IALS_OFFLOAD", "1") != "0":
        try:
            ia = _ials_offload_ab_row()
        except Exception as e:  # pragma: no cover - device-dependent
            ia = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# ials_offload_ab: " + json.dumps(ia))
        rows["ials_offload_ab"] = ia
    # Quantized-gather-table A/B: RMSE per table dtype on the planted
    # split + the analytic bytes removed.  CFK_BENCH_QUANT=0 skips it.
    if os.environ.get("CFK_BENCH_QUANT", "1") != "0":
        try:
            qa = _quant_ab_row()
        except Exception as e:  # pragma: no cover - device-dependent
            qa = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# quant_table: " + json.dumps(qa))
        rows["quant_table"] = qa
    if os.environ.get("CFK_BENCH_HEADLINE", "1") != "0":
        for name, fn in (
            ("full_rank64", full_rank64_row),
            ("full_rank128", full_rank128_row),
            ("ials_ml25m", ials_row),
            ("ialspp_ml25m", ialspp_row),
        ):
            try:
                row = fn()
            except Exception as e:  # pragma: no cover - device-dependent
                row = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            print(f"# {name}: " + json.dumps(row))
            rows[name] = row
    print(_final_summary(rows))
    failed = sorted(n for n, r in rows.items() if "error" in r)
    if failed:
        # every row above ran; an errored one still fails the run
        raise SystemExit(f"bench rows failed: {', '.join(failed)}")


def _steady_state(ds, *, rank, iters=3, repeats=4, lam=0.05,
                  dtype="bfloat16", model="als", alpha=40.0, block_size=32,
                  sweeps=1, solver="pallas") -> dict:
    """Upload-once, min-of-N steady-state timing of the fused iteration.

    The measurement methodology of ``scripts/perf_lab.py`` (blocks upload
    once; a fused ``iters``-iteration step program is timed with a scalar
    device→host fetch as the barrier) — the two-point trainer fit is
    noise-dominated at full-corpus shapes (~40 s fixed upload vs
    ~2 s of signal, BASELINE.md round-3 note).

    The block upload is ASYNC (ROADMAP "async host-to-device chunk
    upload", narrow scope): the ``device_put``s are issued non-blocking,
    the step program is AOT-compiled (``.lower().compile()`` needs only
    avals) while the multi-GB transfer is in flight, and only then does
    the timing wait for the transfer to drain — ``upload_wall_s`` splits
    into ``upload_issue_s`` (host-side issue) and ``upload_wait_s`` (the
    residual transfer NOT hidden behind compilation), so the overlap is
    visible in the record."""
    import functools

    import jax
    import jax.numpy as jnp

    from cfk_tpu.data.blocks import BucketedBlocks
    from cfk_tpu.models import als as als_mod
    from cfk_tpu.ops.solve import init_factors_stats

    t0 = time.time()
    if isinstance(ds.movie_blocks, BucketedBlocks):
        mblocks, ublocks, u_stats, layout_kw = (
            als_mod._bucketed_device_setup(ds)
        )
    else:
        mblocks, ublocks, u_stats, layout_kw = als_mod._tiled_device_setup(
            ds, weighted=model != "als")
    issue_s = time.time() - t0

    key = jax.random.PRNGKey(0)
    u0 = jax.jit(init_factors_stats, static_argnames="rank")(
        key, u_stats["rating_sum"], u_stats["count"], rank=rank
    ).astype(dtype)
    m0 = jnp.zeros((ds.movie_blocks.padded_entities, rank), dtype)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def steps(u, m, mblk, ublk):
        def body(_, carry):
            u, m_prev = carry
            if model != "als":
                from cfk_tpu.models.ials import _ials_iteration_body

                return _ials_iteration_body(
                    u, m_prev, mblk, ublk, lam=lam, alpha=alpha,
                    dt=jnp.dtype(dtype), solver=solver,
                    algorithm="ials++" if model == "ials++" else "als",
                    block_size=block_size, sweeps=sweeps, **layout_kw,
                )
            return als_mod._iteration_body(
                u, mblk, ublk, lam=lam, solve_chunk=None,
                dt=jnp.dtype(dtype), solver=solver, m_prev=m_prev,
                **layout_kw,
            )
        return jax.lax.fori_loop(0, iters, body, (u, m))

    # Trace+compile against avals only — runs under the in-flight upload.
    # The AOT executable is used for every timed call (jit's own cache
    # never sees this program, so going through ``steps(...)`` later
    # would compile a second time).
    t0 = time.time()
    stepc = steps.lower(u0, m0, mblocks, ublocks).compile()
    compile_s = time.time() - t0
    t0 = time.time()
    jax.block_until_ready((mblocks, ublocks))
    np.asarray(jax.tree.leaves(mblocks)[0].ravel()[:1])
    wait_s = time.time() - t0

    t0 = time.time()
    u, m = stepc(u0, m0, mblocks, ublocks)
    sync(u)
    warm = time.time() - t0
    times = []
    for _ in range(repeats):
        t0 = time.time()
        u, m = stepc(u, m, mblocks, ublocks)
        sync(u)
        times.append(time.time() - t0)
    per_iter = [t / iters for t in times]
    return {
        "s_per_iter_min": round(min(per_iter), 4),
        "s_per_iteration_median": round(float(np.median(per_iter)), 4),
        "repeats": repeats,
        "iters_per_call": iters,
        # issue + residual wait; the transfer time hidden behind the
        # compile no longer shows up anywhere — that's the win.
        "upload_wall_s": round(issue_s + wait_s, 3),
        "upload_issue_s": round(issue_s, 3),
        "upload_wait_s": round(wait_s, 3),
        "aot_compile_wall_s": round(compile_s, 3),
        "first_call_wall_s": round(warm, 3),
    }


def _headline_row(metric, *, users, movies, nnz, rank, layout_tag,
                  steady, dtype="bfloat16", implicit=False,
                  prep_s=0.0, table_dtype="float32", gather_rows=None,
                  sweeps=1) -> dict:
    """``table_dtype`` is recorded in every row (the quantized-table knob
    of ``ops.quant`` — "float32" = the identity), and the byte model is
    layout-aware: ``gather_rows`` overrides the 2·nnz default (the
    bucketed layout gathers every padded cell of every width class —
    ``roofline.bucketed_gather_rows``) and ``sweeps`` multiplies it (each
    subspace sweep re-gathers its rectangle)."""
    from cfk_tpu.utils.roofline import (
        als_iteration_cost,
        roofline_row,
        this_device_kind,
    )

    s = steady["s_per_iter_min"]
    cost = als_iteration_cost(
        nnz, users, movies, rank,
        factor_bytes=2 if dtype == "bfloat16" else 4, implicit=implicit,
        table_dtype=table_dtype, gather_rows=gather_rows, sweeps=sweeps,
    )
    return {
        "metric": metric,
        "value": s,
        "unit": "s/iteration",
        # BASELINE.json bar: < 60 s/iteration at full Netflix scale.
        "vs_baseline": round(s / 60.0, 4),
        "ratings_per_sec_per_chip": int(nnz * 2 / s),
        **roofline_row(cost, s, table_dtype,
                       device_kind=this_device_kind()),
        **steady,
        "users": users, "movies": movies, "ratings": nnz, "rank": rank,
        "layout": layout_tag, "dtype": dtype,
        "prep_wall_s": round(prep_s, 1),
    }


def full_rank64_row() -> dict:
    """The flagship headline, driver-captured at the REAL full shape
    (no extrapolation): full Netflix Prize dimensions, rank 64, the
    at-scale default stack (tiled, dense user stream, fused pallas
    Gram + fused reg+LU solve, bf16)."""
    from cfk_tpu.data.cache import cached_scale_dataset

    users, movies, nnz = 480_189, 17_770, 100_480_507
    t0 = time.time()
    # Measured-best chunking (r4 sweep over {32k..1M}²): 64k dense user
    # chunks (the XLA gather engine rate RISES as chunks shrink — ~390M
    # rows/s at 512k, ~470M at 256k — with the knee at 64k: 32k reverses)
    # + 256k accum movie chunks.
    ds = cached_scale_dataset(
        users=users, movies=movies, nnz=nnz, seed=0, layout="tiled",
        chunk_elems=65_536, accum_chunk_elems=262_144, dense_stream=True,
    )
    prep = time.time() - t0
    steady = _steady_state(ds, rank=64, iters=3, repeats=4, lam=0.05)
    row = _headline_row(
        "netflix_full_rank64_steady_s_per_iteration",
        users=users, movies=movies, nnz=nnz, rank=64,
        layout_tag="tiled+dense-stream", steady=steady, prep_s=prep,
    )
    # Gather-slot padding per half (the round-4 lever: the dense user
    # stream carries ~3.4% padded slots vs 26% tile-padded).
    ub, mb = ds.user_blocks, ds.movie_blocks
    row["user_gather_pad_fraction"] = round(
        ub.num_chunks * ub.chunk_cap / nnz - 1.0, 4
    )
    row["movie_gather_pad_fraction"] = round(
        mb.num_chunks * mb.chunk_cap / nnz - 1.0, 4
    )
    # VERDICT r4 #6: the dense kernel's trash-slot share, in the record.
    row["dense_walk_trash_fraction"] = round(ub.dense_trash_fraction, 4)
    return row


def full_rank128_row() -> dict:
    """Full Netflix at rank 128 (the fused LU-128 stack).  Same dense
    64k/256k dataset as the rank-64 row (the layout is rank-independent;
    64k chunks also keep the Gram kernel's [S, 128, 129] output small) —
    measured 1.24 s/iter vs 1.32 on the round-3 padded 128k config."""
    from cfk_tpu.data.cache import cached_scale_dataset

    users, movies, nnz = 480_189, 17_770, 100_480_507
    t0 = time.time()
    ds = cached_scale_dataset(
        users=users, movies=movies, nnz=nnz, seed=0, layout="tiled",
        chunk_elems=65_536, accum_chunk_elems=262_144, dense_stream=True,
    )
    prep = time.time() - t0
    steady = _steady_state(ds, rank=128, iters=3, repeats=4, lam=0.05)
    return _headline_row(
        "netflix_full_rank128_steady_s_per_iteration",
        users=users, movies=movies, nnz=nnz, rank=128,
        layout_tag="tiled+dense-stream", steady=steady, prep_s=prep,
    )


def ials_row() -> dict:
    """MovieLens-25M-shaped implicit feedback, rank 128, full iALS solves
    (steady-state — the two-point fit was recorded misleading here).
    Round 5: the dense stream with the sqrt-reparameterized weight
    (single gs = √aw·f stream) replaced the padded default — padded
    0.662 vs dense 0.630 at 80k chunks, reversing round 4's two-stream
    dense negative (0.87) — and the chunk sweep put the knee at 48k:
    {64k → 0.627, 48k → 0.604, 32k → 0.606, 112k → 0.842}."""
    from cfk_tpu.data.cache import cached_scale_dataset

    users, movies, nnz = 162_541, 59_047, 25_000_095
    t0 = time.time()
    ds = cached_scale_dataset(
        users=users, movies=movies, nnz=nnz, seed=0, layout="tiled",
        chunk_elems=49_152, dense_stream=True,
    )
    prep = time.time() - t0
    steady = _steady_state(
        ds, rank=128, iters=3, repeats=4, lam=0.1, model="ials", alpha=40.0,
    )
    return _headline_row(
        "synthetic_ml25m_ials_steady_s_per_iteration",
        users=users, movies=movies, nnz=nnz, rank=128,
        layout_tag="tiled+dense-stream", steady=steady, implicit=True,
        prep_s=prep,
    )


def ialspp_row() -> dict:
    """Same shape via the iALS++ subspace optimizer (bucketed layout) —
    pinned to one steady-state scalar (VERDICT r3 #8)."""
    from cfk_tpu.data.cache import cached_scale_dataset

    users, movies, nnz = 162_541, 59_047, 25_000_095
    t0 = time.time()
    ds = cached_scale_dataset(
        users=users, movies=movies, nnz=nnz, seed=0, layout="bucketed",
        chunk_elems=524_288,
    )
    prep = time.time() - t0
    steady = _steady_state(
        ds, rank=128, iters=3, repeats=4, lam=0.1, model="ials++",
        alpha=40.0, block_size=32, sweeps=1,
    )
    from cfk_tpu.utils.roofline import bucketed_gather_rows

    return _headline_row(
        "synthetic_ml25m_ialspp_steady_s_per_iteration",
        users=users, movies=movies, nnz=nnz, rank=128,
        layout_tag="bucketed", steady=steady, implicit=True, prep_s=prep,
        # Honest bucketed floor: every padded cell of every width class
        # fetches a row (BENCH_r05's 2·nnz floor understated it by the
        # padding ratio, part of the recorded 9.94×).
        gather_rows=bucketed_gather_rows(ds.movie_blocks, ds.user_blocks),
        sweeps=1,
    )


def at_scale_quick() -> dict:
    """A sub-scale tiled row sized to finish in ~2 min on the chip.

    EVERY axis at 1/3 Netflix (users, movies, AND ratings) so the density
    — hence the tile-padding ratio — and both per-side modes match the
    full corpus: user half stream (160k entities), movie half sliced
    accum (the 160k-row fixed table still exceeds one 131072-row slice).
    Shapes that scale only nnz measure the wrong regime: sparse rows
    explode tile padding ~6×, and small entity counts flip the user half
    into accum.

    Timing is steady-state (``_steady_state``): blocks upload ONCE, then
    a fused 3-iteration step program is timed min-of-N with a scalar
    fetch as the barrier — the ``--scale`` two-point trainer fit would be
    swamped here by the multi-GB block upload (~40 s fixed vs ~0.5 s of
    signal).  The full shape's ground truth is the driver-captured
    ``full_rank64`` row in the same artifact (BENCH_r03's linear-in-nnz
    extrapolation disagreed with the measured number by 13% and was
    dropped)."""
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.utils.roofline import als_iteration_cost

    users, movies, nnz = 160_063, 5_923, 33_493_502
    rank, lam = 64, 0.05
    t0 = time.time()
    from cfk_tpu.data.cache import cached_scale_dataset

    ds = cached_scale_dataset(
        users=users, movies=movies, nnz=nnz, seed=0, layout="tiled",
        chunk_elems=65_536, accum_chunk_elems=262_144, dense_stream=True,
    )
    gen_s = build_s = time.time() - t0

    steady = _steady_state(ds, rank=rank, iters=3, repeats=4, lam=lam)
    s_per_iter = steady["s_per_iter_min"]

    from cfk_tpu.utils.roofline import (
        FULL_NETFLIX_NNZ,
        roofline_row,
        this_device_kind,
    )

    cost = als_iteration_cost(nnz, users, movies, rank, factor_bytes=2)
    return {
        "metric": "synthetic_third_netflix_steady_s_per_iteration",
        "value": s_per_iter,
        "unit": "s/iteration",
        "vs_baseline": round(s_per_iter / (60.0 * nnz / FULL_NETFLIX_NNZ), 4),
        "ratings_per_sec_per_chip": int(nnz * 2 / s_per_iter),
        **roofline_row(cost, s_per_iter, "float32",
                       device_kind=this_device_kind()),
        # Ground truth for the full shape is the driver-captured
        # full_rank64 row (no more linear-in-nnz extrapolation — the two
        # disagreed by 13% in BENCH_r03 and the measured one wins).
        **steady,
        "users": users, "movies": movies, "ratings": nnz, "rank": rank,
        "layout": "tiled+dense-stream", "dtype": "bfloat16",
        "datagen_wall_s": round(gen_s, 3),
        "blockbuild_wall_s": round(build_s, 3),
    }


def medium_main() -> dict:
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.netflix import parse_netflix
    from cfk_tpu.eval.metrics import mse_rmse_from_blocks
    from cfk_tpu.models.als import train_als

    coo = parse_netflix(MEDIUM)
    ds = Dataset.from_coo(coo)
    # The reference publishes ONE run (RMSE 0.759); init RNG makes ours a
    # distribution, so the headline value is the MEDIAN over a fixed seed
    # set, with the best seed reported alongside (seed 38 was the best of a
    # 40-seed scan; the full spread is ~0.758..0.766 — init noise).
    seeds = [0, 1, 2, 3, 4, 38]
    config = ALSConfig(rank=5, lam=0.05, num_iterations=7, seed=seeds[0])

    # Warmup run: trigger compile (first TPU compile is slow, then cached;
    # the same program is reused for every seed).
    t0 = time.time()
    model = train_als(ds, config)
    sync(model.user_factors)
    warm = time.time() - t0

    times, rmses, by_seed = [], [], {}
    for seed in seeds:
        cfg = dataclasses.replace(config, seed=seed)
        t0 = time.time()
        model = train_als(ds, cfg)
        sync(model.user_factors)
        times.append(time.time() - t0)
        _, rmse = mse_rmse_from_blocks(model.predict_dense(), ds)
        rmses.append(rmse)
        by_seed[str(seed)] = round(rmse, 4)

    median_rmse = float(np.median(rmses))
    train_min, train_median = min(times), float(np.median(times))
    n = config.num_iterations
    return {
        "metric": "netflix_medium_rank5_iter7_rmse",
        "value": round(median_rmse, 4),
        "unit": "rmse",
        # vs_baseline compares OUR median over a fixed 6-seed set to the
        # reference's single published run (its init RNG was never swept);
        # ~1.0 means statistically indistinguishable quality — the seed
        # spread (~0.758–0.766) is init noise, not model difference.
        "vs_baseline": round(median_rmse / REF_RMSE_MEDIUM, 4),
        "rmse_median_seed": round(median_rmse, 4),
        "rmse_best_seed": round(min(rmses), 4),
        "rmse_by_seed": by_seed,
        # Wall-clock: min + median over the seed runs (run-to-run
        # variance swings identical runs several-fold; both are
        # reported, min is the capability number).
        "s_per_iteration": round(train_min / n, 4),
        "s_per_iteration_median": round(train_median / n, 4),
        "ratings_per_sec": int(coo.num_ratings * n * 2 / train_min),
        "train_wall_s": round(train_min, 3),
        "first_run_wall_s": round(warm, 3),
        "compile_wall_s": round(max(warm - train_median, 0.0), 3),
        "ratings": coo.num_ratings,
        "seeds": seeds,
    }


def scale_main(args) -> None:
    print(json.dumps(run_scale(args)))


def _plan_provenance_row(config, users, movies, nnz, *, implicit=False,
                         ) -> dict:
    """The provenance columns a config-driven row carries (ISSUE 9)."""
    from cfk_tpu.plan import plan_for_config

    try:
        prov = plan_for_config(
            config, num_users=users, num_movies=movies, nnz=max(nnz, 1),
            implicit=implicit,
        )[1]
    except Exception as e:  # pragma: no cover - never fail a bench row
        return {"plan": f"unresolved: {e}", "plan_source": "error"}
    return prov.as_row()


def run_scale(args) -> dict:
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models.als import train_als

    if args.alspp and (args.ials or args.ialspp):
        raise SystemExit("--alspp is the explicit model; drop --ials/--ialspp")
    if args.ialspp:
        args.ials = True
    if args.planted and args.ials:
        raise SystemExit("--planted generates signed ratings; iALS needs "
                         "non-negative interaction strengths")
    if args.ialspp or args.alspp:
        if args.layout in ("segment", "tiled"):
            args.layout = "bucketed"  # subspace optimizers need padded/bucketed
    if args.ials:
        # MovieLens-25M shape (BASELINE.md implicit-feedback target);
        # ratings act as interaction strengths.
        from cfk_tpu.models.ials import IALSConfig, train_ials

        users, movies, nnz = 162_541, 59_047, 25_000_095
        if args.rank == 64:  # the target config is rank 128
            args.rank = 128
    elif args.full:
        users, movies, nnz = 480_189, 17_770, 100_480_507
    else:
        users, movies, nnz = args.users, args.movies, args.nnz

    t0 = time.time()
    held = None
    if args.planted:
        # Quality validation at unfetchable-corpus shapes (VERDICT #6):
        # ratings come from known rank-`args.rank` factors + N(0, σ²) noise;
        # held-out RMSE near σ proves the at-scale pipeline recovers them.
        from cfk_tpu.data.synthetic import planted_factor_coo

        coo, held = planted_factor_coo(
            users, movies, nnz, rank=args.rank, noise=args.planted_noise,
            heldout=1_000_000, seed=args.seed,
        )
    else:
        coo = synthetic_netflix_coo(users, movies, nnz, seed=args.seed)
    gen_s = time.time() - t0
    t0 = time.time()
    ds = Dataset.from_coo(coo, layout=args.layout, chunk_elems=args.chunk_elems)
    build_s = time.time() - t0

    if args.ials:
        config = IALSConfig(
            rank=args.rank, lam=0.1, alpha=40.0,
            num_iterations=args.iterations, seed=0, layout=args.layout,
            dtype=args.dtype,
            algorithm="ials++" if args.ialspp else "als",
            block_size=args.block_size, sweeps=args.sweeps,
        )
        trainer = train_ials
    else:
        config = ALSConfig(
            rank=args.rank, lam=args.lam, num_iterations=args.iterations,
            seed=0, layout=args.layout, dtype=args.dtype,
            algorithm="als++" if args.alspp else "als",
            block_size=args.block_size, sweeps=args.sweeps,
        )
        trainer = train_als
    # Every trainer call pays the same fixed cost (multi-GB block upload +
    # dispatch) plus a per-iteration cost; timing the trainer at 1 and N
    # iterations and differencing cancels the fixed part exactly — no
    # separate upload probe whose conditions can diverge from the train
    # call's.  Host contention swings identical runs
    # several-fold, so each point is min-of-`repeats` with the two iteration
    # counts interleaved to see the same conditions.
    n1 = config.num_iterations

    def timed(cfg):
        t0 = time.time()
        model = trainer(ds, cfg)
        sync(model.user_factors)
        return time.time() - t0, model

    config1 = dataclasses.replace(config, num_iterations=1)
    warm, _ = timed(config)  # compile both programs
    timed(config1)
    t_n, t_1 = [], []
    for _ in range(args.repeats):
        d1, _ = timed(config1)
        dn, model = timed(config)
        t_1.append(d1)
        t_n.append(dn)
    train_s, short_s = min(t_n), min(t_1)

    steady_s = (train_s - short_s) / (n1 - 1) * n1 if n1 > 1 else 0.0
    # Degenerate when the delta is indistinguishable from timing noise
    # (or one iteration can't separate fixed cost at all) — rerun with more
    # --iterations for signal.
    timing_degenerate = (
        n1 == 1 or steady_s <= 0 or (train_s - short_s) < 0.05 * short_s
    )
    if steady_s <= 0:
        steady_s = train_s  # includes the fixed overhead; flagged above
    s_per_iter = steady_s / n1

    quality = {}
    if held is not None:
        from cfk_tpu.eval.metrics import mse_rmse_heldout

        _, prmse, pn = mse_rmse_heldout(model, ds, held)
        quality = {
            "planted_heldout_rmse": round(prmse, 4),
            "planted_noise_floor": args.planted_noise,
            "planted_rmse_over_floor": round(prmse / args.planted_noise, 3),
            "planted_heldout_cells": pn,
        }

    from cfk_tpu.utils.roofline import als_iteration_cost, bucketed_gather_rows

    cost = als_iteration_cost(
        nnz, users, movies, args.rank,
        factor_bytes=2 if args.dtype == "bfloat16" else 4,
        implicit=args.ials,
        table_dtype=config.table_dtype,
        # Same honest per-width-class floor the default-main ialspp row
        # uses — 2·nnz undercounts the padded cells the bucketed walk
        # actually fetches (measured 1.57× at the ML-25M build).
        gather_rows=(bucketed_gather_rows(ds.movie_blocks, ds.user_blocks)
                     if args.layout == "bucketed" else None),
        sweeps=args.sweeps if (args.ialspp or args.alspp) else 1,
    )
    from cfk_tpu.utils.roofline import (
        FULL_NETFLIX_NNZ,
        roofline_row,
        this_device_kind,
    )

    full_nnz = FULL_NETFLIX_NNZ
    extrapolated = (
        {}
        if nnz >= full_nnz or args.ials
        else {
            # Optimistic-linear in nnz; ground truth for the full shape is
            # the recorded `--scale --full` runs (BASELINE.md).
            "full_netflix_extrapolated_s_per_iter": round(
                s_per_iter * full_nnz / nnz, 4
            ),
        }
    )
    return {
        "metric": (
            "synthetic_ml25m_ialspp_s_per_iteration" if args.ialspp
            else "synthetic_ml25m_ials_s_per_iteration" if args.ials
            else "synthetic_netflix_scale_s_per_iteration"
        ),
        "value": round(s_per_iter, 4),
        "unit": "s/iteration",
        # BASELINE.json bar: < 60 s/iteration at full Netflix scale.
        # Sub-scale runs are scaled by their nnz fraction of the full
        # corpus so the ratio stays an (optimistic-linear) estimate.
        "vs_baseline": round(s_per_iter / (60.0 * nnz / full_nnz), 4),
        "ratings_per_sec_per_chip": int(
            coo.num_ratings * config.num_iterations * 2 / steady_s
        ),
        # Compute-efficiency block (cfk_tpu.utils.roofline): model
        # FLOPs count the algorithmic minimum (Gram 2·nnz·k·(k+1)·2
        # + Cholesky-cost solves), MFU is against the v5e bf16 peak,
        # hbm_roofline_s is the min-traffic floor, and gather_roofline_s
        # the measured row-gather-engine floor — the binding resource for
        # ALS on this chip (see cfk_tpu/utils/roofline.py).
        **roofline_row(cost, s_per_iter, config.table_dtype,
                       device_kind=this_device_kind()),
        # Plan provenance (ISSUE 9): which ExecutionPlan this config
        # resolves to and why — regressions are attributable to the
        # DECISION (model mis-ranking, stale autotune cache, forced
        # fallback), not just the symptom.
        **_plan_provenance_row(config, users, movies, nnz,
                               implicit=args.ials),
        **extrapolated,
        "timing_degenerate": timing_degenerate,
        "repeats": args.repeats,
        "users": users,
        "movies": movies,
        "ratings": nnz,
        "rank": args.rank,
        "layout": args.layout,
        "dtype": args.dtype,
        "algorithm": config.algorithm,
        "train_wall_s": round(train_s, 3),
        "one_iter_wall_s": round(short_s, 3),
        # fixed per-call cost (block upload + dispatch), as implied
        # by the two-point fit
        "fixed_overhead_wall_s": round(
            max(short_s - s_per_iter, 0.0), 3
        ),
        "s_per_iteration_incl_upload": round(train_s / n1, 4),
        # first_run includes compile; the difference can go negative
        # under timing variance, so clamp the estimate.
        "first_run_wall_s": round(warm, 3),
        "compile_wall_s": round(max(warm - train_s, 0.0), 3),
        "datagen_wall_s": round(gen_s, 3),
        "blockbuild_wall_s": round(build_s, 3),
        **quality,
    }


def scale_sweep_main(args) -> None:
    print(json.dumps(run_scale_sweep(args)))


def run_scale_sweep(args) -> dict:
    """``--scale-sweep`` (ISSUE 11/12): s/iter and ratings/sec/chip vs
    problem size — and SHARD COUNT (``--sweep-shards``) — across the
    resident→windowed offload tiers.

    Each point generates a counter-based power-law corpus
    (``cfk_tpu.data.synth`` — chunk/shard-invariant, so the same spec is
    reproducible at any scale), builds stream-mode tiled blocks at the
    point's shard count, resolves the execution plan against a device
    whose HBM budget is ``--sweep-budget-mb`` (default: the detected
    device), and trains through whichever tier the planner picked —
    ``device`` (resident tables, the plain/sharded trainer) or
    ``host_window`` (host stores + per-shard windowed staging,
    ``cfk_tpu.offload``).  Every row records the PER-SHARD memory-budget
    math the decision was made from (tables and blocks divide across
    shards; the all_gather working copy replicates — which is why an
    oversized fixed side still routes to host_window at 2+ shards), the
    staged bytes per table dtype (``--sweep-table-dtypes`` — int8 ships
    (codes, scales) at ~¼ the f32 bytes), and the device↔host_window
    crossing per shard count.  The planner — not the sweep — decides the
    tier, so the sweep doubles as the acceptance check that oversized
    shapes resolve to host_window with provenance instead of OOMing.

    A device-tier point at shards > the available jax device count
    records its budget math and tier but skips the timing (the resident
    arm needs a real/virtual mesh; the windowed arm never does — it is a
    host driver).
    """
    import dataclasses as _dc

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synth import PowerLawSynth, SynthSpec
    from cfk_tpu.models.als import train_als
    from cfk_tpu.offload import budget as _budget
    from cfk_tpu.offload.windowed import train_als_host_window
    from cfk_tpu.plan import DeviceSpec, constraints_from_config, plan
    from cfk_tpu.plan.resolver import shape_for_config
    from cfk_tpu.utils.metrics import Metrics

    import jax as _jax

    device = DeviceSpec.detect()
    if args.sweep_budget_mb is not None:
        device = _dc.replace(device, hbm_bytes=args.sweep_budget_mb * 1e6)
    scales = [float(s) for s in str(args.sweep_scales).split(",") if s]
    shard_counts = [int(s) for s in
                    str(getattr(args, "sweep_shards", "1")).split(",") if s]
    dtypes = [d for d in
              str(getattr(args, "sweep_table_dtypes",
                          "float32")).split(",") if d]
    rows = []
    tier_by_point: dict[str, str] = {}
    for sc in scales:
        users = max(int(args.users * sc), 16)
        movies = max(int(args.movies * sc), 8)
        nnz = max(int(args.nnz * sc), 64)
        t0 = time.time()
        coo = PowerLawSynth(
            SynthSpec(num_users=users, num_movies=movies, nnz=nnz,
                      seed=args.seed)
        ).coo()
        gen_s = time.time() - t0
        for shards in shard_counts:
            t0 = time.time()
            ds = Dataset.from_coo(
                coo, num_shards=shards, layout="tiled",
                chunk_elems=args.chunk_elems,
                tile_rows=args.sweep_tile_rows, accum_max_entities=0,
            )
            build_s = time.time() - t0
            for table_dtype in dtypes:
                config = ALSConfig(
                    rank=args.rank, lam=args.lam,
                    num_iterations=args.iterations, seed=0,
                    layout="tiled", num_shards=shards,
                    dtype=args.dtype, table_dtype=table_dtype,
                    hbm_chunk_elems=args.chunk_elems,
                )
                shape = shape_for_config(
                    config, num_users=ds.user_map.num_entities,
                    num_movies=ds.movie_map.num_entities, nnz=nnz,
                )
                ep, prov = plan(shape, device,
                                constraints_from_config(config))
                tier = ep.offload_tier
                # Keyed per (scale, shards, dtype): int8 can legitimately
                # flip the tier at the same (scale, shards) — quantization
                # shrinks the gather working copy — and the acceptance
                # surface must show every crossing, not the last dtype's.
                tier_by_point[
                    f"scale={sc},shards={shards},table={table_dtype}"
                ] = tier
                # The budget math is recorded from the SAME counts the
                # planner decided on (the dataset's dense entity
                # universe) AT THE POINT'S SHARD COUNT, so the row's
                # fits_device can never disagree with the recorded tier.
                resident = _budget.train_resident_bytes(
                    ds.user_map.num_entities, ds.movie_map.num_entities,
                    nnz, args.rank, dtype=args.dtype,
                    table_dtype=table_dtype, num_shards=shards,
                )
                # Pin the SWEEP's decision into the config: the
                # device-tier arm must not silently re-resolve against
                # the real detected device (an artificial
                # --sweep-budget-mb would otherwise let the trainers
                # route differently than the row's tier label claims).
                config = _dc.replace(config, offload_tier=tier)
                metrics = Metrics()
                resident_ok = (tier != "device" or shards == 1
                               or len(_jax.devices()) >= shards)

                def timed(cfg, staging=None, mts=None):
                    t0 = time.time()
                    if tier == "host_window":
                        model = train_als_host_window(
                            ds, cfg,
                            metrics=mts if mts is not None else metrics,
                            chunks_per_window=args.sweep_window_chunks,
                            device_budget_bytes=device.hbm_bytes,
                            staging=staging,
                        )
                        np.asarray(model.user_factors[:1])
                        timed.last_model = model
                    elif shards > 1:
                        from cfk_tpu.parallel.mesh import make_mesh
                        from cfk_tpu.parallel.spmd import train_als_sharded

                        model = train_als_sharded(ds, cfg,
                                                  make_mesh(shards))
                        sync(model.user_factors)
                    else:
                        model = train_als(ds, cfg)
                        sync(model.user_factors)
                    return time.time() - t0, model

                row = {
                    "scale": sc,
                    "users": users, "movies": movies, "ratings": nnz,
                    "rank": args.rank, "dtype": args.dtype,
                    "table_dtype": table_dtype,
                    "num_shards": shards,
                    "offload_tier": tier,
                    # The PER-SHARD memory-budget math the tier decision
                    # was made from — recorded so BASELINE.md's table is
                    # reproducible arithmetic, not an assertion.
                    "resident_bytes_mb_per_shard": round(
                        resident["total"] / 1e6, 2
                    ),
                    "factor_tables_mb_per_shard": round(
                        resident["factor_tables_bytes"] / 1e6, 2
                    ),
                    "gather_copy_mb": round(
                        resident["gather_copy_bytes"] / 1e6, 2
                    ),
                    "block_arrays_mb_per_shard": round(
                        resident["block_arrays_bytes"] / 1e6, 2
                    ),
                    "device_budget_mb": round(device.hbm_bytes / 1e6, 2),
                    "budget_fraction": _budget.RESIDENT_FRACTION,
                    # THE predicate, not an inline copy — the row's
                    # fits_device must stay the planner's own arithmetic.
                    "fits_device": _budget.fits_device(
                        ds.user_map.num_entities,
                        ds.movie_map.num_entities,
                        nnz, args.rank, hbm_bytes=device.hbm_bytes,
                        dtype=args.dtype, table_dtype=table_dtype,
                        num_shards=shards,
                    ),
                    "datagen_wall_s": round(gen_s, 3),
                    "blockbuild_wall_s": round(build_s, 3),
                    **prov.as_row(),
                }
                # Donation-credit provenance (ISSUE 13): the DEFAULT
                # arithmetic credits the donated solve-side output (the
                # trainers really donate); recording the UN-donated twin
                # makes a tier decision that only holds because of the
                # credit attributable to it in the row itself.
                row["fits_device_without_donation"] = _budget.fits_device(
                    ds.user_map.num_entities, ds.movie_map.num_entities,
                    nnz, args.rank, hbm_bytes=device.hbm_bytes,
                    dtype=args.dtype, table_dtype=table_dtype,
                    num_shards=shards, donation=False,
                )
                row["donation_credit_mb"] = round(
                    _budget.train_resident_bytes(
                        ds.user_map.num_entities,
                        ds.movie_map.num_entities, nnz, args.rank,
                        dtype=args.dtype, table_dtype=table_dtype,
                        num_shards=shards, donation=False,
                    )["solve_output_bytes"] / 1e6, 2,
                )

                def two_point_fit(staging=None, mts=None):
                    # Same two-point (1 vs N iterations) fit as
                    # run_scale: the fixed upload/plan cost cancels
                    # exactly.  Returns (s/iter, wall, cold-start dict).
                    n1 = config.num_iterations
                    config1 = _dc.replace(config, num_iterations=1)
                    m = mts if mts is not None else metrics
                    timed(config, staging, m)  # compile both programs
                    cold = {
                        "time_to_first_step_s": m.gauges.get(
                            "time_to_first_step_s"),
                        "trace_count": m.gauges.get(
                            "offload_trace_count"),
                    }
                    timed(config1, staging, m)
                    t_n, t_1 = [], []
                    for _ in range(args.repeats):
                        t_1.append(timed(config1, staging, m)[0])
                        t_n.append(timed(config, staging, m)[0])
                    train_s, short_s = min(t_n), min(t_1)
                    steady_s = ((train_s - short_s) / (n1 - 1) * n1
                                if n1 > 1 else train_s)
                    if steady_s <= 0:
                        steady_s = train_s
                    return steady_s / n1, train_s, cold

                if not resident_ok:
                    row["s_per_iteration"] = None
                    row["run"] = (f"skipped: resident arm needs "
                                  f"{shards} devices")
                else:
                    per_iter, train_s, cold = two_point_fit()
                    row["s_per_iteration"] = round(per_iter, 4)
                    row["ratings_per_sec_per_chip"] = int(
                        nnz * 2 / max(per_iter, 1e-9) / shards
                    )
                    row["train_wall_s"] = round(train_s, 3)
                    if (tier == "host_window"
                            and getattr(args, "staging_ab", False)):
                        # The staging A/B arm (ISSUE 13): re-time the
                        # SAME point with the serial engine — the PR
                        # 10/11 baseline — so the row carries the
                        # pooled-vs-serial wall-clock ratio plus the
                        # pool's own accounting.  Fresh Metrics per arm
                        # keep the gauges attributable.
                        row.update({
                            "staging": metrics.notes.get(
                                "offload_staging"),
                            "pool_depth": metrics.gauges.get(
                                "offload_pool_depth"),
                            "pool_peak_inflight": metrics.gauges.get(
                                "offload_pool_peak_inflight"),
                            "staged_mb_per_s": metrics.gauges.get(
                                "offload_staged_mb_per_s"),
                            "overlap_hidden_fraction": metrics.gauges.get(
                                "offload_stage_hidden_frac"),
                            "time_to_first_step_s": cold[
                                "time_to_first_step_s"],
                            "trace_count": cold["trace_count"],
                        })
                        from cfk_tpu.utils.metrics import (
                            Metrics as _Metrics,
                        )

                        m_serial = _Metrics()
                        ser_iter, _, _ = two_point_fit(
                            staging="serial", mts=m_serial,
                        )
                        row["s_per_iteration_staging_serial"] = round(
                            ser_iter, 4
                        )
                        row["staging_speedup"] = round(
                            ser_iter / max(per_iter, 1e-9), 3
                        )
                    if (tier == "host_window"
                            and getattr(args, "hot_ab", False)):
                        # The hot-cache A/B arm (ISSUE 15): the point
                        # above ran with the DEFAULT hot resolution
                        # (auto — the coverage knee under the budget);
                        # re-run the SAME point with hot_rows=0 (the PR
                        # 12 full-staging engine) and record the staged
                        # table-byte cut + crc equality — the acceptance
                        # measurement.  One un-timed run per arm is
                        # enough: staged bytes are deterministic.
                        import zlib as _zlib

                        from cfk_tpu.utils.metrics import (
                            Metrics as _Metrics,
                        )

                        def _crc(m):
                            return _zlib.crc32(np.asarray(
                                m.user_factors, np.float32
                            ).tobytes()) & 0xFFFFFFFF

                        crc_on = _crc(timed.last_model)
                        m_off = _Metrics()
                        cfg_off = _dc.replace(config, hot_rows=0)
                        timed(cfg_off, None, m_off)
                        crc_off = _crc(timed.last_model)
                        cold_on = metrics.gauges.get(
                            "offload_staged_cold_mb") or 0.0
                        cold_off = m_off.gauges.get(
                            "offload_staged_cold_mb") or 0.0
                        row.update({
                            "hot_rows": metrics.gauges.get(
                                "offload_hot_rows", 0),
                            "hot_coverage": metrics.gauges.get(
                                "offload_hot_coverage"),
                            "delta_coverage": metrics.gauges.get(
                                "offload_delta_coverage"),
                            "hot_resident_mb": metrics.gauges.get(
                                "offload_hot_resident_mb"),
                            "staged_cold_mb_hot_off": cold_off,
                            "staged_table_cut": (
                                round(cold_off / cold_on, 3)
                                if cold_on else None
                            ),
                            "hot_crc_equal": bool(crc_on == crc_off),
                            "hot_decision": metrics.notes.get(
                                "offload_hot_decision"),
                        })
                if tier == "host_window" and resident_ok:
                    row.update({
                        "windows_m": metrics.gauges.get(
                            "offload_windows_m"),
                        "windows_u": metrics.gauges.get(
                            "offload_windows_u"),
                        "window_rows_m": metrics.gauges.get(
                            "offload_window_rows_m"),
                        "window_rows_u": metrics.gauges.get(
                            "offload_window_rows_u"),
                        # The HONEST staged bytes at this table dtype
                        # (int8 ships codes + per-row scales ≈ ¼ f32 on
                        # the table share, metered separately from the
                        # chunk arrays that cross PCIe regardless).
                        # Split per ISSUE 15: cold = table bytes that
                        # actually crossed PCIe; hot = device-resident
                        # partition bytes (0 / absent when the cache is
                        # off — then cold IS the whole table share).
                        "offload_staged_mb": metrics.gauges.get(
                            "offload_staged_mb"),
                        "offload_staged_cold_mb": metrics.gauges.get(
                            "offload_staged_cold_mb"),
                        "offload_hot_resident_mb": metrics.gauges.get(
                            "offload_hot_resident_mb"),
                        "offload_hot_rows": metrics.gauges.get(
                            "offload_hot_rows"),
                        "offload_hot_coverage": metrics.gauges.get(
                            "offload_hot_coverage"),
                        "plan_held_mb": metrics.gauges.get(
                            "offload_plan_held_mb"),
                        "per_window_budget_mb": round(
                            _budget.window_budget_bytes(
                                device.hbm_bytes) / 1e6, 2
                        ),
                        # Fabric attribution of staged rows (sharded).
                        "staged_rows_local": metrics.gauges.get(
                            "offload_rows_local"),
                        "staged_rows_ici": metrics.gauges.get(
                            "offload_rows_ici"),
                        "staged_rows_dcn": metrics.gauges.get(
                            "offload_rows_dcn"),
                    })
                print("# sweep point: " + json.dumps(row), flush=True)
                rows.append(row)
    tiers = [r["offload_tier"] for r in rows]
    result = {
        "metric": "scale_sweep_s_per_iteration",
        "points": rows,
        "tiers": tiers,
        # The device↔host_window crossing per (scale, shard count) — the
        # ISSUE 12 acceptance surface: an oversized shape must read
        # host_window at EVERY shard count, not just 1.
        "tier_by_point": tier_by_point,
        "crossed_to_host_window": "host_window" in tiers,
    }
    # Fleet tier: the sweep's out-of-core ladder extends past one host —
    # a 2-process Gloo run at a shape whose per-host store footprint a
    # simulated single-host RAM budget refuses.  CFK_BENCH_FLEET=0 skips
    # (it spawns a real worker pair).
    import os as _os

    if _os.environ.get("CFK_BENCH_FLEET", "1") != "0":
        try:
            fleet = _fleet_row()
        except Exception as e:  # pragma: no cover - subprocess-dependent
            fleet = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("# fleet: " + json.dumps(fleet), flush=True)
        result["fleet"] = fleet
    return result


def _fleet_row() -> dict:
    """The fleet scale-sweep row (distributed window exchange): spawn
    TWO real Gloo processes running the offload bench drill — a
    power-law shape whose single-host store footprint the simulated RAM
    budget refuses completes with each process owning half the
    ``HostFactorStore`` — and parse the worker's ``OFFLOAD_BENCH_ROW``:
    per-host residual DCN rows/bytes, the dense no-split baseline and
    the hot/delta reduction against it, and the budget provenance
    proving the single-host refusal + per-process fit."""
    import importlib.util
    import os as _os

    root = _os.path.dirname(_os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "multihost_worker",
        _os.path.join(root, "tests", "multihost_worker.py"),
    )
    mhw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mhw)
    port = 29900 + (_os.getpid() % 200)
    t0 = time.time()
    procs = mhw.spawn_workers(port, 2, None, "--drill", "offload-bench")
    outs = mhw.communicate_all(procs, timeout=540)
    wall = time.time() - t0
    for i, p in enumerate(procs):
        if p.returncode != 0:
            raise RuntimeError(
                f"fleet worker {i} rc={p.returncode}: {outs[i][-400:]}")
    row = None
    for out in outs:
        for line in out.splitlines():
            if line.startswith("OFFLOAD_BENCH_ROW "):
                row = json.loads(line.split(" ", 1)[1])
    if row is None:
        raise RuntimeError("no OFFLOAD_BENCH_ROW in worker output")
    row["wall_s"] = round(wall, 2)
    return row


def _scale_sweep_row() -> dict:
    """The default-main scale-sweep row: tiny shapes under an artificial
    2 MB device budget so the largest point CROSSES into the
    host_window tier on this CPU container — at one AND two shards, with
    f32 and int8 staging (the recorded ``offload_staged_mb`` pair is the
    ¼-bytes acceptance row).  Real budgets are the on-TPU run's job; the
    tier-resolution machinery is what this row exercises.  The 2-shard
    resident points skip timing in-process (no virtual mesh after jax
    init) but still record tier + budget math."""
    ns = argparse.Namespace(
        # rank 64 at 22k movies makes the fixed side's all_gather
        # working copy (13.3k distinct movies · 256 B ≈ 3.4 MB) the
        # dominant resident term — the one sharding cannot divide — so
        # the 1.0× point overflows the 4.6 MB effective budget at one
        # AND two shards (the ISSUE 12 crossing) while the 0.25× point
        # stays resident.  The 2k-user side keeps the hot-entity
        # carry-constrained window small (1.5 MB measured — a stream
        # window can only cut where no entity straddles, and the
        # hottest USER's movie set bounds it), well under the 2.3 MB
        # per-window share.  The 5.11 MB budget additionally puts the
        # int8 2-shard point in the DONATION band (ISSUE 13): its
        # donated per-shard total (3.71 MB) fits while the un-donated
        # twin (5.41 MB — the solved side's output coexisting with its
        # input) would not, so that point re-fits the cheaper resident
        # tier exactly because the trainers donate, and the row records
        # it (fits_device_without_donation=False at offload_tier=device).
        users=2_000, movies=22_000, nnz=60_000, rank=64, iterations=2,
        repeats=2, seed=0, dtype="float32", lam=0.05, chunk_elems=2_048,
        sweep_scales="0.25,1.0", sweep_budget_mb=5.11, sweep_tile_rows=16,
        sweep_window_chunks=2, sweep_shards="1,2",
        sweep_table_dtypes="float32,int8",
    )
    return run_scale_sweep(ns)


def _staging_ab_row() -> dict:
    """The default-main staging A/B row (ISSUE 13): one 4-shard
    host_window point (the unsharded gather copy overflows the small
    budget's 0.9 fraction, so the planner routes host_window) timed
    under both staging engines via the sweep's ``--staging-ab`` arm.

    Read the MEASURED columns, not an assumed story: on THIS CPU
    container the wall is gated by per-window XLA:CPU compute, so the
    honest headline is the pool's ``overlap_hidden_fraction`` (~0.85+
    of staging busy-time removed from the consuming thread; serial
    reads 0.0 by construction) at wall-clock parity —
    ``staging_speedup`` ≈ 1.  The wall-clock win the engine exists for
    needs staging to gate the pipeline, which is the on-TPU regime
    (real PCIe DMA instead of this backend's zero-copy ``device_put``,
    and ~100× faster window compute) — the ROADMAP backlog's
    re-measure.  rank 16 + 2048-cell chunks keep the worst window small
    enough that the budget admits pool depth ≥ 2 (bigger windows clamp
    the depth toward 1 and the pool degrades gracefully to the serial
    schedule)."""
    ns = argparse.Namespace(
        users=20_000, movies=2_000, nnz=120_000, rank=16, iterations=2,
        repeats=2, seed=0, dtype="float32", lam=0.05, chunk_elems=2_048,
        sweep_scales="1.0", sweep_budget_mb=2.7, sweep_tile_rows=16,
        sweep_window_chunks=2, sweep_shards="4",
        sweep_table_dtypes="float32", staging_ab=True,
    )
    return run_scale_sweep(ns)


def _hot_ab_row() -> dict:
    """The default-main hot-cache A/B row (ISSUE 15): one power-law
    2-shard host_window point (the budget refuses residency) run with
    the AUTO hot resolution vs ``hot_rows=0`` via the sweep's
    ``--hot-ab`` arm.

    The acceptance quantity is ``staged_table_cut`` — full-staging cold
    bytes over hot-arm cold bytes, per iteration: the counter-based
    generator is Zipf by construction, so the coverage-curve knee keeps
    the reference head device-resident and the cut should comfortably
    clear 2× (the measured row records the resolved fraction and the
    reference-coverage it bought, plus ``hot_crc_equal`` — the arms are
    bitwise the same factors).  Wall-clock is expected near parity on
    this CPU container (PR 12's zero-copy ``device_put`` — no PCIe leg
    exists to cut; the byte meter is the honest quantity off-TPU)."""
    ns = argparse.Namespace(
        users=2_400, movies=240, nnz=48_000, rank=16, iterations=2,
        repeats=1, seed=0, dtype="float32", lam=0.05, chunk_elems=1_024,
        sweep_scales="1.0", sweep_budget_mb=1.05, sweep_tile_rows=16,
        sweep_window_chunks=2, sweep_shards="2",
        sweep_table_dtypes="float32", hot_ab=True,
    )
    return run_scale_sweep(ns)


def ials_offload_ab_main(args) -> None:
    print(json.dumps(run_ials_offload_ab(args)))


def _ials_offload_ab_row() -> dict:
    """The default-main iALS++ offload A/B row (ISSUE 19): one power-law
    bucketed point under a budget that refuses residency, resident vs
    host_window with the hot cache on (auto knee) and off.  On this CPU
    container wall-clock sits near parity (PR 12's zero-copy
    ``device_put`` — no PCIe leg exists); the honest quantities are crc
    equality (the windowed subspace sweep is bit-identical to the
    resident optimizer), the staged MB/iter meter, and the hot arm's
    staged-table-byte cut at that same crc."""
    ns = argparse.Namespace(
        users=2_400, movies=240, nnz=48_000, rank=16, iterations=2,
        repeats=1, seed=0, dtype="float32", chunk_elems=1_024,
        ials_budget_mb=1.6, ials_window_chunks=2,
    )
    return run_ials_offload_ab(ns)


def run_ials_offload_ab(args) -> dict:
    """iALS++ resident vs host_window A/B (ISSUE 19).

    Three arms on the SAME bucketed implicit dataset: the device-resident
    ``train_ials`` reference, the out-of-core windowed driver with the
    auto hot-row cache, and the same driver with ``hot_rows=0`` (full
    staging).  The budget (``--ials-budget-mb``) is artificial so the
    point exercises the tier machinery on any host; the row records the
    planner's own resolution at that budget (provenance columns), s/iter
    per arm, the staged MB/iter meters (table windows + the global-Gram
    reduction passes), and crc equality of both offload arms against the
    resident factors — the windowed subspace optimizer's bit-exactness
    contract, measured not asserted."""
    import dataclasses as _dc
    import zlib as _zlib

    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synth import PowerLawSynth, SynthSpec
    from cfk_tpu.models.ials import IALSConfig, train_ials
    from cfk_tpu.offload.windowed import train_ials_host_window
    from cfk_tpu.plan import DeviceSpec, constraints_from_config
    from cfk_tpu.plan import plan as _plan
    from cfk_tpu.plan.resolver import shape_for_config
    from cfk_tpu.utils.metrics import Metrics

    users, movies, nnz = args.users, args.movies, args.nnz
    coo = PowerLawSynth(
        SynthSpec(num_users=users, num_movies=movies, nnz=nnz,
                  seed=args.seed)
    ).coo()
    ds = Dataset.from_coo(coo, layout="bucketed",
                          chunk_elems=args.chunk_elems)
    block_size = max(b for b in (32, 16, 8, 4, 2, 1)
                     if args.rank % b == 0)
    config = IALSConfig(
        rank=args.rank, lam=0.1, alpha=40.0,
        num_iterations=args.iterations, seed=0, layout="bucketed",
        dtype=args.dtype, algorithm="ials++", block_size=block_size,
    )
    budget = args.ials_budget_mb * 1e6
    n = max(args.iterations, 1)

    # The planner's OWN resolution at this budget (tier un-pinned): the
    # acceptance surface is that bucketed×host_window resolves for the
    # implicit family, with provenance — not just that the driver runs.
    device = _dc.replace(DeviceSpec.detect(), hbm_bytes=budget)
    shape = shape_for_config(
        config, num_users=ds.user_map.num_entities,
        num_movies=ds.movie_map.num_entities, nnz=nnz, implicit=True,
    )
    ep, prov = _plan(shape, device, constraints_from_config(config))

    def crc(model):
        return (
            _zlib.crc32(np.asarray(model.user_factors,
                                   np.float32).tobytes()),
            _zlib.crc32(np.asarray(model.movie_factors,
                                   np.float32).tobytes()),
        )

    def timed(fn):
        model = fn()  # warm: compile every program
        np.asarray(model.user_factors[:1])
        best = None
        for _ in range(max(args.repeats, 1)):
            t0 = time.time()
            model = fn()
            np.asarray(model.user_factors[:1])
            dt = time.time() - t0
            best = dt if best is None else min(best, dt)
        return best, model

    res_s, res_model = timed(lambda: train_ials(ds, config))
    res_crc = crc(res_model)

    hw_cfg = _dc.replace(config, offload_tier="host_window")
    arms = {}
    for name, hot in (("hot_auto", None), ("hot_off", 0)):
        metrics = Metrics()
        wall, model = timed(lambda: train_ials_host_window(
            ds, hw_cfg, metrics=metrics,
            chunks_per_window=args.ials_window_chunks,
            device_budget_bytes=budget, hot_rows=hot,
        ))
        g = metrics.gauges
        arms[name] = {
            "s_per_iteration": round(wall / n, 4),
            "staged_mb_per_iter": round(
                (g.get("offload_staged_mb") or 0.0) / n, 3),
            "staged_cold_mb_per_iter": round(
                (g.get("offload_staged_cold_mb")
                 or g.get("offload_staged_mb") or 0.0) / n, 3),
            "gram_staged_mb_per_iter": round(
                (g.get("offload_gram_staged_mb") or 0.0) / n, 3),
            "windows_m": g.get("offload_windows_m"),
            "windows_u": g.get("offload_windows_u"),
            "hot_rows": g.get("offload_hot_rows", 0),
            "hot_coverage": g.get("offload_hot_coverage"),
            "gram_reserved_mb": g.get("offload_gram_reserved_mb"),
            "crc_equal_resident": crc(model) == res_crc,
        }
    cold = arms["hot_off"]["staged_cold_mb_per_iter"]
    hot_cold = arms["hot_auto"]["staged_cold_mb_per_iter"]
    res_per_iter = res_s / n
    return {
        "metric": "ialspp_offload_ab",
        "value": arms["hot_auto"]["s_per_iteration"],
        "unit": "s/iteration",
        "users": ds.user_map.num_entities,
        "movies": ds.movie_map.num_entities,
        "ratings": nnz, "rank": args.rank, "algorithm": "ials++",
        "device_budget_mb": round(budget / 1e6, 2),
        "planner_tier": ep.offload_tier,
        "planner_layout": ep.layout,
        **prov.as_row(),
        "resident_s_per_iteration": round(res_per_iter, 4),
        "offload_over_resident": round(
            arms["hot_auto"]["s_per_iteration"] / max(res_per_iter, 1e-9),
            3),
        "staged_table_cut": (round(cold / hot_cold, 2)
                             if hot_cold else None),
        "factors_bit_exact": all(
            a["crc_equal_resident"] for a in arms.values()),
        "arms": arms,
    }


def _virtual_cpu_mesh(shards: int):
    """Force an N-virtual-device CPU platform; MUST run before the first
    jax computation (XLA reads the host-device-count flag at backend
    init).  Shared by every virtual-mesh bench mode.  Returns the jax
    module."""
    import os

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={shards}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


def overlap_ab_main(args) -> None:
    print(json.dumps(run_overlap_ab(args)))


def _overlap_ab_row() -> dict:
    """The default-run overlap row: a subprocess, because the virtual CPU
    mesh needs ``xla_force_host_platform_device_count`` set before jax
    initializes (main() has already initialized the backend by now)."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, __file__, "--overlap-ab"],
        capture_output=True, text=True, timeout=3600,
    )
    if out.returncode != 0:
        tail = (out.stderr or out.stdout).strip()[-300:]
        return {"error": f"overlap-ab subprocess failed: {tail}"}
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_overlap_ab(args) -> dict:
    """Tentpole A/B: double-buffered (overlap=on) vs serial (overlap=off)
    ring exchange, plus the per-half-iteration exchange/compute split, on
    the ML-25M-proportioned synthetic shape scaled by ``--overlap-div``.

    By default runs on a virtual CPU mesh (like ``--compare-exchange``):
    one chip is all this environment exposes, so absolute seconds are
    CPU-relative — the A/B ratio, the split, and the bit-exactness check
    are the portable quantities.  On a host with a real multi-chip mesh,
    pass ``--overlap-device-mesh`` to measure the ICI story on the actual
    devices instead.
    The split is measured with ``ring_probe`` steps (exchange = only the
    S−1 ppermutes per half; compute = the same Gram/solve work with no
    transfers), each with the same step/jit scaffold as the real
    iteration.
    """
    import dataclasses as dc

    if args.overlap_device_mesh:
        # Real-hardware mode (the ROADMAP follow-up): use whatever devices
        # the default platform exposes — requires >= --shards of them.
        import jax
    else:
        jax = _virtual_cpu_mesh(args.shards)
    import jax.numpy as jnp

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.ops.solve import init_factors_stats
    from cfk_tpu.parallel import spmd
    from cfk_tpu.parallel.mesh import make_mesh, shard_rows

    div = args.overlap_div
    users, movies, nnz = 162_541 // div, 59_047 // div, 25_000_095 // div
    rank, s, iters = args.overlap_rank, args.shards, args.iterations
    coo = synthetic_netflix_coo(users, movies, nnz, seed=args.seed)
    ds = Dataset.from_coo(
        coo, layout="tiled", num_shards=s, ring=True,
        chunk_elems=args.overlap_chunk_elems,
    )
    mesh = make_mesh(s)
    base = ALSConfig(
        rank=rank, lam=0.05, num_iterations=iters, seed=0, layout="tiled",
        exchange="ring", solver="cholesky", num_shards=s,
    )

    mtree, utree, step_kw = spmd.gathered_layout_trees(ds, base)
    mtree = shard_rows(mesh, mtree)
    utree = shard_rows(mesh, utree)

    def init_factors():
        key = jax.random.PRNGKey(0)
        u0 = jax.jit(init_factors_stats, static_argnames="rank")(
            key, jnp.asarray(ds.user_blocks.rating_sum),
            jnp.asarray(ds.user_blocks.count), rank=rank,
        )
        m0 = jnp.zeros((ds.movie_blocks.padded_entities, rank), jnp.float32)
        return shard_rows(mesh, u0), shard_rows(mesh, m0)

    def timed(cfg, probe=None):
        step = jax.jit(
            spmd.make_training_step(
                mesh, cfg, spmd.tree_specs(mtree), spmd.tree_specs(utree),
                ring_probe=probe, **step_kw,
            )
        )
        u, m = init_factors()
        u, m = step(u, m, mtree, utree)  # compile + warm
        jax.block_until_ready((u, m))
        times = []
        for _ in range(args.repeats):
            t0 = time.time()
            for _ in range(iters):
                u, m = step(u, m, mtree, utree)
            jax.block_until_ready((u, m))
            times.append((time.time() - t0) / iters)
        return min(times), np.asarray(u, np.float32), np.asarray(
            m, np.float32
        )

    on_s, on_u, on_m = timed(dc.replace(base, overlap=True))
    off_s, off_u, off_m = timed(dc.replace(base, overlap=False))
    # The split: same scaffold, phase-isolated steps (timing-only factors).
    exch_s, _, _ = timed(base, probe="exchange")
    comp_s, _, _ = timed(base, probe="compute")
    max_diff = float(
        max(np.abs(on_u - off_u).max(), np.abs(on_m - off_m).max())
    )
    return {
        "metric": "synthetic_ml25m_ring_overlap_ab_s_per_iteration",
        "value": round(on_s, 4),
        "unit": "s/iteration",
        # the A/B itself: ≤ 1.0 = overlap=on no slower than the serial
        # schedule (the acceptance bar; the win is hardware-dependent —
        # CPU has no async ICI, so ~1.0 is the honest expectation here).
        "vs_baseline": round(on_s / off_s, 4),
        "overlap_on_s_per_iter": round(on_s, 4),
        "overlap_off_s_per_iter": round(off_s, 4),
        # per-ITERATION split (both halves): transfers-only vs
        # compute-only step timings from the ring probes.
        "exchange_s_per_iter": round(exch_s, 4),
        "compute_s_per_iter": round(comp_s, 4),
        # what perfect overlap could hide at these phase durations
        "exchange_fraction_of_serial": round(
            exch_s / max(exch_s + comp_s, 1e-12), 4
        ),
        "max_abs_factor_diff_on_vs_off": max_diff,
        "users": users, "movies": movies, "ratings": nnz, "rank": rank,
        "shards": s, "iterations": iters, "repeats": args.repeats,
        "layout": "tiled+ring", "overlap_div": div,
        "backend": (
            f"{jax.default_backend()}-device-mesh"
            if args.overlap_device_mesh
            else "cpu-virtual-mesh (relative timings)"
        ),
    }


def fused_ab_main(args) -> None:
    print(json.dumps(run_fused_ab(args)))


def _fused_ab_row() -> dict:
    """The default-run fused/split row: a subprocess, because the virtual
    CPU mesh needs ``xla_force_host_platform_device_count`` set before jax
    initializes (main() has already initialized the backend by now)."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, __file__, "--fused-ab"],
        capture_output=True, text=True, timeout=3600,
    )
    if out.returncode != 0:
        tail = (out.stderr or out.stdout).strip()[-300:]
        return {"error": f"fused-ab subprocess failed: {tail}"}
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_fused_ab(args) -> dict:
    """Tentpole A/B: fused Gram+solve epilogue (each chunk's normal
    equations solved inside the Gram kernel's VMEM residency) vs the split
    Gram→HBM→solve schedule, on the ML-25M-proportioned synthetic shape
    scaled by ``--fused-div``, sharded over a virtual CPU mesh.

    Like ``--overlap-ab``, absolute seconds on the CPU mesh are relative
    only (the emulation route has no VMEM to win back); the portable
    quantities are the factor-equivalence check (bit-exact on the
    emulation route — the twin and the split path run the identical
    segment-sum + fused reg+solve) and the analytic per-chunk HBM traffic
    the fused path removes on the real Pallas route: the split schedule
    writes the [Ec+1, k, k] A-batch + [Ec+1, k] b to HBM and reads both
    back for the batched solve; fused writes only the solved [Ec+1, k]
    rows + one [k, k+1] carry row.
    """
    import dataclasses as dc

    jax = _virtual_cpu_mesh(args.shards)
    import jax.numpy as jnp

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.ops.solve import init_factors_stats
    from cfk_tpu.parallel import spmd
    from cfk_tpu.parallel.mesh import make_mesh, shard_rows

    div = args.fused_div
    users, movies, nnz = 162_541 // div, 59_047 // div, 25_000_095 // div
    rank, s, iters = args.fused_rank, args.shards, args.iterations
    coo = synthetic_netflix_coo(users, movies, nnz, seed=args.seed)
    # Force BOTH halves into the dense-stream chunk scan (accum off): the
    # per-chunk fused epilogue is what this A/B measures, and at the
    # div-scaled shape the default accum threshold would swallow both
    # halves into the end-of-scan solve (whose fused/split pair differs by
    # elimination algorithm, not by the removed round-trip).
    ds = Dataset.from_coo(
        coo, layout="tiled", num_shards=s,
        chunk_elems=args.fused_chunk_elems,
        accum_max_entities=0, dense_stream=True,
    )
    mesh = make_mesh(s)
    base = ALSConfig(
        rank=rank, lam=0.05, num_iterations=iters, seed=0, layout="tiled",
        exchange="all_gather", solver="pallas", num_shards=s,
    )

    mtree, utree, step_kw = spmd.gathered_layout_trees(ds, base)
    mtree = shard_rows(mesh, mtree)
    utree = shard_rows(mesh, utree)

    def init_factors():
        key = jax.random.PRNGKey(0)
        u0 = jax.jit(
            init_factors_stats, static_argnames=("rank", "num_entities")
        )(
            key, jnp.asarray(ds.user_blocks.rating_sum),
            jnp.asarray(ds.user_blocks.count), rank=rank,
            num_entities=ds.user_blocks.num_entities,
        )
        m0 = jnp.zeros((ds.movie_blocks.padded_entities, rank), jnp.float32)
        return shard_rows(mesh, u0), shard_rows(mesh, m0)

    def timed(cfg):
        step = jax.jit(
            spmd.make_training_step(
                mesh, cfg, spmd.tree_specs(mtree), spmd.tree_specs(utree),
                **step_kw,
            )
        )
        u, m = init_factors()
        u, m = step(u, m, mtree, utree)  # compile + warm
        jax.block_until_ready((u, m))
        times = []
        for _ in range(args.repeats):
            t0 = time.time()
            for _ in range(iters):
                u, m = step(u, m, mtree, utree)
            jax.block_until_ready((u, m))
            times.append((time.time() - t0) / iters)
        return min(times), np.asarray(u, np.float32), np.asarray(
            m, np.float32
        )

    on_s, on_u, on_m = timed(dc.replace(base, fused_epilogue=True))
    off_s, off_u, off_m = timed(dc.replace(base, fused_epilogue=False))
    max_diff = float(
        max(np.abs(on_u - off_u).max(), np.abs(on_m - off_m).max())
    )
    # Analytic per-chunk HBM traffic on the real Pallas route.  BOTH
    # halves run the per-chunk dstream scan here (accum_max_entities=0
    # above), so the removed-per-iteration number sums both; the headline
    # per-chunk pair is quoted from the user half (the bigger scan).
    def _half_bytes(blocks):
        s_rows = blocks.chunk_entities + 1  # Ec + trash
        split = 2 * s_rows * rank * (rank + 1) * 4  # A+b write AND readback
        fused = s_rows * rank * 4 + rank * (rank + 1) * 4  # x + carry row
        return split, fused, blocks.num_chunks

    ub = ds.user_blocks
    split_ab, fused_wb, chunks_per_iter = _half_bytes(ub)
    removed_iter = sum(
        (sp - fu) * nc
        for sp, fu, nc in (_half_bytes(ds.user_blocks),
                           _half_bytes(ds.movie_blocks))
    )
    return {
        "metric": "synthetic_ml25m_fused_epilogue_ab_s_per_iteration",
        "value": round(on_s, 4),
        "unit": "s/iteration",
        # the A/B itself: ≤ 1.0 = fused no slower than split.  On the CPU
        # emulation route both run the same XLA ops, so ~1.0 is the honest
        # expectation here; the HBM win is Pallas-route-only.
        "vs_baseline": round(on_s / off_s, 4),
        "fused_on_s_per_iter": round(on_s, 4),
        "fused_off_s_per_iter": round(off_s, 4),
        "max_abs_factor_diff_fused_vs_split": max_diff,
        "factors_bit_exact": bool(max_diff == 0.0),
        # per-chunk HBM bytes on the Pallas route (analytic, from the
        # built statics): what split round-trips vs what fused writes back.
        "split_chunk_ab_roundtrip_bytes": split_ab,
        "fused_chunk_writeback_bytes": fused_wb,
        "removed_bytes_per_chunk": split_ab - fused_wb,
        "stream_chunks_per_shard_per_iter": chunks_per_iter,
        "removed_bytes_per_iter_per_shard": removed_iter,
        "chunk_entities": ub.chunk_entities,
        "user_half_mode": ub.mode,
        "movie_half_mode": ds.movie_blocks.mode,
        "users": users, "movies": movies, "ratings": nnz, "rank": rank,
        "shards": s, "iterations": iters, "repeats": args.repeats,
        "layout": "tiled+all_gather", "fused_div": div,
        "backend": "cpu-virtual-mesh (relative timings; HBM bytes analytic)",
    }


def gather_ab_main(args) -> None:
    print(json.dumps(run_gather_ab(args)))


def _gather_ab_row() -> dict:
    """The default-run in-kernel-gather A/B row: a subprocess, because the
    virtual CPU mesh needs ``xla_force_host_platform_device_count`` set
    before jax initializes (main() has already initialized the backend)."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, __file__, "--gather-ab"],
        capture_output=True, text=True, timeout=3600,
    )
    if out.returncode != 0:
        tail = (out.stderr or out.stdout).strip()[-300:]
        return {"error": f"gather-ab subprocess failed: {tail}"}
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_gather_ab(args) -> dict:
    """Tentpole A/B: in-kernel neighbor gather (the Gram kernels DMA the
    indexed factor rows straight from the HBM-resident table) vs the XLA
    gather that materializes the [C, k] stream, on the
    ML-25M-proportioned synthetic shape scaled by ``--gather-div``,
    sharded over a virtual CPU mesh.

    Like ``--fused-ab``, absolute seconds on the CPU mesh are relative
    only (the emulation route runs the identical append-zero-row + gather
    + premultiply either way — which is exactly what makes the factor
    check BIT-EXACT here); the portable quantities are that equivalence
    and the analytic per-chunk HBM traffic the fused gather removes on
    the real Pallas route: the XLA schedule writes the gathered [C, k]
    stream to HBM and the kernel reads it straight back, so the fused
    gather retires 2·C·k·factor_bytes per chunk (the kernel's own table-
    row reads replace the gather engine's — they are the irreducible
    side both schedules pay).
    """
    import dataclasses as dc

    jax = _virtual_cpu_mesh(args.shards)
    import jax.numpy as jnp

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.ops.solve import init_factors_stats
    from cfk_tpu.parallel import spmd
    from cfk_tpu.parallel.mesh import make_mesh, shard_rows

    div = args.gather_div
    users, movies, nnz = 162_541 // div, 59_047 // div, 25_000_095 // div
    rank, s, iters = args.gather_rank, args.shards, args.iterations
    coo = synthetic_netflix_coo(users, movies, nnz, seed=args.seed)
    # Both halves in the dense-stream chunk scan (like --fused-ab): the
    # per-chunk gather is what this A/B toggles.
    ds = Dataset.from_coo(
        coo, layout="tiled", num_shards=s,
        chunk_elems=args.gather_chunk_elems,
        accum_max_entities=0, dense_stream=True,
    )
    mesh = make_mesh(s)
    base = ALSConfig(
        rank=rank, lam=0.05, num_iterations=iters, seed=0, layout="tiled",
        exchange="all_gather", solver="pallas", num_shards=s,
    )

    mtree, utree, step_kw = spmd.gathered_layout_trees(ds, base)
    mtree = shard_rows(mesh, mtree)
    utree = shard_rows(mesh, utree)

    def init_factors():
        key = jax.random.PRNGKey(0)
        u0 = jax.jit(
            init_factors_stats, static_argnames=("rank", "num_entities")
        )(
            key, jnp.asarray(ds.user_blocks.rating_sum),
            jnp.asarray(ds.user_blocks.count), rank=rank,
            num_entities=ds.user_blocks.num_entities,
        )
        m0 = jnp.zeros((ds.movie_blocks.padded_entities, rank), jnp.float32)
        return shard_rows(mesh, u0), shard_rows(mesh, m0)

    def timed(cfg):
        step = jax.jit(
            spmd.make_training_step(
                mesh, cfg, spmd.tree_specs(mtree), spmd.tree_specs(utree),
                **step_kw,
            )
        )
        u, m = init_factors()
        u, m = step(u, m, mtree, utree)  # compile + warm
        jax.block_until_ready((u, m))
        times = []
        for _ in range(args.repeats):
            t0 = time.time()
            for _ in range(iters):
                u, m = step(u, m, mtree, utree)
            jax.block_until_ready((u, m))
            times.append((time.time() - t0) / iters)
        return min(times), np.asarray(u, np.float32), np.asarray(
            m, np.float32
        )

    on_s, on_u, on_m = timed(dc.replace(base, in_kernel_gather=True))
    off_s, off_u, off_m = timed(dc.replace(base, in_kernel_gather=False))
    max_diff = float(
        max(np.abs(on_u - off_u).max(), np.abs(on_m - off_m).max())
    )
    # Analytic per-chunk HBM traffic removed on the real Pallas route:
    # the materialized stream's write + readback.  Factor bytes follow
    # the config dtype (f32 here; the production bf16 stack halves it).
    fb = 2 if base.dtype == "bfloat16" else 4
    cap = ds.user_blocks.chunk_cap
    removed_chunk = 2 * cap * rank * fb
    chunks_iter = ds.user_blocks.num_chunks + ds.movie_blocks.num_chunks
    return {
        "metric": "synthetic_ml25m_gather_ab_s_per_iteration",
        "value": round(on_s, 4),
        "unit": "s/iteration",
        # ≤ 1.0 = in-kernel gather no slower than the XLA gather.  On the
        # CPU emulation route both run the same XLA ops, so ~1.0 is the
        # honest expectation; the HBM win is Pallas-route-only.
        "vs_baseline": round(on_s / off_s, 4),
        "gather_fused_s_per_iter": round(on_s, 4),
        "gather_xla_s_per_iter": round(off_s, 4),
        "max_abs_factor_diff_fused_vs_xla": max_diff,
        "factors_bit_exact": bool(max_diff == 0.0),
        # the retired stream: HBM write + readback of [C, k] per chunk.
        "removed_bytes_per_chunk": removed_chunk,
        "stream_chunks_per_shard_per_iter": chunks_iter,
        "removed_bytes_per_iter_per_shard": removed_chunk * chunks_iter,
        "chunk_cap_entries": cap,
        "users": users, "movies": movies, "ratings": nnz, "rank": rank,
        "shards": s, "iterations": iters, "repeats": args.repeats,
        "layout": "tiled+all_gather", "gather_div": div,
        "backend": "cpu-virtual-mesh (relative timings; HBM bytes analytic)",
    }


def _quant_sweep(args, dtypes=("float32", "bfloat16", "int8")) -> dict:
    """Shared worker for --quant-ab / --quality-bytes: train the planted
    split once per table dtype (single device, tiled dense-stream — the
    at-scale stack) and report per-dtype wall time, held-out RMSE, factor
    delta vs the f32 run, and the analytic gather bytes per row.

    The f32 run is the exact pre-quantization path (bit-identical by the
    ``quant`` contract), so its RMSE is the quality baseline and its
    factors the delta reference."""
    import dataclasses as dc

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import planted_factor_coo
    from cfk_tpu.eval.metrics import mse_rmse_heldout
    from cfk_tpu.models.als import train_als
    from cfk_tpu.utils.roofline import table_gather_bytes_per_row

    div = args.quant_div
    users, movies, nnz = 162_541 // div, 59_047 // div, 25_000_095 // div
    rank = args.quant_rank
    coo, held = planted_factor_coo(
        users, movies, nnz, rank=rank, noise=args.planted_noise,
        heldout=max(nnz // 5, 2_000), seed=args.seed,
    )
    ds = Dataset.from_coo(
        coo, layout="tiled", chunk_elems=args.quant_chunk_elems,
        dense_stream=True, accum_max_entities=0,
    )
    base = ALSConfig(
        rank=rank, lam=0.05, num_iterations=args.iterations, seed=0,
        layout="tiled", solver="pallas",
    )
    per = {}
    f32_u = None
    for td in dtypes:
        cfg = dc.replace(base, table_dtype=td)
        model = train_als(ds, cfg)  # compile + warm
        model.user_factors.block_until_ready()
        t0 = time.time()
        model = train_als(ds, cfg)
        model.user_factors.block_until_ready()
        train_s = time.time() - t0
        _, rmse, ncells = mse_rmse_heldout(model, ds, held)
        uf = np.asarray(model.user_factors, np.float32)
        if f32_u is None:
            f32_u = uf
        per[td] = {
            "train_s": round(train_s, 4),
            "s_per_iteration": round(train_s / args.iterations, 4),
            "heldout_rmse": round(rmse, 5),
            "max_abs_factor_delta_vs_f32": round(
                float(np.abs(uf - f32_u).max()), 6
            ),
            "gather_bytes_per_row": table_gather_bytes_per_row(rank, td),
        }
    shape = {
        "users": users, "movies": movies, "ratings": nnz, "rank": rank,
        "iterations": args.iterations, "layout": "tiled+dense-stream",
        "planted_noise_floor": args.planted_noise,
        "heldout_cells": int(held.num_ratings),
    }
    return {"per_dtype": per, "shape": shape}


def run_quant_ab(args) -> dict:
    """Tentpole (b) A/B: quantized HBM gather tables (``ops.quant``) —
    f32 vs bf16 vs int8+scale, factor delta + held-out RMSE + the
    analytic gather bytes removed from the roofline floor.  CPU timings
    are relative only (the emulation route upcasts either way); the
    portable quantities are the quality contract (bf16 RMSE ≤ 1.01× f32,
    the recorded int8 ratio) and the bytes arithmetic (bf16 halves the
    f32 row, int8+scale quarters it at rank ≥ 32)."""
    sweep = _quant_sweep(args)
    per, shape = sweep["per_dtype"], sweep["shape"]
    f32 = per["float32"]
    row = {
        "metric": "planted_quant_table_ab",
        "value": per["bfloat16"]["heldout_rmse"],
        "unit": "rmse(bf16 table)",
        # ≤ 1.01 = the bf16-table quality contract on the planted split.
        "vs_baseline": round(
            per["bfloat16"]["heldout_rmse"] / f32["heldout_rmse"], 4
        ),
        "int8_rmse_vs_f32": round(
            per["int8"]["heldout_rmse"] / f32["heldout_rmse"], 4
        ),
        "bytes_removed_per_row_bf16": (
            f32["gather_bytes_per_row"]
            - per["bfloat16"]["gather_bytes_per_row"]
        ),
        "bytes_removed_per_row_int8": (
            f32["gather_bytes_per_row"] - per["int8"]["gather_bytes_per_row"]
        ),
        **{f"{td}_{k}": v for td, d in per.items() for k, v in d.items()},
        **shape,
        "backend": "cpu (relative timings; bytes analytic)",
    }
    return row


def run_quality_bytes(args) -> dict:
    """The RMSE-vs-table-dtype curve on the planted split: quality as a
    function of gather bytes per row — the measured side of the
    approximate-computing trade (arXiv 1808.03843)."""
    sweep = _quant_sweep(args)
    per, shape = sweep["per_dtype"], sweep["shape"]
    f32 = per["float32"]["heldout_rmse"]
    curve = [
        {
            "table_dtype": td,
            "gather_bytes_per_row": d["gather_bytes_per_row"],
            "heldout_rmse": d["heldout_rmse"],
            "rmse_vs_f32": round(d["heldout_rmse"] / f32, 4),
        }
        for td, d in per.items()
    ]
    return {
        "metric": "planted_quality_vs_table_bytes",
        "value": curve[-1]["rmse_vs_f32"],
        "unit": "rmse_ratio(int8)",
        "vs_baseline": curve[1]["rmse_vs_f32"],
        "curve": curve,
        **shape,
    }


def quant_ab_main(args) -> None:
    print(json.dumps(run_quant_ab(args)))


def quality_bytes_main(args) -> None:
    print(json.dumps(run_quality_bytes(args)))


def _quant_ab_row() -> dict:
    """Default-run quant A/B row — in-process (single device, no virtual
    mesh to pre-configure, unlike the sharded A/B rows)."""
    import argparse as _ap

    args = _ap.Namespace(
        quant_div=256, quant_rank=16, quant_chunk_elems=16_384,
        iterations=3, planted_noise=0.2, seed=0,
    )
    return run_quant_ab(args)


def health_ab_main(args) -> None:
    print(json.dumps(run_health_ab(args)))


def _health_ab_row() -> dict:
    """Default-run sentinel-overhead row, in the parent process (it computes
    on the default backend: a child could not share the chip)."""
    return run_health_ab(_build_parser().parse_args(["--health-ab"]))


def run_health_ab(args) -> dict:
    """Resilience A/B: the health sentinel's in-carry probe (isfinite +
    norm watchdogs folded into the fused fori_loop carry at
    ``health_check_every=1`` — the worst-case cadence) vs the plain loop,
    on the dense-stream tiled config.  The acceptance budget is < 2%
    s/iter overhead; factors must be bit-identical (the probe reads the
    carry, never writes it).
    """
    import jax
    import jax.numpy as jnp

    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models import als as als_mod

    div = args.health_div
    users, movies, nnz = 162_541 // div, 59_047 // div, 25_000_095 // div
    rank, iters = args.health_rank, args.iterations
    coo = synthetic_netflix_coo(users, movies, nnz, seed=args.seed)
    ds = Dataset.from_coo(
        coo, layout="tiled", chunk_elems=args.chunk_elems,
        dense_stream=True,
    )
    mblocks, ublocks, u_stats, layout_kw = als_mod._tiled_device_setup(ds)
    jax.block_until_ready((mblocks, ublocks))

    def timed(health_every):
        def run():
            out = als_mod._train_loop(
                jax.random.PRNGKey(0), mblocks, ublocks, u_stats,
                rank=rank, num_iterations=iters, lam=0.05,
                solve_chunk=None, dtype="float32", solver="cholesky",
                health_every=health_every, health_norm_limit=1e6,
                **layout_kw,
            )
            jax.block_until_ready(out)
            return out
        out = run()  # compile + warm
        times = []
        for _ in range(args.repeats):
            t0 = time.time()
            out = run()
            times.append((time.time() - t0) / iters)
        return min(times), np.asarray(out[0], np.float32)

    on_s, on_u = timed(1)
    off_s, off_u = timed(None)
    max_diff = float(np.abs(on_u - off_u).max())
    return {
        "metric": "synthetic_ml25m_health_sentinel_ab_s_per_iteration",
        "value": round(on_s, 4),
        "unit": "s/iteration",
        # the acceptance number: sentinel-on / sentinel-off s/iter.
        "vs_baseline": round(on_s / off_s, 4),
        "overhead_frac": round(on_s / off_s - 1.0, 4),
        "health_on_s_per_iter": round(on_s, 4),
        "health_off_s_per_iter": round(off_s, 4),
        "max_abs_factor_diff_health_vs_plain": max_diff,
        "factors_bit_exact": bool(max_diff == 0.0),
        "health_check_every": 1,
        "users": users, "movies": movies, "ratings": nnz, "rank": rank,
        "iterations": iters, "repeats": args.repeats,
        "layout": "tiled dense-stream, single device",
        "backend": jax.default_backend(),
    }


def ckpt_ab_main(args) -> None:
    print(json.dumps(run_ckpt_ab(args)))


def _ckpt_ab_row() -> dict:
    """Default-run checkpoint-writer A/B row, in the parent process (it
    computes on the default backend: a child could not share the chip)."""
    return run_ckpt_ab(_build_parser().parse_args(["--ckpt-ab"]))


def run_ckpt_ab(args) -> dict:
    """Preemption-tolerance A/B: the async checkpoint writer
    (``CheckpointManager.save_async`` — serialize+fsync+atomic-rename on a
    background thread) vs the synchronous writer, on the stepped trainer
    at per-iteration save cadence.  The acceptance contract: factors are
    BIT-EXACT across the axis (the async path writes the same bytes, just
    off the step loop's critical path), and the row records the per-save
    stall removed from the step loop (the disk work the device no longer
    idles behind).
    """
    import os
    import tempfile

    import numpy as np

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models.als import train_als
    from cfk_tpu.transport.checkpoint import CheckpointManager
    from cfk_tpu.utils.metrics import Metrics

    div = args.ckpt_div
    users, movies, nnz = 162_541 // div, 59_047 // div, 25_000_095 // div
    rank, iters = args.ckpt_rank, max(args.iterations, 6)
    coo = synthetic_netflix_coo(users, movies, nnz, seed=args.seed)
    ds = Dataset.from_coo(
        coo, layout="tiled", chunk_elems=args.chunk_elems,
    )
    cfg = ALSConfig(rank=rank, lam=0.05, num_iterations=iters, seed=0,
                    layout="tiled", solver="cholesky")

    def run(async_write):
        best = None
        for r in range(args.repeats):
            with tempfile.TemporaryDirectory() as d:
                mgr = CheckpointManager(d, async_write=async_write)
                metrics = Metrics()
                t0 = time.time()
                model = train_als(ds, cfg, checkpoint_manager=mgr,
                                  metrics=metrics)
                wall = time.time() - t0
                row = (
                    metrics.phases["checkpoint"],
                    metrics.phases["train"],
                    wall,
                    model.host_factors(),
                    int(metrics.counters["checkpoints"]),
                )
                if best is None or row[0] < best[0]:
                    best = row
        return best

    a_ckpt, a_train, a_wall, a_factors, saves = run(True)
    s_ckpt, s_train, s_wall, s_factors, _ = run(False)
    bit_exact = (
        np.array_equal(a_factors[0], s_factors[0])
        and np.array_equal(a_factors[1], s_factors[1])
    )
    return {
        "metric": "synthetic_ml25m_ckpt_ab_save_stall_s_per_save",
        # the headline: in-step-loop stall per save with the ASYNC writer
        "value": round(a_ckpt / max(saves, 1), 5),
        "unit": "s/save (in the step loop)",
        # ≤ 1.0 = async saves stall the step loop no more than sync; the
        # removed stall is the honest win (serialize+fsync+rename bytes
        # identical — bit_exact pins it).
        "vs_baseline": round(a_ckpt / s_ckpt, 4) if s_ckpt > 0 else 0.0,
        "sync_save_stall_s_per_save": round(s_ckpt / max(saves, 1), 5),
        "async_save_stall_s_per_save": round(a_ckpt / max(saves, 1), 5),
        "save_stall_removed_s_per_save": round(
            (s_ckpt - a_ckpt) / max(saves, 1), 5
        ),
        "save_stall_removed_s_per_iter": round((s_ckpt - a_ckpt) / iters, 5),
        "sync_wall_s": round(s_wall, 3),
        "async_wall_s": round(a_wall, 3),
        "saves_per_run": saves,
        "factors_bit_exact": bool(bit_exact),
        "users": users, "movies": movies, "ratings": nnz, "rank": rank,
        "iterations": iters, "repeats": args.repeats,
        "layout": "tiled, single device, checkpoint_every=1",
    }


def foldin_main(args) -> None:
    print(json.dumps(run_foldin(args)))


def _foldin_row() -> dict:
    """Default-run streaming fold-in row, in the parent process (it computes
    on the default backend: a child could not share the chip)."""
    return run_foldin(_build_parser().parse_args(["--foldin"]))


def run_foldin(args) -> dict:
    """Streaming fold-in row (ISSUE 6): updates/sec absorbed by the
    exactly-once stream loop, and fold-in quality vs a warm full retrain
    on a held-out TIME split of the bench dataset.

    The bench dataset is planted-factor (so held-out RMSE measures real
    recovery, not noise-fitting); its generation order is the stream's
    logical time.  The prefix trains the base model, the suffix arrives as
    streaming rating updates folded in by ``StreamSession`` (one restricted
    half-iteration per micro-batch, factors+cursor committed atomically
    per batch — the full durability path, not a math-only shortcut), and
    held-out cells drawn from the same planted model score three states:
    base (stale), fold-in (fresh users, stale movies), and a warm full
    retrain seeded from the folded factors (both sides fresh — the quality
    ceiling).  The acceptance contract is fold-in RMSE within 2% of the
    retrain (``foldin_rmse_over_retrain`` ≤ 1.02): the stream suffix is a
    small fraction of the corpus, so near-optimal movie factors should
    cost fold-in almost nothing — if they don't, the fold-in math is
    wrong, not just slow.
    """
    import tempfile

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset, RatingsCOO
    from cfk_tpu.data.synthetic import planted_factor_coo
    from cfk_tpu.eval.metrics import mse_rmse_heldout
    from cfk_tpu.models.als import train_als
    from cfk_tpu.streaming import StreamConfig, StreamProducer, StreamSession
    from cfk_tpu.transport import InMemoryBroker
    from cfk_tpu.transport.checkpoint import CheckpointManager
    from cfk_tpu.utils.metrics import Metrics

    div = args.foldin_div
    users, movies, nnz = 162_541 // div, 59_047 // div, 25_000_095 // div
    rank = args.foldin_rank
    iters = max(args.iterations, 8)  # base must be near-converged: the
    # retrain's extra iterations must measure the stream info, not
    # leftover base convergence
    coo, held = planted_factor_coo(
        users, movies, nnz, rank=rank, noise=args.planted_noise,
        heldout=max(nnz // 5, 10_000), seed=args.seed,
    )
    stream_n = min(args.foldin_updates, nnz // 4)
    base_coo = RatingsCOO(
        movie_raw=coo.movie_raw[:-stream_n],
        user_raw=coo.user_raw[:-stream_n],
        rating=coo.rating[:-stream_n],
    )
    ds = Dataset.from_coo(base_coo, layout="tiled",
                          chunk_elems=args.chunk_elems)
    cfg = ALSConfig(rank=rank, lam=0.05, num_iterations=iters, seed=0,
                    layout="tiled", solver="cholesky",
                    health_check_every=1)
    t0 = time.time()
    base_model = train_als(ds, cfg)
    base_train_s = time.time() - t0
    broker = InMemoryBroker()
    prod = StreamProducer(broker)
    prod.send_many(
        coo.user_raw[-stream_n:], coo.movie_raw[-stream_n:],
        coo.rating[-stream_n:],
    )
    metrics = Metrics()
    with tempfile.TemporaryDirectory() as d:
        sess = StreamSession(
            ds, cfg, broker, CheckpointManager(d, async_write=True),
            # padded fold-in, explicitly: the row's label always said so,
            # but foldin_layout='auto' resolved TILED off the tiled base
            # config — and the padded rectangle is the micro-batch
            # default the prewarm grid covers (ISSUE 13).
            stream=StreamConfig(batch_records=args.foldin_batch_records,
                                foldin_layout="padded"),
            base_model=base_model, metrics=metrics,
        )
        # Warm-start columns (ISSUE 13): trace the fold-in pow2 bucket
        # grid up front, then time the FIRST real micro-batch separately
        # — its trace count must be 0 (the ROADMAP-measured "per-batch
        # jit re-trace dominates" bound, paid at startup instead of
        # against the stream's first updates).
        from cfk_tpu.streaming.foldin import trace_count as _fold_traces

        warm = sess.prewarm()
        traces0 = _fold_traces()
        t0 = time.time()
        sess.step()
        first_batch_s = time.time() - t0
        first_batch_traces = _fold_traces() - traces0
        t0 = time.time()
        sess.run()
        absorb_s = time.time() - t0 + first_batch_s
        drain_traces = _fold_traces() - traces0
        _, rmse_base, _ = mse_rmse_heldout(base_model, ds, held)
        _, rmse_fold, held_cells = mse_rmse_heldout(sess.model(), ds, held)
        t0 = time.time()
        sess.retrain()
        retrain_s = time.time() - t0
        _, rmse_retrain, _ = mse_rmse_heldout(sess.model(), ds, held)
        # retrain() commits through the async writer; drain before the
        # tempdir teardown races the pending write
        from cfk_tpu.resilience.loop import drain_checkpoints

        drain_checkpoints(sess.manager)
    ratio = rmse_fold / rmse_retrain
    return {
        "metric": "synthetic_ml25m_foldin_updates_per_s_absorbed",
        "value": round(stream_n / absorb_s, 1),
        "unit": "updates/s (stream drain incl. per-batch atomic commits)",
        # fold-in RMSE over the warm-retrain RMSE; ≤ 1.02 is the contract
        "vs_baseline": round(ratio, 4),
        "foldin_rmse": round(rmse_fold, 4),
        "retrain_rmse": round(rmse_retrain, 4),
        "base_rmse": round(rmse_base, 4),
        "foldin_rmse_over_retrain": round(ratio, 4),
        "within_2pct_of_retrain": bool(ratio <= 1.02),
        "heldout_cells": held_cells,
        "updates": stream_n,
        "updates_fresh": int(metrics.counters.get("updates_fresh", 0)),
        "batches": int(sess.stream_step),
        "batch_records": args.foldin_batch_records,
        "absorb_wall_s": round(absorb_s, 3),
        "foldin_solve_s": round(metrics.phases.get("foldin_solve", 0.0), 3),
        "commit_s": round(metrics.phases.get("commit", 0.0), 3),
        "stage_s": round(metrics.phases.get("stage", 0.0), 3),
        # Warm-start columns (ISSUE 13): prewarm cost, the first real
        # batch's wall + NEW TRACES (0 = the prewarm contract held), and
        # the whole drain's trace count.
        "prewarm_s": warm.get("prewarm_s"),
        "prewarm_programs": warm.get("programs"),
        "time_to_first_batch_s": round(first_batch_s, 4),
        "first_batch_new_traces": int(first_batch_traces),
        "trace_count": int(drain_traces),
        "base_train_s": round(base_train_s, 3),
        "retrain_s": round(retrain_s, 3),
        "planted_noise_floor": args.planted_noise,
        "users": users, "movies": movies, "ratings": nnz, "rank": rank,
        "base_iterations": iters,
        "layout": "tiled base, padded fold-in, InMemoryBroker",
    }


def serve_main(args) -> None:
    print(json.dumps(run_serve(args)))


def _serve_row() -> dict:
    """Default-run top-K serving row (subprocess: the shard sweep needs
    the virtual-mesh flag before jax init, like the other A/B rows)."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, __file__, "--serve"],
        capture_output=True, text=True, timeout=3600,
    )
    if out.returncode != 0:
        tail = (out.stderr or out.stdout).strip()[-300:]
        return {"error": f"serve subprocess failed: {tail}"}
    return json.loads(out.stdout.strip().splitlines()[-1])


def _serve_factors(args, rng):
    """Synthetic factor tables at the requested shape.

    Mixture-of-Gaussians ITEM factors with user vectors aligned to the
    components under a skewed popularity law — trained CF factor tables
    cluster (the IVF premise the two-stage index banks on), and the
    two_stage rows' MEASURED batch-union width depends on that structure,
    so i.i.d. factors would misstate the one cost axis this bench exists
    to record.  Exact-scan cost stays value-independent either way."""
    import numpy as np

    k = args.serve_rank
    ncomp = min(64, max(args.serve_movies // 16, 1))
    comp = rng.standard_normal((ncomp, k)).astype(np.float32) * 0.3
    m = (comp[rng.integers(0, ncomp, size=args.serve_movies)]
         + rng.standard_normal((args.serve_movies, k),
                               dtype=np.float32) * 0.05)
    w = 1.0 / np.arange(1, ncomp + 1, dtype=np.float64) ** 1.2
    # sorted draw: zipf traffic hammers LOW user rows (loadgen), and the
    # heavy components sort first, so the hot rows share components —
    # a coalesced batch's probed clusters then OVERLAP, the same
    # popularity-skew premise the hot-row device cache (PR 14) banks on
    u_comp = np.sort(rng.choice(ncomp, size=args.serve_users,
                                p=w / w.sum()))
    u = (comp[u_comp]
         + rng.standard_normal((args.serve_users, k),
                               dtype=np.float32) * 0.05)
    return u, m


def _serve_engine(args, jnp_users, rng, *, table_dtype, shards, mesh,
                  plan=None, serve_mode="exact"):
    """Engine + synthetic serving state at the requested shape.

    Factors come from ``_serve_factors`` (clustered — see its docstring);
    the seen-CSR is built only for the loadgen's user pool (the rows
    traffic will touch), at the ML-25M mean ratings/user, so exclusion
    masking is exercised at realistic widths without materializing 25M
    seen cells.
    """
    from cfk_tpu.serving.engine import ServeEngine

    u, m = _serve_factors(args, rng)
    seen, indptr = _serve_seen_csr(args, jnp_users, rng)
    return ServeEngine(
        u, m, num_users=args.serve_users, num_movies=args.serve_movies,
        seen_movies=seen, seen_indptr=indptr, table_dtype=table_dtype,
        tile_m=args.serve_tile_m, mesh=mesh, plan=plan,
        serve_mode=serve_mode,
        clusters=args.serve_clusters or None,
        probe_clusters=args.serve_probe_clusters or None,
    )


def _serve_seen_csr(args, jnp_users, rng):
    """Seen-CSR for the loadgen pool at the ML-25M mean ratings/user: the
    rows traffic will touch get realistic exclusion widths without
    materializing 25M seen cells."""
    import numpy as np

    mean_seen = max(1, args.serve_nnz // args.serve_users)
    pool = np.unique(jnp_users)
    counts = np.zeros(args.serve_users, np.int64)
    counts[pool] = rng.poisson(mean_seen, pool.shape[0]).clip(1)
    indptr = np.zeros(args.serve_users + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    seen = np.empty(indptr[-1], np.int32)
    for row in pool:
        lo, hi = indptr[row], indptr[row + 1]
        seen[lo:hi] = np.sort(rng.choice(
            args.serve_movies, size=hi - lo, replace=False
        )).astype(np.int32)
    return seen, indptr


def serve_fleet_main(args) -> None:
    print(json.dumps(run_serve_fleet(args)))


def _serve_fleet_row() -> dict:
    """Default-run replicated-fleet serving row, in the parent process (its
    replicas compute on the default backend: a child could not share the
    chip)."""
    return run_serve_fleet(_build_parser().parse_args(["--serve-fleet"]))


def run_serve_fleet(args) -> dict:
    """Replicated serving fleet bench (ISSUE 18 / ROADMAP item 3):
    goodput QPS scaling and admission shed rate vs replica count at the
    ML-25M shape.

    Every fleet size drives the SAME shaped open loop, deliberately
    overloaded — ``--serve-fleet-load`` (default 1.25) x the fleet's
    measured aggregate capacity — through the full replicated path:
    user-keyed routing into N request-log partitions, per-replica
    admission control, engine, response log.  Overload is the point:
    each replica's admission queue is bounded at one measured batch, so
    goodput (engine-served responses/s) tracks fleet capacity while the
    excess is shed as explicit retriable rejections instead of queue
    bloat — the row records both axes.  Latency quantiles are over ALL
    responses (served + rejected), the client-observed truth under
    overload; replica threads score concurrently (jax releases the GIL
    in compute), so the scaling column measures the one-host ceiling.
    """
    import numpy as np

    from cfk_tpu.serving import (
        ServeClient,
        ServeFleet,
        run_open_loop,
        zipf_user_rows,
    )
    from cfk_tpu.serving.engine import ServeEngine
    from cfk_tpu.transport import InMemoryBroker

    k = args.serve_k
    batch = args.serve_fleet_batch
    nreq = args.serve_fleet_requests
    replica_list = [int(n) for n in args.serve_fleet_replicas.split(",")
                    if n]
    traffic = zipf_user_rows(args.serve_users, nreq, seed=args.seed + 3)
    pool = np.concatenate([
        zipf_user_rows(args.serve_users, 4096, seed=args.seed + 1),
        traffic,
    ])
    rng = np.random.default_rng(args.seed + 2)
    u, m = _serve_factors(args, rng)
    seen, indptr = _serve_seen_csr(args, pool, rng)
    engines: dict = {}

    def factory(i: int):
        # full-table copies per replica (the one-host stand-in for
        # per-host meshes); cached across fleet sizes so each replica
        # engine prewarms exactly once for the whole sweep
        if i not in engines:
            eng = ServeEngine(
                u, m, num_users=args.serve_users,
                num_movies=args.serve_movies, seen_movies=seen,
                seen_indptr=indptr, tile_m=args.serve_tile_m,
            )
            eng.prewarm(k, max_batch=batch, user_rows=pool)
            engines[i] = eng
        return engines[i]

    # Per-replica capacity: steady-state direct-call batch time (min of
    # repeats) — sizes the admission queue AND the offered rate.
    eng0 = factory(0)
    qrows = pool[:batch]
    eng0.topk(qrows, k)
    times = []
    for _ in range(args.repeats):
        t0 = time.time()
        eng0.topk(qrows, k)
        times.append(time.time() - t0)
    capacity = batch / min(times)
    rows = []
    for n in replica_list:
        broker = InMemoryBroker()
        # Poll depth 4x the admission bound: the replica DRAINS backlog
        # every step and sheds what it cannot admit — the queue stays
        # bounded under overload instead of growing in the log.
        fleet = ServeFleet(
            factory, broker, replicas=n, max_batch=4 * batch,
            admission_max_queue=batch,
        )
        fleet.seed_store(u, m, num_users=args.serve_users)
        rate = max(args.serve_fleet_load * capacity * n, 1.0)
        with fleet:
            client = ServeClient(broker, route_by_user=True)
            c0 = fleet.counters()
            report = run_open_loop(
                client, rate_qps=rate, num_requests=nreq,
                user_rows=traffic, k=k,
            )
            c1 = fleet.counters()
        served = c1["served"] - c0["served"]
        shed = c1["shed"] - c0["shed"]
        batches = c1["batches"] - c0["batches"]
        row = {
            "replicas": n,
            "batch": batch,
            "k": k,
            "capacity_per_replica_qps": round(capacity, 1),
            "offered_qps": round(rate, 1),
            **report.as_row(),
            # loadgen can't see the fleet's servers — batch accounting
            # comes from the fleet counters instead
            "batches": int(batches),
            "mean_batch": round(served / batches, 1) if batches else 0.0,
            "goodput_qps": round(served / report.wall_s, 1),
            "served": int(served),
            "shed": int(shed),
            "shed_rate": round(shed / max(served + shed, 1), 4),
            "users": args.serve_users, "movies": args.serve_movies,
            "rank": args.serve_rank, "tile_m": args.serve_tile_m,
        }
        print("# serve_fleet: " + json.dumps(row), flush=True)
        rows.append(row)
    base = next((r for r in rows if r["replicas"] == 1), rows[0])
    best = max(rows, key=lambda r: r["goodput_qps"])
    return {
        "metric": "serve_fleet_ml25m",
        "unit": "goodput_qps",
        "value": best["goodput_qps"],
        "replicas": best["replicas"],
        "scaling_vs_1": round(
            best["goodput_qps"] / max(base["goodput_qps"], 1e-9), 2),
        "shed_rate": best["shed_rate"],
        "capacity_per_replica_qps": round(capacity, 1),
        "rows": rows,
    }


def run_serve(args) -> dict:
    """Top-K serving at ML-25M scale (ISSUE 8 / ROADMAP item 1): QPS and
    p50/p99 latency across batch size, table dtype, and shard count.

    Each row: (1) the engine's steady-state batch time at that config
    (direct ``topk`` calls, min over repeats — the ``vs_roofline``
    denominator comes from ``serve_batch_cost``'s table-scan floor), and
    (2) an open-loop run through the full request path (InMemory log →
    ``RecommendServer`` batch coalescing → engine → response log) at 70%
    of the measured capacity, reporting achieved QPS and p50/p99 — the
    repo's first latency-axis bench rows.  Multi-shard rows run the
    item-sharded path on a virtual CPU mesh (equality with single-shard
    is pinned by tier-1 tests; rows here measure the merge overhead).

    ISSUE 16 adds the serve-mode axis: two_stage rows run the clustered
    centroid-probe → shortlist-rescore path and EVERY row now records
    measured ``recall_at_k`` (vs the same engine's bit-exact scan;
    exact rows are 1.0 by construction) and ``bytes_scanned_per_batch``
    for the EXECUTED mode (the two_stage figure uses the REAL batch-union
    shortlist width, not the closed-form expectation), with
    ``vs_roofline`` against that mode's own floor.  The summary carries
    the headline A/B: the bytes cut of the best two_stage row over its
    exact twin at the same (batch, dtype), with its recall.
    """
    import numpy as np

    shard_list = [int(s) for s in args.serve_shards.split(",") if s]
    jx = _virtual_cpu_mesh(max(max(shard_list), 1))
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.serving import (
        RecommendServer,
        ServeClient,
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

    rng = np.random.default_rng(args.seed)
    # ONE user pool feeds the seen-CSR build, the warm-up/calibration
    # batches, AND the open-loop traffic — traffic rows outside the CSR
    # pool would score with empty exclusion masks and flatter the row.
    traffic = zipf_user_rows(
        args.serve_users, args.serve_requests, seed=args.seed + 3
    )
    pool = np.concatenate([
        zipf_user_rows(args.serve_users, 4096, seed=args.seed + 1),
        traffic,
    ])
    batch_list = [int(b) for b in args.serve_batches.split(",") if b]
    dtype_list = [d for d in args.serve_dtypes.split(",") if d]
    mode_list = [m for m in args.serve_modes.split(",") if m]
    sweeps = []
    for mode in mode_list:
        sweeps += [(b, "float32", 1, mode) for b in batch_list]
        sweeps += [(batch_list[-1], d, 1, mode) for d in dtype_list
                   if d != "float32"]
        if mode == "exact":
            # two_stage rescores its (small) shortlist on one device —
            # the shard axis partitions the full scan, so it is an
            # exact-mode axis only
            sweeps += [(batch_list[-1], "float32", s, mode)
                       for s in shard_list if s > 1]
    rows = []
    engines: dict = {}
    prewarms: dict = {}
    for batch, td, shards, mode in sweeps:
        key = (td, shards, mode)
        if key not in engines:
            mesh = make_mesh(shards) if shards > 1 else None
            engines[key] = _serve_engine(
                args, pool, np.random.default_rng(args.seed + 2),
                table_dtype=td, shards=shards, mesh=mesh, serve_mode=mode,
            )
            # Warm-start (ISSUE 13): trace/compile the pow2 batch-bucket
            # set before traffic — the per-row first batch then shows
            # its cold wall + ZERO new traces (single-device engines;
            # the sharded jit has its own cache and reads 0 either way).
            prewarms[key] = engines[key].prewarm(
                args.serve_k, max_batch=max(batch_list), user_rows=pool,
            )
        eng = engines[key]
        qrows = pool[:batch]
        tr0 = eng.trace_count
        t0 = time.time()
        eng.topk(qrows, args.serve_k)  # first real batch (post-prewarm)
        first_batch_s = time.time() - t0
        first_batch_traces = eng.trace_count - tr0
        times = []
        for _ in range(args.repeats):
            t0 = time.time()
            eng.topk(qrows, args.serve_k)
            times.append(time.time() - t0)
        batch_s = min(times)
        capacity = batch / batch_s
        broker = InMemoryBroker()
        ensure_serve_topics(broker)
        server = RecommendServer(eng, broker, max_batch=batch)
        client = ServeClient(broker)
        warm_serve_programs(client, server, pool, args.serve_k, batch)
        rate = max(capacity * 0.7, 1.0)
        report = run_open_loop(
            client, rate_qps=rate, num_requests=args.serve_requests,
            user_rows=traffic,
            k=args.serve_k, server=server, drive_server=True,
        )
        # recall vs the SAME engine's bit-exact scan (force_exact skips
        # the candidate stage but keeps table/masks/jit), and the scan
        # accounting of the executed mode — both first-class per row
        from cfk_tpu.serving import recall_at_k

        _, ids = eng.topk(qrows, args.serve_k)
        scan = dict(eng.last_scan)
        if mode == "two_stage" and scan.get("serve_mode") == "two_stage":
            _, oracle = eng.topk(qrows, args.serve_k, force_exact=True)
            recall = float(recall_at_k(ids, oracle))
            cost = serve_batch_cost(
                args.serve_movies, args.serve_rank, batch, args.serve_k,
                table_dtype=td, serve_mode="two_stage",
                clusters=scan["clusters"],
                probe_clusters=scan["probe_clusters"],
                shortlist_rows=scan["shortlist_rows_padded"],
            )
        else:
            recall = 1.0
            cost = serve_batch_cost(
                args.serve_movies, args.serve_rank, batch, args.serve_k,
                table_dtype=td, m_pad=eng.table_rows,
            )
        row = {
            "batch": batch,
            "table_dtype": td,
            "shards": shards,
            "k": args.serve_k,
            "serve_mode": scan.get("serve_mode", mode),
            "recall_at_k": round(recall, 4),
            "batch_s": round(batch_s, 5),
            "capacity_qps": round(capacity, 1),
            **report.as_row(),
            **serve_roofline_row(cost, batch_s, td,
                                 device_kind=this_device_kind()),
            **{kk: scan[kk] for kk in ("clusters", "probe_clusters",
                                       "shortlist_rows") if kk in scan},
            "users": args.serve_users, "movies": args.serve_movies,
            "rank": args.serve_rank, "tile_m": args.serve_tile_m,
            "backend": jx.default_backend(),
            # Warm-start columns (ISSUE 13).
            "prewarm_s": prewarms[key].get("prewarm_s"),
            "prewarm_programs": prewarms[key].get("programs"),
            "time_to_first_batch_s": round(first_batch_s, 5),
            "first_batch_new_traces": int(first_batch_traces),
        }
        print("# serve: " + json.dumps(row), flush=True)
        rows.append(row)
    best = max(rows, key=lambda r: r["qps"])
    out = {
        "metric": "serve_topk_ml25m",
        "unit": "qps",
        "value": best["qps"],
        "p50_ms": best["p50_ms"],
        "p99_ms": best["p99_ms"],
        "best_batch": best["batch"],
        "vs_roofline": best.get("vs_roofline", "not measured"),
        "rows": rows,
    }
    # Headline two_stage-vs-exact pair (ISSUE 16 acceptance): the bytes
    # cut at the matching (batch, dtype, shards) exact row, maximized
    # over two_stage rows, with the recall that bought it.
    exact_by_key = {(r["batch"], r["table_dtype"], r["shards"]): r
                    for r in rows if r["serve_mode"] == "exact"}
    ab = None
    for r in rows:
        if r["serve_mode"] != "two_stage":
            continue
        ex = exact_by_key.get((r["batch"], r["table_dtype"], r["shards"]))
        if ex is None:
            continue
        cut = ex["bytes_scanned_per_batch"] / max(
            r["bytes_scanned_per_batch"], 1)
        if ab is None or cut > ab["bytes_cut"]:
            ab = {"bytes_cut": round(cut, 2),
                  "recall_at_k": r["recall_at_k"],
                  "batch": r["batch"], "table_dtype": r["table_dtype"],
                  "two_stage_qps": r["qps"], "exact_qps": ex["qps"]}
    if ab is not None:
        out["bytes_cut"] = ab["bytes_cut"]
        out["recall_at_k"] = ab["recall_at_k"]
        out["serve_ab"] = ab
    return out


def _plan_ab_args():
    """The default-main --plan-ab arg surface (parser defaults)."""
    import argparse

    return argparse.Namespace(
        seed=0, repeats=3, serve_users=162_541, serve_movies=59_047,
        serve_nnz=25_000_095, serve_rank=128, serve_k=100,
        serve_tile_m=2048,
    )


def plan_ab_main(args) -> None:
    print(json.dumps(run_plan_ab(args)))


def run_plan_ab(args) -> dict:
    """ISSUE 9 acceptance row: the execution planner's serve plan vs the
    static pre-planner defaults, measured.

    The resolver is given the ML-25M serve shape (rank 128, K=100 — a
    non-default shape) with table dtype and batch quantum FREE; the
    table-scan byte model picks the quantized table and a large quantum.
    Both configurations are then measured on THIS host as per-request
    service time (batch time / batch), so the row shows the resolver
    choosing a measurably cheaper plan than the static defaults (f32
    table, the engine's default batch quantum of 8) with the provenance
    — chosen plan + model-estimated + measured cost — in the row.  The
    measured-vs-estimated pair per config is the model-calibration
    record ROADMAP item 5 asks for.
    """
    import numpy as np

    from cfk_tpu.plan import DeviceSpec, ProblemShape, plan_cost
    from cfk_tpu.serving import plan_for_serving, zipf_user_rows

    rng = np.random.default_rng(args.seed)
    pool = zipf_user_rows(args.serve_users, 4096, seed=args.seed + 1)
    ep, prov = plan_for_serving(
        args.serve_users, args.serve_movies, args.serve_rank,
        k_top=args.serve_k,
    )
    device = DeviceSpec.detect()
    shape = ProblemShape(
        num_users=args.serve_users, num_movies=args.serve_movies,
        nnz=max(args.serve_users, args.serve_movies),
        rank=args.serve_rank, kind="serve", serve_k=args.serve_k,
    )

    def measure(table_dtype, batch, plan=None):
        # The plan arm's engine CONSUMES the plan (ServeEngine(plan=...)
        # — batch quantum + movie tile rows + dtype from the plan), so
        # the measured configuration is the resolved plan, not a
        # lookalike; the static arm keeps the engine's own defaults.
        eng = _serve_engine(
            args, pool, np.random.default_rng(args.seed + 2),
            table_dtype=table_dtype, shards=1, mesh=None, plan=plan,
        )
        qrows = pool[:batch]
        eng.topk(qrows, args.serve_k)  # warmup / compile
        times = []
        for _ in range(args.repeats):
            t0 = time.time()
            eng.topk(qrows, args.serve_k)
            times.append(time.time() - t0)
        return min(times) / batch  # per request-slot

    import dataclasses as _dc

    static_plan = _dc.replace(
        ep, table_dtype="float32", serve_batch_quantum=8,
    )
    static_s = measure("float32", 8)
    plan_s = measure(ep.table_dtype, ep.serve_batch_quantum, plan=ep)
    prov.measured_s = plan_s
    row = {
        "metric": "plan_ab_serve_per_request_s",
        "unit": "s/request",
        "value": round(plan_s, 6),
        "static_per_request_s": round(static_s, 6),
        "plan_speedup_vs_static": round(static_s / max(plan_s, 1e-12), 2),
        "static_plan": static_plan.summary(),
        "static_est_s": round(
            plan_cost(shape, device, static_plan).seconds, 6
        ),
        "plan_est_s_measured_ratio": round(
            plan_s / max(prov.est_cost_s or plan_s, 1e-12), 2
        ),
        **prov.as_row(),
        "users": args.serve_users, "movies": args.serve_movies,
        "rank": args.serve_rank, "k": args.serve_k,
        "static_tile_m": args.serve_tile_m,
        "plan_tile_m": ep.serve_tile_m,
    }
    return row


def compare_exchange_main(args) -> None:
    """The reference's headline experiment (its README.md:216-224): the
    block-to-block join (ring) vs the all-to-all join (all_gather), same
    dataset, on an 8-virtual-device CPU mesh.

    One real chip is attached in this environment, so the multi-shard
    collectives run on the virtual mesh: wall-clock is RELATIVE (CPU
    backend), correctness (ring == all_gather) is exact, and per-device
    memory is analytic from the actual array shapes — the quantity that
    decides the trade on real hardware.  See BASELINE.md for the recorded
    table and what real multi-chip would change.
    """
    _virtual_cpu_mesh(args.shards)
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models.als import train_als
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import train_als_sharded

    s = args.shards
    users, movies, nnz = args.users, args.movies, args.nnz
    coo = synthetic_netflix_coo(users, movies, nnz, seed=args.seed)
    mesh = make_mesh(s)
    k = args.rank
    base = dict(rank=k, lam=0.05, num_iterations=args.iterations, seed=0,
                layout="tiled", solver="cholesky", num_shards=s)
    ref = train_als(
        Dataset.from_coo(coo, layout="tiled"),
        ALSConfig(**{**base, "num_shards": 1}),
    ).predict_dense()

    def run(exchange):
        ds = Dataset.from_coo(coo, layout="tiled", num_shards=s,
                              ring=exchange == "ring")
        cfg = ALSConfig(**base, exchange=exchange)
        t0 = time.time()
        model = train_als_sharded(ds, cfg, mesh)
        model.user_factors.block_until_ready()
        warm = time.time() - t0
        times = []
        for _ in range(args.repeats):
            t0 = time.time()
            model = train_als_sharded(ds, cfg, mesh)
            model.user_factors.block_until_ready()
            times.append(time.time() - t0)
        err = float(np.abs(model.predict_dense() - ref).max())
        # Analytic per-device bytes for the user half (the big side): the
        # fixed-side factors each device must hold, PLUS the per-entity
        # accumulator when the half actually runs in accum mode — which the
        # all_gather path may too (small entity counts); charging it to
        # ring alone would inflate the ratio.
        fb = 2 if cfg.dtype == "bfloat16" else 4
        f_pad = ds.movie_blocks.padded_entities
        e_local = ds.user_blocks.local_entities
        acc = (e_local + 1) * (k * k + k) * 4
        if exchange == "all_gather":
            exch_bytes = f_pad * k * fb  # full fixed table per device
            if ds.user_blocks.mode == "accum":
                exch_bytes += acc
        else:
            exch_bytes = (f_pad // s) * k * fb + acc
        return min(times), warm, err, exch_bytes

    ag_s, ag_warm, ag_err, ag_mem = run("all_gather")
    rg_s, rg_warm, rg_err, rg_mem = run("ring")
    n = args.iterations
    print(json.dumps({
        "metric": "exchange_compare_ring_over_allgather_time",
        "value": round(rg_s / ag_s, 4),
        "unit": "ratio (virtual 8-dev CPU mesh; relative only)",
        "vs_baseline": round(rg_s / ag_s, 4),
        "allgather_s_per_iter": round(ag_s / n, 4),
        "ring_s_per_iter": round(rg_s / n, 4),
        "allgather_maxerr_vs_1way": ag_err,
        "ring_maxerr_vs_1way": rg_err,
        "allgather_exchange_bytes_per_device": ag_mem,
        "ring_exchange_bytes_per_device": rg_mem,
        "ring_over_allgather_memory": round(rg_mem / ag_mem, 3),
        "users": users, "movies": movies, "ratings": nnz,
        "rank": k, "shards": s,
    }))


def _build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--scale", action="store_true",
                        help="synthetic Netflix-Prize-shaped throughput bench")
    parser.add_argument("--full", action="store_true",
                        help="real Netflix Prize dimensions (480k x 17.7k x 100M)")
    parser.add_argument("--ials", action="store_true",
                        help="implicit-feedback iALS at MovieLens-25M "
                        "dimensions (162k x 59k x 25M, rank 128)")
    parser.add_argument("--ialspp", action="store_true",
                        help="same shape via iALS++ subspace optimization "
                        "(bucketed layout, --block-size coordinate blocks)")
    parser.add_argument("--alspp", action="store_true",
                        help="explicit model via als++ subspace optimization "
                        "(bucketed layout)")
    parser.add_argument("--block-size", type=int, default=32)
    parser.add_argument("--sweeps", type=int, default=1)
    parser.add_argument("--users", type=int, default=48_000)
    parser.add_argument("--movies", type=int, default=1_777)
    parser.add_argument("--nnz", type=int, default=10_000_000)
    parser.add_argument("--rank", type=int, default=64)
    parser.add_argument("--iterations", type=int, default=3)
    parser.add_argument("--repeats", type=int, default=3,
                        help="timed (upload, train) pairs; min of each is "
                        "reported (run-to-run variance)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--layout",
                        choices=["padded", "bucketed", "segment", "tiled"],
                        default="tiled")
    parser.add_argument("--dtype", choices=["float32", "bfloat16"],
                        default="bfloat16",
                        help="factor storage/exchange dtype for the scale "
                        "bench; Gram accumulation and solves are float32 "
                        "either way (medium-config RMSE is identical to "
                        "1e-4: 0.758223 bf16 vs 0.758264 f32)")
    parser.add_argument("--chunk-elems", type=int, default=524_288,
                        help="entries per tiled/segment chunk; 512k beat 1M on-chip\n                        (segment accumulators fit VMEM)")
    parser.add_argument("--lam", type=float, default=0.05,
                        help="explicit-model regularization for the scale "
                        "bench (ALS-WR lambda*n semantics; planted runs "
                        "want ~0.002 — the lambda*n ridge must stay below "
                        "the O(1)-scale planted Gram)")
    parser.add_argument("--planted", action="store_true",
                        help="generate ratings from known planted factors + "
                        "noise and report held-out recovery RMSE vs the "
                        "noise floor (quality validation at unfetchable-"
                        "corpus shapes)")
    parser.add_argument("--planted-noise", type=float, default=0.2)
    parser.add_argument("--compare-exchange", action="store_true",
                        help="ring (block-to-block join) vs all_gather "
                        "(all-to-all join) on an 8-virtual-device CPU mesh "
                        "— the reference's README.md:216-224 experiment")
    parser.add_argument("--shards", type=int, default=8)
    parser.add_argument("--fused-ab", action="store_true",
                        help="fused Gram+solve epilogue vs split "
                        "Gram→HBM→solve A/B + per-chunk HBM traffic "
                        "estimate on a virtual CPU mesh (ML-25M shape / "
                        "--fused-div)")
    parser.add_argument("--fused-div", type=int, default=128,
                        help="ML-25M shape divisor for --fused-ab (the "
                        "default keeps the CPU-mesh A/B under a few "
                        "minutes — the emulation route interprets the "
                        "solve kernels)")
    parser.add_argument("--fused-rank", type=int, default=16)
    parser.add_argument("--fused-chunk-elems", type=int, default=16_384,
                        help="tiled chunk size for --fused-ab (small "
                        "enough that the stream half scans several chunks "
                        "per shard, so the per-chunk fusion is exercised)")
    parser.add_argument("--gather-ab", action="store_true",
                        help="in-kernel DMA gather vs XLA materialized-"
                        "stream gather A/B + removed-HBM-stream-bytes "
                        "estimate on a virtual CPU mesh (ML-25M shape / "
                        "--gather-div)")
    parser.add_argument("--gather-div", type=int, default=128,
                        help="ML-25M shape divisor for --gather-ab (the "
                        "default keeps the CPU-mesh A/B under a few "
                        "minutes)")
    parser.add_argument("--gather-rank", type=int, default=16)
    parser.add_argument("--gather-chunk-elems", type=int, default=16_384,
                        help="tiled chunk size for --gather-ab (several "
                        "chunks per shard so the per-chunk gather is "
                        "exercised; must keep tile alignment for the "
                        "fused-gather gate)")
    parser.add_argument("--overlap-ab", action="store_true",
                        help="double-buffered vs serial ring exchange A/B "
                        "+ exchange/compute timing split on a virtual CPU "
                        "mesh (ML-25M shape / --overlap-div)")
    parser.add_argument("--overlap-div", type=int, default=64,
                        help="ML-25M shape divisor for --overlap-ab (1 = "
                        "the full 162k x 59k x 25M shape; the default "
                        "keeps the CPU-mesh A/B under a few minutes)")
    parser.add_argument("--overlap-rank", type=int, default=32)
    parser.add_argument("--overlap-device-mesh", action="store_true",
                        help="run --overlap-ab on the real device mesh "
                        "(needs >= --shards devices) instead of the "
                        "virtual CPU mesh — the mode that measures the "
                        "actual ICI overlap win")
    parser.add_argument("--overlap-chunk-elems", type=int, default=32_768,
                        help="tiled chunk size for --overlap-ab (small "
                        "enough that each shard streams several chunks, "
                        "so the chunk pipeline is exercised too)")
    parser.add_argument("--health-ab", action="store_true",
                        help="A/B the health sentinel's in-carry probe "
                        "(health_check_every=1) against the plain fused "
                        "loop on the dense-stream tiled config; reports "
                        "the s/iter overhead fraction (< 2%% budget) and "
                        "checks factors stay bit-identical")
    parser.add_argument("--health-div", type=int, default=64,
                        help="shape divisor for --health-ab (ML-25M "
                        "proportions scaled down)")
    parser.add_argument("--health-rank", type=int, default=16)
    parser.add_argument("--ckpt-ab", action="store_true",
                        help="async vs sync checkpoint writer A/B on the "
                        "stepped trainer at per-iteration save cadence: "
                        "records the per-save stall removed from the step "
                        "loop and checks factors stay bit-exact")
    parser.add_argument("--ckpt-div", type=int, default=32,
                        help="shape divisor for --ckpt-ab (ML-25M "
                        "proportions scaled down)")
    parser.add_argument("--ckpt-rank", type=int, default=32)
    parser.add_argument("--foldin", action="store_true",
                        help="streaming fold-in row: updates/sec absorbed "
                        "by the exactly-once stream loop + fold-in RMSE vs "
                        "a warm full retrain on a held-out time split of "
                        "the planted bench dataset (≤ 1.02x is the "
                        "acceptance contract)")
    parser.add_argument("--foldin-div", type=int, default=64,
                        help="shape divisor for --foldin (ML-25M "
                        "proportions scaled down)")
    parser.add_argument("--foldin-rank", type=int, default=16)
    parser.add_argument("--foldin-updates", type=int, default=4096,
                        help="streamed suffix size (the time split's tail)")
    parser.add_argument("--foldin-batch-records", type=int, default=256,
                        help="log records per micro-batch (the offset-"
                        "committed replay quantum)")
    parser.add_argument("--quant-ab", action="store_true",
                        help="quantized-gather-table A/B (ops.quant): f32 "
                        "vs bf16 vs int8+scale on the planted split — "
                        "held-out RMSE per table dtype (bf16 <= 1.01x f32 "
                        "is the contract), factor delta vs f32, and the "
                        "analytic gather bytes removed per row")
    parser.add_argument("--quality-bytes", action="store_true",
                        help="emit the RMSE-vs-table-dtype curve on the "
                        "planted split (quality as a function of gather "
                        "bytes per row)")
    parser.add_argument("--quant-div", type=int, default=256,
                        help="shape divisor for --quant-ab/--quality-bytes "
                        "(ML-25M proportions scaled down)")
    parser.add_argument("--quant-rank", type=int, default=16)
    parser.add_argument("--quant-chunk-elems", type=int, default=16_384)
    parser.add_argument("--serve", action="store_true",
                        help="top-K serving bench (ISSUE 8): QPS + p50/p99 "
                        "at ML-25M scale through the full request path "
                        "(log → batch coalescing → score+top-K kernel → "
                        "response log), swept over batch size, table "
                        "dtype, shard count, and serve mode "
                        "(exact/two_stage, ISSUE 16), each row with its "
                        "executed-mode vs_roofline, recall_at_k, and "
                        "measured bytes_scanned_per_batch")
    parser.add_argument("--serve-users", type=int, default=162_541)
    parser.add_argument("--serve-movies", type=int, default=59_047)
    parser.add_argument("--serve-nnz", type=int, default=25_000_095,
                        help="implied ratings count — sets the synthetic "
                        "seen-list widths (ML-25M mean ~154/user)")
    parser.add_argument("--serve-rank", type=int, default=128)
    parser.add_argument("--serve-k", type=int, default=100)
    parser.add_argument("--serve-tile-m", type=int, default=2048)
    parser.add_argument("--serve-batches", default="16,64,256",
                        help="comma list of coalesced batch sizes to sweep")
    parser.add_argument("--serve-dtypes", default="float32,bfloat16,int8",
                        help="comma list of table dtypes to sweep (at the "
                        "largest batch)")
    parser.add_argument("--serve-shards", default="1,4",
                        help="comma list of item-axis shard counts (>1 "
                        "rows run the sharded merge on a virtual mesh)")
    parser.add_argument("--serve-requests", type=int, default=256,
                        help="open-loop requests per row")
    parser.add_argument("--serve-modes", default="exact,two_stage",
                        help="comma list of retrieval modes (ISSUE 16): "
                        "two_stage rows run the clustered candidate -> "
                        "rescore path; every row records recall_at_k + "
                        "measured bytes_scanned_per_batch")
    parser.add_argument("--serve-clusters", type=int, default=1024,
                        help="two_stage k-means cluster count (0 = engine "
                        "auto ~sqrt(movies); default tuned for the ML-25M "
                        "shape so the batch union stays narrow)")
    parser.add_argument("--serve-probe-clusters", type=int, default=32,
                        help="clusters probed per user (0 = engine auto "
                        "at the 0.95 recall floor)")
    parser.add_argument("--serve-fleet", action="store_true",
                        help="replicated serving fleet bench (ISSUE 18): "
                        "goodput QPS scaling + admission shed rate vs "
                        "replica count through the full replicated path "
                        "(user-keyed routing -> per-replica admission "
                        "control -> engine -> response log), every fleet "
                        "size driven at --serve-fleet-load x its measured "
                        "aggregate capacity")
    parser.add_argument("--serve-fleet-replicas", default="1,2,4",
                        help="comma list of fleet sizes to sweep")
    parser.add_argument("--serve-fleet-requests", type=int, default=1024,
                        help="open-loop requests per fleet size")
    parser.add_argument("--serve-fleet-batch", type=int, default=64,
                        help="admitted batch per replica step (the "
                        "admission queue bound; each step drains up to "
                        "4x this from the log and sheds the excess as "
                        "retriable rejections)")
    parser.add_argument("--serve-fleet-load", type=float, default=1.25,
                        help="offered rate as a multiple of the fleet's "
                        "measured aggregate capacity (>1 exercises "
                        "admission shedding)")
    parser.add_argument("--scale-sweep", action="store_true",
                        help="out-of-core scale sweep (ISSUE 11): s/iter "
                        "and ratings/sec/chip vs problem size across the "
                        "resident->windowed offload tiers, with the "
                        "memory-budget math per row; the planner picks "
                        "the tier per point")
    parser.add_argument("--sweep-scales", default="0.5,1.0,2.0",
                        help="comma list of multipliers applied to "
                        "--users/--movies/--nnz per sweep point")
    parser.add_argument("--sweep-budget-mb", type=float, default=None,
                        help="artificial device HBM budget (MB) the tier "
                        "resolution runs against; default = the detected "
                        "device's real budget")
    parser.add_argument("--sweep-tile-rows", type=int, default=128,
                        help="tile rows of the sweep's stream-tiled blocks")
    parser.add_argument("--sweep-window-chunks", type=int, default=4,
                        help="chunks per staged window on the host_window "
                        "tier")
    parser.add_argument("--sweep-shards", default="1",
                        help="comma list of shard counts per sweep point "
                        "(ISSUE 12): the tier resolves against the "
                        "PER-SHARD budget; host_window points run the "
                        "sharded windowed driver (no mesh needed), "
                        "device points at >1 shards need that many jax "
                        "devices or record budget math only")
    parser.add_argument("--staging-ab", action="store_true",
                        help="staging-engine A/B modifier on "
                        "--scale-sweep (ISSUE 13): every host_window "
                        "point is timed twice — the pooled staging "
                        "engine (the default) vs the serial double "
                        "buffer (the PR 10/11 baseline) — and the row "
                        "records the wall-clock ratio plus pool depth, "
                        "staged MB/s, the overlap-hidden fraction, "
                        "trace_count and time_to_first_step_s; the "
                        "4-shard point is the ISSUE 13 acceptance "
                        "measurement")
    parser.add_argument("--hot-ab", action="store_true",
                        help="hot-row-cache A/B modifier on --scale-sweep "
                        "(ISSUE 15): every host_window point re-runs with "
                        "hot_rows=0 (the PR 12 full-staging engine) next "
                        "to the default auto resolution, recording the "
                        "resolved hot fraction, the reference-coverage "
                        "fraction, hot-resident vs cold-staged MB, the "
                        "staged-table-byte cut, and crc equality between "
                        "the arms — the ISSUE 15 acceptance measurement")
    parser.add_argument("--sweep-table-dtypes", default="float32",
                        help="comma list of gather-table dtypes per sweep "
                        "point — int8 rows record the (codes, scales) "
                        "staged bytes (~1/4 of f32 on the table share)")
    parser.add_argument("--ials-offload-ab", action="store_true",
                        help="iALS++ resident vs host_window A/B "
                        "(ISSUE 19): the bucketed subspace optimizer "
                        "device-resident vs streamed through the "
                        "out-of-core windowed driver under "
                        "--ials-budget-mb, hot cache auto and off — "
                        "crc equality, s/iter, staged MB/iter (table "
                        "windows + global-Gram reduction passes), the "
                        "hot arm's staged-table-byte cut, and the "
                        "planner's own tier resolution at that budget")
    parser.add_argument("--ials-budget-mb", type=float, default=1.6,
                        help="artificial device budget (MB) the iALS "
                        "offload A/B runs against")
    parser.add_argument("--ials-window-chunks", type=int, default=2,
                        help="chunks per staged width-class window in "
                        "the iALS offload A/B")
    parser.add_argument("--plan-ab", action="store_true",
                        help="execution-planner A/B (ISSUE 9): the "
                        "resolver's serve plan (free table dtype + batch "
                        "quantum at the ML-25M rank-128 shape) vs the "
                        "static pre-planner defaults, measured per "
                        "request-slot, provenance in the row")
    return parser


if __name__ == "__main__":
    cli_args = _build_parser().parse_args()
    run = (
        (lambda: ials_offload_ab_main(cli_args))
        if cli_args.ials_offload_ab
        else (lambda: scale_sweep_main(cli_args))
        if cli_args.scale_sweep
        else (lambda: plan_ab_main(cli_args))
        if cli_args.plan_ab
        else (lambda: serve_fleet_main(cli_args))
        if cli_args.serve_fleet
        else (lambda: serve_main(cli_args))
        if cli_args.serve
        else (lambda: quant_ab_main(cli_args))
        if cli_args.quant_ab
        else (lambda: quality_bytes_main(cli_args))
        if cli_args.quality_bytes
        else (lambda: foldin_main(cli_args))
        if cli_args.foldin
        else (lambda: ckpt_ab_main(cli_args))
        if cli_args.ckpt_ab
        else (lambda: health_ab_main(cli_args))
        if cli_args.health_ab
        else (lambda: gather_ab_main(cli_args))
        if cli_args.gather_ab
        else (lambda: fused_ab_main(cli_args))
        if cli_args.fused_ab
        else (lambda: overlap_ab_main(cli_args))
        if cli_args.overlap_ab
        else (lambda: compare_exchange_main(cli_args))
        if cli_args.compare_exchange
        else (lambda: scale_main(cli_args))
        if (cli_args.scale or cli_args.full or cli_args.ials
            or cli_args.ialspp or cli_args.alspp)
        else main
    )
    run()
