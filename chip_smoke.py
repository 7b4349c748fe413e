#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives the main path — build blocks → train → evaluate → serve
top-K — through the entry points a user calls, at the shape the README
headlines (the Netflix-Prize deployment: 480,189 users × 17,770 movies ×
100,480,507 ratings, rank 64, λ 0.05, bf16 factors, tiled layout + dense user
stream, 65,536 / 262,144-entry chunks), on ONE TPU chip, and checks what comes
out against plain references.  Data comes from ``--seed``; nothing outside the
checkout is read.

    python chip_smoke.py               # one chip: phases 0-2, then the ok line
    python chip_smoke.py --multichip   # four chips: sharded vs one device ONLY
    python chip_smoke.py --rehearsal   # any backend, tiny shape, no ok line

Every check is fatal: the first one that fails ends the process with a
non-zero code and no result line.  Without an accelerator the script fails at
Phase 0 — it never falls back to the CPU; ``--rehearsal`` is the explicit,
labelled exception for finding wrong paths and arguments before a chip run.

Timings are printed with ``block_until_ready`` barriers and the device's name,
as information.  None of them is a benchmark result.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

# The deployment (the Netflix Prize shape) and, for --multichip, a cut of
# it to 1/10 on every axis
# (row lengths kept: ~209 ratings per user, ~5.7k per movie): that phase
# checks what exists only across chips (mesh, collectives, sharded state),
# not speed.
NETFLIX = dict(users=480_189, movies=17_770, ratings=100_480_507)
MULTICHIP = dict(users=48_019, movies=1_777, ratings=10_048_051)
REHEARSAL = dict(users=3_000, movies=400, ratings=60_000)
RANK, LAM, ITERS = 64, 0.05, 3
CHUNK, ACCUM_CHUNK = 65_536, 262_144
# bf16 keeps 8 significand bits: storing a solved row costs up to 2⁻⁸ relative
# per element, and the kernel's Gram of bf16 rows is exact in float32, so the
# trainer's rows sit within a few 1e-3 of a float32 solve from the same movie
# factors.  2e-2 of the row's largest element leaves ~5× margin.
BF16_ROW_TOL = 2e-2
# Sharded vs one device, float32 factors: the same normal equations summed in
# another order (per-shard chunks, psum/ring rotations), amplified by two
# iterations of solves — dryrun_multichip's bound.
MULTICHIP_TOL = 1e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        print(f"FAILED: {what}", flush=True)
        sys.exit(1)
    print(f"  ok: {what}", flush=True)


def say(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class Device:
    platform: str
    kind: str
    count: int

    @property
    def label(self) -> str:
        return f"{self.count}× {self.kind} ({self.platform})"


# -- Phase 0 -----------------------------------------------------------------

def phase0_device(args) -> Device:
    """First touch of JAX: an accelerator or nothing."""
    say("== phase 0: device")
    import jax
    import jaxlib

    devices = jax.devices()
    dev = Device(devices[0].platform, devices[0].device_kind, len(devices))
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a CPU-only rehearsal box
        libtpu = "not installed"
    say(f"  jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu}")
    say(f"  device: {dev.label}")
    if args.rehearsal:
        say("  REHEARSAL: any backend, tiny shape — not a chip run")
    elif dev.platform != "tpu":
        say(f"FAILED: JAX found no accelerator (platform {dev.platform!r}); "
            "chip_smoke.py runs on a TPU or not at all")
        sys.exit(2)
    want = 4 if args.multichip else 1
    if args.rehearsal:
        check(dev.count >= want, f"at least {want} device(s) to rehearse on")
    else:
        check(dev.count == want,
              f"{want} device(s) for this mode, found {dev.count}")

    from cfk_tpu.config import enable_compile_cache
    from cfk_tpu.data import _native
    from cfk_tpu.ops.pallas.interpret import resolve_interpret
    from cfk_tpu.ops.solve import _resolve_solver

    cache_dir = enable_compile_cache()
    src = ("JAX_COMPILATION_CACHE_DIR"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "fixed in-checkout default")
    say(f"  compile cache: {cache_dir} ({src})")
    if _native.available():
        say("  native ingest library: loaded (native/libcfk_native.so)")
    elif _native.build():
        say("  native ingest library: built from tracked sources "
            "(make -C native libcfk_native.so)")
    else:
        say("  native ingest library: unavailable (no make/g++) — "
            "pure-Python block build")
    if not args.rehearsal:
        # What the backend-sniffing defaults must resolve to on this path.
        check(resolve_interpret(None) is False,
              "interpret=None resolves to compiled Mosaic kernels")
        check(_resolve_solver("auto") == "pallas",
              'solver="auto" resolves to the Pallas solve kernels')
        from cfk_tpu.utils.roofline import device_peaks

        device_peaks(dev.kind)  # raises for a chip with no published peaks
        say(f"  peaks table has a row for {dev.kind!r}")
    return dev


def barrier_check(dev: Device) -> None:
    """Is ``block_until_ready`` a true barrier here?  Time one large matmul
    loop both ways — ended by ``block_until_ready`` and ended by a scalar
    device→host fetch, which cannot return before the value exists — and
    hold the former to the chip's published peak: a call that returned
    before the device finished would report more FLOP/s than the chip has."""
    import jax
    import jax.numpy as jnp

    from cfk_tpu.utils.roofline import device_peaks

    n, reps = 4096, 64

    @jax.jit
    def loop(x):
        y = jax.lax.fori_loop(
            0, reps, lambda _, y: (y @ x).astype(jnp.bfloat16) * 0.5 ** 6, x)
        return y, y[0, 0]  # the scalar rides the same program

    x = jnp.full((n, n), 2.0 ** -6, jnp.bfloat16)
    float(jax.block_until_ready(loop(x))[1])  # compile + warm both paths
    t0 = time.perf_counter()
    jax.block_until_ready(loop(x))
    t_bur = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(loop(x)[1])
    t_fetch = time.perf_counter() - t0
    flops = 2 * n ** 3 * reps
    peak = device_peaks(dev.kind).peak_bf16_flops
    say(f"  barrier: {reps}× {n}³ bf16 matmul — block_until_ready "
        f"{t_bur * 1e3:.1f} ms ({flops / t_bur / 1e12:.0f} TFLOP/s), scalar "
        f"fetch {t_fetch * 1e3:.1f} ms ({flops / t_fetch / 1e12:.0f} TFLOP/s); "
        f"published peak {peak / 1e12:.0f} [{dev.label}]")
    check(flops / t_bur <= 1.05 * peak and t_bur >= 0.8 * t_fetch,
          "block_until_ready waits for the device (not faster than the "
          "chip's peak, nor than a scalar fetch of the result)")


# -- Phase 1 -----------------------------------------------------------------

def chunking(args) -> dict:
    """The deployment's chunk sizes; a rehearsal shrinks them (and the tile
    and the accum/stream switch) so its tiny shape still takes both modes."""
    if args.rehearsal:
        return dict(tile_rows=16, chunk_elems=2048, accum_chunk_elems=4096,
                    accum_max_entities=1024)
    return dict(chunk_elems=CHUNK, accum_chunk_elems=ACCUM_CHUNK)


def build_dataset(shape: dict, seed: int, **build_kw):
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo

    t0 = time.perf_counter()
    coo = synthetic_netflix_coo(shape["users"], shape["movies"],
                                shape["ratings"], seed=seed)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    ds = Dataset.from_coo(coo, layout="tiled", **build_kw)
    return ds, coo, t_gen, time.perf_counter() - t0


def resolved_plan(ds, cfg, dev: Device, pin: bool):
    """Print what the planner and the static gates resolve this run to;
    with ``pin`` fail unless it is the route this deployment expects on a
    TPU: Pallas solver, XLA gather (rank 64 bf16 is off Mosaic's row-DMA
    tiling), fused Gram+solve epilogue on the streamed user half."""
    import jax.numpy as jnp

    from cfk_tpu.ops.solve import _resolve_solver
    from cfk_tpu.ops.tiled import (
        default_tiled_gram_backend,
        resolve_tiled_route,
    )
    from cfk_tpu.plan import plan_for_config

    plan, prov = plan_for_config(
        cfg, num_users=ds.user_map.num_entities,
        num_movies=ds.movie_map.num_entities,
        nnz=int(ds.movie_blocks.count.sum()))
    knobs = plan.half_step_kwargs(cfg)
    say(f"  plan: {prov.summary()}")
    solver = _resolve_solver(knobs["solver"])
    backend = default_tiled_gram_backend()
    table = jnp.dtype(cfg.dtype if knobs["table_dtype"] == "float32"
                      else knobs["table_dtype"])
    routes = {}
    for side, blk in (("movie", ds.movie_blocks), ("user", ds.user_blocks)):
        gather, fused_lam = resolve_tiled_route(
            blk.mode, blk.statics, cfg.rank, cfg.lam, table_dtype=table,
            solver=knobs["solver"],
            fused_epilogue=knobs["fused_epilogue"],
            in_kernel_gather=knobs["in_kernel_gather"],
            reg_solve_algo=knobs["reg_solve_algo"])
        routes[side] = (blk.mode, gather, fused_lam is not None)
        say(f"  {side} half: mode={blk.mode} statics={blk.statics} "
            f"gather={gather} "
            f"epilogue={'fused' if fused_lam is not None else 'split'}")
    say(f"  solver={solver} gram_backend={backend} "
        f"table_dtype={table.name} factors={cfg.dtype} "
        f"tier={plan.offload_tier} [{dev.label}]")
    if pin:
        check(plan.offload_tier == "device", "tables resident on the device")
        check(solver == "pallas" and backend == "pallas",
              "Pallas Gram backend + Pallas solver")
        check(routes["user"] == ("dstream", "xla", True),
              "user half: dense stream, XLA gather, fused epilogue")
        check(routes["movie"][:2] == ("accum", "xla"),
              "movie half: accum mode, XLA gather")
    return knobs


def aot_compile_train(ds, cfg, knobs, iters: int, pin: bool) -> float:
    """Compile the trainer's own loop program from avals (no upload yet) and
    look inside: it must hold Mosaic kernels, so an XLA twin cannot pass for
    the kernel.  With the persistent cache on, ``train_als``'s own compile
    of the same program is then a cache hit."""
    import jax

    from cfk_tpu.models import als

    mb, ub = ds.movie_blocks, ds.user_blocks
    aval = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    t0 = time.perf_counter()
    compiled = als._train_loop.lower(
        jax.random.PRNGKey(cfg.seed),
        aval(als._tiled_host_arrays(mb)), aval(als._tiled_host_arrays(ub)),
        aval({"rating_sum": ub.rating_sum, "count": ub.count}),
        **als._train_loop_statics(
            dataclasses.replace(cfg, num_iterations=iters), knobs,
            solve_chunk=None, health=None),
        **als._tiled_layout_kw(ds),
    ).compile()
    secs = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    say(f"  compiled the {iters}-iteration train loop in {secs:.1f} s: "
        f"{kernels} tpu_custom_call site(s)")
    if pin:
        check(kernels >= 3, "the compiled half-steps hold Mosaic kernels "
              "(Gram+solve epilogue, Gram, reg+solve)")
    return secs


def train(ds, cfg, iters: int):
    from cfk_tpu.models.als import train_als
    from cfk_tpu.utils.metrics import Metrics

    import jax

    metrics = Metrics()
    t0 = time.perf_counter()
    model = train_als(ds, dataclasses.replace(cfg, num_iterations=iters),
                      metrics=metrics)
    jax.block_until_ready((model.user_factors, model.movie_factors))
    return model, time.perf_counter() - t0, dict(metrics.phases)


def sample_rmse(model, ds, seed: int, n: int = 1 << 22) -> float:
    """Train RMSE by the repo's evaluator on a seeded sample of ratings
    (all of them when there are fewer than ``n``)."""
    from cfk_tpu.data.blocks import RatingsCOO
    from cfk_tpu.eval.metrics import mse_rmse_from_model

    coo = ds.coo_dense
    if coo.rating.shape[0] > n:
        pick = np.random.default_rng(seed).choice(
            coo.rating.shape[0], size=n, replace=False)
        coo = RatingsCOO(movie_raw=coo.movie_raw[pick],
                         user_raw=coo.user_raw[pick], rating=coo.rating[pick])
    return mse_rmse_from_model(
        model, dataclasses.replace(ds, coo_dense=coo))[1]


def reference_rows(model, ds, seed: int, lam: float, n_users: int = 256):
    """(sampled user rows, the trainer's rows, reference rows): a plain
    float32 ``jax.numpy`` solve of (MᵤᵀMᵤ + λ·nᵤ·I) u = Mᵤᵀ r at
    ``precision="highest"`` from the trainer's final movie factors — the
    system the last user half-step solved."""
    import jax
    import jax.numpy as jnp

    coo = ds.coo_dense
    num_users = ds.user_map.num_entities
    rows = np.sort(np.random.default_rng(seed + 1).choice(
        num_users, size=min(n_users, num_users), replace=False))
    sel = np.flatnonzero(np.isin(coo.user_raw, rows))
    seg = np.searchsorted(rows, coo.user_raw[sel]).astype(np.int32)
    movie = coo.movie_raw[sel].astype(np.int32)
    rating = coo.rating[sel].astype(np.float32)
    m = jnp.asarray(model.movie_factors, jnp.float32)
    k = m.shape[1]

    @jax.jit
    def normal_eqs(seg_c, movie_c, rating_c):
        f = m[movie_c]  # [C, k]; padding carries rating 0 and seg = n
        a = jax.ops.segment_sum(
            jnp.einsum("ck,cl->ckl", f, f, precision="highest"),
            seg_c, num_segments=rows.size + 1)
        b = jax.ops.segment_sum(f * rating_c[:, None], seg_c,
                                num_segments=rows.size + 1)
        return a, b

    chunk = 8192  # [C, k, k] float32 outer products: 128 MiB at rank 64
    a = jnp.zeros((rows.size + 1, k, k), jnp.float32)
    b = jnp.zeros((rows.size + 1, k), jnp.float32)
    for lo in range(0, sel.size, chunk):
        pad = max(0, lo + chunk - sel.size)
        take = lambda x, fill: jnp.asarray(np.concatenate(
            [x[lo:lo + chunk], np.full(pad, fill, x.dtype)]))
        da, db = normal_eqs(take(seg, rows.size), take(movie, 0),
                            take(rating, 0))
        a, b = a + da, b + db
    count = np.bincount(seg, minlength=rows.size).astype(np.float32)
    reg = lam * jnp.maximum(jnp.asarray(count), 1.0)
    with jax.default_matmul_precision("highest"):
        ref = jnp.linalg.solve(
            a[:-1] + reg[:, None, None] * jnp.eye(k, dtype=jnp.float32),
            b[:-1, :, None])[..., 0]
    got = np.asarray(model.user_factors, np.float32)[rows]
    return rows, got, np.asarray(ref), sel.size


def phase1_train(args, dev: Device, shape: dict):
    say("== phase 1: build blocks, train, evaluate")
    from cfk_tpu.config import ALSConfig

    say(f"  shape: {shape['users']:,} users × {shape['movies']:,} movies × "
        f"{shape['ratings']:,} ratings, rank {RANK}, λ {LAM}, bf16 factors"
        + ("  (REHEARSAL shape)" if args.rehearsal else
           "  (the Netflix-Prize deployment, uncut)"))
    ds, _, t_gen, t_build = build_dataset(
        shape, args.seed, dense_stream=True, **chunking(args))
    say(f"  set-up: data from seed {args.seed} in {t_gen:.1f} s, "
        f"block build in {t_build:.1f} s (host)")
    cfg = ALSConfig(rank=RANK, lam=LAM, num_iterations=ITERS,
                    dtype="bfloat16", layout="tiled", seed=args.seed)
    knobs = resolved_plan(ds, cfg, dev, pin=not args.rehearsal)
    t_compile = sum(aot_compile_train(ds, cfg, knobs, it,
                                      pin=not args.rehearsal)
                    for it in (1, ITERS))

    runs = {}
    for iters in (1, ITERS):
        model, wall, phases = train(ds, cfg, iters)
        rmse = sample_rmse(model, ds, args.seed)
        runs[iters] = (model, wall, phases, rmse)
        say(f"  train_als({iters} iteration(s)): {wall:.2f} s wall "
            f"(upload {phases.get('blocks_to_device', 0.0):.2f} s, "
            f"train phase {phases.get('train', 0.0):.2f} s incl. its "
            f"compile or cache load) — sampled train RMSE {rmse:.4f} "
            f"[{dev.label}]")
    model = runs[ITERS][0]
    u = np.asarray(model.user_factors, np.float32)
    m = np.asarray(model.movie_factors, np.float32)
    check(u.shape == (ds.user_blocks.padded_entities, RANK)
          and m.shape == (ds.movie_blocks.padded_entities, RANK),
          f"factor shapes {u.shape}, {m.shape}")
    check(bool(np.isfinite(u).all() and np.isfinite(m).all()),
          "factors finite")
    check(runs[ITERS][3] < runs[1][3],
          f"train RMSE falls from iteration 1 to {ITERS}: "
          f"{runs[1][3]:.4f} → {runs[ITERS][3]:.4f}")
    s_per_iter = (runs[ITERS][2].get("train", 0.0)
                  - runs[1][2].get("train", 0.0)) / (ITERS - 1)
    say(f"  information, not a claim: ~{s_per_iter:.3f} s/iteration "
        f"((train phase of {ITERS} − of 1) / {ITERS - 1}, "
        f"block_until_ready-bounded); set-up: compile {t_compile:.1f} s "
        f"[{dev.label}]")

    rows, got, ref, n_ratings = reference_rows(model, ds, args.seed, LAM)
    scale = np.maximum(np.abs(ref).max(axis=1), 1e-6)
    err = np.abs(got - ref).max(axis=1) / scale
    say(f"  reference solve: {rows.size} sampled users ({n_ratings:,} "
        f"ratings): max row error {err.max():.2e}, median "
        f"{np.median(err):.2e} of the row's largest element")
    check(bool(np.isfinite(ref).all()) and float(err.max()) <= BF16_ROW_TOL,
          f"trainer rows within {BF16_ROW_TOL} (bf16 storage) of the plain "
          "float32 solve")
    return ds, model


# -- Phase 2 -----------------------------------------------------------------

def phase2_serve(args, dev: Device, ds, model) -> None:
    say("== phase 2: serve top-K")
    import jax
    import jax.numpy as jnp

    from cfk_tpu.plan.registry import REGISTRY
    from cfk_tpu.serving import engine_from_model
    from cfk_tpu.serving.topk_kernel import topk_scores_pallas

    batch, k_top, n_batches = 64, 10, 3
    t0 = time.perf_counter()
    eng = engine_from_model(model, ds)
    t_engine = time.perf_counter() - t0
    rng = np.random.default_rng(args.seed + 2)
    users = rng.choice(model.num_users, size=batch * (n_batches + 1),
                       replace=False)
    warm = eng.prewarm(k_top, max_batch=batch, user_rows=users[:batch])
    say(f"  engine over {model.num_movies:,} × {RANK} items "
        f"(table_dtype={eng.table_dtype}, tile_m={eng.tile_m}) in "
        f"{t_engine:.1f} s; prewarm: {warm['programs']} program(s), "
        f"{warm['new_traces']} trace(s), {warm['prewarm_s']:.1f} s")

    # which scorer: the registry's topk slot, and what it lowers to here
    spec = REGISTRY.get("topk", "mosaic_tpu")
    check(spec.loader() is topk_scores_pallas
          and REGISTRY.backend_available("mosaic_tpu"),
          "scorer: serving.topk_kernel.topk_scores_pallas (registry slot "
          "'topk', backend mosaic_tpu)")
    if not args.rehearsal:
        text = jax.jit(functools.partial(
            topk_scores_pallas, k_top=k_top, num_movies=eng.num_movies,
            tile_m=eng.tile_m,
        )).lower(
            jax.ShapeDtypeStruct((batch, RANK), jnp.float32),
            jax.ShapeDtypeStruct((eng.table_rows, RANK),
                                 jnp.dtype(eng.table_dtype)), None,
            jax.ShapeDtypeStruct(
                (eng.table_rows // eng.tile_m, batch, 16), jnp.int32),
        ).compile().as_text()
        check("tpu_custom_call" in text,
              "the scorer compiles to a Mosaic kernel on this backend")

    u_all, m_all = model.host_factors()
    coo = ds.coo_dense
    worst, times, traces = 0.0, [], eng.trace_count
    for i in range(n_batches):
        rows = users[batch * (i + 1): batch * (i + 2)]
        t0 = time.perf_counter()
        vals, ids = eng.topk(rows, k_top, exclude_seen=True)
        times.append(time.perf_counter() - t0)
        scores = u_all[rows] @ m_all.T  # the same scores, exact
        seen_of = {int(r): set() for r in rows}
        hit = np.flatnonzero(np.isin(coo.user_raw, rows))
        for r, mv in zip(coo.user_raw[hit], coo.movie_raw[hit]):
            seen_of[int(r)].add(int(mv))
        for j, r in enumerate(rows):
            scores[j, list(seen_of[int(r)])] = -np.inf
        want = -np.sort(-scores, axis=1)[:, :k_top]
        got = np.take_along_axis(scores, ids.astype(np.int64), axis=1)
        # ids may differ from argsort's only where scores tie: compare the
        # exact scores AT the returned ids with the exact top-K scores
        tol = 1e-4 * np.maximum(np.abs(want), 1.0)
        check(bool((np.abs(got - want) <= tol).all()
                   and (np.abs(vals - want) <= tol).all()),
              f"batch {i}: {batch} users, top-{k_top} ids and scores match "
              "numpy's exact top-K (ties allowed), no seen movie returned")
        worst = max(worst, float(np.abs(vals - want).max()))
    say(f"  {n_batches} batches of {batch}: "
        f"{[round(t * 1e3, 1) for t in times]} ms per batch incl. host "
        f"assembly and {eng.trace_count - traces} new trace(s) + compile(s) "
        f"of the scorer (information); largest score difference "
        f"{worst:.2e} [{dev.label}]")


# -- --multichip -------------------------------------------------------------

def multichip(args, dev: Device) -> None:
    """Only what exists across chips: ``train_als_sharded`` on a 4-device
    mesh, ring and all_gather, against ``train_als`` on one device."""
    say("== multichip: train_als_sharded (ring, all_gather) vs one device")
    import jax

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.models.als import train_als
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import train_als_sharded

    shape = REHEARSAL if args.rehearsal else MULTICHIP
    say(f"  shape: {shape['users']:,} × {shape['movies']:,} × "
        f"{shape['ratings']:,}, rank {RANK}, float32 factors, 2 iterations"
        + ("" if args.rehearsal else
           " (the deployment cut to 1/10 on every axis, row lengths kept: "
           "this phase checks the mesh, the collectives and the sharded "
           "state, not speed)"))
    devices = jax.devices()[:4]
    mesh = make_mesh(4, devices=devices)
    say(f"  mesh: {[str(d) for d in mesh.devices.ravel()]}")
    base = dict(rank=RANK, lam=LAM, num_iterations=2, dtype="float32",
                layout="tiled", seed=args.seed)
    small = chunking(args)
    # stream-mode user half on one device and under all_gather (the fused
    # epilogue + dense stream of phase 1); the ring needs accum halves
    stream_cap = small.pop("accum_max_entities", 4096)
    # The sharded runs go FIRST: each device's peak memory then shows its own
    # share of blocks and factors, before device 0 hosts the whole reference.
    sharded = {}
    for exchange in ("ring", "all_gather"):
        ring = exchange == "ring"
        ds, *_ = build_dataset(
            shape, args.seed, num_shards=4, ring=ring, ring_warn=False,
            dense_stream=not ring,
            **({} if ring else {"accum_max_entities": stream_cap}), **small)
        t0 = time.perf_counter()
        model = train_als_sharded(
            ds, ALSConfig(num_shards=4, exchange=exchange, **base), mesh)
        jax.block_until_ready((model.user_factors, model.movie_factors))
        wall = time.perf_counter() - t0
        for name, x in (("user", model.user_factors),
                        ("movie", model.movie_factors)):
            homes = [s.device for s in x.addressable_shards]
            rows = {s.data.shape[0] for s in x.addressable_shards}
            check(len(homes) == 4 and set(homes) == set(devices)
                  and rows == {x.shape[0] // 4},
                  f"{exchange}: {name} factors [{x.shape[0]}, {x.shape[1]}] "
                  f"in 4 shards of {x.shape[0] // 4} rows on 4 devices")
        stats = [d.memory_stats() for d in devices]
        if all(stats):  # the CPU backend of a rehearsal reports none
            peaks = [s["peak_bytes_in_use"] for s in stats]
            say(f"  {exchange}: peak bytes per device "
                f"{[f'{p / 2**20:.0f} MiB' for p in peaks]}")
            check(min(peaks) > 0 and max(peaks) <= 2 * min(peaks),
                  f"{exchange}: blocks and factors spread over all four "
                  "devices (peak memory within 2× of each other)")
        sharded[exchange] = (model.predict_dense(), wall)
        del model, ds

    ds1, *_ = build_dataset(shape, args.seed, dense_stream=True,
                            accum_max_entities=stream_cap, **small)
    ref_model = train_als(ds1, ALSConfig(**base))
    ref = ref_model.predict_dense()
    check(bool(np.isfinite(ref).all()), "one-device predictions finite")
    scale = max(1.0, float(np.abs(ref).max()))
    for exchange, (preds, wall) in sharded.items():
        delta = float(np.abs(preds - ref).max()) / scale
        say(f"  {exchange}: rel max err vs one device {delta:.2e} "
            f"({wall:.1f} s wall incl. compile) [{dev.label}]")
        check(bool(np.isfinite(preds).all()) and delta <= MULTICHIP_TOL,
              f"{exchange}: within {MULTICHIP_TOL} of the one-device result")

    # Item-axis sharded serving (ServeEngine(shards=4): the table goes up a
    # shard at a time, each chip scans its slice and builds its slice of the
    # exclusion rectangle, one all_gather of the [B, K] selections, a final
    # merge) against the one-device engine over the same factors.
    from cfk_tpu.serving import engine_from_model

    rows = np.random.default_rng(args.seed + 3).choice(
        ref_model.num_users, size=64, replace=False)
    v1, i1 = engine_from_model(ref_model, ds1).topk(rows, 10)
    eng4 = engine_from_model(ref_model, ds1, shards=4)
    table = eng4._table[0]
    parts = table.addressable_shards
    check([s.device for s in parts] == list(devices)
          and {s.data.shape[0] for s in parts} == {table.shape[0] // 4},
          f"sharded serving: item table [{table.shape[0]}, {table.shape[1]}] "
          f"in 4 shards of {table.shape[0] // 4} rows, one on each device, "
          "not whole on device 0")
    v4, i4 = eng4.topk(rows, 10)
    same = float((i1 == i4).mean())
    say(f"  sharded serving: 64 users, K=10 — {same:.3f} of ids identical, "
        f"largest score difference {np.abs(v1 - v4).max():.2e} "
        f"[{dev.label}]")
    check(bool((np.abs(v1 - v4) <= 1e-4 * np.maximum(np.abs(v1), 1)).all()),
          "sharded serving: the four-shard top-K scores are the one-device "
          "engine's (ids may differ only where scores tie)")


# -- main --------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic ratings and of every sample")
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: ONLY train_als_sharded (ring, "
                    "all_gather) vs train_als on one device")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny shape on whatever backend JAX finds, to find "
                    "wrong paths before a chip run; labelled, and never "
                    "prints the ok line")
    args = ap.parse_args()
    t_start = time.perf_counter()
    dev = phase0_device(args)
    if args.multichip:
        multichip(args, dev)
    else:
        if not args.rehearsal:
            barrier_check(dev)
        ds, model = phase1_train(
            args, dev, REHEARSAL if args.rehearsal else NETFLIX)
        phase2_serve(args, dev, ds, model)
    from cfk_tpu.config import enable_compile_cache

    cache_dir = enable_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"== done in {time.perf_counter() - t_start:.0f} s; compile cache "
        f"{cache_dir} holds {entries} entries")
    if args.rehearsal:
        say("REHEARSAL passed — not a chip run, no result line")
        return
    check(entries > 0, "the compile cache holds entries after the run")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.kind, "count": dev.count}}),
        flush=True)


if __name__ == "__main__":
    main()
