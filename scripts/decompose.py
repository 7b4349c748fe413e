"""Per-term decomposition of one tiled ALS/iALS iteration on the chip.

VERDICT r4 #4: at 3.7–10× the gather-engine floor (rank 128 / iALS), the
binding term is unidentified — only the rank-64 iteration had a measured
breakdown.  This script times each PREFIX of the production half-step
pipeline (the ``stage`` hook in ``cfk_tpu.ops.tiled``, which runs the
literal production ops and sinks them into a scalar) and differences the
prefixes into per-term costs:

    gather          = neighbor-factor gather (+ weighted premultiply)
    kernel          = gram - gather        (the fused pallas Gram walk)
    scatter (accum) = accum - gram         (accumulator scatter-add)
    solve           = full - gram|accum    (reg+LU/GJ solves, + transforms)
    misc            = iteration - movie_full - user_full

Every probe is wrapped in the same ``iters``-deep fori_loop as the
production steady-state measurement, with a 1-ulp factor perturbation per
trip so loop-invariant code motion cannot collapse the loop (the round-3
pallas micro-bench artifact).  The constant per-call cost of the sync
fetch is identical across probes, so the DIFFERENCES are clean even though
raw mins include it.

Usage (flagship dense config):
    python -u scripts/decompose.py --layout tiled --dense-stream \
        --chunk-elems 65536 --accum-chunk-elems 262144 --rank 64
iALS (ML-25M shape):
    python -u scripts/decompose.py --layout tiled --ials \
        --users 162541 --movies 59047 --nnz 25000095 --chunk-elems 81920
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from perf_lab import get_dataset, make_parser, sync  # noqa: E402


def main() -> None:
    p = make_parser()
    p.add_argument("--halves", default="movie,user",
                   help="comma list of halves to decompose")
    args = p.parse_args()
    if args.layout != "tiled":
        raise SystemExit("decompose supports the tiled layout")
    ds = get_dataset(args)

    import jax
    import jax.numpy as jnp

    from cfk_tpu.models import als as als_mod
    from cfk_tpu.ops.tiled import ials_tiled_half_step, tiled_half_step

    mblocks, ublocks, u_stats, layout_kw = als_mod._tiled_device_setup(
        ds, weighted=args.ials)
    jax.block_until_ready((mblocks, ublocks))
    np.asarray(jax.tree.leaves(mblocks)[0].ravel()[:1])
    print(f"# modes: movie={layout_kw['m_chunks'][1]} "
          f"user={layout_kw['u_chunks'][1]}", flush=True)

    k, dt = args.rank, args.dtype
    key = jax.random.PRNGKey(0)
    ku, km = jax.random.split(key)
    # Random factors of the production shapes/dtype; values don't affect
    # timing (data-independent compute), scale ~1 keeps solves finite.
    u0 = (jax.random.normal(ku, (ds.user_blocks.padded_entities, k))
          .astype(dt) * 0.3)
    m0 = (jax.random.normal(km, (ds.movie_blocks.padded_entities, k))
          .astype(dt) * 0.3)

    lam, alpha = 0.05 if not args.ials else 0.1, args.alpha

    def half_fn(half, stage):
        blk = mblocks if half == "movie" else ublocks
        chunks = layout_kw["m_chunks" if half == "movie" else "u_chunks"]
        ents = layout_kw["m_entities" if half == "movie" else "u_entities"]
        fixed0 = u0 if half == "movie" else m0

        @functools.partial(jax.jit, donate_argnums=())
        def run(fixed, blk):
            def body(i, carry):
                f, acc = carry
                if args.ials:
                    x = ials_tiled_half_step(
                        f, blk, chunks, ents, lam, alpha,
                        solver=args.solver, stage=stage)
                else:
                    x = tiled_half_step(
                        f, blk, chunks, ents, lam,
                        solver=args.solver, stage=stage)
                # 1-ulp-scale data dependence: blocks loop-invariant code
                # motion from collapsing the iters loop; numerically inert.
                f = f + (x[0, 0] * 1e-30).astype(f.dtype)
                return f, acc + x[:1, :1].astype(jnp.float32)
            _, acc = jax.lax.fori_loop(
                0, args.iters, body, (fixed, jnp.zeros((1, 1), jnp.float32)))
            return acc
        return lambda: sync(run(fixed0, blk))

    def iteration_fn():
        @functools.partial(jax.jit, donate_argnums=())
        def run(u, m, mblk, ublk):
            def body(i, carry):
                u, m_prev = carry
                if args.ials:
                    from cfk_tpu.models.ials import _ials_iteration_body
                    return _ials_iteration_body(
                        u, m_prev, mblk, ublk, lam=lam, alpha=alpha,
                        dt=jnp.dtype(dt), solver=args.solver,
                        algorithm="als", block_size=32, sweeps=1,
                        **layout_kw)
                return als_mod._iteration_body(
                    u, mblk, ublk, lam=lam, solve_chunk=None,
                    dt=jnp.dtype(dt), solver=args.solver, m_prev=m_prev,
                    **layout_kw)
            u, m = jax.lax.fori_loop(0, args.iters, body, (u, m))
            return u
        return lambda: sync(run(u0, m0, mblocks, ublocks))

    # Either half may land in accum mode (the mode guard below skips the
    # accum probe for stream/dstream halves).
    stages = ("gather", "gram", "accum", "full")
    mode = {"movie": layout_kw["m_chunks"][1],
            "user": layout_kw["u_chunks"][1]}
    rows: dict[str, float] = {}

    def measure(name, thunk):
        thunk()  # compile + first run
        times = []
        for i in range(args.repeats):
            t0 = time.time()
            thunk()
            times.append(time.time() - t0)
        best = min(times) / args.iters
        rows[name] = round(best, 4)
        print(f"# {name}: {best:.4f} s/iter (min of {args.repeats})",
              flush=True)

    for half in args.halves.split(","):
        for stage in stages:
            if stage == "accum" and mode[half] != "accum":
                continue
            measure(f"{half}_{stage}", half_fn(half, stage))
    measure("iteration", iteration_fn())

    out = dict(rows)
    for half in args.halves.split(","):
        g = rows.get(f"{half}_gather")
        gr = rows.get(f"{half}_gram")
        ac = rows.get(f"{half}_accum")
        fu = rows.get(f"{half}_full")
        if g is not None and gr is not None:
            out[f"{half}_kernel_derived"] = round(gr - g, 4)
        if ac is not None and gr is not None:
            out[f"{half}_scatter_derived"] = round(ac - gr, 4)
        if fu is not None:
            pre = ac if ac is not None else gr
            out[f"{half}_solve_derived"] = round(fu - pre, 4)
    if "movie_full" in rows and "user_full" in rows:
        out["misc_derived"] = round(
            rows["iteration"] - rows["movie_full"] - rows["user_full"], 4)
    out.update(rank=k, dtype=dt, layout=args.layout, ials=args.ials,
               chunk_elems=args.chunk_elems,
               accum_chunk_elems=args.accum_chunk_elems,
               dense_stream=args.dense_stream, iters=args.iters,
               repeats=args.repeats)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
