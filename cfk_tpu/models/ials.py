"""Implicit-feedback ALS (iALS, Hu-Koren-Volinsky 2008) — second model family.

Same block-partitioned layout as the explicit model, different normal
equations: per entity A = YᵀY + Σ_obs (c−1)·f fᵀ + λI with confidence
c = 1 + α·r, preferences 1 at observed cells.  The global Gram YᵀY is
computed once per half-iteration — locally per shard and ``psum``'d over the
mesh (a [k,k] collective, the cheapest message in the whole framework).

This is the "MovieLens-25M implicit, rank 128" family.  The
reference has no implicit model; capability parity plus one — but the
transport/ingest/checkpoint plumbing is shared with the explicit path.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from cfk_tpu.config import ALSConfig
from cfk_tpu.data.blocks import BucketedBlocks, Dataset, SegmentBlocks, TiledBlocks
from cfk_tpu.models.als import (
    ALSModel,
    _blocks_to_device,
    _bucketed_device_setup,
    _segment_device_setup,
    _tiled_device_setup,
)
from cfk_tpu.ops.solve import (
    ials_half_step,
    ials_half_step_bucketed,
    ials_half_step_segment,
    init_factors,
    init_factors_stats,
)
from cfk_tpu.parallel.mesh import AXIS, shard_rows, to_host


@dataclasses.dataclass(frozen=True)
class IALSConfig(ALSConfig):
    """iALS hyper-parameters; ``lam`` here is plain-λI regularization.

    ``algorithm="ials++"`` switches the per-entity solve from the full k×k
    normal equations to subspace block coordinate descent (Rendle et al.,
    PAPERS.md): ``sweeps`` passes over ``rank/block_size`` coordinate blocks
    per half-iteration, warm-started from the previous epoch's factors.
    With ``block_size == rank`` one sweep equals the full solve exactly.
    """

    alpha: float = 40.0
    lam: float = 0.1

    def _valid_algorithms(self) -> tuple[str, ...]:
        return ("als", "ials++")

    def _check_host_window(self) -> None:
        """Implicit out-of-core (ISSUE 19): the windowed driver streams
        the BUCKETED width-class layout (the global-Gram reduction plus
        per-class windows), for both the full implicit solve and the
        iALS++ subspace sweeps — the tiled stream-mode layout is the
        explicit family's format."""
        if self.layout != "bucketed":
            raise ValueError(
                "offload_tier='host_window' for the implicit family "
                "streams the bucketed width-class layout; layout="
                f"{self.layout!r}"
            )

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.alpha <= 0:
            raise ValueError(f"alpha must be > 0, got {self.alpha}")
        if self.exchange != "all_gather":
            raise ValueError(
                "iALS currently supports exchange='all_gather' only (the "
                "global-Gram trick needs the full fixed side per shard)"
            )


def _ials_half(fixed, blk, *, lam, alpha, solver, gram=None, chunks=None,
               entities=None, x_prev=None, algorithm="als", block_size=32,
               sweeps=1, overlap=None, fused_epilogue=None,
               in_kernel_gather=None, reg_solve_algo=None, table_dtype=None):
    """Dispatch on block layout (tuple = buckets, dict with segment ids =
    flat segment run, other dict = padded rectangle).  ``algorithm="ials++"``
    runs warm-started subspace sweeps from ``x_prev`` instead of full
    solves (padded/bucketed layouts)."""
    if algorithm == "ials++":
        from cfk_tpu.ops.subspace import (
            ials_pp_half_step,
            ials_pp_half_step_bucketed,
        )

        pp_kw = dict(
            gram=gram, block_size=block_size, sweeps=sweeps, solver=solver,
            in_kernel_gather=in_kernel_gather,
            fused_epilogue=fused_epilogue, reg_solve_algo=reg_solve_algo,
            table_dtype=table_dtype,
        )
        if isinstance(blk, tuple):
            return ials_pp_half_step_bucketed(
                fixed, x_prev, blk, chunks, entities, lam, alpha,
                overlap=overlap, **pp_kw,
            )
        return ials_pp_half_step(
            fixed, x_prev, blk["neighbor_idx"], blk["rating"], blk["mask"],
            lam, alpha, **pp_kw,
        )
    if isinstance(blk, tuple):
        return ials_half_step_bucketed(
            fixed, blk, chunks, entities, lam, alpha, gram=gram,
            solver=solver, overlap=overlap, reg_solve_algo=reg_solve_algo,
            fused_epilogue=fused_epilogue, in_kernel_gather=in_kernel_gather,
            table_dtype=table_dtype,
        )
    if "weight" in blk or "tile_meta" in blk:  # tiled layout
        from cfk_tpu.ops.tiled import ials_tiled_half_step

        # dstream blocks run the weighted dense path (gw premultiply)
        # when staged with their weighted channels; unweighted staging
        # raises a rebuild/steering error inside.
        return ials_tiled_half_step(
            fixed, blk, chunks, entities, lam, alpha, gram=gram,
            solver=solver, overlap=overlap, fused_epilogue=fused_epilogue,
            in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
            table_dtype=table_dtype,
        )
    from cfk_tpu.ops import quant

    fixed = quant.gather_operand_view(fixed, table_dtype)
    if "seg_rel" in blk:
        return ials_half_step_segment(
            fixed, blk["neighbor_idx"], blk["rating"], blk["mask"],
            blk["seg_rel"], blk["chunk_entity"], blk["group_sizes"],
            blk["carry_in"], blk["last_seg"], entities, lam, alpha,
            gram=gram, statics=chunks, solver=solver,
            reg_solve_algo=reg_solve_algo,
        )
    return ials_half_step(
        fixed, blk["neighbor_idx"], blk["rating"], blk["mask"], lam, alpha,
        gram=gram, solver=solver, reg_solve_algo=reg_solve_algo,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "rank", "num_iterations", "lam", "alpha", "dtype", "solver",
        "algorithm", "block_size", "sweeps", "overlap", "fused_epilogue",
        "in_kernel_gather", "reg_solve_algo", "table_dtype",
        "health_every", "health_norm_limit",
        "m_chunks", "u_chunks", "m_entities", "u_entities",
    ),
)
def _train_loop(
    key, movie_blocks, user_blocks, u_stats=None, *, rank, num_iterations, lam,
    alpha, dtype, solver="cholesky", algorithm="als", block_size=32, sweeps=1,
    overlap=None, fused_epilogue=None, in_kernel_gather=None,
    reg_solve_algo=None, table_dtype=None,
    health_every=None, health_norm_limit=0.0,
    m_chunks=None, u_chunks=None, m_entities=None, u_entities=None,
):
    dt = jnp.dtype(dtype)
    if u_stats is not None:  # bucketed layout
        u = init_factors_stats(key, u_stats["rating_sum"], u_stats["count"], rank)
        m_rows = m_entities
    else:
        u = init_factors(
            key, user_blocks["rating"], user_blocks["mask"], user_blocks["count"], rank
        )
        m_rows = movie_blocks["rating"].shape[0]
    u = u.astype(dt)
    m0 = jnp.zeros((m_rows, rank), dtype=dt)

    def step(u, m_prev):
        return _ials_iteration_body(
            u, m_prev, movie_blocks, user_blocks,
            lam=lam, alpha=alpha, dt=dt, solver=solver,
            algorithm=algorithm, block_size=block_size, sweeps=sweeps,
            overlap=overlap, fused_epilogue=fused_epilogue,
            in_kernel_gather=in_kernel_gather,
            reg_solve_algo=reg_solve_algo, table_dtype=table_dtype,
            m_chunks=m_chunks, u_chunks=u_chunks,
            m_entities=m_entities, u_entities=u_entities,
        )

    if health_every is None:
        return lax.fori_loop(
            0, num_iterations, lambda i, c: step(*c), (u, m0)
        )

    # In-carry health word, as in als._train_loop (see there).
    from cfk_tpu.resilience import sentinel

    def probed(i, carry):
        u, m_prev, hw = carry
        u2, m2 = step(u, m_prev)
        hw = sentinel.fold_probe(
            hw, i, u2, m2, every=health_every,
            norm_limit=health_norm_limit, total=num_iterations,
        )
        return u2, m2, hw

    return lax.fori_loop(
        0, num_iterations, probed, (u, m0, sentinel.carry_init())
    )


def _ials_iteration_body(u, m_prev, movie_blocks, user_blocks, *, lam, alpha,
                         dt, solver, algorithm, block_size, sweeps,
                         overlap=None, fused_epilogue=None,
                         in_kernel_gather=None, reg_solve_algo=None,
                         table_dtype=None,
                         m_chunks=None, u_chunks=None,
                         m_entities=None, u_entities=None):
    """One full iALS iteration (movies from users, then users from movies) —
    the single source of the per-iteration math for the fused-loop and
    checkpointed paths (mirrors ``als._iteration_body``)."""
    alg = dict(algorithm=algorithm, block_size=block_size, sweeps=sweeps,
               overlap=overlap, fused_epilogue=fused_epilogue,
               in_kernel_gather=in_kernel_gather,
               reg_solve_algo=reg_solve_algo, table_dtype=table_dtype)
    m = _ials_half(
        u, movie_blocks, lam=lam, alpha=alpha, solver=solver,
        chunks=m_chunks, entities=m_entities, x_prev=m_prev, **alg,
    ).astype(dt)
    u_new = _ials_half(
        m, user_blocks, lam=lam, alpha=alpha, solver=solver,
        chunks=u_chunks, entities=u_entities, x_prev=u, **alg,
    ).astype(dt)
    return (u_new, m)


@functools.partial(
    jax.jit,
    static_argnames=(
        "lam", "alpha", "dtype", "solver", "algorithm", "block_size",
        "sweeps", "overlap", "fused_epilogue", "in_kernel_gather",
        "reg_solve_algo", "table_dtype", "m_chunks", "u_chunks",
        "m_entities", "u_entities",
    ),
    donate_argnums=(0, 1),
)
def _one_iteration(
    u, m_prev, movie_blocks, user_blocks, *, lam, alpha, dtype,
    solver="cholesky", algorithm="als", block_size=32, sweeps=1,
    overlap=None, fused_epilogue=None, in_kernel_gather=None,
    reg_solve_algo=None, table_dtype=None,
    m_chunks=None, u_chunks=None, m_entities=None, u_entities=None,
):
    return _ials_iteration_body(
        u, m_prev, movie_blocks, user_blocks,
        lam=lam, alpha=alpha, dt=jnp.dtype(dtype), solver=solver,
        algorithm=algorithm, block_size=block_size, sweeps=sweeps,
        overlap=overlap, fused_epilogue=fused_epilogue,
        in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
        table_dtype=table_dtype,
        m_chunks=m_chunks, u_chunks=u_chunks,
        m_entities=m_entities, u_entities=u_entities,
    )


def _check_nonnegative_strengths(dataset: Dataset) -> None:
    """iALS semantics require interaction strengths ≥ 0 (confidence
    c = 1 + α·r must be ≥ 1, and the sqrt-reparameterized weight stream
    takes √(α·r) — ``ops.tiled.ials_tiled_half_step``).  A negative rating
    would silently train an inconsistent normal equation, so steer loudly
    at trainer entry (one host-side pass over the ratings, ~0.1 s at
    100M)."""
    import numpy as np

    r = dataset.coo_dense.rating
    if not r.size:
        return
    mn = float(np.min(r))
    if mn < 0:
        raise ValueError(
            "iALS requires non-negative interaction strengths "
            f"(min rating {mn}); rescale or clamp the data "
            "(see cfk_tpu.models.ials docstring)"
        )


def train_ials(
    dataset: Dataset,
    config: IALSConfig,
    *,
    checkpoint_manager=None,
    checkpoint_every: int = 1,
    metrics=None,
    fault_injector=None,
    preemption_guard=None,
    watchdog=None,
) -> ALSModel:
    """Single-device implicit ALS. Ratings in the dataset are interaction
    strengths (counts, play-time, explicit stars — anything ≥ 0).

    Checkpoint semantics match ``als.train_als``: without a manager the loop
    runs as one fused ``fori_loop``; with one, iterations step from Python,
    factors are journaled every ``checkpoint_every`` iterations, and training
    resumes from the latest committed step (the reference's ``setup.sh:18-21``
    journal applies to every model, so ours does too).  Health sentinel /
    recovery / ``fault_injector`` / ``preemption_guard`` / ``watchdog``
    semantics also match ``train_als``."""
    from cfk_tpu.resilience.loop import validate_cadence
    from cfk_tpu.resilience.sentinel import health_from_config
    from cfk_tpu.utils.metrics import Metrics

    from cfk_tpu.plan import plan_for_config

    _check_nonnegative_strengths(dataset)
    health = health_from_config(config)
    validate_cadence(checkpoint_every, health)
    metrics = metrics if metrics is not None else Metrics()
    # Execution plan + provenance (cfk_tpu.plan) — the same seam as
    # als.train_als: pinned config knobs pass through bit-identically,
    # deferred knobs are priced, provenance rides metrics + manifests.
    exec_plan, plan_prov = plan_for_config(
        config,
        num_users=dataset.user_map.num_entities,
        num_movies=dataset.movie_map.num_entities,
        nnz=max(int(dataset.movie_blocks.count.sum()), 1),
        implicit=True,
    )
    knobs = exec_plan.half_step_kwargs(config)
    metrics.note("plan", plan_prov.summary())
    if exec_plan.offload_tier == "host_window":
        # Out-of-core implicit tier (ISSUE 19): the memory-budget
        # predicate said the resident tables cannot fit (or the config
        # pinned the tier), so training runs through the bucketed
        # windowed driver — global-Gram reduction + width-class windows,
        # bit-exact vs the resident bucketed path on the same blocks.
        unsupported = [
            name for name, v in (
                ("checkpoint_manager", checkpoint_manager),
                ("fault_injector", fault_injector),
                ("preemption_guard", preemption_guard),
                ("watchdog", watchdog),
            ) if v is not None
        ]
        if unsupported:
            raise NotImplementedError(
                f"offload_tier='host_window' does not support "
                f"{unsupported} yet — the windowed driver keeps factors "
                "in host stores (see cfk_tpu/offload/windowed.py; "
                "window-level fault injection uses its window_faults=)"
            )
        from cfk_tpu.offload.windowed import train_ials_host_window

        # Same knob-threading seam as als.train_als's host_window exit:
        # every knob the windowed driver reads off the config is either
        # pinned there or deferred with the config's own sentinel — the
        # recorded provenance cannot diverge from execution.
        return train_ials_host_window(
            dataset, config, metrics=metrics, plan_provenance=plan_prov,
        )
    key = jax.random.PRNGKey(config.seed)
    if isinstance(dataset.movie_blocks, BucketedBlocks):
        mblocks, ublocks, u_stats, layout_kw = _bucketed_device_setup(dataset)
    elif isinstance(dataset.movie_blocks, SegmentBlocks):
        mblocks, ublocks, u_stats, layout_kw = _segment_device_setup(dataset)
    elif isinstance(dataset.movie_blocks, TiledBlocks):
        mblocks, ublocks, u_stats, layout_kw = _tiled_device_setup(
            dataset, weighted=dataset.movie_blocks.mode == "dstream"
            or dataset.user_blocks.mode == "dstream"
        )
    else:
        mblocks = _blocks_to_device(dataset.movie_blocks)
        ublocks = _blocks_to_device(dataset.user_blocks)
        u_stats = None
        layout_kw = {}
    stepped = (checkpoint_manager is not None or fault_injector is not None
               or preemption_guard is not None or watchdog is not None)
    if not stepped:
        from cfk_tpu.telemetry import record_event, span

        train_s_before = metrics.phases.get("train", 0.0)
        # One span per fused fori_loop — see models/als.py (per-iteration
        # host spans live on the stepped path only).
        with metrics.phase("train"), \
                span("train/fused_loop", iters=config.num_iterations):
            out = _train_loop(
                key,
                mblocks,
                ublocks,
                u_stats,
                rank=config.rank,
                num_iterations=config.num_iterations,
                lam=config.lam,
                alpha=config.alpha,
                dtype=config.dtype,
                solver=knobs["solver"],
                algorithm=config.algorithm,
                block_size=config.block_size,
                sweeps=config.sweeps,
                overlap=knobs["overlap"],
                fused_epilogue=knobs["fused_epilogue"],
                in_kernel_gather=knobs["in_kernel_gather"],
                reg_solve_algo=knobs["reg_solve_algo"],
                table_dtype=knobs["table_dtype"],
                health_every=None if health is None else health.every,
                health_norm_limit=(
                    0.0 if health is None else health.norm_limit
                ),
                **layout_kw,
            )
            u, m = out[0], out[1]
            u.block_until_ready()
        report = None
        if health is not None:
            from cfk_tpu.resilience.sentinel import report_from_carry

            report = report_from_carry(out[2], u, m)
        if report is None or report.healthy:
            metrics.incr("iterations", config.num_iterations)
            record_event("train", "fused_loop_done",
                         iters=config.num_iterations)
        else:
            import warnings

            # The fused attempt is discarded and replayed below, so keep
            # its accounting out of the headline counters: its wall time
            # moves to "train_discarded" and its iterations are not
            # counted (the stepped replay re-detects this divergence and
            # does the health_trips / rollback accounting exactly once).
            discarded = metrics.phases.get("train", 0.0) - train_s_before
            metrics.phases["train"] = train_s_before
            metrics.phases["train_discarded"] += discarded
            metrics.note("fused_loop_trip", report.summary())
            warnings.warn(
                f"health sentinel tripped in the fused training loop "
                f"({report.summary()}); replaying through the "
                "resilient stepped loop"
            )
            stepped = True
    if stepped:
        dt = jnp.dtype(config.dtype)

        def init_fn():
            if u_stats is not None:
                u = init_factors_stats(
                    key, u_stats["rating_sum"], u_stats["count"], config.rank
                ).astype(dt)
            else:
                u = init_factors(
                    key, ublocks["rating"], ublocks["mask"], ublocks["count"],
                    config.rank,
                ).astype(dt)
            m = jnp.zeros((dataset.movie_blocks.padded_entities, config.rank), dt)
            return u, m

        def make_step(ov):
            def step_fn(u, m):
                return _one_iteration(
                    u, m, mblocks, ublocks,
                    lam=ov.lam, alpha=config.alpha, dtype=config.dtype,
                    solver=knobs["solver"], algorithm=config.algorithm,
                    block_size=config.block_size, sweeps=config.sweeps,
                    overlap=knobs["overlap"],
                    fused_epilogue=ov.fused_epilogue,
                    in_kernel_gather=knobs["in_kernel_gather"],
                    # GJ escalation rung as a threaded jit-static (see
                    # als.train_als make_step).
                    reg_solve_algo=(ov.reg_solve_algo
                                    or knobs["reg_solve_algo"]),
                    table_dtype=knobs["table_dtype"],
                    **layout_kw,
                )

            return step_fn

        from cfk_tpu.resilience.loop import resilient_train_loop
        from cfk_tpu.resilience.policy import Overrides, policy_from_config

        u, m = resilient_train_loop(
            checkpoint_manager,
            model="ials",
            rank=config.rank,
            num_iterations=config.num_iterations,
            u_shape=(dataset.user_blocks.padded_entities, config.rank),
            m_shape=(dataset.movie_blocks.padded_entities, config.rank),
            dtype=dt,
            init_fn=init_fn,
            make_step=make_step,
            base_overrides=Overrides(
                lam=config.lam, fused_epilogue=knobs["fused_epilogue"]
            ),
            metrics=metrics,
            checkpoint_every=checkpoint_every,
            health=health,
            policy=policy_from_config(config),
            fault_injector=fault_injector,
            preemption_guard=preemption_guard,
            watchdog=watchdog,
            plan_provenance=plan_prov,
        )
    return ALSModel(
        user_factors=u,
        movie_factors=m,
        num_users=dataset.user_map.num_entities,
        num_movies=dataset.movie_map.num_entities,
    )


def make_ials_training_step(
    mesh: Mesh,
    config: IALSConfig,
    *,
    m_chunks=None,
    u_chunks=None,
    m_local=None,
    u_local=None,
    mspecs=None,
    uspecs=None,
    segment=False,
    tiled=False,
    m_ring=False,
    u_ring=False,
):
    """Jittable one-full-iteration SPMD step for iALS.

    Per half-iteration: psum the local [k,k] Grams, all_gather the fixed
    factors, solve local entities (per width bucket when ``m_chunks`` given,
    or by segment_sum over the flat local run when ``segment=True``).
    ``config.algorithm="ials++"`` swaps the full solves for warm-started
    subspace sweeps — entities are row-sharded and the sweep is per-entity,
    so the only additional data it needs is the side's own previous local
    factors (no extra collectives).
    """
    from cfk_tpu.parallel.spmd import gathered_half, wrap_step

    if m_ring or u_ring:
        raise ValueError(
            "iALS needs the full fixed side per shard (global-Gram trick): "
            "ring-built tiled blocks are unusable — rebuild with "
            "Dataset.from_coo(..., ring=False)"
        )
    if config.algorithm == "ials++":
        from cfk_tpu.ops.subspace import (
            ials_pp_half_step,
            ials_pp_half_step_bucketed,
        )

        alg = dict(block_size=config.block_size, sweeps=config.sweeps,
                   solver=config.solver,
                   in_kernel_gather=config.in_kernel_gather,
                   fused_epilogue=config.fused_epilogue,
                   reg_solve_algo=config.reg_solve_algo,
                   table_dtype=config.table_dtype)

        if m_chunks is not None:  # bucketed layout

            def pp_bkt(chunks, local):
                def solve(fixed_full, prev_local, blk, gram):
                    return ials_pp_half_step_bucketed(
                        fixed_full, prev_local, blk, chunks, local,
                        config.lam, config.alpha, gram=gram,
                        overlap=config.overlap, **alg,
                    )

                return solve

            return wrap_step(
                mesh, config,
                gathered_half(pp_bkt(m_chunks, m_local), with_gram=True,
                              with_prev=True,
                              table_dtype=config.table_dtype),
                gathered_half(pp_bkt(u_chunks, u_local), with_gram=True,
                              with_prev=True,
                              table_dtype=config.table_dtype),
                mspecs, uspecs, carry_prev=True,
            )

        def pp_padded(fixed_full, prev_local, blk, gram):
            return ials_pp_half_step(
                fixed_full, prev_local, blk["neighbor"], blk["rating"],
                blk["mask"], config.lam, config.alpha, gram=gram, **alg,
            )

        spec = {
            "neighbor": P(AXIS, None),
            "rating": P(AXIS, None),
            "mask": P(AXIS, None),
            "count": P(AXIS),
        }
        half = gathered_half(pp_padded, with_gram=True, with_prev=True,
                             table_dtype=config.table_dtype)
        return wrap_step(mesh, config, half, half, spec, spec,
                         carry_prev=True)

    if tiled:  # tile-padded layout

        from cfk_tpu.ops.tiled import ials_tiled_half_step

        def tl_solve(chunks, local):
            def solve(fixed_full, blk, gram):
                return ials_tiled_half_step(
                    fixed_full, blk, chunks, local, config.lam, config.alpha,
                    gram=gram, solver=config.solver, overlap=config.overlap,
                    fused_epilogue=config.fused_epilogue,
                    in_kernel_gather=config.in_kernel_gather,
                    reg_solve_algo=config.reg_solve_algo,
                    table_dtype=config.table_dtype,
                )

            return solve

        return wrap_step(
            mesh, config,
            gathered_half(tl_solve(m_chunks, m_local), with_gram=True,
                          table_dtype=config.table_dtype),
            gathered_half(tl_solve(u_chunks, u_local), with_gram=True,
                          table_dtype=config.table_dtype),
            mspecs, uspecs,
        )

    if segment:  # flat segment layout

        def seg_solve(statics, local):
            def solve(fixed_full, blk, gram):
                return ials_half_step_segment(
                    fixed_full, blk["neighbor"], blk["rating"], blk["mask"],
                    blk["seg"], blk["entity"], blk["gsizes"], blk["cin"],
                    blk["lseg"], local, config.lam, config.alpha,
                    gram=gram, statics=statics, solver=config.solver,
                    reg_solve_algo=config.reg_solve_algo,
                )

            return solve

        return wrap_step(
            mesh, config,
            gathered_half(seg_solve(m_chunks, m_local), with_gram=True,
                          table_dtype=config.table_dtype),
            gathered_half(seg_solve(u_chunks, u_local), with_gram=True,
                          table_dtype=config.table_dtype),
            mspecs, uspecs,
        )

    if m_chunks is not None:  # bucketed layout

        def bkt_solve(chunks, local):
            def solve(fixed_full, blk, gram):
                return ials_half_step_bucketed(
                    fixed_full, blk, chunks, local, config.lam, config.alpha,
                    gram=gram, solver=config.solver, overlap=config.overlap,
                    reg_solve_algo=config.reg_solve_algo,
                    fused_epilogue=config.fused_epilogue,
                    in_kernel_gather=config.in_kernel_gather,
                    table_dtype=config.table_dtype,
                )

            return solve

        return wrap_step(
            mesh, config,
            gathered_half(bkt_solve(m_chunks, m_local), with_gram=True,
                          table_dtype=config.table_dtype),
            gathered_half(bkt_solve(u_chunks, u_local), with_gram=True,
                          table_dtype=config.table_dtype),
            mspecs, uspecs,
        )

    def padded_solve(fixed_full, blk, gram):
        return ials_half_step(
            fixed_full, blk["neighbor"], blk["rating"], blk["mask"],
            config.lam, config.alpha, gram=gram, solver=config.solver,
            reg_solve_algo=config.reg_solve_algo,
        )

    spec = {
        "neighbor": P(AXIS, None),
        "rating": P(AXIS, None),
        "mask": P(AXIS, None),
        "count": P(AXIS),
    }
    half = gathered_half(padded_solve, with_gram=True,
                         table_dtype=config.table_dtype)
    return wrap_step(mesh, config, half, half, spec, spec)


def train_ials_sharded(
    dataset: Dataset,
    config: IALSConfig,
    mesh: Mesh,
    *,
    checkpoint_manager=None,
    checkpoint_every: int = 1,
    metrics=None,
    fault_injector=None,
    preemption_guard=None,
    watchdog=None,
) -> ALSModel:
    """Multi-device iALS over a 1-D mesh, with optional checkpoint/resume.

    Health sentinel / rollback+escalation / ``fault_injector`` /
    ``preemption_guard`` / ``watchdog`` semantics match
    ``train_als_sharded`` (iALS is all_gather-only, so the probe is the
    step-level factor word — there is no ring carry to instrument)."""
    from cfk_tpu.utils.metrics import Metrics

    from cfk_tpu.config import apply_overlap_xla_flags
    from cfk_tpu.resilience.loop import validate_cadence
    from cfk_tpu.resilience.sentinel import health_from_config

    from cfk_tpu.plan import plan_for_config

    _check_nonnegative_strengths(dataset)
    health = health_from_config(config)
    validate_cadence(checkpoint_every, health)
    apply_overlap_xla_flags(config)
    metrics = metrics if metrics is not None else Metrics()
    from cfk_tpu.parallel.spmd import validate_sharded_dataset
    from cfk_tpu.transport.checkpoint import resume_state_synced

    validate_sharded_dataset(dataset, config, mesh)
    exec_plan, plan_prov = plan_for_config(
        config,
        num_users=dataset.user_map.num_entities,
        num_movies=dataset.movie_map.num_entities,
        nnz=max(int(dataset.movie_blocks.count.sum()), 1),
        implicit=True,
    )
    metrics.note("plan", plan_prov.summary())
    from cfk_tpu.parallel.spmd import _config_under_plan

    # Same seam as train_als_sharded: the sharded step builder reads its
    # knobs off the config, so execute the plan by writing its
    # half_step_kwargs back over the knob fields (identity for
    # pinned/default configs).
    config = _config_under_plan(config, exec_plan)

    def to_tree(blocks):
        return {
            "neighbor": blocks.neighbor_idx,
            "rating": blocks.rating,
            "mask": blocks.mask,
            "count": blocks.count,
        }

    from cfk_tpu.parallel.spmd import gathered_layout_trees, tree_specs

    gathered = gathered_layout_trees(
        dataset, config,
        weighted=isinstance(dataset.movie_blocks, TiledBlocks)
        and "dstream" in (dataset.movie_blocks.mode,
                          dataset.user_blocks.mode),
    )
    stats_init = gathered is not None  # bucketed/segment: init from stats
    step_kw = {}
    if gathered is not None:
        mtree, utree, step_kw = gathered
        step_kw.update(mspecs=tree_specs(mtree), uspecs=tree_specs(utree))
        mtree = shard_rows(mesh, mtree)
        utree = shard_rows(mesh, utree)
    else:
        mtree = shard_rows(mesh, to_tree(dataset.movie_blocks))
        utree = shard_rows(mesh, to_tree(dataset.user_blocks))

    dt = jnp.dtype(config.dtype)

    def init_fn():
        # Draw at the REAL entity count so the init (hence the trajectory)
        # is independent of shard-count padding — see init_factors_stats.
        key = jax.random.PRNGKey(config.seed)
        init_kw = dict(
            rank=config.rank,
            num_entities=dataset.user_blocks.num_entities,
        )
        if stats_init:
            u = jax.jit(
                init_factors_stats, static_argnames=("rank", "num_entities")
            )(
                key,
                jnp.asarray(dataset.user_blocks.rating_sum),
                jnp.asarray(dataset.user_blocks.count),
                **init_kw,
            ).astype(dt)
        else:
            u = jax.jit(
                init_factors, static_argnames=("rank", "num_entities")
            )(
                key,
                jnp.asarray(dataset.user_blocks.rating),
                jnp.asarray(dataset.user_blocks.mask),
                jnp.asarray(dataset.user_blocks.count),
                **init_kw,
            ).astype(dt)
        u = shard_rows(mesh, u)
        m = shard_rows(
            mesh, np.zeros((dataset.movie_blocks.padded_entities, config.rank), dt)
        )
        return u, m

    from cfk_tpu.parallel.spmd import _sharded_resilient_loop

    u, m = _sharded_resilient_loop(
        checkpoint_manager,
        model="ials",
        dataset=dataset,
        config=config,
        mesh=mesh,
        dtype=dt,
        init_fn=init_fn,
        make_raw_step=lambda cfg: make_ials_training_step(
            mesh, cfg, **step_kw
        ),
        mtree=mtree,
        utree=utree,
        metrics=metrics,
        checkpoint_every=checkpoint_every,
        health=health,
        fault_injector=fault_injector,
        preemption_guard=preemption_guard,
        watchdog=watchdog,
        resume_fn=lambda: resume_state_synced(
            checkpoint_manager,
            rank=config.rank,
            model="ials",
            num_iterations=config.num_iterations,
            u_shape=(dataset.user_blocks.padded_entities, config.rank),
            m_shape=(dataset.movie_blocks.padded_entities, config.rank),
            num_shards=config.num_shards,
        ),
        save_meta={"rank": config.rank, "model": "ials",
                   "num_shards": config.num_shards},
        plan_provenance=plan_prov,
    )

    return ALSModel(
        user_factors=u,
        movie_factors=m,
        num_users=dataset.user_map.num_entities,
        num_movies=dataset.movie_map.num_entities,
    )
