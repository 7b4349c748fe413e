"""Explicit-feedback ALS-WR — the flagship model.

Single-device training loop with exact reference semantics
(``apps/ALSApp.java:115-151`` unrolled topology, re-expressed as a jitted
``lax.fori_loop``):

  - init user factors: avg-rating + U(0,1) (``processors/UFeatureInitializer.java:50-56``)
  - per iteration i: solve movies from users (``MFeatureCalculator-i``), then
    users from movies (``UFeatureCalculator-i``)
  - prediction P = U·Mᵀ (``processors/FeatureCollector.java:91-92``), rows =
    users ascending id, cols = movies ascending id.

The multi-device SPMD path lives in ``cfk_tpu.parallel``; this module is the
1-shard special case and the semantic reference for its equivalence tests.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from cfk_tpu.config import ALSConfig
from cfk_tpu.data.blocks import (
    BucketedBlocks,
    Dataset,
    PaddedBlocks,
    SegmentBlocks,
    TiledBlocks,
)
from cfk_tpu.ops.solve import (
    als_half_step,
    als_half_step_bucketed,
    als_half_step_segment,
    init_factors,
    init_factors_stats,
)


@dataclasses.dataclass(frozen=True)
class ALSModel:
    """Trained factor matrices (rows = ascending external id order)."""

    user_factors: jax.Array  # [num_users, k]  (includes pad rows at the end)
    movie_factors: jax.Array  # [num_movies, k]
    num_users: int
    num_movies: int

    def host_factors(self) -> tuple[np.ndarray, np.ndarray]:
        """float32 host copies of (U, M) with pad rows trimmed.

        The one place factor hosting is defined — the dense predictor and
        the factored evaluators (``cfk_tpu.eval.metrics.mse_rmse_from_model``,
        ``cfk_tpu.eval.ranking.ranks_from_model``) all share it, so they can
        never diverge on trimming/dtype.  Works under multi-process JAX too:
        non-addressable sharded factors are process_allgather'd so every host
        sees the same matrices.  Cached: the post-training path (MSE eval,
        ranking eval, CSV dump) fetches from device exactly once.
        """
        return self._host_factors

    @functools.cached_property
    def _host_factors(self) -> tuple[np.ndarray, np.ndarray]:
        from cfk_tpu.parallel.mesh import to_host

        u = to_host(self.user_factors)[: self.num_users].astype(np.float32)
        m = to_host(self.movie_factors)[: self.num_movies].astype(np.float32)
        return u, m

    def predict_dense(self, *, allow_huge: bool = False) -> np.ndarray:
        """Dense prediction matrix P = U·Mᵀ, [num_users, num_movies].

        Refuses matrices over ~4e9 cells (16 GB float32) unless
        ``allow_huge`` — at full-Netflix scale the dense matrix is the one
        thing that genuinely cannot scale (the reference's collector had
        the same ceiling); serve with ``recommend_top_k`` instead, which is
        chunked and never materializes P.
        """
        cells = self.num_users * self.num_movies
        if cells > 4_000_000_000 and not allow_huge:
            raise ValueError(
                f"dense prediction matrix would be {self.num_users}×"
                f"{self.num_movies} = {cells:.2e} float32 cells; use "
                "recommend_top_k (chunked top-K serving) or pass "
                "allow_huge=True if you really have the RAM"
            )
        u, m = self.host_factors()
        return u @ m.T

    def recommend_top_k(self, user_rows, k: int = 10, *, dataset=None,
                        chunk: int = 8192):
        """Top-K movie rows per user row; see ``cfk_tpu.eval.recommend``."""
        from cfk_tpu.eval.recommend import recommend_top_k

        return recommend_top_k(self, user_rows, k, dataset=dataset, chunk=chunk)


def _blocks_to_device(blocks: PaddedBlocks) -> dict[str, jax.Array]:
    return {
        "neighbor_idx": jnp.asarray(blocks.neighbor_idx),
        "rating": jnp.asarray(blocks.rating),
        "mask": jnp.asarray(blocks.mask),
        "count": jnp.asarray(blocks.count),
    }


def _bucketed_to_device(blocks: BucketedBlocks):
    """Device trees (pytree of per-bucket dicts) + static chunk hints."""
    trees, chunks = blocks.to_tree()
    return jax.tree.map(jnp.asarray, trees), chunks


def _segment_to_device(blocks: SegmentBlocks) -> dict[str, jax.Array]:
    return {
        "neighbor_idx": jnp.asarray(blocks.neighbor_idx),
        "rating": jnp.asarray(blocks.rating),
        "mask": jnp.asarray(blocks.mask),
        "seg_rel": jnp.asarray(blocks.seg_rel),
        "chunk_entity": jnp.asarray(blocks.chunk_entity),
        "chunk_count": jnp.asarray(blocks.chunk_count),
        "group_sizes": jnp.asarray(blocks.group_sizes),
        "carry_in": jnp.asarray(blocks.carry_in),
        "last_seg": jnp.asarray(blocks.last_seg),
    }


def _stats_setup_guard(blocks, layout: str) -> None:
    if blocks.num_shards != 1:
        raise ValueError(
            f"{layout} blocks were built for num_shards={blocks.num_shards}; "
            "their row/segment indices are shard-local, so the single-device "
            "trainer needs num_shards=1 — use the sharded trainer, or rebuild "
            "with Dataset.from_coo(..., num_shards=1)"
        )


def _bucketed_device_setup(dataset: Dataset):
    """Single-device bucketed setup shared by train_als / train_ials:
    device block trees, user init stats, and the static layout kwargs."""
    mb, ub = dataset.movie_blocks, dataset.user_blocks
    _stats_setup_guard(mb, "bucketed")
    mblocks, m_chunks = _bucketed_to_device(mb)
    ublocks, u_chunks = _bucketed_to_device(ub)
    u_stats = {
        "rating_sum": jnp.asarray(ub.rating_sum),
        "count": jnp.asarray(ub.count),
    }
    layout_kw = dict(
        m_chunks=m_chunks,
        u_chunks=u_chunks,
        m_entities=mb.padded_entities,
        u_entities=ub.padded_entities,
    )
    return mblocks, ublocks, u_stats, layout_kw


def _tiled_host_arrays(blocks: TiledBlocks, weighted: bool = False
                       ) -> dict[str, np.ndarray]:
    """The host arrays one tiled half-step reads, keyed as the device dict
    (``_tiled_to_device`` uploads exactly these; ``chip_smoke.py`` derives
    the step program's avals from them)."""
    if blocks.mode == "dstream":
        # Window metadata rides in tile_meta; upload only what the model's
        # kernel reads — the weighted channels (tile-aligned weight +
        # stream-aligned rating_dense, ~1 GB at full Netflix) only for
        # iALS, never for the unit-weight explicit path.
        d = {
            "neighbor_idx": blocks.neighbor_idx,
            "rating": blocks.rating,
            "tile_meta": blocks.tile_meta,
            "chunk_entity": blocks.chunk_entity,
            "chunk_count": blocks.chunk_count,
            "carry_in": blocks.carry_in,
            "last_seg": blocks.last_seg,
            "count": blocks.count,
        }
        if weighted:
            if not blocks.weight.size or blocks.rating_dense is None:
                raise ValueError(
                    "these dense-stream blocks predate the weighted "
                    "channels — rebuild the dataset (delete its cache)"
                )
            d["weight"] = blocks.weight
            d["rating_dense"] = blocks.rating_dense
        return d
    return {
        "neighbor_idx": blocks.neighbor_idx,
        "rating": blocks.rating,
        "weight": blocks.weight,
        "tile_seg": blocks.tile_seg,
        "chunk_base": blocks.chunk_base,
        "chunk_entity": blocks.chunk_entity,
        "chunk_count": blocks.chunk_count,
        "carry_in": blocks.carry_in,
        "last_seg": blocks.last_seg,
        "slice_starts": blocks.slice_starts,
        "count": blocks.count,
    }


def _tiled_to_device(blocks: TiledBlocks, weighted: bool = False
                     ) -> dict[str, jax.Array]:
    return {name: jnp.asarray(x)
            for name, x in _tiled_host_arrays(blocks, weighted).items()}


def _tiled_layout_kw(dataset: Dataset) -> dict:
    """The tiled layout's static step kwargs; statics carry
    ("tiled", mode, ...)."""
    mb, ub = dataset.movie_blocks, dataset.user_blocks
    return dict(
        m_chunks=("tiled", mb.mode) + mb.statics,
        u_chunks=("tiled", ub.mode) + ub.statics,
        m_entities=mb.padded_entities,
        u_entities=ub.padded_entities,
    )


def _tiled_device_setup(dataset: Dataset, weighted: bool = False):
    """Single-device tiled-layout setup.

    ``weighted=True`` (the iALS trainer) stages the dense-stream weighted
    channels too."""
    mb, ub = dataset.movie_blocks, dataset.user_blocks
    _stats_setup_guard(mb, "tiled")
    u_stats = {
        "rating_sum": jnp.asarray(ub.rating_sum),
        "count": jnp.asarray(ub.count),
    }
    return (_tiled_to_device(mb, weighted), _tiled_to_device(ub, weighted),
            u_stats, _tiled_layout_kw(dataset))


def _train_loop_statics(config: ALSConfig, knobs: dict, *, solve_chunk,
                        health) -> dict:
    """``_train_loop``'s static arguments for a config under its resolved
    plan knobs — ``train_als`` calls the loop with exactly these, and
    ``chip_smoke.py`` compiles the same program from avals with them."""
    return dict(
        rank=config.rank,
        num_iterations=config.num_iterations,
        lam=config.lam,
        solve_chunk=solve_chunk,
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
        health_norm_limit=0.0 if health is None else health.norm_limit,
    )


def _segment_device_setup(dataset: Dataset):
    """Single-device segment-layout setup: flat device arrays, init stats,
    static local-entity counts + scan-window hints."""
    mb, ub = dataset.movie_blocks, dataset.user_blocks
    _stats_setup_guard(mb, "segment")
    u_stats = {
        "rating_sum": jnp.asarray(ub.rating_sum),
        "count": jnp.asarray(ub.count),
    }
    layout_kw = dict(
        m_chunks=mb.statics,
        u_chunks=ub.statics,
        m_entities=mb.padded_entities,
        u_entities=ub.padded_entities,
    )
    return _segment_to_device(mb), _segment_to_device(ub), u_stats, layout_kw


def _half(fixed, blk, *, lam, solve_chunk, solver, chunks=None, entities=None,
          x_prev=None, algorithm="als", block_size=32, sweeps=1,
          overlap=None, fused_epilogue=None, in_kernel_gather=None,
          reg_solve_algo=None, table_dtype=None):
    """Solve one side against fixed factors; dispatches on the block layout
    (tuple = width buckets, dict with segment ids = flat segment run,
    other dict = one padded rectangle).  ``algorithm="als++"`` runs
    warm-started subspace sweeps from ``x_prev`` instead of full solves
    (padded/bucketed layouts).  ``table_dtype`` quantizes the gather table
    (``ops.quant``) — the tiled/bucketed/subspace entries quantize and
    fold internally; the padded/segment paths take the bf16 cast here
    (config validation refuses int8 for them)."""
    if algorithm == "als++":
        from cfk_tpu.ops.subspace import (
            als_pp_half_step,
            als_pp_half_step_bucketed,
        )

        pp_kw = dict(
            block_size=block_size, sweeps=sweeps, solver=solver,
            in_kernel_gather=in_kernel_gather,
            fused_epilogue=fused_epilogue, reg_solve_algo=reg_solve_algo,
            table_dtype=table_dtype,
        )
        if isinstance(blk, tuple):
            return als_pp_half_step_bucketed(
                fixed, x_prev, blk, chunks, entities, lam,
                overlap=overlap, **pp_kw,
            )
        return als_pp_half_step(
            fixed, x_prev, blk["neighbor_idx"], blk["rating"], blk["mask"],
            blk["count"], lam, **pp_kw,
        )
    if isinstance(blk, tuple):
        return als_half_step_bucketed(
            fixed, blk, chunks, entities, lam, solver=solver,
            overlap=overlap, reg_solve_algo=reg_solve_algo,
            fused_epilogue=fused_epilogue, in_kernel_gather=in_kernel_gather,
            table_dtype=table_dtype,
        )
    if "weight" in blk or "tile_meta" in blk:  # tiled layout
        from cfk_tpu.ops.tiled import tiled_half_step

        return tiled_half_step(
            fixed, blk, chunks, entities, lam, solver=solver,
            overlap=overlap, fused_epilogue=fused_epilogue,
            in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
            table_dtype=table_dtype,
        )
    from cfk_tpu.ops import quant

    fixed = quant.gather_operand_view(fixed, table_dtype)
    if "seg_rel" in blk:
        return als_half_step_segment(
            fixed,
            blk["neighbor_idx"],
            blk["rating"],
            blk["mask"],
            blk["seg_rel"],
            blk["chunk_entity"],
            blk["chunk_count"],
            blk["group_sizes"],
            blk["carry_in"],
            blk["last_seg"],
            entities,
            lam,
            statics=chunks,
            solver=solver,
            reg_solve_algo=reg_solve_algo,
        )
    return als_half_step(
        fixed,
        blk["neighbor_idx"],
        blk["rating"],
        blk["mask"],
        blk["count"],
        lam,
        solve_chunk=solve_chunk,
        solver=solver,
        overlap=overlap,
        reg_solve_algo=reg_solve_algo,
    )


_LAYOUT_STATICS = ("m_chunks", "u_chunks", "m_entities", "u_entities")
_ALG_STATICS = ("algorithm", "block_size", "sweeps", "overlap",
                "fused_epilogue", "in_kernel_gather", "reg_solve_algo",
                "table_dtype")


@functools.partial(
    jax.jit,
    static_argnames=("rank", "num_iterations", "lam", "solve_chunk", "dtype",
                     "solver", "health_every", "health_norm_limit")
    + _LAYOUT_STATICS + _ALG_STATICS,
)
def _train_loop(
    key: jax.Array,
    movie_blocks,
    user_blocks,
    u_stats=None,
    *,
    rank: int,
    num_iterations: int,
    lam: float,
    solve_chunk: int | None,
    dtype: str = "float32",
    solver: str = "cholesky",
    algorithm: str = "als",
    block_size: int = 32,
    sweeps: int = 1,
    overlap: bool | None = None,
    fused_epilogue: bool | None = None,
    in_kernel_gather: bool | None = None,
    reg_solve_algo: str | None = None,
    table_dtype: str | None = None,
    health_every: int | None = None,
    health_norm_limit: float = 0.0,
    m_chunks=None,
    u_chunks=None,
    m_entities=None,
    u_entities=None,
):
    dt = jnp.dtype(dtype)
    if u_stats is not None:  # bucketed layout: init from per-entity stats
        u = init_factors_stats(key, u_stats["rating_sum"], u_stats["count"], rank)
        m_rows = m_entities
    else:
        u = init_factors(
            key, user_blocks["rating"], user_blocks["mask"], user_blocks["count"], rank
        )
        m_rows = movie_blocks["rating"].shape[0]
    u = u.astype(dt)
    m0 = jnp.zeros((m_rows, rank), dtype=dt)

    def step(i, u, m_prev):
        return _iteration_body(
            u, movie_blocks, user_blocks,
            lam=lam, solve_chunk=solve_chunk, dt=dt, solver=solver,
            algorithm=algorithm, block_size=block_size, sweeps=sweeps,
            overlap=overlap, fused_epilogue=fused_epilogue,
            in_kernel_gather=in_kernel_gather,
            reg_solve_algo=reg_solve_algo, table_dtype=table_dtype,
            m_prev=m_prev,
            m_chunks=m_chunks, u_chunks=u_chunks,
            m_entities=m_entities, u_entities=u_entities,
        )

    if health_every is None:
        u_final, m_final = jax.lax.fori_loop(
            0, num_iterations, lambda i, c: step(i, *c), (u, m0)
        )
        return u_final, m_final

    # Health sentinel folded into the fori_loop carry: an int32
    # [first_bad_iter, reasons] word updated (via lax.cond, so off-cadence
    # iterations pay nothing) every ``health_every`` iterations — the host
    # inspects it once after the loop and reruns through the resilient
    # stepped loop only when it tripped (cfk_tpu.resilience.sentinel).
    from cfk_tpu.resilience import sentinel

    def probed(i, carry):
        u, m_prev, hw = carry
        u2, m2 = step(i, u, m_prev)
        hw = sentinel.fold_probe(
            hw, i, u2, m2, every=health_every,
            norm_limit=health_norm_limit, total=num_iterations,
        )
        return u2, m2, hw

    return jax.lax.fori_loop(
        0, num_iterations, probed, (u, m0, sentinel.carry_init())
    )


def _iteration_body(u, movie_blocks, user_blocks, *, lam, solve_chunk, dt,
                    solver="cholesky", algorithm="als", block_size=32,
                    sweeps=1, overlap=None, fused_epilogue=None,
                    in_kernel_gather=None, reg_solve_algo=None,
                    table_dtype=None, m_prev=None, m_chunks=None,
                    u_chunks=None, m_entities=None, u_entities=None):
    """One full iteration (solve M from U, then U from M) — the single source
    of the per-iteration math for both the fused-loop and checkpointed paths.

    Factors are stored in ``dt`` (bfloat16 halves HBM traffic); Gram
    contractions accumulate float32 inside the half-step kernels.
    ``algorithm="als++"`` warm-starts each side from its previous factors
    (``m_prev`` / the ``u`` carry) with subspace sweeps.
    """
    alg = dict(algorithm=algorithm, block_size=block_size, sweeps=sweeps,
               overlap=overlap, fused_epilogue=fused_epilogue,
               in_kernel_gather=in_kernel_gather,
               reg_solve_algo=reg_solve_algo, table_dtype=table_dtype)
    m = _half(
        u, movie_blocks, lam=lam, solve_chunk=solve_chunk, solver=solver,
        chunks=m_chunks, entities=m_entities, x_prev=m_prev, **alg,
    ).astype(dt)
    u_new = _half(
        m, user_blocks, lam=lam, solve_chunk=solve_chunk, solver=solver,
        chunks=u_chunks, entities=u_entities, x_prev=u, **alg,
    ).astype(dt)
    return u_new, m


@functools.partial(
    jax.jit,
    static_argnames=("lam", "solve_chunk", "dtype", "solver")
    + _LAYOUT_STATICS + _ALG_STATICS,
    donate_argnums=(0, 1),
)
def _one_iteration(
    u: jax.Array,
    m_prev: jax.Array,
    movie_blocks,
    user_blocks,
    *,
    lam: float,
    solve_chunk: int | None,
    dtype: str,
    solver: str = "cholesky",
    algorithm: str = "als",
    block_size: int = 32,
    sweeps: int = 1,
    overlap: bool | None = None,
    fused_epilogue: bool | None = None,
    in_kernel_gather: bool | None = None,
    reg_solve_algo: str | None = None,
    table_dtype: str | None = None,
    m_chunks=None,
    u_chunks=None,
    m_entities=None,
    u_entities=None,
) -> tuple[jax.Array, jax.Array]:
    return _iteration_body(
        u, movie_blocks, user_blocks,
        lam=lam, solve_chunk=solve_chunk, dt=jnp.dtype(dtype), solver=solver,
        algorithm=algorithm, block_size=block_size, sweeps=sweeps,
        overlap=overlap, fused_epilogue=fused_epilogue,
        in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
        table_dtype=table_dtype, m_prev=m_prev,
        m_chunks=m_chunks, u_chunks=u_chunks,
        m_entities=m_entities, u_entities=u_entities,
    )


def train_als(
    dataset: Dataset,
    config: ALSConfig,
    *,
    checkpoint_manager=None,
    checkpoint_every: int = 1,
    metrics=None,
    fault_injector=None,
    preemption_guard=None,
    watchdog=None,
    warm_start=None,
) -> ALSModel:
    """Train ALS-WR on one device. Returns factors in ascending-id order.

    Without a checkpoint manager the whole loop runs as one fused
    ``fori_loop`` program; with one, iterations are stepped from Python so
    factors can be saved every ``checkpoint_every`` iterations and training
    resumes from the latest step.  ``metrics`` (a ``cfk_tpu.utils.metrics.
    Metrics``) records phase timings and iteration counters when provided.

    ``config.health_check_every`` arms the numerical-health sentinel: the
    fused loop folds the probe into its carry and, when it trips, the run
    is replayed through the resilient stepped loop, which rolls back to the
    last good state and climbs the escalation ladder
    (``cfk_tpu.resilience``).  ``fault_injector`` (chaos testing only)
    forces the stepped loop so faults can fire at step boundaries.

    ``preemption_guard``/``watchdog`` (``cfk_tpu.resilience.preempt``) arm
    preemption tolerance: they also force the stepped loop (the fused
    ``fori_loop`` exposes no iteration boundary to poll), which polls the
    guard between iterations — on SIGTERM/SIGINT it drains the async
    checkpoint writer, commits a final checkpoint, and returns resumable —
    and ticks the watchdog per completed iteration.

    ``warm_start=(u0, m0)`` seeds the factors instead of the reference's
    avg-rating + U(0,1) init — the streaming fold-in path's periodic full
    retrains pass the live factors here (``cfk_tpu.streaming.session``).
    Rows are host arrays in this dataset's ascending-id order; shorter
    matrices are zero-padded to the padded entity counts, longer ones
    refused.  Forces the stepped (resilient) loop; a resumable checkpoint
    in ``checkpoint_manager`` still wins over the seed (resume semantics
    are unchanged — the warm start only defines iteration 0).
    """
    from cfk_tpu.resilience.loop import validate_cadence
    from cfk_tpu.resilience.sentinel import health_from_config
    from cfk_tpu.utils.metrics import Metrics

    from cfk_tpu.config import enable_compile_cache
    from cfk_tpu.plan import plan_for_config

    # Before the first compile (ISSUE 13): warm-start compile caching.
    enable_compile_cache(getattr(config, "compile_cache_dir", None))
    health = health_from_config(config)
    validate_cadence(checkpoint_every, health)
    metrics = metrics if metrics is not None else Metrics()
    num_ratings = int(dataset.movie_blocks.count.sum())
    metrics.gauge("num_users", dataset.user_map.num_entities)
    metrics.gauge("num_movies", dataset.movie_map.num_entities)
    metrics.gauge("num_ratings", num_ratings)
    # Resolve the execution plan (cfk_tpu.plan): the config's concrete
    # knobs arrive as pinned constraints, the deferred ones are priced by
    # the cost model, and the trainer reads the knob values through the
    # plan seam below — bit-identical routing for pinned/default configs,
    # with provenance (chosen plan + estimated cost + cache hit/miss)
    # recorded in the metrics and in every checkpoint manifest.
    exec_plan, plan_prov = plan_for_config(
        config,
        num_users=dataset.user_map.num_entities,
        num_movies=dataset.movie_map.num_entities,
        nnz=max(num_ratings, 1),
    )
    knobs = exec_plan.half_step_kwargs(config)
    metrics.note("plan", plan_prov.summary())
    if exec_plan.offload_tier == "host_window":
        # Out-of-core tier (ISSUE 11): the memory-budget predicate said
        # the resident tables cannot fit (or the config pinned the tier),
        # so training runs through the windowed host-offload driver —
        # bit-exact vs the resident path on the same stream blocks.
        unsupported = [
            name for name, v in (
                ("checkpoint_manager", checkpoint_manager),
                ("fault_injector", fault_injector),
                ("preemption_guard", preemption_guard),
                ("watchdog", watchdog),
                ("warm_start", warm_start),
            ) if v is not None
        ]
        if unsupported:
            raise NotImplementedError(
                f"offload_tier='host_window' does not support "
                f"{unsupported} yet — the windowed driver keeps factors "
                "in host stores (see cfk_tpu/offload/windowed.py; "
                "window-level fault injection uses its window_faults=)"
            )
        from cfk_tpu.offload.windowed import train_als_host_window

        # Threading the CONFIG here is exactly the plan's half_step_kwargs
        # seam: every knob the windowed driver reads is either always
        # pinned by the config (table_dtype, overlap — concrete dataclass
        # defaults) or deferred, in which case half_step_kwargs returns
        # the config's own sentinel (None/"auto") — the same value the
        # driver reads off the config.  Execution can therefore never
        # diverge from the provenance recorded above.
        return train_als_host_window(
            dataset, config, metrics=metrics, plan_provenance=plan_prov,
        )
    key = jax.random.PRNGKey(config.seed)
    bucketed = isinstance(dataset.movie_blocks, BucketedBlocks)
    segment = isinstance(dataset.movie_blocks, SegmentBlocks)
    tiled = isinstance(dataset.movie_blocks, TiledBlocks)
    with metrics.phase("blocks_to_device"):
        if bucketed:
            mblocks, ublocks, u_stats, layout_kw = _bucketed_device_setup(dataset)
        elif segment:
            mblocks, ublocks, u_stats, layout_kw = _segment_device_setup(dataset)
        elif tiled:
            mblocks, ublocks, u_stats, layout_kw = _tiled_device_setup(dataset)
        else:
            mblocks = _blocks_to_device(dataset.movie_blocks)
            ublocks = _blocks_to_device(dataset.user_blocks)
            u_stats = None
            layout_kw = {}
    # The padded layout consumes the unified HBM budget at solve time:
    # entities per chunk derived from the wider rectangle (conservative for
    # the narrower side).  Build-time layouts consumed it at from_coo.
    solve_chunk = None
    if not (bucketed or segment or tiled):
        width = max(
            dataset.movie_blocks.neighbor_idx.shape[1],
            dataset.user_blocks.neighbor_idx.shape[1],
        )
        solve_chunk = config.padded_solve_chunk(width)
    stepped = (checkpoint_manager is not None or fault_injector is not None
               or preemption_guard is not None or watchdog is not None
               or warm_start is not None)
    if not stepped:
        from cfk_tpu.telemetry import record_event, span

        train_s_before = metrics.phases.get("train", 0.0)
        # ONE span for the whole fused fori_loop: the iterations live
        # inside a single jit, so per-iteration host spans exist only on
        # the stepped path (resilience/loop.py) — the device-side
        # breakdown is the jax-profiler trace's job (same --trace-dir).
        with metrics.phase("train"), \
                span("train/fused_loop", iters=config.num_iterations):
            out = _train_loop(
                key, mblocks, ublocks, u_stats,
                **_train_loop_statics(config, knobs, solve_chunk=solve_chunk,
                                      health=health),
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

        def _padded_seed(x, rows, what):
            x = np.asarray(x)
            if x.shape[0] > rows or x.shape[1:] != (config.rank,):
                raise ValueError(
                    f"warm_start {what} factors have shape {x.shape}; this "
                    f"dataset solves [{rows}, {config.rank}] (padded rows) — "
                    "rebuild the seed against the same entity universe"
                )
            out = jnp.zeros((rows, config.rank), dt)
            return out.at[: x.shape[0]].set(jnp.asarray(x, dtype=dt))

        def init_fn():
            if warm_start is not None:
                wu, wm = warm_start
                return (
                    _padded_seed(
                        wu, dataset.user_blocks.padded_entities, "user"),
                    _padded_seed(
                        wm, dataset.movie_blocks.padded_entities, "movie"),
                )
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
                    lam=ov.lam, solve_chunk=solve_chunk,
                    dtype=config.dtype, solver=knobs["solver"],
                    algorithm=config.algorithm, block_size=config.block_size,
                    sweeps=config.sweeps, overlap=knobs["overlap"],
                    fused_epilogue=ov.fused_epilogue,
                    in_kernel_gather=knobs["in_kernel_gather"],
                    # The GJ escalation rung: a real jit-static now, so the
                    # rebuilt step re-traces with the overridden elimination
                    # (it used to ride the CFK_REG_SOLVE_ALGO env var).
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
            model="als",
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
