"""Out-of-core training: host factor stores + windowed half-steps.

The ALX move (arXiv 2112.02194): accept that factor tables exceed one
chip's HBM, keep them in host RAM (``HostFactorStore``), and stream
WINDOWS of the fixed side through the device while the solve streams the
chunk scan.  The execution per chunk is literally the resident tiled
half-step — ``ops.tiled.als_half_step_tiled`` (stream/all_gather mode) or
the ring schedules' per-slice chunk body (``parallel.spmd.
_make_tiled_slice_grams``'s ops, ring/hier_ring mode) run unmodified
against the staged window with rebased indices (PR 4's in-kernel gather
reads from ANY-memory-space tables, so the kernels just point at the
window) — which is what makes the windowed path BIT-EXACT vs the resident
path (``tests/test_offload.py`` + ``tests/test_offload_sharded.py`` pin it
per knob: shard count, exchange/ici_group, table dtype, gather mode, fused
epilogue, overlap).

Schedule per half-step (the ``ops/pipeline.py`` shape, one level up):

    stage(window 0)                     # host gather + device_put
    for w: stage(w+1)  ||  compute(w)   # double buffer
            scatter solved rows of w back to the host store

Window w's jitted compute is DISPATCHED first (jit dispatch is async),
then window w+1's host gather + ``device_put`` run under it — so the host
staging work AND the PCIe transfer both hide under the Gram+solve exactly
as the chunk pipelines overlap their gathers.  In the sharded ring modes
the same double buffer runs under the visit schedule's inner-ICI
rotations: window w+1 of the NEXT slice visit stages while the current
slice's Grams accumulate, and the only DCN-share traffic is each window's
row set gathered from a remote store shard — the "window residual" —
never the flat ring's O(S) full-table rotation.

Staged bytes per dtype (ISSUE 12): f32 windows stage 4 B/cell, bf16 2
(the cast is per-element, host-cast == device-cast bit-exactly), and int8
tables stage the (1-byte codes, one f32 per-row scale) pair the kernels
consume — a quarter of the f32 bytes — quantized ON THE HOST by
``store.quantize_rows_host``, whose arithmetic is pinned bit-identical to
the in-jit ``ops.quant.quantize_table`` (the per-row scheme makes a
window's rows quantize independently of the table around them).

``train_als_host_window`` is the ``offload_tier="host_window"`` executor
the planner resolves oversized problems to (``plan/resolver.py`` gates the
``device`` tier on ``offload.budget`` — the same per-shard predicate the
window sizing here consumes, so a plan can never promise a resident table
that does not fit).  Explicit ALS on the tiled layout; one process
driving all shards (each shard's windows stage against the entity-range
store shard placement a multi-host deployment would pin per host).
"""

from __future__ import annotations

import functools
import time

import jax
import numpy as np

from cfk_tpu.config import ALSConfig
from cfk_tpu.offload import budget as _budget
from cfk_tpu.offload.staging import (
    DEFAULT_POOL_DEPTH,
    StagingStats,
    WindowStager,
    pool_workers_for,
    resolve_staging,
    stats_add,
)
# _np_dtype: the ONE validated name→numpy-dtype mapping (raises on
# anything but float32/bfloat16 — no silent fallthrough).
from cfk_tpu.offload.store import (
    HostFactorStore,
    StoreIntegrityError,
    _np_dtype,
    quantize_rows_host,
)
from cfk_tpu.offload.window import (
    BucketWindowPlan,
    RingWindowPlan,
    WindowPlan,
    build_bucket_window_plan,
    build_ring_window_plan,
    build_window_plan,
)
from cfk_tpu.telemetry import record_event, span
from cfk_tpu.telemetry.recorder import dump_flight

# Trace counter for the windowed driver's jits: the bodies below bump it
# once per TRACE (python side effects run only while tracing), so the
# staging-A/B bench rows can report `trace_count` and a warm compile
# cache (ALSConfig.compile_cache_dir) shows up as fewer compile seconds
# at an unchanged trace count.
_TRACES = [0]


def trace_count() -> int:
    """Traces of the windowed driver's jitted programs this process."""
    return _TRACES[0]


def _stage_dtype(store_dtype: str, table_dtype: str | None) -> str:
    """The dtype windows cross PCIe at: bf16 tables stage bf16 (half the
    transfer), int8 tables stage the (int8 codes, f32 per-row scales)
    pair (a quarter — ``quantize_rows_host`` on the host side of the
    PCIe, bit-identical to the in-jit quantization the resident path
    runs); f32 stages the storage dtype."""
    if table_dtype in ("bfloat16", "int8"):
        return table_dtype
    return store_dtype


def _stage_cell_bytes(stage_name: str) -> tuple[int, int]:
    """(bytes per staged table cell, per-row overhead bytes)."""
    if stage_name == "int8":
        return 1, 4  # codes + one f32 scale per row
    return _np_dtype(stage_name).itemsize, 0


def _staged_donate_argnums(base: tuple, staged: tuple) -> tuple:
    """Donation positions for a window jit: ``base`` (device-owned
    carries — always donatable) plus the staged-table positions on TPU
    only.  On CPU ``jax.device_put`` ZERO-COPY-ALIASES host numpy arrays
    (measured in this container), and jax refuses to donate an aliased
    buffer with a "donated buffers were not usable" warning per program —
    so the staged (tbl, scale) pair donates only where the PCIe copy
    makes it device-owned (on-TPU validation backlog re-measures the
    reclaim).  The chunk operands are NEVER donated: they are stage-time
    VIEWS of the TiledBlocks, and a donated alias would let XLA scribble
    on the dataset itself."""
    if jax.default_backend() == "tpu":
        return base + staged
    return base


def _window_half_impl(tbl, scale, nb, rt, wt, ts, ent, cnt, cin, lseg, *,
                      statics, lam, solver, overlap, fused_epilogue,
                      in_kernel_gather, reg_solve_algo, table_dtype,
                      out_dtype):
    """One window's chunks through the UNMODIFIED stream-mode half-step
    (``return_chunk_rows`` skips the device scatter — the host does it).

    ``scale`` is the staged int8 window's per-row dequant scale (None for
    f32/bf16 staging): the fold into the weight channel happens HERE, the
    canonical order ``quantize_tiled_operand`` applies on the resident
    path, and the codes then flow to the half-step as an
    already-quantized table (``table_dtype=None`` — quantizing again
    would be wrong)."""
    from cfk_tpu.ops import quant
    from cfk_tpu.ops.tiled import tiled_half_step

    _TRACES[0] += 1
    if scale is not None:
        wt = quant.fold_scale(wt, scale, nb)
        table_dtype = None
    blk = dict(neighbor_idx=nb, rating=rt, weight=wt, tile_seg=ts,
               chunk_entity=ent, chunk_count=cnt, carry_in=cin,
               last_seg=lseg)
    xs = tiled_half_step(
        tbl, blk, ("tiled", "stream") + statics, 1, lam,
        solver=solver, overlap=overlap, fused_epilogue=fused_epilogue,
        in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
        table_dtype=table_dtype, return_chunk_rows=True,
    )
    return xs.astype(jax.numpy.dtype(out_dtype))


@functools.lru_cache(maxsize=None)
def _window_half_jit():
    """The stream-mode window jit, built lazily so the staged-pair
    donation can consult the backend (see ``_staged_donate_argnums``)."""
    return jax.jit(
        _window_half_impl,
        static_argnames=("statics", "lam", "solver", "overlap",
                         "fused_epilogue", "in_kernel_gather",
                         "reg_solve_algo", "table_dtype", "out_dtype"),
        donate_argnums=_staged_donate_argnums((), (0, 1)),
    )


@functools.lru_cache(maxsize=None)
def _window_half_hot_jit():
    """The stream-mode window jit under the hot/delta engine (ISSUE 15):
    the SAME program as ``_window_half_jit`` — one trace of the identical
    chunk body — but WITHOUT the staged-pair donation: the assembled
    (tbl, scale) window table must OUTLIVE the call, because the
    successor window's delta reuse copies its shared cold rows out of it
    device-to-device (the resident-cold arena).  Donating it would hand
    XLA a buffer the next assembly still reads."""
    return jax.jit(
        _window_half_impl,
        static_argnames=("statics", "lam", "solver", "overlap",
                         "fused_epilogue", "in_kernel_gather",
                         "reg_solve_algo", "table_dtype", "out_dtype"),
    )


def _ring_window_impl(acc_a, acc_b, tbl, scale, nb, rt, wt, ts, ent, *,
                      statics, backend, gather, int8):
    """One staged ring window's chunks, accumulated into the shard's
    persistent per-entity Gram carry — op-for-op the flat/hier ring's
    per-slice chunk body (``parallel.spmd._make_tiled_slice_grams``),
    with the staged window replacing the rotated block (gathered values
    are bitwise the block rows, so the Grams — and their scatter-add
    order — are identical)."""
    import jax.numpy as jnp
    from jax import lax

    from cfk_tpu.ops import quant
    from cfk_tpu.ops.tiled import _entity_gram_chunk

    _TRACES[0] += 1

    ncw, cap, t, e_c = statics
    nt = cap // t
    k = tbl.shape[-1]
    if gather == "fused":
        fz = tbl
    else:
        fz = jnp.concatenate([tbl, jnp.zeros((1, k), tbl.dtype)])

    def chunk_body(i, acc):
        a0, b0 = acc
        nb_c = lax.dynamic_slice(nb, (i * cap,), (cap,))
        rt_c = lax.dynamic_slice(rt, (i * cap,), (cap,))
        wt_c = lax.dynamic_slice(wt, (i * cap,), (cap,))
        ts_c = lax.dynamic_slice(ts, (i * nt,), (nt,))
        ent_c = lax.dynamic_slice(ent, (i * e_c,), (e_c,))
        wt_c = quant.fold_scale(wt_c, scale, nb_c)
        a, b = _entity_gram_chunk(
            fz, nb_c, wt_c, rt_c, ts_c, t, e_c + 1, backend,
            unit_weights=not int8,
            zero_appended=gather != "fused", gather=gather,
        )
        return (a0.at[ent_c].add(a[:e_c]), b0.at[ent_c].add(b[:e_c]))

    return lax.fori_loop(0, ncw, chunk_body, (acc_a, acc_b))


@functools.lru_cache(maxsize=None)
def _ring_window_jit():
    """The ring-mode window jit.  Donates the persistent Gram carry pair
    (ISSUE 13): the accumulation is in-place by construction
    (``acc.at[...].add``), so donation lets the output accumulator ALIAS
    the input — input and output never coexist across the dispatch
    boundary, which is exactly the ×2→×1 reservation reclaim
    ``budget.ring_accumulator_reservation`` credits (the
    ``models/als.py``/``spmd.py`` ``donate_argnums`` idiom applied at the
    window boundary).  The staged (tbl, scale) pair additionally donates
    on TPU (``_staged_donate_argnums``); the chunk operands never do
    (stage-time views of the blocks)."""
    return jax.jit(
        _ring_window_impl,
        static_argnames=("statics", "backend", "gather", "int8"),
        donate_argnums=_staged_donate_argnums((0, 1), (2, 3)),
    )


@functools.lru_cache(maxsize=None)
def _ring_window_hot_jit():
    """The ring-mode window jit under the hot/delta engine: identical
    program to ``_ring_window_jit`` with the Gram-carry donation kept
    (the ×1 accumulator reservation) but the staged-table donation
    dropped — the assembled window table is the successor's delta-reuse
    source (see ``_window_half_hot_jit``)."""
    return jax.jit(
        _ring_window_impl,
        static_argnames=("statics", "backend", "gather", "int8"),
        donate_argnums=(0, 1),
    )


def _assemble_impl(delta, dscale, prev_tbl, prev_scale, hot_tbl, hot_scale,
                   keep_dst, keep_src, new_dst, hot_dst, hot_src, *,
                   window_rows, int8):
    """Assemble one window's staged table from its three sources
    (ISSUE 15): the PCIe-staged cold delta, the predecessor window's
    assembled table (device-to-device reuse of shared cold rows), and
    the device-resident hot partition.  Every row is a COPY of bytes
    bitwise identical to what full staging would have produced, so the
    assembled table — and everything computed from it — is bit-exact vs
    the PR 12 engine by construction.

    Index pads point AT ``window_rows`` (out of bounds) and are dropped
    by the explicit scatter ``mode="drop"``; rows no source claims stay
    zero — they are the [row_count, window_rows) pad rows no rebased
    neighbor index ever references (the full-staging path filled them
    with row-0 repeats; either value is unread)."""
    import jax.numpy as jnp

    _TRACES[0] += 1
    r = window_rows
    tbl = jnp.zeros((r, delta.shape[-1]), delta.dtype)
    tbl = tbl.at[keep_dst].set(prev_tbl[keep_src], mode="drop")
    tbl = tbl.at[new_dst].set(delta, mode="drop")
    tbl = tbl.at[hot_dst].set(hot_tbl[hot_src], mode="drop")
    if not int8:
        return tbl, None
    sc = jnp.zeros((r,), jnp.float32)
    sc = sc.at[keep_dst].set(prev_scale[keep_src], mode="drop")
    sc = sc.at[new_dst].set(dscale, mode="drop")
    sc = sc.at[hot_dst].set(hot_scale[hot_src], mode="drop")
    return tbl, sc


@functools.lru_cache(maxsize=None)
def _assemble_jit():
    """The window-assembly jit.  Shapes re-trace per (delta bucket,
    index widths, window_rows) — a scatter/gather-only program, cheap
    next to the window compute (which keeps ONE trace because it always
    sees the same assembled [window_rows, k] table shape)."""
    return jax.jit(
        _assemble_impl, static_argnames=("window_rows", "int8"),
    )


def _hot_update_impl(hot_tbl, hot_scale, xs, src, dst, *, int8):
    """Scatter one window's solved hot rows back into the device
    partition IN PLACE — no host round-trip (ISSUE 15).  ``src`` indexes
    the solved [rows, k] output (last finalization slot per entity — the
    host scatter's last-write-wins), ``dst`` the partition (pads are out
    of bounds, dropped).  The cast/quantization is the in-jit arithmetic
    the host staging pipeline is pinned bit-identical to
    (``store.quantize_rows_host`` ≡ ``quant.quantize_table``), so a hot
    row's device copy always matches what re-staging it from the host
    master would produce."""
    from cfk_tpu.ops import quant

    _TRACES[0] += 1
    rows = xs[src]
    if int8:
        codes, scales = quant.quantize_table(rows, "int8")
        return (hot_tbl.at[dst].set(codes, mode="drop"),
                hot_scale.at[dst].set(scales, mode="drop"))
    return (hot_tbl.at[dst].set(rows.astype(hot_tbl.dtype), mode="drop"),
            hot_scale)


@functools.lru_cache(maxsize=None)
def _hot_update_jit():
    """The scatter-back jit.  The partition pair donates on TPU only
    (``_staged_donate_argnums``: in-place update ⇒ output aliases input;
    on CPU the initial ``device_put`` zero-copy-aliases host numpy and
    jax refuses aliased donations with a warning)."""
    return jax.jit(
        _hot_update_impl, static_argnames=("int8",),
        donate_argnums=_staged_donate_argnums((), (0, 1)),
    )


@functools.partial(
    jax.jit,
    static_argnames=("local", "lam", "solver", "fused_epilogue",
                     "reg_solve_algo", "out_dtype"),
    # NOT donated: the solve's [local, k] output is smaller than either
    # accumulator, so no output can alias them — XLA refuses the
    # donation ("donated buffers were not usable") and nothing is
    # reclaimed.  The window-boundary donation in _ring_window_jit is
    # where the ×2→×1 accumulator reservation actually comes from.
)
def _ring_solve_jit(acc_a, acc_b, cnt, *, local, lam, solver,
                    fused_epilogue, reg_solve_algo, out_dtype):
    from cfk_tpu.ops.solve import regularized_solve

    _TRACES[0] += 1
    x = regularized_solve(
        acc_a[:local], acc_b[:local], cnt, lam, solver,
        fused=fused_epilogue, algo=reg_solve_algo,
    )
    return x.astype(jax.numpy.dtype(out_dtype))


class WindowIntegrityError(RuntimeError):
    """A staged window's bytes no longer match the host store's (torn or
    corrupted transfer, caught by the staging checksum — the window
    analog of the checkpoint crc32 contract)."""


def hier_visit_order(num_shards: int, inner: int, shard: int) -> list[int]:
    """The slice visit order of ``parallel.spmd.half_step_tiled_ring_hier``
    for one shard: phases walk the outer (DCN) ring, inner steps walk the
    ICI ring — ``held(p, j) = ((g−p)%O)·I + (i+p−j)%I``.  ``inner ==
    num_shards`` degenerates to the flat ring's ``(shard − r) % S``
    order, which is the exchange='ring' schedule (the bit-identity the
    resident paths already pin)."""
    if inner < 1 or num_shards % inner != 0:
        raise ValueError(
            f"inner ring size {inner} must divide num_shards={num_shards}"
        )
    outer = num_shards // inner
    g, i_pos = shard // inner, shard % inner
    return [
        ((g - p) % outer) * inner + (i_pos + p - j) % inner
        for p in range(outer) for j in range(inner)
    ]


def _stage_table(fixed_store: HostFactorStore, rows: np.ndarray, *,
                 stage_np, int8: bool, faults, iteration: int, side: str,
                 window: int, shard: int, verify_windows: bool,
                 stats: dict | None, home_shard: int, ici_group: int):
    """Gather + (optionally) quantize one window's table rows on the host
    — the staging pipeline up to the ``device_put`` hand-off.

    Fault hooks and the integrity checksum run on the GATHERED rows
    (before quantization, so a NaN fault poisons the int8 scale exactly
    as the resident in-jit quantization would); the fabric attribution
    meters which store shard each row came from relative to the compute
    shard's home (local / same-ICI-group / DCN — the hier exchange's
    payload accounting)."""
    import zlib

    if faults is not None:
        faults.delay(iteration, side, window, shard=shard)
    tbl = fixed_store.gather(rows)
    if not int8 and tbl.dtype != stage_np:
        tbl = tbl.astype(stage_np)
    src_crc = zlib.crc32(tbl.tobytes()) if verify_windows else None
    # The fault hook models in-flight staging corruption: it fires
    # BETWEEN the source checksum and the device transfer.
    if faults is not None:
        tbl = faults.apply_window(iteration, side, window, tbl,
                                  shard=shard)
    if verify_windows and zlib.crc32(tbl.tobytes()) != src_crc:
        raise WindowIntegrityError(
            f"shard {shard} side {side!r} iteration {iteration} window "
            f"{window}: staged bytes diverge from the host store "
            "(torn/corrupt transfer)"
        )
    if int8:
        data, scale = quantize_rows_host(tbl)
    else:
        data, scale = tbl, None
    if stats is not None and fixed_store.num_shards > 1:
        owners = fixed_store.shard_of_rows(rows)
        home = (owners == home_shard)
        group = (owners // max(ici_group, 1)
                 == home_shard // max(ici_group, 1))
        # stats_add: staging may run on pool worker threads (ISSUE 13),
        # where an unguarded read-modify-write would lose counts.
        stats_add(stats, "rows_local", int(home.sum()))
        stats_add(stats, "rows_ici", int((group & ~home).sum()))
        stats_add(stats, "rows_dcn", int((~group).sum()))
    return data, scale


def _stage_window(fixed_store: HostFactorStore, plan_obj, w: int, *,
                  stage_np, int8: bool, faults, iteration: int, side: str,
                  shard: int, verify_windows: bool, stats: dict | None,
                  ici_group: int) -> tuple:
    """Stage window ``w`` of either plan kind (the stream ``WindowPlan``
    or the ``RingWindowPlan`` — both expose rows / neighbor_idx /
    stage_chunks): host gather + optional quantization + checksum via
    ``_stage_table``, staged-bytes metering, then the ``device_put``
    hand-off.  ONE copy of the metering so the bench rows recorded from
    both execution shapes can never drift apart."""
    data, scale = _stage_table(
        fixed_store, plan_obj.rows[w], stage_np=stage_np, int8=int8,
        faults=faults, iteration=iteration, side=side, window=w,
        shard=shard, verify_windows=verify_windows, stats=stats,
        home_shard=shard, ici_group=ici_group,
    )
    host = (data, scale, plan_obj.neighbor_idx[w],
            *plan_obj.stage_chunks(w))
    if stats is not None:
        stats_add(stats, "windows_staged", 1)
        # The FULL staged working set — table (+ int8 scales) AND chunk
        # arrays — the same quantity the per-window budget was sized
        # against (staged_bytes_per_window), so the recorded arithmetic
        # reproduces the sizing decision.  The chunk arrays are
        # zero-copy VIEWS of the block arrays on the host, but they
        # still cross PCIe per window — staged bytes meter the transfer,
        # not host allocations.  The TABLE share is metered separately
        # as staged_cold_bytes: the bytes the staging dtype AND the hot
        # cache lever (with the cache off — this path — every table row
        # is "cold"; int8 (codes, scales) ≈ ¼ of f32, the honest
        # per-dtype ratio the bench rows record).  Metered from the HOST
        # arrays BEFORE the device_put hand-off — the device (tbl,
        # scale) pair is donated through the window jit (ISSUE 13), so
        # nothing may read it after dispatch.
        stats_add(stats, "staged_bytes",
                  sum(a.nbytes for a in host if a is not None))
        stats_add(stats, "staged_cold_bytes",
                  data.nbytes + (scale.nbytes if scale is not None else 0))
        # rows_staged counts REAL table rows (pre-pad) on every staging
        # path — full windows here, the delta path in
        # _stage_window_delta, and the window_stage span attrs all agree
        # — while the byte meters above record the PADDED transfer.
        stats_add(stats, "rows_staged", int(plan_obj.row_counts[w]))
    # ONE pytree device_put for the whole window (None leaves pass
    # through): per-array puts paid jax dispatch overhead 7-10× per
    # window, which dominated staging at small windows — one issue per
    # window is also the shape a real PCIe queue wants.
    return jax.device_put(host)


def _stage_window_delta(fixed_store: HostFactorStore, plan_obj, hmap, w: int,
                        *, stage_np, int8: bool, faults, iteration: int,
                        side: str, shard: int, verify_windows: bool,
                        stats: dict | None, ici_group: int) -> tuple:
    """Stage window ``w``'s COLD DELTA (ISSUE 15): only the cold rows the
    predecessor window in the schedule did not already stage cross PCIe —
    the hot partition and the device-kept rows are assembled on device by
    ``_assemble_jit``.  Gather + quantize + checksum run through the SAME
    ``_stage_table`` as full staging (the fault hooks and the crc32
    integrity contract see exactly the bytes that ship), then the delta
    pads to its pow2 bucket (static jit shapes; the pad rows scatter out
    of bounds and are dropped)."""
    rows = hmap.delta_rows[w]
    data, scale = _stage_table(
        fixed_store, rows, stage_np=stage_np, int8=int8, faults=faults,
        iteration=iteration, side=side, window=w, shard=shard,
        verify_windows=verify_windows, stats=stats, home_shard=shard,
        ici_group=ici_group,
    )
    d = int(rows.shape[0])
    bucket = hmap.delta_bucket(w)
    pad = np.zeros((bucket, fixed_store.rank), dtype=data.dtype)
    pad[:d] = data
    if scale is not None:
        ps = np.zeros((bucket,), dtype=np.float32)
        ps[:d] = scale
        scale = ps
    data = pad
    host = (data, scale, plan_obj.neighbor_idx[w],
            *plan_obj.stage_chunks(w))
    if stats is not None:
        stats_add(stats, "windows_staged", 1)
        # Same metering seam as full staging: staged_bytes is the whole
        # transfer (delta table + chunk arrays), staged_cold_bytes the
        # table share that actually shipped — the quantity the hot
        # engine exists to cut, recorded at the PADDED bucket size (the
        # honest transfer, not the pre-pad row count).
        stats_add(stats, "staged_bytes",
                  sum(a.nbytes for a in host if a is not None))
        stats_add(stats, "staged_cold_bytes",
                  data.nbytes + (scale.nbytes if scale is not None else 0))
        stats_add(stats, "rows_staged", d)
        stats_add(stats, "rows_delta_skipped", int(hmap.keep_dst[w].size))
        stats_add(stats, "rows_hot_device", int(hmap.hot_dst[w].size))
    return jax.device_put(host)


class HotPartition:
    """One fixed side's device-resident hot rows (ISSUE 15), stored
    dequant-ready at the STAGING dtype: f32/bf16 data, or the (int8
    codes, f32 per-row scales) pair — exactly the bytes full staging
    would have shipped for these rows, so a window assembled from the
    partition is bitwise the fully-staged window.

    The host master store stays ground truth: ``rebuild`` re-gathers the
    partition from it (driver rollback — a poisoned partition is erased
    by the same snapshot restore that heals the stores), while the
    steady-state updates come from ``_hot_update_jit``'s in-place device
    scatter-back (no host round-trip)."""

    def __init__(self, rows: np.ndarray, stage_name: str) -> None:
        self.rows = np.asarray(rows, dtype=np.int64)
        self.stage_name = stage_name
        self.int8 = stage_name == "int8"
        self._stage_np = None if self.int8 else _np_dtype(stage_name)
        self.data = None
        self.scale = None

    @property
    def num_rows(self) -> int:
        return int(self.rows.shape[0])

    @property
    def nbytes(self) -> int:
        if self.data is None:
            return 0
        return int(self.data.nbytes
                   + (self.scale.nbytes if self.scale is not None else 0))

    def rebuild(self, store: HostFactorStore) -> None:
        """(Re)gather the partition from the host master — the initial
        build and the rollback path share it, so a recovered run's
        partition is bit-identical to a fresh one."""
        tbl = store.gather(self.rows)
        if tbl.shape[0] == 0:
            # A 0-row side still participates in the delta engine (the
            # other side may be the hot one); keep one zeros row so the
            # assembly's padded gathers stay in bounds (pad destinations
            # are out of bounds and dropped, so the value is never used).
            tbl = np.zeros((1, store.rank), dtype=tbl.dtype)
        if self.int8:
            data, scale = quantize_rows_host(tbl)
        else:
            data = (tbl if tbl.dtype == self._stage_np
                    else tbl.astype(self._stage_np))
            scale = None
        self.data = jax.device_put(data)
        self.scale = None if scale is None else jax.device_put(scale)

    def poison(self, rows: np.ndarray) -> None:
        """Chaos seam: NaN the given PARTITION positions in the device
        copy (the int8 pair poisons the scale — the only leaf that can
        go nonfinite, same as the in-flight quantization contract).  The
        host master is untouched, so rollback + ``rebuild`` recovers
        bit-exactly."""
        import jax.numpy as jnp

        rows = np.asarray(rows, dtype=np.int32)
        if self.int8:
            self.scale = self.scale.at[rows].set(jnp.nan, mode="drop")
        else:
            self.data = self.data.at[rows].set(
                jnp.asarray(np.nan, self.data.dtype), mode="drop"
            )


class _HotHalf:
    """One (side, shard)'s view of the hot/delta engine for a half-step:
    the FIXED side's partition (read by window assembly), the SOLVE
    side's partition (scatter-back target), this shard's window split
    map, and the device-resident index constants (built once — they are
    plan-time constants, so only the delta table pays PCIe per
    iteration)."""

    def __init__(self, fixed: HotPartition, solve: HotPartition | None,
                 hmap, sb_maps) -> None:
        self.fixed = fixed
        self.solve = solve
        self.hmap = hmap
        self.sb = sb_maps  # stream: {w: (src, dst)}; ring: (src, dst)
        r = hmap.window_rows
        self._idx = {}
        for w in hmap.prev_of:
            hp, kp = hmap.hot_pad, hmap.keep_pad
            bucket = hmap.delta_bucket(w)
            self._idx[w] = jax.device_put((
                _pad_idx(hmap.keep_dst[w], kp, r),
                _pad_idx(hmap.keep_src[w], kp, 0),
                _pad_idx(hmap.delta_dst[w], bucket, r),
                _pad_idx(hmap.hot_dst[w], hp, r),
                _pad_idx(hmap.hot_src[w], hp, 0),
            ))
        if isinstance(sb_maps, dict):
            pad = max((v[0].size for v in sb_maps.values()), default=0)
            self.sb_pad = pad
            f = solve.num_rows if solve is not None else 0
            self._sb_idx = {
                w: jax.device_put((_pad_idx(src, pad, 0),
                                   _pad_idx(dst, pad, f)))
                for w, (src, dst) in sb_maps.items()
            } if pad else {}
        else:
            self.sb_pad = 0 if sb_maps is None else int(sb_maps[0].size)
            self._sb_idx = (None if not self.sb_pad
                            else jax.device_put(tuple(sb_maps)))

    def idx(self, w):
        return self._idx[w]

    def sb_idx(self, w=None):
        return self._sb_idx if w is None else self._sb_idx.get(w)


def _fixed_rows_of(plan_obj) -> int:
    """The fixed-table row space a plan's windows gather from — the
    store's own row count (stream plans record it; ring plans address
    slice·H + local over every slice)."""
    if hasattr(plan_obj, "table_rows"):
        return int(plan_obj.table_rows)
    return int(plan_obj.num_slices * plan_obj.statics[3])


def _pad_idx(arr: np.ndarray, width: int, pad_val: int) -> np.ndarray:
    out = np.full((max(int(width), 1),), pad_val, dtype=np.int32)
    out[: arr.size] = arr
    return out


def _hot_zero_prev(window_rows: int, rank: int, stage_name: str):
    """The chain head's predecessor: a zeros (tbl, scale) pair at the
    staging dtype (nothing is kept from it — the first window of every
    schedule stages its full cold set as delta)."""
    import jax.numpy as jnp

    if stage_name == "int8":
        return (jnp.zeros((window_rows, rank), jnp.int8),
                jnp.zeros((window_rows,), jnp.float32))
    dt = jnp.bfloat16 if stage_name == "bfloat16" else jnp.float32
    return jnp.zeros((window_rows, rank), dt), None


def _own_stager(fixed_store, plan_obj, schedule, *, table_dtype, faults,
                iteration, side, shard, verify_windows, stats, ici_group,
                hot=None) -> WindowStager:
    """A single-shard SERIAL stager for direct half-step callers (tests,
    library use): byte-for-byte the PR 10/11 schedule — staging runs on
    the consuming thread at the classic double-buffer positions.  The
    sharded driver passes a shared pooled stager instead.  With ``hot``
    (a ``_HotHalf``), tasks stage the cold delta instead of the full
    window."""
    stage_name = _stage_dtype(fixed_store.dtype, table_dtype)
    int8 = stage_name == "int8"
    stage_np = None if int8 else _np_dtype(stage_name)

    def stage_task(d, w):
        if hot is not None:
            return _stage_window_delta(
                fixed_store, plan_obj, hot.hmap, w, stage_np=stage_np,
                int8=int8, faults=faults, iteration=iteration, side=side,
                shard=d, verify_windows=verify_windows, stats=stats,
                ici_group=ici_group,
            )
        return _stage_window(
            fixed_store, plan_obj, w, stage_np=stage_np, int8=int8,
            faults=faults, iteration=iteration, side=side, shard=d,
            verify_windows=verify_windows, stats=stats,
            ici_group=ici_group,
        )

    return WindowStager([(shard, w) for w in schedule], stage_task,
                        mode="serial", stats=stats,
                        span_attrs=lambda d, w: _stage_span_attrs(
                            hot.hmap if hot is not None else None,
                            plan_obj, w))


def _stage_span_attrs(hmap, plan_obj, w: int) -> dict:
    """The ``window_stage`` span attrs (ISSUE 15): rows_staged /
    rows_delta_skipped / rows_hot per window, so the trace shows the
    reuse.  ONE copy shared by the direct half-step callers and the
    sharded driver (the PR 11 no-two-meters discipline) — rows are REAL
    (pre-pad) counts, matching the ``rows_staged`` stats key.  Plan-time
    constants: a pure lookup, safe on worker threads."""
    if hmap is None:
        return {"rows_staged": int(plan_obj.row_counts[w])}
    return {
        "rows_staged": int(len(hmap.delta_rows[w])),
        "rows_delta_skipped": int(hmap.keep_dst[w].size),
        "rows_hot": int(hmap.hot_dst[w].size),
    }


def windowed_half_step(
    fixed_store: HostFactorStore, wplan: WindowPlan, *, lam: float,
    out_dtype: str = "float32", solver: str = "auto", overlap=None,
    fused_epilogue=None, in_kernel_gather=None, reg_solve_algo=None,
    table_dtype: str | None = None, faults=None, iteration: int = 0,
    side: str = "", stats: dict | None = None, verify_windows: bool = False,
    shard: int = 0, ici_group: int = 1, stager: WindowStager | None = None,
    hot: "_HotHalf | None" = None, host: int = 0,
) -> np.ndarray:
    """Solve one shard's entities against a host-resident fixed table,
    window by window (the stream-mode / all_gather-exchange scan).
    Returns the solved [local_entities, rank] host array in ``out_dtype``
    (untouched rows zero — exactly the resident scatter's output).
    ``faults`` (chaos only) is a ``resilience.faults.WindowFaultInjector``;
    ``verify_windows`` checksums each staged window at the store (crc32
    before the staging hand-off) against what is about to ship, and
    raises ``WindowIntegrityError`` on a mismatch — NaN poisoning is
    caught by the factor sentinel either way, but a TORN window is
    finite-and-wrong, which only an integrity check can see.  Scope is
    the HOST staging pipeline up to the ``device_put`` hand-off (which is
    where the chaos fault hook models its corruption); verifying the PCIe
    DMA itself would need a device-side checksum — on-TPU follow-up.

    ``stager`` (ISSUE 13): the staging engine serving this shard's
    windows — the sharded driver passes ONE pooled stager shared across
    every shard of a half-iteration, so shard d+1's staging overlaps
    shard d's compute on worker threads.  ``None`` builds a private
    serial stager (the classic double-buffer schedule, unchanged
    behavior for direct callers); the faults/verify/stats arguments
    configure only that private stager — a shared stager carries its
    own."""
    k = fixed_store.rank
    out = np.zeros((wplan.local_entities, k), dtype=_np_dtype(out_dtype))
    n_w = wplan.num_windows
    own = stager is None
    if own:
        stager = _own_stager(
            fixed_store, wplan, wplan.schedule(), table_dtype=table_dtype,
            faults=faults, iteration=iteration, side=side, shard=shard,
            verify_windows=verify_windows, stats=stats,
            ici_group=ici_group, hot=hot,
        )
    half_kw = dict(
        statics=wplan.statics, lam=float(lam), solver=solver,
        overlap=overlap, fused_epilogue=fused_epilogue,
        in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
        table_dtype=table_dtype, out_dtype=out_dtype,
    )
    stage_name = _stage_dtype(fixed_store.dtype, table_dtype)
    prev = (None if hot is None
            else _hot_zero_prev(wplan.window_rows, k, stage_name))
    try:
        staged = stager.take() if n_w else None
        for w in range(n_w):
            # DISPATCH window w's compute first (jit dispatch is async),
            # THEN take window w+1 — a serial stager runs the host gather
            # + device_put HERE, under the dispatched compute (the PR 10
            # double buffer); a pooled stager usually has it already
            # staged by a worker — and only then join w's result.  The
            # compute span covers dispatch → join, so a pooled staging
            # worker's window_stage span visibly overlaps it.
            with span("train/iter/half_step/window_compute",
                      side=side, shard=shard, window=w, host=host):
                if hot is None:
                    xs = _window_half_jit()(*staged, **half_kw)
                else:
                    # Assemble from delta + predecessor + hot partition,
                    # then the SAME window program (one trace — the
                    # assembled table shape never changes) WITHOUT the
                    # staged donation (the next window reuses this one).
                    delta, dscale, *rest = staged
                    tbl, scale = _assemble_jit()(
                        delta, dscale, *prev,
                        hot.fixed.data, hot.fixed.scale, *hot.idx(w),
                        window_rows=wplan.window_rows,
                        int8=hot.fixed.int8,
                    )
                    xs = _window_half_hot_jit()(tbl, scale, *rest,
                                                **half_kw)
                    prev = (tbl, scale)
                    sb = hot.sb_idx(w)
                    if sb is not None:
                        # Solved hot rows of THIS side scatter back into
                        # its partition in place — no host round-trip.
                        hot.solve.data, hot.solve.scale = _hot_update_jit()(
                            hot.solve.data, hot.solve.scale, xs, *sb,
                            int8=hot.solve.int8,
                        )
                nxt = stager.take() if w + 1 < n_w else None
                xs_np = np.asarray(xs)
            ent = wplan.chunk_entity_of(w)
            real = ent < wplan.local_entities
            out[ent[real]] = xs_np[real]
            staged = nxt
    finally:
        if own:
            stager.close()
    return out


def ring_windowed_half_step(
    fixed_store: HostFactorStore, rplan: RingWindowPlan, *, lam: float,
    visits: list[int], count_local: np.ndarray, out_dtype: str = "float32",
    solver: str = "auto", overlap=None, fused_epilogue=None,
    in_kernel_gather=None, reg_solve_algo=None,
    table_dtype: str | None = None, faults=None, iteration: int = 0,
    side: str = "", stats: dict | None = None, verify_windows: bool = False,
    shard: int = 0, ici_group: int = 1, stager: WindowStager | None = None,
    hot: "_HotHalf | None" = None, host: int = 0,
) -> np.ndarray:
    """One shard's ring/hier-ring half-iteration against staged windows.

    ``visits`` is the slice visit order the resident exchange would
    deliver blocks in (``hier_visit_order``); per visit, the slice's
    windows stage ahead (the shared pooled ``stager``, or a private
    serial one — see ``windowed_half_step``) while the persistent
    per-entity Gram accumulator — the SAME [E_local+1, k(,k)] carry the
    resident ring holds, DONATED through each window call so input and
    output never coexist (ISSUE 13) — absorbs each window's chunk Grams.
    One solve at the end.  The staged window is the slice rows this
    shard's chunks actually reference (the window residual) — never the
    whole block, which is how the flat ring's O(S) full-table traffic
    disappears."""
    import jax.numpy as jnp

    from cfk_tpu.ops.tiled import (
        default_tiled_gram_backend,
        resolve_gather_mode,
    )

    k = fixed_store.rank
    nc, cap, t, h, e_c = rplan.statics
    nt = cap // t
    local = rplan.local_entities
    backend = default_tiled_gram_backend()
    stage_name = _stage_dtype(fixed_store.dtype, table_dtype)
    gather = resolve_gather_mode(
        in_kernel_gather, backend, cap, nt, t, e_c + 1, k,
        table_dtype=stage_name,
    )
    int8 = stage_name == "int8"
    schedule = rplan.schedule(visits)
    own = stager is None
    if own:
        stager = _own_stager(
            fixed_store, rplan, schedule, table_dtype=table_dtype,
            faults=faults, iteration=iteration, side=side, shard=shard,
            verify_windows=verify_windows, stats=stats,
            ici_group=ici_group, hot=hot,
        )
    acc_a = jnp.zeros((local + 1, k, k), jnp.float32)
    acc_b = jnp.zeros((local + 1, k), jnp.float32)
    prev = (None if hot is None
            else _hot_zero_prev(rplan.window_rows, k, stage_name))
    try:
        staged = stager.take() if schedule else None
        for i, w in enumerate(schedule):
            # Dispatch this window's accumulation (async), then take the
            # next visit's window under it — the inner-ICI-rotation
            # overlap of the resident hier ring, one level up.  The
            # donated carry rebinds; nothing may read the pre-call pair.
            # The ring_visit span is the exchange-phase timeline: visit
            # order IS the block-delivery order the resident ring/hier
            # ring would rotate, so the trace shows each phase's staging
            # (window residual — the DCN-hop payload) against compute.
            with span("train/iter/half_step/ring_visit",
                      side=side, shard=shard, visit=i, window=w,
                      host=host):
                if hot is None:
                    acc_a, acc_b = _ring_window_jit()(
                        acc_a, acc_b, *staged,
                        statics=(rplan.window_chunks, cap, t, e_c),
                        backend=backend, gather=gather, int8=int8,
                    )
                else:
                    delta, dscale, *rest = staged
                    tbl, scale = _assemble_jit()(
                        delta, dscale, *prev,
                        hot.fixed.data, hot.fixed.scale, *hot.idx(w),
                        window_rows=rplan.window_rows,
                        int8=hot.fixed.int8,
                    )
                    acc_a, acc_b = _ring_window_hot_jit()(
                        acc_a, acc_b, tbl, scale, *rest,
                        statics=(rplan.window_chunks, cap, t, e_c),
                        backend=backend, gather=gather, int8=int8,
                    )
                    prev = (tbl, scale)
                staged = (stager.take() if i + 1 < len(schedule) else None)
    finally:
        if own:
            stager.close()
    with span("train/iter/half_step/ring_solve", side=side, shard=shard):
        x = _ring_solve_jit(
            acc_a, acc_b, jax.numpy.asarray(count_local), local=local,
            lam=float(lam), solver=solver, fused_epilogue=fused_epilogue,
            reg_solve_algo=reg_solve_algo, out_dtype=out_dtype,
        )
        if hot is not None and hot.sb_pad:
            # The ring modes solve once at the end: one in-place scatter
            # of this shard's hot solve rows back into the partition.
            hot.solve.data, hot.solve.scale = _hot_update_jit()(
                hot.solve.data, hot.solve.scale, x, *hot.sb_idx(),
                int8=hot.solve.int8,
            )
        x = np.asarray(x)
    return x


def _resolve_side_modes(dataset, config: ALSConfig
                        ) -> tuple[bool, bool]:
    """(movie_side_ring, user_side_ring) — which execution shape each
    half runs, mirroring the resident trainer's resolution EXACTLY: the
    ring exchanges apply only at num_shards > 1 (a single-device trainer
    never consults the exchange knob), ``exchange='auto'`` takes each
    half's ring flag AS BUILT (the resident per-side memory optimum,
    ``spmd.gathered_layout_trees``), and the explicit exchanges require
    matching blocks (validated by ``_blocks_for``)."""
    from cfk_tpu.data.blocks import TiledBlocks

    if config.num_shards == 1 or config.exchange == "all_gather":
        return False, False
    if config.exchange in ("ring", "hier_ring"):
        return True, True
    # exchange == "auto": per-side, from how the blocks were built.
    mb, ub = dataset.movie_blocks, dataset.user_blocks
    return (
        bool(isinstance(mb, TiledBlocks) and mb.ring),
        bool(isinstance(ub, TiledBlocks) and ub.ring),
    )


def _blocks_for(dataset, config: ALSConfig, tile_rows: int | None,
                ring_m: bool, ring_u: bool):
    """The tiled blocks the windowed driver runs on, per side.

    Stream (all_gather-shape) sides need stream mode at the config's
    shard count — the dataset's own blocks when they qualify, else a
    rebuild from the dense COO with accum mode disabled (accum's
    persistent [E, k, k] device accumulator is exactly the structure the
    out-of-core regime cannot hold).  Ring sides need the dataset's
    ring-built accum blocks as-is (their slice structure IS the exchange
    schedule; no rebuild can synthesize it honestly).  Mismatches raise
    with the same remedies the resident trainer gives."""
    from cfk_tpu.data.blocks import TiledBlocks, build_tiled_blocks

    s = config.num_shards
    mb, ub = dataset.movie_blocks, dataset.user_blocks

    def side_ok(blocks, ring):
        if not isinstance(blocks, TiledBlocks) or blocks.num_shards != s:
            return False
        if ring:
            return blocks.mode == "accum" and blocks.ring
        return blocks.mode == "stream" and not blocks.ring

    rebuilt = None

    def stream_rebuild():
        nonlocal rebuilt
        if rebuilt is None:
            coo = dataset.coo_dense
            t = tile_rows or (mb.tile_rows
                              if isinstance(mb, TiledBlocks) else 128)
            build = functools.partial(
                build_tiled_blocks, num_shards=s, tile_rows=t,
                chunk_elems=config.chunk_cells(), accum_max_entities=0,
            )
            m_dense = coo.movie_raw.astype(np.int64)
            u_dense = coo.user_raw.astype(np.int64)
            rebuilt = (
                build(m_dense, u_dense, coo.rating,
                      dataset.movie_map.num_entities,
                      dataset.user_map.num_entities),
                build(u_dense, m_dense, coo.rating,
                      dataset.user_map.num_entities,
                      dataset.movie_map.num_entities),
            )
        return rebuilt

    sides = (("movie", mb, ring_m, 0), ("user", ub, ring_u, 1))
    # Validate first: mismatches that cannot be rebuilt raise with the
    # resident trainer's own remedies.
    for name, blocks, ring, _ in sides:
        if ring and not side_ok(blocks, True):
            # Ring blocks cannot be synthesized here — their slice
            # structure IS the exchange schedule.
            raise ValueError(
                f"exchange={config.exchange!r} windowed training runs "
                f"the {name} half on ring-built tiled blocks at "
                f"num_shards={s}; rebuild with Dataset.from_coo(..., "
                f"layout='tiled', num_shards={s}, ring=True)"
            )
        if (not ring and isinstance(blocks, TiledBlocks) and blocks.ring):
            # Mirror the resident trainer: an all_gather half on
            # ring-built blocks raises there too — silently rebuilding
            # would train a different exchange schedule than the
            # resident path the bit-exactness contract compares against.
            raise ValueError(
                f"exchange={config.exchange!r} runs the {name} half as "
                "a stream scan, but its blocks were ring-built; pass "
                "exchange='ring'/'hier_ring' (the windowed ring driver) "
                "or rebuild with ring=False"
            )
    # If ANY stream side needs the rebuild, rebuild EVERY stream side:
    # mixing dataset-built and driver-rebuilt stream blocks could differ
    # in chunking (the dataset's build parameters vs the config's), and
    # one consistent build is the PR 10 discipline.
    rebuild_streams = any(
        not ring and not side_ok(blocks, False)
        for _, blocks, ring, _ in sides
    )
    out = [
        stream_rebuild()[idx] if (not ring and rebuild_streams)
        else blocks
        for _, blocks, ring, idx in sides
    ]
    return out[0], out[1]


def _probe(u: np.ndarray, m: np.ndarray, norm_limit: float | None) -> str | None:
    """Host-side sentinel over the solved stores: NaN/Inf anywhere, or a
    factor-row 2-norm past the watchdog limit.  Returns the trip reason or
    None (the same reason vocabulary as ``resilience.sentinel``)."""
    for name, x in (("user", u), ("movie", m)):
        xf = np.asarray(x, dtype=np.float32)
        if not np.isfinite(xf).all():
            return f"nonfinite {name} factors"
        if norm_limit is not None:
            n = float(np.sqrt((xf * xf).sum(axis=1)).max()) if xf.size else 0.0
            if n > norm_limit:
                return f"{name} row norm {n:.3g} > {norm_limit:.3g}"
    return None


def resolve_window_inner(config: ALSConfig) -> int:
    """The windowed driver's inner-ring size: the SAME resolution the
    resident hier ring uses (``parallel.spmd.resolve_ici_group``) for
    ``hier_ring`` — visit order must match the exchange being replaced —
    and one flat ring otherwise."""
    if config.exchange == "hier_ring":
        from cfk_tpu.parallel.spmd import resolve_ici_group

        return resolve_ici_group(config)
    return config.num_shards


def train_als_host_window(
    dataset,
    config: ALSConfig,
    *,
    metrics=None,
    window_faults=None,
    tile_rows: int | None = None,
    chunks_per_window: int | None = None,
    device_budget_bytes: float | None = None,
    plan_provenance=None,
    verify_windows: bool | None = None,
    staging: str | None = None,
    pool_depth: int | None = None,
    hot_rows: int | None = None,
    checkpoint_manager=None,
    checkpoint_every: int = 1,
    watchdog=None,
    fleet=None,
    fleet_manifests=None,
):
    """ALS-WR with host-resident factor tables and windowed half-steps.

    Same math, init, and iteration order as ``train_als`` (one shard) or
    ``parallel.spmd.train_als_sharded`` (sharded — all_gather, ring, or
    hier_ring exchange) on the same tiled blocks — bit-exact at every
    supported knob (``tests/test_offload.py`` /
    ``tests/test_offload_sharded.py``).  Explicit ALS, ``layout='tiled'``.
    Under ``jax.distributed`` with ``process_count() > 1`` the SAME entry
    point runs the fleet mode (ISSUE 17): each process keeps only its
    own entity-range store slice and the hier-ring DCN phases allgather
    the cold window residual (``offload/exchange.py``) into a read-only
    mirror — factors stay crc-identical to the one-process driver, whose
    per-shard schedules are the degenerate single-host case;
    divergence recovery runs the PR 3 ladder against in-RAM last-good
    snapshots of the stores (each rung is recorded with the loop
    vocabulary and as a plan transition when provenance rides along).

    ``device_budget_bytes`` bounds the staged working set PER SHARD
    (default: the detected device's HBM through ``offload.budget`` — the
    SAME predicate the planner gates the ``device`` tier with);
    ``chunks_per_window`` overrides the derived window size.

    ``hot_rows`` (ISSUE 15) sizes the skew-aware hot-row device cache:
    ``None`` defers to ``config.hot_rows`` (whose ``None`` default is
    AUTO — the coverage-curve knee of the plans' own cross-window
    reference counts, clamped by the budget headroom left after the
    accumulator + window + delta-arena reservations); ``0`` pins the
    cache OFF (byte-for-byte the PR 12 engine); ``>= 1`` pins the TOTAL
    resident row count across both sides (split proportionally to each
    side's reference mass), raising when the reservation cannot fit —
    the same loud-refusal convention as the per-window budget.  With the
    cache on, windows stage only their COLD DELTA vs the schedule
    predecessor; factors are crc-identical across the knob (the
    assembled window tables are bitwise the fully-staged ones).

    ``staging`` (ISSUE 13) picks the host staging engine's mode —
    ``"pool"`` (the default: one bounded thread pool per half-iteration
    stages every shard's windows ahead of consumption, overlapping the
    host gather/quantize/checksum/``device_put`` across shards AND
    windows) or ``"serial"`` (the PR 10/11 one-thread double buffer, the
    A/B baseline) — defaulting to ``config.staging``.  ``pool_depth``
    bounds the staged-ahead windows (default ``config.staging_pool_depth``
    or ``offload.staging.DEFAULT_POOL_DEPTH``), and is always CLAMPED so
    ``depth + 1`` worst-case windows fit the per-shard staging budget
    next to the ring accumulator reservation (``budget.max_pool_depth``
    — the staging-arena term).  Both modes are crc-identical to each
    other and to the resident paths.

    ``fleet`` injects the multi-process transport explicitly (the
    threaded elastic harness and tests; ``None`` auto-detects the jax
    runtime as before).  ``fleet_manifests`` — a
    ``cfk_tpu.offload.elastic.FleetManifests`` over the fleet's shared
    per-host checkpoint tree — arms **elastic membership** (ISSUE 20,
    overridable via ``config.fleet_elastic``): a dead peer triggers the
    shrink protocol (min-agree the last jointly covered step from the
    manifests, repartition ownership over the survivors, reload the
    orphaned slice from committed bytes, roll back, continue) instead
    of an exit, and a restarted host passed a ``fleet`` whose
    ``is_joiner`` is set rejoins at an iteration boundary via the
    health-gated readmission handshake.  Factors reconverge
    crc-identical to the uninterrupted run (shard-count-invariant init
    + committed-byte reload).
    """
    from cfk_tpu.config import enable_compile_cache
    from cfk_tpu.ops.solve import init_factors_stats
    from cfk_tpu.resilience.policy import (
        Overrides,
        TrainingDivergedError,
        policy_from_config,
    )
    from cfk_tpu.transport.checkpoint import should_save
    from cfk_tpu.utils.metrics import Metrics

    enable_compile_cache(getattr(config, "compile_cache_dir", None))
    if config.algorithm != "als":
        raise ValueError(
            f"host-window offload supports the explicit ALS optimizer; "
            f"algorithm={config.algorithm!r} (iALS needs the global YᵀY "
            "over the full fixed table — an out-of-core reduction is the "
            "documented follow-up)"
        )
    if config.layout != "tiled":
        raise ValueError(
            f"host-window offload streams the tiled layout; "
            f"layout={config.layout!r}"
        )
    # Fleet mode (ISSUE 17): under a multi-process jax runtime each
    # process owns only its contiguous shard block's store slice and the
    # halves exchange cold window residuals over the hier-ring's DCN
    # phases (offload.exchange).  Everything below that reads or writes
    # a factor table goes through the slice store or its ResidualMirror;
    # the single-process path is byte-for-byte untouched.
    from cfk_tpu.offload import elastic as _elastic
    from cfk_tpu.offload import exchange as _exchange

    metrics = metrics if metrics is not None else Metrics()
    if fleet is None and jax.process_count() > 1:
        fleet = _exchange.GlooFleet()
    joiner = fleet is not None and getattr(fleet, "is_joiner", False)
    if fleet is not None and not joiner:
        if config.num_shards % fleet.num_processes != 0:
            raise ValueError(
                f"num_shards={config.num_shards} must be divisible by "
                f"the fleet size ({fleet.num_processes} processes) for "
                "contiguous shard-block store ownership"
            )
    # Elastic membership (ISSUE 20): armed when per-host fleet manifests
    # are available (config.fleet_elastic overrides).  The transport is
    # wrapped for transient-vs-fatal classification — retried transient
    # collective failures never shrink the fleet; exhaustion or a fatal
    # error raises PeerDeadError, which the loop turns into the shrink
    # protocol instead of an exit.
    elastic_on = fleet is not None and (
        config.fleet_elastic if config.fleet_elastic is not None
        else fleet_manifests is not None
    )
    if elastic_on and fleet_manifests is None:
        raise ValueError(
            "fleet_elastic=True needs fleet_manifests (the shrink "
            "protocol agrees on and reloads from the per-host manifest "
            "tree); pass a cfk_tpu.offload.elastic.FleetManifests"
        )
    if elastic_on and not isinstance(fleet, _elastic.ElasticFleet):
        fleet = _elastic.ElasticFleet(
            fleet,
            retry=_elastic.RetryPolicy(
                attempts=config.fleet_retry_attempts,
                base=config.fleet_retry_base_s,
                max_delay=config.fleet_retry_max_delay_s,
            ),
            collective_timeout_s=config.fleet_collective_timeout_s,
            metrics=metrics,
        )
    fleet_epoch = 0
    s = config.num_shards
    ring_m, ring_u = _resolve_side_modes(dataset, config)
    any_ring = ring_m or ring_u
    inner = resolve_window_inner(config) if any_ring else max(s, 1)
    with metrics.phase("window_plan"):
        mb, ub = _blocks_for(dataset, config, tile_rows, ring_m, ring_u)
        stage_name = _stage_dtype(config.dtype, config.table_dtype)
        cell_bytes, row_overhead = _stage_cell_bytes(stage_name)
        if device_budget_bytes is None:
            from cfk_tpu.plan import DeviceSpec

            device_budget_bytes = DeviceSpec.detect().hbm_bytes
        # The ring modes hold a persistent per-shard Gram accumulator
        # next to the staged windows; reserve it at ×1 (ISSUE 13:
        # ``_ring_window_jit`` DONATES the carry pair, so a window call's
        # output accumulator aliases its input — the ×2 the PR 11
        # dispatch boundary used to keep alive is reclaimed, which is
        # exactly why the budget now admits larger windows here) before
        # splitting the remainder across the window double buffer.
        acc_reserved = 0.0
        for blocks, ring in ((mb, ring_m), (ub, ring_u)):
            if ring:
                acc_reserved = max(
                    acc_reserved,
                    _budget.ring_accumulator_reservation(
                        blocks.local_entities, config.rank, donated=True
                    ),
                )
        per_window_budget = _budget.window_budget_bytes(
            device_budget_bytes, reserved_bytes=acc_reserved
        )

        def side_plans(blocks, fixed, ring, cpw):
            if ring:
                return [build_ring_window_plan(blocks, shard=d,
                                               chunks_per_window=cpw)
                        for d in range(s)]
            return [build_window_plan(blocks, fixed.padded_entities,
                                      chunks_per_window=cpw, shard=d)
                    for d in range(s)]

        def plans_for(cpw):
            return (side_plans(mb, ub, ring_m, cpw),
                    side_plans(ub, mb, ring_u, cpw))

        cpw = chunks_per_window or 4
        while True:
            m_plans, u_plans = plans_for(cpw)
            worst = max(
                p.staged_bytes_per_window(config.rank, cell_bytes,
                                          row_overhead_bytes=row_overhead)
                for p in (*m_plans, *u_plans)
            )
            if worst <= per_window_budget or cpw == 1:
                break
            cpw = max(1, cpw // 2)
        if worst > per_window_budget:
            raise ValueError(
                f"one staged window needs {worst / 1e6:.1f} MB but the "
                f"per-window budget is {per_window_budget / 1e6:.1f} MB "
                "((device_budget · RESIDENT_FRACTION − ring accumulator "
                "reserve) / WINDOW_BUFFERS) — lower hbm_chunk_elems so "
                "single chunks fit the budget"
            )
        # Staging engine resolution (ISSUE 13): mode from the explicit
        # argument or the config, depth clamped by the staging arena —
        # depth + 1 worst-case windows must fit the budget share next to
        # the accumulator reservation, so a deep pool can never promise
        # device memory the window sizing above did not leave free.
        staging = resolve_staging(
            staging if staging is not None
            else getattr(config, "staging", "auto"),
        )
        if pool_depth is None:
            pool_depth = (getattr(config, "staging_pool_depth", None)
                          or DEFAULT_POOL_DEPTH)
        pool_depth = max(1, min(
            int(pool_depth),
            _budget.max_pool_depth(device_budget_bytes, worst,
                                   reserved_bytes=acc_reserved),
        ))
        # --- skew-aware hot-row cache resolution (ISSUE 15) ----------
        # Decided HERE, at window-plan build time, from the plans' own
        # per-window row sets: the planner's plan field carries the
        # budget-admitted TARGET; this is the exact resolution against
        # the real reference skew.  The window sizing above is untouched
        # by the knob on purpose — hot on/off share cpw, so their
        # schedules (and therefore every bit) are identical.
        from cfk_tpu.offload import hot as _hotmod

        requested = (hot_rows if hot_rows is not None
                     else getattr(config, "hot_rows", None))
        schedules = {
            ("m", d): (m_plans[d].schedule(hier_visit_order(s, inner, d))
                       if ring_m else m_plans[d].schedule())
            for d in range(s)
        }
        schedules.update({
            ("u", d): (u_plans[d].schedule(hier_visit_order(s, inner, d))
                       if ring_u else u_plans[d].schedule())
            for d in range(s)
        })
        hot_note = None
        f_u = f_m = 0
        if requested != 0:
            row_b = _budget.stage_row_bytes(config.rank, stage_name)
            arena = max(
                p.window_rows * row_b for p in (*m_plans, *u_plans)
            )
            live = (pool_depth + 1 if staging == "pool"
                    else _budget.WINDOW_BUFFERS)
            live = max(live, _budget.WINDOW_BUFFERS)
            hot_reserved = acc_reserved + live * worst + arena
            admit = _budget.max_hot_rows(
                device_budget_bytes, config.rank, stage_name,
                reserved_bytes=hot_reserved,
            )
            # Per-side reference counts over the FIXED table each side's
            # windows gather, zeroed outside the rows the OTHER half
            # provably re-solves (so an in-place device copy can never
            # go stale vs the host master — on real data this is a
            # no-op: referenced rows have interactions, interactions
            # make solve entities).
            counts_u = _hotmod.reference_counts(
                m_plans, _fixed_rows_of(m_plans[0])
            )
            counts_m = _hotmod.reference_counts(
                u_plans, _fixed_rows_of(u_plans[0])
            )
            solved_u = np.concatenate([
                _hotmod.solved_rows_of(u_plans[d], d, ub.local_entities)
                for d in range(s)
            ]) if s else np.zeros(0, np.int64)
            solved_m = np.concatenate([
                _hotmod.solved_rows_of(m_plans[d], d, mb.local_entities)
                for d in range(s)
            ]) if s else np.zeros(0, np.int64)
            mask_u = np.zeros(counts_u.shape, bool)
            mask_u[solved_u] = True
            counts_u[~mask_u] = 0
            mask_m = np.zeros(counts_m.shape, bool)
            mask_m[solved_m] = True
            counts_m[~mask_m] = 0
            slots_u = int(counts_u.sum())
            slots_m = int(counts_m.sum())
            if requested is None:
                f_u = _hotmod.knee_hot_rows(counts_u)
                f_m = _hotmod.knee_hot_rows(counts_m)
                total = f_u + f_m
                if total > admit:
                    # Budget clamp, proportional — deterministic ints.
                    f_u = f_u * admit // max(total, 1)
                    f_m = min(admit - f_u, f_m)
                    hot_note = (f"knee clamped by budget headroom "
                                f"({admit} rows admitted)")
                else:
                    hot_note = "coverage-curve knee within headroom"
            else:
                req = int(requested)
                if not _budget.hot_reservation_fits(
                    req, config.rank, stage_name, device_budget_bytes,
                    reserved_bytes=hot_reserved,
                ):
                    need = _budget.hot_reservation_bytes(
                        req, config.rank, stage_name
                    )
                    raise ValueError(
                        f"hot_rows={req} pinned but its reservation "
                        f"({need / 1e6:.2f} MB at the {stage_name!r} "
                        f"staging dtype) exceeds the headroom left by "
                        f"the accumulator/window/delta-arena terms "
                        f"({admit * row_b / 1e6:.2f} MB ≈ {admit} rows) "
                        "— lower hot_rows, raise the device budget, or "
                        "use hot_rows=0 (the full-staging engine)"
                    )
                denom = max(slots_u + slots_m, 1)
                f_u = req * slots_u // denom
                f_m = req - f_u
                hot_note = f"pinned total {req}"
            f_u = min(f_u, int((counts_u > 0).sum()))
            f_m = min(f_m, int((counts_m > 0).sum()))
            if f_u + f_m == 0:
                hot_note = (hot_note or "") + "; resolved 0 (off)"
        hot_ctx = None
        if f_u + f_m > 0:
            rows_hot_u = _hotmod.select_hot_rows(counts_u, f_u)
            rows_hot_m = _hotmod.select_hot_rows(counts_m, f_m)
            hmaps = {
                ("m", d): _hotmod.build_hot_map(
                    m_plans[d], schedules[("m", d)], rows_hot_u)
                for d in range(s)
            }
            hmaps.update({
                ("u", d): _hotmod.build_hot_map(
                    u_plans[d], schedules[("u", d)], rows_hot_m)
                for d in range(s)
            })
            hot_ctx = {"rows_u": rows_hot_u, "rows_m": rows_hot_m,
                       "maps": hmaps, "note": hot_note}
    metrics.gauge("offload_windows_m",
                  sum(p.num_windows for p in m_plans))
    metrics.gauge("offload_windows_u",
                  sum(p.num_windows for p in u_plans))
    metrics.gauge("offload_window_rows_m",
                  max(p.window_rows for p in m_plans))
    metrics.gauge("offload_window_rows_u",
                  max(p.window_rows for p in u_plans))
    metrics.gauge("offload_chunks_per_window", cpw)
    metrics.gauge("offload_shards", s)
    metrics.gauge(
        "offload_plan_held_mb",
        round(sum(p.plan_held_bytes()
                  for p in (*m_plans, *u_plans)) / 1e6, 3),
    )
    if any_ring:
        metrics.gauge("offload_ici_group", inner)
        metrics.gauge("offload_acc_reserved_mb",
                      round(acc_reserved / 1e6, 3))
        metrics.note("offload_exchange", config.exchange)
    metrics.note("offload_staging", staging)
    if staging == "pool":
        metrics.gauge("offload_pool_depth", pool_depth)
        metrics.gauge("offload_pool_workers",
                      pool_workers_for(pool_depth))
    metrics.note("offload_hot", "on" if hot_ctx is not None else "off")
    if hot_note:
        metrics.note("offload_hot_decision", hot_note)
    if hot_ctx is not None:
        maps_all = hot_ctx["maps"].values()
        slots_total = sum(m.slots_total for m in maps_all)
        metrics.gauge("offload_hot_rows", f_u + f_m)
        metrics.gauge("offload_hot_rows_u", f_u)
        metrics.gauge("offload_hot_rows_m", f_m)
        if slots_total:
            # Reference coverage: the fraction of per-window row-slots
            # served from the device (hot partition + delta reuse) — the
            # staged-table-byte cut before pow2 padding.
            metrics.gauge("offload_hot_coverage", round(
                sum(m.slots_hot for m in hot_ctx["maps"].values())
                / slots_total, 4))
            metrics.gauge("offload_delta_coverage", round(
                sum(m.slots_kept for m in hot_ctx["maps"].values())
                / slots_total, 4))

    # Init: identical to the resident trainers (init_factors_stats drawn
    # at the REAL entity count — the shard-count-invariant init — zero
    # movie seed).
    key = jax.random.PRNGKey(config.seed)
    u0 = jax.jit(
        init_factors_stats, static_argnames=("rank", "num_entities")
    )(
        key, jax.numpy.asarray(ub.rating_sum), jax.numpy.asarray(ub.count),
        rank=config.rank, num_entities=ub.num_entities,
    ).astype(jax.numpy.dtype(config.dtype))
    u_full_init = np.asarray(u0)
    rows_u_total = ub.padded_entities
    rows_m_total = mb.padded_entities
    visits_all = [hier_visit_order(s, inner, d) for d in range(s)]
    hmaps_m = hmaps_u = rows_hot_u = rows_hot_m = None
    if hot_ctx is not None:
        hmaps_m = [hot_ctx["maps"][("m", d)] for d in range(s)]
        hmaps_u = [hot_ctx["maps"][("u", d)] for d in range(s)]
        rows_hot_u = hot_ctx["rows_u"]
        rows_hot_m = hot_ctx["rows_m"]
    u_store = m_store = None
    own_u = own_m = fleet_sides = owned_shards = None
    hot_u_part = hot_m_part = None
    hot_halves: dict = {}

    def _load_full(step: int):
        """Both full tables at committed ``step``, reassembled from
        every reachable host's manifest bytes (the elastic reload)."""
        u_full = fleet_manifests.load_rows(step, 0, rows_u_total, "u",
                                           rank=config.rank)
        m_full = fleet_manifests.load_rows(step, 0, rows_m_total, "m",
                                           rank=config.rank)
        return u_full, m_full

    def _build_hot_halves(step) -> None:
        """Hot partitions + per-(side, shard) contexts (ISSUE 15): the
        device copies gather from the masters (the movie side starts
        all-zero, exactly like its store), index constants device_put
        once — only the cold delta crosses PCIe per window from here
        on.  Rebuilt whole on every partition change (init, elastic
        shrink, rejoin): the rebuild-≡-restage invariant keeps the
        post-change bits identical to a fresh run's."""
        nonlocal hot_u_part, hot_m_part, hot_halves
        hot_halves = {}
        hot_u_part = hot_m_part = None
        if hot_ctx is None:
            return
        hot_u_part = HotPartition(hot_ctx["rows_u"], stage_name)
        hot_m_part = HotPartition(hot_ctx["rows_m"], stage_name)
        if fleet is None:
            hot_u_part.rebuild(u_store)
            hot_m_part.rebuild(m_store)
        else:
            # Fleet: the masters are slices, so the initial partitions
            # build from transient full-table views (u0 is already fully
            # materialized on every process; the movie side is zeros —
            # or, after an elastic reload, the committed bytes of
            # ``step``).  From here on each half START rebuilds the
            # FIXED side's partition from the exchange mirror — master
            # bytes, the same pinned rebuild-≡-restage invariant the
            # rollback path relies on — replacing the in-half device
            # scatter-back (disabled below: its update would be
            # process-local, and the next half's rebuild overwrites it
            # anyway).
            if step is None:
                u_full = u_full_init
                m_full = np.zeros((rows_m_total, config.rank),
                                  _np_dtype(config.dtype))
            else:
                u_full, m_full = _load_full(int(step))
            hot_u_part.rebuild(HostFactorStore.from_array(
                np.asarray(u_full, _np_dtype(config.dtype)),
                dtype=config.dtype))
            hot_m_part.rebuild(HostFactorStore.from_array(
                np.asarray(m_full, _np_dtype(config.dtype)),
                dtype=config.dtype))
        from cfk_tpu.offload import hot as _hotmod
        for d in (range(s) if fleet is None else owned_shards):
            if fleet is not None:
                # No in-half device scatter-back across a fleet (the
                # update would be process-local); the mirror rebuild at
                # each half start refreshes the partition from master
                # bytes instead.  Ring mode disables via None (guarded
                # by sb_pad), stream mode via an empty map dict.
                sb_m = None if ring_m else {}
            else:
                sb_m = (_hotmod.ring_scatter_back(d, mb.local_entities,
                                                  hot_m_part.rows)
                        if ring_m else
                        _hotmod.scatter_back_maps(m_plans[d], d,
                                                  mb.local_entities,
                                                  hot_m_part.rows))
            hot_halves[("m", d)] = _HotHalf(
                hot_u_part, hot_m_part, hot_ctx["maps"][("m", d)], sb_m,
            )
            if fleet is not None:
                sb_u = None if ring_u else {}
            else:
                sb_u = (_hotmod.ring_scatter_back(d, ub.local_entities,
                                                  hot_u_part.rows)
                        if ring_u else
                        _hotmod.scatter_back_maps(u_plans[d], d,
                                                  ub.local_entities,
                                                  hot_u_part.rows))
            hot_halves[("u", d)] = _HotHalf(
                hot_m_part, hot_u_part, hot_ctx["maps"][("u", d)], sb_u,
            )
        metrics.gauge("offload_hot_resident_mb", round(
            (hot_u_part.nbytes + hot_m_part.nbytes) / 1e6, 3))

    def _setup_partition(new_fleet, step=None) -> None:
        """THE partition constructor: ownership maps, store slices,
        exchange plans, mirrors, and hot partitions for the CURRENT
        fleet (or single-host when ``new_fleet`` is None).  ``step``
        None seeds from init (every process draws the SAME full u0 —
        deterministic, shard-count-invariant — and keeps its owned
        slice; store bounds coincide with shard solve ranges, so solve
        write-back stays purely local); otherwise the stores reload
        committed step bytes from the fleet manifests — the elastic
        shrink/rejoin repartition path."""
        nonlocal fleet, u_store, m_store, own_u, own_m
        nonlocal fleet_sides, owned_shards
        fleet = new_fleet
        if fleet is None:
            if step is None:
                u_store = HostFactorStore.from_array(u_full_init,
                                                     dtype=config.dtype,
                                                     num_shards=s)
                m_store = HostFactorStore(rows_m_total, config.rank,
                                          dtype=config.dtype,
                                          num_shards=s)
            else:
                u_full, m_full = _load_full(int(step))
                u_store = HostFactorStore.from_array(
                    u_full, dtype=config.dtype, num_shards=s)
                m_store = HostFactorStore.from_array(
                    m_full, dtype=config.dtype, num_shards=s)
            own_u = own_m = fleet_sides = owned_shards = None
        else:
            own_u = _exchange.OwnershipMap(s, fleet.num_processes,
                                           fleet.process,
                                           rows_u_total // s)
            own_m = _exchange.OwnershipMap(s, fleet.num_processes,
                                           fleet.process,
                                           rows_m_total // s)
            owned_shards = own_u.owned_shards()
            u_lo, u_hi = own_u.row_bounds()
            m_lo, m_hi = own_m.row_bounds()
            if step is None:
                u_store = HostFactorStore.from_array(
                    u_full_init[u_lo:u_hi], dtype=config.dtype,
                    num_shards=own_u.shards_per_process,
                )
                m_store = HostFactorStore(m_hi - m_lo, config.rank,
                                          dtype=config.dtype,
                                          num_shards=own_m.shards_per_process)
            else:
                u_store = HostFactorStore.from_array(
                    fleet_manifests.load_rows(int(step), u_lo, u_hi, "u",
                                              rank=config.rank),
                    dtype=config.dtype,
                    num_shards=own_u.shards_per_process,
                )
                m_store = HostFactorStore.from_array(
                    fleet_manifests.load_rows(int(step), m_lo, m_hi, "m",
                                              rank=config.rank),
                    dtype=config.dtype,
                    num_shards=own_m.shards_per_process,
                )
            explan_m = _exchange.build_half_exchange(
                own_u, m_plans, [schedules[("m", d)] for d in range(s)],
                inner=inner, visits=visits_all if ring_m else None,
                hmaps=hmaps_m, hot_rows=rows_hot_u, side="m",
            )
            explan_u = _exchange.build_half_exchange(
                own_m, u_plans, [schedules[("u", d)] for d in range(s)],
                inner=inner, visits=visits_all if ring_u else None,
                hmaps=hmaps_u, hot_rows=rows_hot_m, side="u",
            )
            fleet_sides = {
                "m": (_exchange.ResidualMirror(u_store, own_u), explan_m),
                "u": (_exchange.ResidualMirror(m_store, own_m), explan_u),
            }
            metrics.gauge("offload_fleet_processes", fleet.num_processes)
            metrics.gauge("offload_fleet_process", fleet.process)
            metrics.gauge("offload_fleet_epoch", fleet_epoch)
            metrics.gauge("offload_exchange_phases",
                          explan_m.num_phases + explan_u.num_phases)
            metrics.gauge("offload_exchange_recv_rows_iter",
                          explan_m.recv_rows_total
                          + explan_u.recv_rows_total)
            metrics.gauge("offload_exchange_rows_dense_iter",
                          explan_m.dense_rows_total
                          + explan_u.dense_rows_total)
        _build_hot_halves(step)

    # Resume / rejoin.  Non-joiners build their initial partition, then
    # roll forward to the newest jointly restorable step: with fleet
    # manifests that is the manifest-coverage agreement (pure filesystem
    # reads, tightened by the collective min); otherwise the PR 17
    # per-manager fleet-min path, unchanged.  A restarted host instead
    # runs the readmission handshake FIRST — its partition is whatever
    # the surviving fleet admits it back into.
    start_it = 0
    if joiner:
        info = {
            "healthy": fleet_manifests is not None,
            "pid": int(getattr(fleet, "orig_process", -1)),
        }
        adm = fleet.join(info)
        fleet_epoch = int(adm["epoch"])
        start_it = int(adm["step"])
        _setup_partition(fleet, start_it)
        metrics.gauge("offload_resumed_from", start_it)
        metrics.gauge("offload_fleet_epoch", fleet_epoch)
        metrics.incr("fleet_rejoined")
        record_event("fleet", "fleet_rejoined", pid=info["pid"],
                     epoch=fleet_epoch, iteration=start_it)
    else:
        _setup_partition(fleet, None)
        if fleet is not None and fleet_manifests is not None:
            step = fleet_manifests.latest_coverage_step(rows_u_total,
                                                        rows_m_total)
            step = -1 if step is None else int(step)
            step = int(_exchange.agree_min_i32(fleet, step))
            if step >= 0:
                _setup_partition(fleet, step)
                start_it = step
                metrics.gauge("offload_resumed_from", step)
                record_event("train", "offload_resume", iteration=step)
        elif checkpoint_manager is not None:
            # Resume (ISSUE 17): restore the newest checkpoint step
            # EVERY process holds intact — the fleet-wide minimum of
            # each host's latest_valid_iteration, so a host whose shard
            # slice died recovers from its own manifest while the
            # survivors roll back to the same step (the PR 5 lockstep
            # contract, per-host stores edition).
            latest = checkpoint_manager.latest_valid_iteration()
            step = -1 if latest is None else int(latest)
            if fleet is not None:
                step = _exchange.agree_min_i32(fleet, step)
            if step >= 0:
                st = checkpoint_manager.restore(iteration=step)
                if st.user_factors.shape != (u_store.rows, config.rank):
                    raise ValueError(
                        f"checkpoint step {step} holds user factors "
                        f"{st.user_factors.shape} but this process's store "
                        f"slice is {(u_store.rows, config.rank)} — resuming "
                        "under a different fleet size or shard count is not "
                        "a thing the ownership map can reinterpret"
                    )
                u_store.write_range(0, np.asarray(st.user_factors))
                m_store.write_range(0, np.asarray(st.movie_factors))
                start_it = step
                metrics.gauge("offload_resumed_from", step)
                record_event("train", "offload_resume", iteration=step)
                # Re-gather the hot partitions from the RESUMED masters
                # (single mode reads them directly; fleet partitions are
                # rebuilt from the mirror at each half start anyway).
                _build_hot_halves(None)

    policy = policy_from_config(config)
    base_ov = Overrides(lam=config.lam, fused_epilogue=config.fused_epilogue)
    ov = base_ov
    norm_limit = (config.health_norm_limit
                  if config.health_check_every is not None else None)
    probe_every = config.health_check_every or 1
    # StagingStats, not a dict: pooled staging increments these from
    # worker threads (the guard the donated-buffer/step-hook audit asks
    # for — every gauge below reads HOST-side counters metered before
    # the device_put hand-off, never a donated device array).
    stats = StagingStats()
    if verify_windows is None:
        # Checksumming every staged window costs a host pass over its
        # bytes, and its scope is the host staging pipeline up to the
        # device_put hand-off (exactly the seam the chaos fault hook
        # corrupts) — so it defaults on precisely when a fault plan is
        # armed.  It is NOT a PCIe-DMA integrity check (that needs a
        # device-side checksum; on-TPU follow-up).
        verify_windows = window_faults is not None
    half_kw = dict(
        out_dtype=config.dtype, solver=config.solver,
        overlap=bool(config.overlap),
        in_kernel_gather=config.in_kernel_gather,
        table_dtype=config.table_dtype, faults=window_faults, stats=stats,
        verify_windows=verify_windows, ici_group=inner,
    )
    m_local = mb.local_entities
    u_local = ub.local_entities
    count_m = mb.count.reshape(s, -1)
    count_u = ub.count.reshape(s, -1)

    stage_name_cfg = _stage_dtype(config.dtype, config.table_dtype)
    int8_cfg = stage_name_cfg == "int8"
    stage_np_cfg = None if int8_cfg else _np_dtype(stage_name_cfg)

    def half(side, fixed_store, plans, local, counts, it, ring):
        """One half-iteration across every shard: per-shard windowed
        scans against the shared host store, in this side's execution
        shape (``ring`` — the per-side resolution of
        ``_resolve_side_modes``, so an ``exchange='auto'`` mixed build
        runs each half exactly as the resident trainer would).  Reads
        one store, writes a host buffer (committed by the caller) — no
        read-after-write hazard across shards, matching the resident
        step's solve-all-then-exchange structure.

        ONE staging engine serves the whole half (ISSUE 13): the task
        list flattens every shard's schedule shard-major — exactly the
        order the per-shard half-steps consume below — and the pool
        stages ahead across that order, so shard d+1's host gather +
        ``device_put`` run under shard d's dispatched compute instead of
        after it.  Staging is a pure read of ``fixed_store`` (written
        only after the half commits), so any staging-ahead interleave is
        bit-safe; consumption order — and therefore every bit — is
        unchanged.  ``close()`` in the ``finally`` drains workers before
        any rollback can swap the store under them."""
        algo = ov.reg_solve_algo or config.reg_solve_algo
        shards = range(s) if fleet is None else owned_shards
        hot_on = bool(hot_halves)
        if armed and fleet is None:
            # Gather-boundary integrity check (ISSUE 20): the fixed
            # table is about to be staged — verify its sealed shards
            # before any rotten byte can launder into a window.  Fleet
            # mode scrubs at the lockstep boundary instead (a raise
            # here would desync the collective schedule).
            fixed_store.scrub()
        fixed_read = fixed_store
        if fleet is not None:
            # Distributed window exchange (ISSUE 17): every DCN phase's
            # cold residual lands in the mirror BEFORE compute starts
            # (the pooled stager may stage any window ahead), then the
            # fixed side's hot partition rebuilds from the just-shipped
            # master bytes.  The staging pipeline below runs unchanged
            # against the mirror — same gathers, same checksums, same
            # fabric attribution, same bits.
            mirror, explan = fleet_sides[side]
            _exchange.exchange_half(explan, fixed_store, mirror, fleet,
                                    stats=stats, iteration=it)
            if hot_on:
                hot_halves[(side, shards.start)].fixed.rebuild(mirror)
            fixed_read = mirror
        out = np.zeros((local * len(shards), config.rank),
                       dtype=_np_dtype(config.dtype))
        schedules = [
            (plans[d].schedule(hier_visit_order(s, inner, d)) if ring
             else plans[d].schedule())
            for d in range(s)
        ]
        tasks = [(d, w) for d in shards for w in schedules[d]]
        if hot_on and window_faults is not None:
            # Chaos seam (ISSUE 15): poison the FIXED side's device
            # partition before the half reads it — the host master is
            # untouched, so the sentinel trip that follows rolls back
            # and `rebuild` recovers the partition bit-exactly.
            part = hot_halves[(side, shards.start)].fixed
            pois = (window_faults.apply_hot(it, side, part.num_rows)
                    if hasattr(window_faults, "apply_hot") else None)
            if pois is not None:
                record_event("fault", "hot_cache_corruption",
                             iteration=it, side=side, rows=len(pois))
                part.poison(pois)

        def stage_task(d, w):
            if hot_on:
                return _stage_window_delta(
                    fixed_read, plans[d], hot_halves[(side, d)].hmap, w,
                    stage_np=stage_np_cfg, int8=int8_cfg,
                    faults=window_faults, iteration=it, side=side,
                    shard=d, verify_windows=verify_windows, stats=stats,
                    ici_group=inner,
                )
            return _stage_window(
                fixed_read, plans[d], w, stage_np=stage_np_cfg,
                int8=int8_cfg, faults=window_faults, iteration=it,
                side=side, shard=d, verify_windows=verify_windows,
                stats=stats, ici_group=inner,
            )

        def stage_attrs(d, w):
            attrs = _stage_span_attrs(
                hot_halves[(side, d)].hmap if hot_on else None,
                plans[d], w,
            )
            attrs["host"] = 0 if fleet is None else fleet.process
            return attrs

        stager = WindowStager(tasks, stage_task, mode=staging,
                              depth=pool_depth, stats=stats,
                              span_attrs=stage_attrs)
        try:
            for d in shards:
                kw = dict(half_kw, lam=ov.lam,
                          fused_epilogue=ov.fused_epilogue,
                          reg_solve_algo=algo, iteration=it, side=side,
                          shard=d, stager=stager,
                          hot=hot_halves.get((side, d)),
                          host=0 if fleet is None else fleet.process)
                with span("train/iter/half_step", side=side, shard=d,
                          ring=bool(ring), iteration=it,
                          host=0 if fleet is None else fleet.process):
                    if ring:
                        rows = ring_windowed_half_step(
                            fixed_read, plans[d],
                            visits=hier_visit_order(s, inner, d),
                            count_local=counts[d], **kw,
                        )
                    else:
                        rows = windowed_half_step(fixed_read, plans[d],
                                                  **kw)
                out[(d - shards.start) * local:
                    (d - shards.start + 1) * local] = rows
        finally:
            stager.close()
        return out

    # Probing + last-good snapshots cost a full host pass + memcpy over
    # both stores per cadence — at the ALX regime that is gigabytes per
    # iteration — so they arm only when something can trip: the sentinel
    # (health_check_every), the staging checksum, or a chaos fault plan.
    # Unarmed runs match the resident trainer's default (no sentinel).
    armed = (config.health_check_every is not None
             or verify_windows or window_faults is not None)

    snap = (u_store.copy(), m_store.copy()) if armed else (None, None)
    snap_iter = start_it
    trips = 0
    it = start_it
    degraded = False
    traces0 = trace_count()
    train_t0 = time.time()
    first_step_s = None

    def _rebuild_hot() -> None:
        """Rollback heals the hot partitions the same way it heals the
        stores: re-gather from the restored host masters (ISSUE 15 —
        a poisoned or stale device partition cannot survive a rollback,
        so replay is bit-identical to a fresh run)."""
        if hot_u_part is not None and fleet is None:
            hot_u_part.rebuild(u_store)
            hot_m_part.rebuild(m_store)
        # Fleet: partitions rebuild from the exchange mirror at each
        # half start (master bytes of the ROLLED-BACK stores — the
        # exchange rebinds to the restored slice), so there is nothing
        # to heal here.

    def trip(reason: str) -> bool:
        """Rollback + ladder climb; returns False when retries are
        exhausted (degrade — the caller breaks the loop)."""
        nonlocal u_store, m_store, it, trips, ov
        trips += 1
        metrics.incr("health_trips")
        metrics.note(f"health_trip_{trips}", f"iteration {it}: {reason}")
        # Flight-record + dump: the ring buffer holds the window/half
        # events of the iterations leading here — the forensic timeline
        # every chaos offload scenario asserts on.
        record_event("fault", "health_trip", iteration=it, trip=trips,
                     reason=reason)
        dump_flight(f"health_trip_{trips}")
        if trips > policy.max_recoveries:
            detail = (
                f"recovery exhausted after {policy.max_recoveries} "
                f"trips; last: {reason}"
            )
            if policy.on_unrecoverable == "raise":
                record_event("fault", "unrecoverable", detail=detail)
                dump_flight("unrecoverable")
                raise TrainingDivergedError(detail)
            metrics.note("degraded", detail)
            record_event("fault", "degraded", detail=detail)
            dump_flight("degraded")
            u_store, m_store = snap
            it = snap_iter
            u_store.seal()
            m_store.seal()
            _rebuild_hot()
            return False
        u_store, m_store = snap[0].copy(), snap[1].copy()
        it = snap_iter
        # Snapshot copies start unsealed (HostFactorStore.copy()) —
        # reseal so the integrity scrub keeps covering the rolled-back
        # bytes.
        u_store.seal()
        m_store.seal()
        _rebuild_hot()
        metrics.incr("rollbacks")
        new_ov = policy.escalate(ov, trips)
        detail = (
            f"rung {trips}: rollback to iter {snap_iter}, "
            f"lam={new_ov.lam}, fused={new_ov.fused_epilogue}, "
            f"algo={new_ov.reg_solve_algo or config.reg_solve_algo}"
        )
        if new_ov != ov:
            metrics.gauge("escalation_level", trips)
            metrics.note(f"escalation_{trips}", detail)
            record_event("fault", "escalation", rung=trips, detail=detail)
        ov = new_ov
        if plan_provenance is not None:
            t = plan_provenance.record_transition(
                "recovery_escalation", detail
            )
            metrics.note(f"plan_transition_{trips}", str(t))
        return True

    def _shrink_infeasible(why: str) -> bool:
        record_event("fault", "fleet_shrink_infeasible", iteration=it,
                     detail=why)
        metrics.note("fleet_shrink_infeasible", why)
        dump_flight("fleet_shrink_infeasible")
        return False

    def _fleet_shrink(err) -> bool:
        """The shrink protocol (ISSUE 20): a peer is dead for good —
        min-agree the last jointly covered step from the per-host
        manifests, reform (or drop) the fleet, repartition ownership
        over the survivors, reload the orphaned slice from committed
        bytes, roll back, continue.  Returns False when live shrink is
        infeasible (the caller re-raises into the bounded-exit path) —
        ARCHITECTURE.md's "what still requires restart" list."""
        nonlocal it, snap, snap_iter, fleet_epoch
        record_event("fault", "fleet_peer_dead", iteration=it,
                     peers=[int(p) for p in getattr(err, "peers", ())],
                     detail=str(err))
        metrics.incr("fleet_peers_lost")
        if fleet is None or fleet_manifests is None:
            return False
        try:
            alive = [int(p) for p in fleet.surviving(err)]
        except _elastic.ShrinkInfeasibleError as e2:
            return _shrink_infeasible(str(e2))
        me = int(getattr(fleet, "orig_process", fleet.process))
        if not alive or me not in alive:
            return _shrink_infeasible(
                f"this host ({me}) is not in the surviving set {alive}"
            )
        if s % len(alive) != 0:
            return _shrink_infeasible(
                f"num_shards={s} is not divisible by the surviving "
                f"fleet size {len(alive)} — contiguous shard-block "
                "ownership cannot repartition; restart required"
            )
        step = fleet_manifests.latest_coverage_step(rows_u_total,
                                                    rows_m_total)
        if step is None:
            return _shrink_infeasible(
                "no checkpoint step is jointly covered by the reachable "
                "manifests — nothing to reload the orphaned slice from"
            )
        try:
            new_fleet = fleet.shrink_to(alive)
        except _elastic.ShrinkInfeasibleError as e2:
            return _shrink_infeasible(str(e2))
        if new_fleet is not None and len(alive) > 1:
            # >1 survivors share a reformed transport: tighten the
            # filesystem agreement with the collective min (identical
            # by construction on shared storage; belt and braces on
            # anything eventually-consistent).
            step = int(_exchange.agree_min_i32(new_fleet, int(step)))
        fleet_epoch = (int(getattr(new_fleet, "epoch", fleet_epoch + 1))
                       if new_fleet is not None else fleet_epoch + 1)
        _setup_partition(new_fleet, int(step))
        it = int(step)
        if armed:
            snap = (u_store.copy(), m_store.copy())
            snap_iter = it
            u_store.seal()
            m_store.seal()
        metrics.incr("fleet_shrinks")
        metrics.gauge("offload_fleet_epoch", fleet_epoch)
        metrics.note(
            f"fleet_shrink_{fleet_epoch}",
            f"peers {[int(p) for p in getattr(err, 'peers', ())]} lost; "
            f"continuing with {len(alive)} host(s) from step {step} at "
            f"epoch {fleet_epoch}",
        )
        record_event("fleet", "fleet_shrink", epoch=fleet_epoch,
                     alive=alive, step=int(step))
        dump_flight("fleet_shrink")
        return True

    def _poll_rejoin() -> bool:
        """The readmission handshake's fleet side, run at every
        iteration boundary: triage pending join requests (health gate +
        shard divisibility, refused by rank 0), then allgather the
        candidate so admission is unanimous at ONE boundary — a request
        visible to only some members postpones to the next boundary.
        On admission every member acks, the epoch bumps (stale frames
        from the joiner's previous life are fenced from here on), and
        everyone — joiner included — rebuilds the partition at the
        agreed step.  Returns True when membership changed (the caller
        restarts the boundary)."""
        nonlocal it, snap, snap_iter, fleet_epoch
        cand = -1
        for pid, info in fleet.poll_joiners():
            if not info.get("healthy", True):
                if fleet.process == 0:
                    fleet.refuse_join(int(pid), "health gate failed")
                continue
            if s % (fleet.num_processes + 1) != 0:
                if fleet.process == 0:
                    fleet.refuse_join(
                        int(pid),
                        f"num_shards={s} not divisible by the rejoined "
                        f"fleet size {fleet.num_processes + 1}",
                    )
                continue
            cand = int(pid)
            break
        words = fleet.allgather_i32([cand])
        cands = [int(w[0]) for w in words]
        if len(set(cands)) != 1 or cands[0] < 0:
            return False
        pid = cands[0]
        step = fleet_manifests.latest_coverage_step(rows_u_total,
                                                    rows_m_total)
        step = -1 if step is None else int(step)
        step = int(_exchange.agree_min_i32(fleet, step))
        if step < 0:
            if fleet.process == 0:
                fleet.refuse_join(
                    pid, "no jointly covered checkpoint step to rejoin at"
                )
            return False
        new_alive = sorted(set(int(p) for p in fleet.alive) | {pid})
        new_epoch = int(getattr(fleet, "epoch", fleet_epoch)) + 1
        fleet.admit(pid, new_epoch, new_alive, step)
        fleet_epoch = int(getattr(fleet, "epoch", new_epoch))
        _setup_partition(fleet, step)
        it = step
        if armed:
            snap = (u_store.copy(), m_store.copy())
            snap_iter = it
            u_store.seal()
            m_store.seal()
        metrics.incr("fleet_rejoins")
        metrics.gauge("offload_fleet_epoch", fleet_epoch)
        record_event("fleet", "fleet_rejoin", pid=pid, epoch=fleet_epoch,
                     step=step, alive=new_alive)
        dump_flight("fleet_rejoin")
        return True

    def _save_meta() -> dict:
        """Checkpoint manifest meta: the ISSUE 20 schema extension —
        fleet epoch, membership, and this host's owned row ranges, so
        the shrink/rejoin protocol can agree on coverage and reload any
        slice from pure manifest reads."""
        u_bounds = ((0, rows_u_total) if own_u is None
                    else own_u.row_bounds())
        m_bounds = ((0, rows_m_total) if own_m is None
                    else own_m.row_bounds())
        return {
            "tier": "host_window",
            "processes": (1 if fleet is None
                          else int(fleet.num_processes)),
            "process": 0 if fleet is None else int(fleet.process),
            "fleet_epoch": int(fleet_epoch),
            "alive": ([0] if fleet is None else
                      [int(p) for p in
                       getattr(fleet, "alive",
                               range(fleet.num_processes))]),
            "u_row_lo": int(u_bounds[0]), "u_row_hi": int(u_bounds[1]),
            "m_row_lo": int(m_bounds[0]), "m_row_hi": int(m_bounds[1]),
        }

    if watchdog is not None:
        watchdog.arm()
    try:
        with metrics.phase("train"):
            while it < config.num_iterations:
                try:
                    with span("train/iter", i=it, tier="host_window"):
                        m_new = half("m", u_store, m_plans, m_local,
                                     count_m, it, ring_m)
                        m_store.write_range(0, m_new)
                        if armed:
                            m_store.seal()
                        u_new = half("u", m_store, u_plans, u_local,
                                     count_u, it, ring_u)
                        u_store.write_range(0, u_new)
                        if armed:
                            u_store.seal()
                    record_event("train", "iter", i=it, tier="host_window")
                    it += 1
                    metrics.incr("iterations")
                    if (checkpoint_manager is not None
                            and should_save(it, checkpoint_every,
                                            config.num_iterations)):
                        # Per-process manifest of the OWNED slice, after
                        # the iteration commit — the recovery unit a
                        # killed host's replacement restores (fleet-min
                        # agreement at startup picks the step every host
                        # holds).
                        checkpoint_manager.save(
                            it, u_store.as_array(), m_store.as_array(),
                            meta=_save_meta(),
                        )
                    if (window_faults is not None
                            and hasattr(window_faults, "apply_store")):
                        # Master-store chaos seam (ISSUE 20): bit-rot
                        # lands AFTER the seal and the checkpoint commit
                        # — the committed bytes stay clean, which is
                        # exactly what the repair path restores.
                        window_faults.apply_store(it - 1, "u", u_store)
                        window_faults.apply_store(it - 1, "m", m_store)
                    if watchdog is not None:
                        watchdog.tick(it)
                    if first_step_s is None:
                        # Cold-start attribution (ISSUE 13): how long
                        # until the first full iteration lands — the
                        # quantity a warm persistent compile cache
                        # (compile_cache_dir) shrinks.
                        first_step_s = time.time() - train_t0
                    if (elastic_on and fleet is not None
                            and getattr(fleet, "supports_join", False)):
                        if _poll_rejoin():
                            continue
                    if not armed:
                        continue
                    if (it % probe_every != 0
                            and it < config.num_iterations):
                        continue
                    reason = _probe(u_new, m_new, norm_limit)
                    if reason is None:
                        try:
                            # Boundary scrub (ISSUE 20): both masters
                            # verified against their seals once per
                            # probe cadence.  Fleet mode folds a hit
                            # into the lockstep trip below (a raise here
                            # would desync the collective schedule);
                            # single mode raises into the checkpoint-
                            # repair handler.
                            u_store.scrub()
                            m_store.scrub()
                        except StoreIntegrityError as e:
                            if fleet is None:
                                raise
                            reason = f"store integrity: {e}"
                    if fleet is not None:
                        # Lockstep trip sync (the PR 5 contract): one
                        # word per process; ANY nonzero rolls every host
                        # back to the same snapshot step with the same
                        # ladder rung — the collective schedules stay
                        # aligned.
                        flags = _exchange.any_flag(fleet,
                                                   reason is not None)
                        if reason is None and flags.any():
                            peers = [p for p in range(fleet.num_processes)
                                     if flags[p]]
                            reason = f"lockstep trip from peer {peers}"
                    if reason is None:
                        snap = (u_store.copy(), m_store.copy())
                        snap_iter = it
                        continue
                    if not trip(reason):
                        degraded = True
                        break
                except WindowIntegrityError as e:
                    # The staging checksum caught a torn/corrupt window
                    # BEFORE it reached a kernel; the store is intact, so
                    # rollback + replay is exact (the stores may hold a
                    # half-written m — the snapshot restore erases it).
                    if fleet is not None:
                        # A half-iteration trip desyncs the fleet's
                        # collective schedule (peers are already past the
                        # probe sync) — fatal here; peers are bounded by
                        # the Gloo transport error or their StallWatchdog.
                        record_event("fault", "window_integrity_fleet",
                                     iteration=it, detail=str(e))
                        dump_flight("window_integrity_fleet")
                        raise
                    if not trip(f"window integrity: {e}"):
                        degraded = True
                        break
                    continue
                except StoreIntegrityError as e:
                    # Host-RAM bit-rot in a MASTER table (the seals
                    # caught it at a gather boundary or the boundary
                    # scrub): the store itself is wrong, so a snapshot
                    # rollback only helps if the snapshot predates the
                    # rot — the committed checkpoint bytes are the
                    # authoritative repair source.
                    record_event("fault", "store_integrity", iteration=it,
                                 shard=getattr(e, "shard", -1),
                                 detail=str(e))
                    metrics.incr("store_integrity_detected")
                    repair_step = (
                        checkpoint_manager.latest_valid_iteration()
                        if (fleet is None and checkpoint_manager
                            is not None) else None
                    )
                    if repair_step is None:
                        # No committed bytes to repair from: the in-RAM
                        # last-good snapshot is the only recourse.
                        dump_flight("store_integrity")
                        if not trip(f"store integrity: {e}"):
                            degraded = True
                            break
                        continue
                    st = checkpoint_manager.restore(int(repair_step))
                    u_store = HostFactorStore.from_array(
                        np.asarray(st.user_factors), dtype=config.dtype,
                        num_shards=u_store.num_shards,
                    )
                    m_store = HostFactorStore.from_array(
                        np.asarray(st.movie_factors), dtype=config.dtype,
                        num_shards=m_store.num_shards,
                    )
                    it = int(repair_step)
                    u_store.seal()
                    m_store.seal()
                    snap = (u_store.copy(), m_store.copy())
                    snap_iter = it
                    _rebuild_hot()
                    metrics.incr("store_repairs")
                    record_event("fault", "store_repair", iteration=it,
                                 step=int(repair_step))
                    dump_flight("store_integrity_repair")
                    continue
                except _elastic.PeerDeadError as e:
                    # A peer is gone for good (retries exhausted / fatal
                    # transport error / collective timeout).  Elastic
                    # fleets shrink and continue; anything else keeps
                    # the PR 16 bounded-exit contract (the caller's
                    # StallWatchdog/drill harness handles the exit).
                    if not (elastic_on and _fleet_shrink(e)):
                        raise
                    continue
    finally:
        if watchdog is not None:
            watchdog.disarm()
    metrics.gauge("offload_windows_staged", stats.get("windows_staged", 0))
    metrics.gauge("offload_staged_mb",
                  round(stats.get("staged_bytes", 0) / 1e6, 3))
    # The staged TABLE share, split per ISSUE 15: cold bytes actually
    # shipped over PCIe vs the device-resident hot partition (0 when the
    # cache is off — then cold == the whole table share, the PR 12
    # number under its new name).
    metrics.gauge("offload_staged_cold_mb",
                  round(stats.get("staged_cold_bytes", 0) / 1e6, 3))
    for key_ in ("rows_staged", "rows_delta_skipped", "rows_hot_device"):
        if key_ in stats:
            metrics.gauge(f"offload_{key_}", stats[key_])
    # Staging-engine accounting (ISSUE 13): busy = summed staging task
    # seconds, stall = the consuming thread's exposed wait (== busy in
    # serial mode by construction), hidden = 1 − stall/busy.  All read
    # from HOST-side counters — never a donated device buffer.
    busy = float(stats.get("stage_busy_s", 0.0))
    stall = float(stats.get("stage_stall_s", 0.0))
    metrics.gauge("offload_stage_busy_s", round(busy, 4))
    metrics.gauge("offload_stage_stall_s", round(stall, 4))
    if busy > 0:
        metrics.gauge("offload_stage_hidden_frac",
                      round(max(0.0, 1.0 - stall / busy), 4))
        metrics.gauge("offload_staged_mb_per_s",
                      round(stats.get("staged_bytes", 0) / 1e6 / busy, 2))
    if staging == "pool":
        metrics.gauge("offload_pool_peak_inflight",
                      stats.get("pool_peak_inflight", 0))
        metrics.gauge("offload_pool_worker_stagings",
                      stats.get("pool_worker_stagings", 0))
    metrics.gauge("offload_trace_count", trace_count() - traces0)
    if first_step_s is not None:
        metrics.gauge("time_to_first_step_s", round(first_step_s, 4))
    for key_ in ("rows_local", "rows_ici", "rows_dcn"):
        if key_ in stats:
            metrics.gauge(f"offload_{key_}", stats[key_])
    if fleet is not None:
        # Residual DCN accounting (ISSUE 17): rows/bytes a pairwise DCN
        # fabric would carry per the exchange manifests (cumulative-
        # deduped cold residual — the quantity the hot/delta split
        # shrinks), plus the actual allgather wire bytes (pad × peers).
        for key_ in ("exchange_rows_dcn", "exchange_bytes_dcn",
                     "exchange_wire_bytes"):
            if key_ in stats:
                metrics.gauge(f"offload_{key_}", stats[key_])
        metrics.gauge("offload_exchange_mb_dcn",
                      round(stats.get("exchange_bytes_dcn", 0) / 1e6, 3))
        metrics.gauge("offload_exchange_wire_mb",
                      round(stats.get("exchange_wire_bytes", 0) / 1e6, 3))
    if degraded:
        metrics.gauge("iterations_completed", snap_iter)

    from cfk_tpu.models.als import ALSModel

    if fleet is None:
        u_arr, m_arr = u_store.as_array(), m_store.as_array()
    else:
        # Final hand-off: assemble the full tables from every process's
        # slice (the drills' crc comparison reads this; slice-only
        # consumers at true ALX scale would skip it — ROADMAP).
        u_arr = _exchange.allgather_store(fleet, u_store, own_u)
        m_arr = _exchange.allgather_store(fleet, m_store, own_m)
    return ALSModel(
        user_factors=u_arr,
        movie_factors=m_arr,
        num_users=dataset.user_map.num_entities,
        num_movies=dataset.movie_map.num_entities,
    )


# ---------------------------------------------------------------------------
# Out-of-core iALS / iALS++ (ISSUE 19): the global-Gram reduction over the
# host store, the bucketed width-class window jits, and the implicit driver.
# ---------------------------------------------------------------------------


def _gram_block_impl(acc, data, scale):
    """One staged block's contribution to the global YᵀY accumulator —
    the SAME ``gram_block_add`` body the resident ``global_gram_blocked``
    scans (per-block bits are scan-length-invariant, so the streamed
    reduction is bit-equal to the resident in-jit scan), fed the
    dequantized view the kernels read (``quant.dequantize_table`` — the
    int8 Gram must see codes·scale, not raw codes)."""
    from cfk_tpu.ops import quant
    from cfk_tpu.ops.solve import gram_block_add

    _TRACES[0] += 1
    return gram_block_add(acc, quant.dequantize_table(data, scale))


@functools.lru_cache(maxsize=None)
def _gram_block_jit():
    """The Gram-reduction jit.  The accumulator donates (in-place add —
    output aliases input, the ring-carry idiom at the [k,k] scale); the
    staged block pair additionally donates on TPU only
    (``_staged_donate_argnums`` — on CPU ``device_put`` zero-copy-aliases
    the host block)."""
    return jax.jit(
        _gram_block_impl,
        donate_argnums=_staged_donate_argnums((0,), (1, 2)),
    )


def windowed_store_gram(store: HostFactorStore, *,
                        table_dtype: str | None = None,
                        stats: dict | None = None,
                        block_rows: int | None = None):
    """Global YᵀY of a host-resident factor table, reduced block-by-block
    into a device [k, k] f32 accumulator (ISSUE 19's piece 1).

    The implicit half-steps need the FULL fixed-side Gram, which the
    resident bucketed paths compute in-jit from the whole table — exactly
    the array the out-of-core regime cannot hold.  Here the store streams
    through the device in ``ops.solve.GRAM_BLOCK_ROWS`` blocks at the
    STAGING dtype (host cast / ``quantize_rows_host`` — per-row pinned
    bit-identical to the resident in-jit quantization), each block's
    partial Gram accumulating via the SAME ``gram_block_add`` body the
    resident ``global_gram_blocked`` scans.  The tail block zero-pads in
    the dequantized domain (int8 pads ship zero codes with scale 1.0 —
    dequantize to exact 0.0 rows, a zero Gram contribution), matching the
    resident zero-pad bit-for-bit.  Result: the streamed accumulator is
    BIT-EQUAL to the resident global Gram at every staging dtype.

    The [k,k] accumulator plus the double-buffered staged block are the
    ``budget.gram_reservation_bytes`` term the driver reserves before
    window sizing — refused loudly when it does not fit.

    Lifetime: recomputed from the host MASTERS at the start of each half
    (never carried across iterations), so the rollback ladder's store
    restore heals the accumulator for free — replay recomputes it from
    the restored bytes."""
    from cfk_tpu.ops.solve import GRAM_BLOCK_ROWS

    import jax.numpy as jnp

    br = int(block_rows) if block_rows else GRAM_BLOCK_ROWS
    stage_name = _stage_dtype(store.dtype, table_dtype)
    int8 = stage_name == "int8"
    stage_np = None if int8 else _np_dtype(stage_name)
    k = store.rank
    acc = jnp.zeros((k, k), jnp.float32)
    for lo in range(0, store.rows, br):
        hi = min(lo + br, store.rows)
        tbl = store.gather(np.arange(lo, hi, dtype=np.int64))
        if int8:
            data, scale = quantize_rows_host(tbl)
        else:
            data = (tbl if tbl.dtype == stage_np
                    else tbl.astype(stage_np))
            scale = None
        if hi - lo < br:
            pad = np.zeros((br, k), dtype=data.dtype)
            pad[: hi - lo] = data
            data = pad
            if scale is not None:
                ps = np.ones((br,), dtype=np.float32)
                ps[: hi - lo] = scale
                scale = ps
        if stats is not None:
            stats_add(stats, "gram_staged_bytes",
                      data.nbytes + (scale.nbytes if scale is not None
                                     else 0))
            stats_add(stats, "gram_blocks_staged", 1)
        data, scale = jax.device_put((data, scale))
        acc = _gram_block_jit()(acc, data, scale)
    return acc


def _bucket_window_impl(tbl, scale, nb, rt, mk, gram, *, shape, lam, alpha,
                        solver, overlap, fused_epilogue, in_kernel_gather,
                        reg_solve_algo, out_dtype):
    """One staged width-class window through the UNMODIFIED resident
    bucket piece (``ops.solve.ials_half_step_bucketed``'s solve_piece):
    the ported gather/Gram kernels where the static gates admit them,
    else the legacy XLA schedule against the dequantized window view.
    Whole-bucket windows run the direct call; chunked windows run the
    resident ``chunk_map`` scan at the resident per-chunk batch shape
    (scan-length-invariant bits for length ≥ 2 — the plan's floor), so
    the per-entity solves are bit-identical to the resident walk."""
    import jax.numpy as jnp

    from cfk_tpu.ops import bucketed as bport
    from cfk_tpu.ops import quant
    from cfk_tpu.ops.pipeline import chunk_map
    from cfk_tpu.ops.solve import (
        gather_gram_implicit,
        regularized_solve_matrix,
    )

    _TRACES[0] += 1
    ncw, chunk, width, whole = shape
    view = quant.dequantize_table(tbl, scale)
    k = view.shape[-1]
    reg_m = gram + lam * jnp.eye(k, dtype=jnp.float32)

    def solve_piece(ni, rt_c, mk_c):
        rows = ni.shape[0]
        modes = bport.resolve_bucket_modes(
            fused_epilogue, in_kernel_gather, solver, rows, width, k,
            None, reg_solve_algo, table_dtype=tbl.dtype,
        )
        if modes is None:
            a_obs, b = gather_gram_implicit(view, ni, alpha * rt_c, mk_c)
            return regularized_solve_matrix(a_obs, b, reg_m, solver,
                                            algo=reg_solve_algo)
        fused, gather = modes
        wt, rt_b = bport.ials_reparam(rt_c, mk_c, alpha)
        return bport.bucket_gram_solve(
            tbl, scale, ni, wt, rt_b, reg_m, lam=0.0, reg_mode="matrix",
            solver=solver, fused=fused, gather=gather, algo=reg_solve_algo,
        )

    if whole:
        xs = solve_piece(nb.reshape(chunk, width),
                         rt.reshape(chunk, width),
                         mk.reshape(chunk, width))
    else:
        xs = chunk_map(
            solve_piece,
            (nb.reshape(ncw, chunk, width), rt.reshape(ncw, chunk, width),
             mk.reshape(ncw, chunk, width)),
            ncw, overlap=overlap,
        ).reshape(ncw * chunk, k)
    return xs.astype(jnp.dtype(out_dtype))


_BUCKET_STATICS = ("shape", "lam", "alpha", "solver", "overlap",
                   "fused_epilogue", "in_kernel_gather", "reg_solve_algo",
                   "out_dtype")


@functools.lru_cache(maxsize=None)
def _bucket_window_jit():
    """The bucketed-iALS window jit (one trace per width-class shape).
    The staged (tbl, scale) pair donates on TPU only; the Gram
    accumulator is NEVER donated — every window of the half reads it."""
    return jax.jit(
        _bucket_window_impl, static_argnames=_BUCKET_STATICS,
        donate_argnums=_staged_donate_argnums((), (0, 1)),
    )


@functools.lru_cache(maxsize=None)
def _bucket_window_hot_jit():
    """Same program under the hot/delta engine: no staged donation — the
    assembled window table is the successor's delta-reuse source."""
    return jax.jit(
        _bucket_window_impl, static_argnames=_BUCKET_STATICS,
    )


def _bucket_window_pp_impl(tbl, scale, nb, rt, mk, xw, gram, *, shape, lam,
                           alpha, block_size, sweeps, solver, overlap,
                           fused_epilogue, in_kernel_gather,
                           reg_solve_algo, out_dtype):
    """One staged width-class window through the UNMODIFIED iALS++
    subspace sweep (``ops.subspace._sweep_rect`` — the identical body the
    resident ``ials_pp_half_step_bucketed`` walks), warm-started from the
    staged ``xw`` rows (the solve side's previous factors gathered per
    window slot; trash slots zero — exactly the resident warm walk's
    zero-seeded scratch row).  The sweeps are purely per-entity, so the
    windowed per-chunk results are bit-identical to the resident scan at
    the same chunk shape."""
    import jax.numpy as jnp

    from cfk_tpu.ops.pipeline import chunk_map
    from cfk_tpu.ops.subspace import _sweep_rect

    _TRACES[0] += 1
    ncw, chunk, width, whole = shape
    k = xw.shape[-1]

    def sweep_piece(xb, ni, rt_c, mk_c):
        for _ in range(sweeps):
            xb = _sweep_rect(
                tbl, xb, ni, rt_c, mk_c, lam, alpha, gram, block_size,
                solver, scale=scale, in_kernel_gather=in_kernel_gather,
                fused_epilogue=fused_epilogue,
                reg_solve_algo=reg_solve_algo,
            )
        return xb

    x0 = xw.astype(jnp.float32)
    if whole:
        xs = sweep_piece(x0, nb.reshape(chunk, width),
                         rt.reshape(chunk, width),
                         mk.reshape(chunk, width))
    else:
        xs = chunk_map(
            sweep_piece,
            (x0.reshape(ncw, chunk, k), nb.reshape(ncw, chunk, width),
             rt.reshape(ncw, chunk, width), mk.reshape(ncw, chunk, width)),
            ncw, overlap=overlap,
        ).reshape(ncw * chunk, k)
    return xs.astype(jnp.dtype(out_dtype))


_BUCKET_PP_STATICS = _BUCKET_STATICS + ("block_size", "sweeps")


@functools.lru_cache(maxsize=None)
def _bucket_window_pp_jit():
    """The iALS++ window jit: staged table pair AND the per-window
    warm-start rows donate on TPU (both are freshly staged per window);
    the Gram accumulator never donates."""
    return jax.jit(
        _bucket_window_pp_impl, static_argnames=_BUCKET_PP_STATICS,
        donate_argnums=_staged_donate_argnums((), (0, 1, 5)),
    )


@functools.lru_cache(maxsize=None)
def _bucket_window_pp_hot_jit():
    """iALS++ under the hot/delta engine: the assembled table outlives
    the call (delta reuse), so nothing donates."""
    return jax.jit(
        _bucket_window_pp_impl, static_argnames=_BUCKET_PP_STATICS,
    )


def _bucket_stager(fixed_store, bplan, schedule, *, table_dtype, faults,
                   iteration, side, shard, verify_windows, stats, ici_group,
                   hot=None, x_prev=None, mode="serial",
                   depth=1) -> WindowStager:
    """The staging engine for one bucketed half: the SAME
    ``_stage_window`` / ``_stage_window_delta`` pipeline the tiled driver
    runs (gather → fault hook → checksum → quantize → ONE ``device_put``),
    plus — for iALS++ — each window's warm-start rows ``x_prev[entity]``
    appended to the staged tuple (gathered from an immutable snapshot
    padded with one zeros trash row, so pooled staging threads read a
    frozen array; the bytes are metered into ``staged_bytes`` — they
    cross PCIe like every other staged operand)."""
    stage_name = _stage_dtype(fixed_store.dtype, table_dtype)
    int8 = stage_name == "int8"
    stage_np = None if int8 else _np_dtype(stage_name)
    x_pad = None
    if x_prev is not None:
        xp = np.asarray(x_prev)
        x_pad = np.zeros((bplan.local_entities + 1, xp.shape[1]),
                         dtype=xp.dtype)
        x_pad[: bplan.local_entities] = xp[: bplan.local_entities]

    def stage_task(d, w):
        if hot is not None:
            staged = _stage_window_delta(
                fixed_store, bplan, hot.hmap, w, stage_np=stage_np,
                int8=int8, faults=faults, iteration=iteration, side=side,
                shard=d, verify_windows=verify_windows, stats=stats,
                ici_group=ici_group,
            )
        else:
            staged = _stage_window(
                fixed_store, bplan, w, stage_np=stage_np, int8=int8,
                faults=faults, iteration=iteration, side=side, shard=d,
                verify_windows=verify_windows, stats=stats,
                ici_group=ici_group,
            )
        if x_pad is None:
            return staged
        xw = x_pad[bplan.chunk_entity_of(w)]
        if stats is not None:
            stats_add(stats, "staged_bytes", xw.nbytes)
        return staged + (jax.device_put(xw),)

    return WindowStager([(shard, w) for w in schedule], stage_task,
                        mode=mode, depth=depth, stats=stats,
                        span_attrs=lambda d, w: _stage_span_attrs(
                            hot.hmap if hot is not None else None,
                            bplan, w))


def bucket_windowed_half_step(
    fixed_store: HostFactorStore, bplan: BucketWindowPlan, *, gram,
    lam: float, alpha: float, algorithm: str = "als", block_size: int = 32,
    sweeps: int = 1, x_prev: np.ndarray | None = None,
    out_dtype: str = "float32", solver: str = "auto", overlap=None,
    fused_epilogue=None, in_kernel_gather=None, reg_solve_algo=None,
    table_dtype: str | None = None, faults=None, iteration: int = 0,
    side: str = "", stats: dict | None = None,
    verify_windows: bool = False, shard: int = 0, ici_group: int = 1,
    stager: WindowStager | None = None, hot: "_HotHalf | None" = None,
    host: int = 0,
) -> np.ndarray:
    """Solve one side's bucketed entities against a host-resident fixed
    table, width-class window by window (ISSUE 19's piece 2).

    ``gram`` is the device [k,k] f32 global YᵀY of the fixed table
    (``windowed_store_gram``), shared read-only by every window.
    ``algorithm='als'`` runs the full per-entity implicit solve;
    ``'ials++'`` runs ``sweeps`` subspace passes warm-started from
    ``x_prev`` (the solve side's previous factors, [padded_entities, k]
    host array — REQUIRED for ials++; untouched entities keep their
    previous rows in the output, exactly the resident warm walk).
    Returns the solved [padded_entities, rank] host array in
    ``out_dtype``.  Same staging/fault/checksum/hot-delta semantics as
    ``windowed_half_step`` — the hot engine's assembly, scatter-back, and
    delta reuse run UNMODIFIED against the width-class windows."""
    k = fixed_store.rank
    pp = algorithm == "ials++"
    out_np = _np_dtype(out_dtype)
    if pp:
        if x_prev is None:
            raise ValueError(
                "algorithm='ials++' needs x_prev (the solve side's "
                "previous factors) for the warm-started subspace sweeps"
            )
        out = np.array(np.asarray(x_prev)[: bplan.local_entities],
                       dtype=out_np, copy=True)
    else:
        out = np.zeros((bplan.local_entities, k), dtype=out_np)
    n_w = bplan.num_windows
    own = stager is None
    if own:
        stager = _bucket_stager(
            fixed_store, bplan, bplan.schedule(), table_dtype=table_dtype,
            faults=faults, iteration=iteration, side=side, shard=shard,
            verify_windows=verify_windows, stats=stats,
            ici_group=ici_group, hot=hot,
            x_prev=x_prev if pp else None,
        )
    half_kw = dict(
        lam=float(lam), alpha=float(alpha), solver=solver, overlap=overlap,
        fused_epilogue=fused_epilogue, in_kernel_gather=in_kernel_gather,
        reg_solve_algo=reg_solve_algo, out_dtype=out_dtype,
    )
    if pp:
        half_kw.update(block_size=int(block_size), sweeps=int(sweeps))
    stage_name = _stage_dtype(fixed_store.dtype, table_dtype)
    prev = (None if hot is None
            else _hot_zero_prev(bplan.window_rows, k, stage_name))
    try:
        staged = stager.take() if n_w else None
        for w in range(n_w):
            shape = bplan.window_shape(w)
            with span("train/iter/half_step/window_compute",
                      side=side, shard=shard, window=w, host=host):
                if hot is None:
                    if pp:
                        xs = _bucket_window_pp_jit()(*staged, gram,
                                                     shape=shape,
                                                     **half_kw)
                    else:
                        xs = _bucket_window_jit()(*staged, gram,
                                                  shape=shape, **half_kw)
                else:
                    delta, dscale, nb, rt, mk, *xw_t = staged
                    tbl, scale = _assemble_jit()(
                        delta, dscale, *prev,
                        hot.fixed.data, hot.fixed.scale, *hot.idx(w),
                        window_rows=bplan.window_rows,
                        int8=hot.fixed.int8,
                    )
                    if pp:
                        xs = _bucket_window_pp_hot_jit()(
                            tbl, scale, nb, rt, mk, xw_t[0], gram,
                            shape=shape, **half_kw)
                    else:
                        xs = _bucket_window_hot_jit()(
                            tbl, scale, nb, rt, mk, gram,
                            shape=shape, **half_kw)
                    prev = (tbl, scale)
                    sb = hot.sb_idx(w)
                    if sb is not None:
                        hot.solve.data, hot.solve.scale = _hot_update_jit()(
                            hot.solve.data, hot.solve.scale, xs, *sb,
                            int8=hot.solve.int8,
                        )
                nxt = stager.take() if w + 1 < n_w else None
                xs_np = np.asarray(xs)
            ent = bplan.chunk_entity_of(w)
            real = ent < bplan.local_entities
            out[ent[real]] = xs_np[real]
            staged = nxt
    finally:
        if own:
            stager.close()
    return out


def train_ials_host_window(
    dataset,
    config,
    *,
    metrics=None,
    window_faults=None,
    chunks_per_window: int | None = None,
    device_budget_bytes: float | None = None,
    plan_provenance=None,
    verify_windows: bool | None = None,
    staging: str | None = None,
    pool_depth: int | None = None,
    hot_rows: int | None = None,
):
    """Implicit ALS / iALS++ with host-resident factor tables and
    windowed width-class half-steps (ISSUE 19's tentpole driver).

    Same math, init, and iteration order as ``models.ials.train_ials`` on
    the same bucketed blocks — bit-exact against the resident trainer's
    stepped loop (one program per half-step, as here), and against its
    fused ``fori_loop`` wherever XLA compiles the sweep the same way
    inside the one big program: pinned per knob by
    ``tests/test_offload_ials.py`` (table dtype, hot cache, window size)
    at ``block_size < rank``.  With full-rank blocks (``block_size ==
    rank``) the fused loop sums in another order and the two agree to
    float32 round-off only (4.8e-7 absolute after two toy iterations:
    ``test_windowed_vs_resident_at_full_rank_blocks``).  Per
    half-iteration:

        gram  = windowed_store_gram(fixed store)   # streamed YᵀY
        solve = width-class windows through the resident bucket pieces
        commit = store.write_range (the atomic host hand-off)

    The [k,k] Gram accumulator + its double-buffered staged block are
    reserved via ``budget.gram_reservation_bytes`` BEFORE window sizing,
    and the sizing refuses loudly — naming the Gram reserve — when one
    window cannot fit next to it.  Divergence recovery runs the PR 3
    ladder against in-RAM last-good snapshots; the Gram accumulator needs
    no snapshot (recomputed from the restored masters each half), and the
    hot partitions rebuild from them — replay is bit-identical.

    Single-process only (the fleet residual exchange is tiled-layout;
    bucketed fleet mode is a documented follow-up)."""
    from cfk_tpu.config import enable_compile_cache
    from cfk_tpu.data.blocks import BucketedBlocks
    from cfk_tpu.ops.solve import init_factors_stats
    from cfk_tpu.resilience.policy import (
        Overrides,
        TrainingDivergedError,
        policy_from_config,
    )
    from cfk_tpu.utils.metrics import Metrics

    import jax.numpy as jnp

    enable_compile_cache(getattr(config, "compile_cache_dir", None))
    if getattr(config, "alpha", None) is None:
        raise ValueError(
            "host-window iALS needs an implicit-feedback config "
            "(IALSConfig — the confidence weight alpha drives the solve)"
        )
    if config.algorithm not in ("als", "ials++"):
        raise ValueError(
            f"host-window iALS supports algorithm in ('als', 'ials++'); "
            f"got {config.algorithm!r}"
        )
    if config.layout != "bucketed":
        raise ValueError(
            f"host-window iALS streams the bucketed width-class layout; "
            f"layout={config.layout!r}"
        )
    if jax.process_count() > 1:
        raise NotImplementedError(
            "the multi-process fleet mode (ISSUE 17) is tiled-layout "
            "only; bucketed iALS fleet exchange is a documented follow-up"
        )
    mb, ub = dataset.movie_blocks, dataset.user_blocks
    if not isinstance(mb, BucketedBlocks) or not isinstance(
            ub, BucketedBlocks):
        raise ValueError(
            "host-window iALS needs BucketedBlocks on both sides — "
            "build the dataset with layout='bucketed'"
        )
    s = config.num_shards
    if mb.num_shards != s or ub.num_shards != s:
        raise ValueError(
            f"blocks built at num_shards={mb.num_shards}/{ub.num_shards} "
            f"but config.num_shards={s} — rebuild the dataset"
        )
    pp = config.algorithm == "ials++"
    metrics = metrics if metrics is not None else Metrics()
    with metrics.phase("window_plan"):
        stage_name = _stage_dtype(config.dtype, config.table_dtype)
        cell_bytes, row_overhead = _stage_cell_bytes(stage_name)
        if device_budget_bytes is None:
            from cfk_tpu.plan import DeviceSpec

            device_budget_bytes = DeviceSpec.detect().hbm_bytes
        # The global-Gram reduction holds a [k,k] f32 accumulator plus a
        # double-buffered staged Gram block next to the staged windows —
        # one more reservation term, carved out BEFORE the window split
        # (the ring-accumulator template).
        gram_reserved = _budget.gram_reservation_bytes(
            config.rank, stage_name
        )
        per_window_budget = _budget.window_budget_bytes(
            device_budget_bytes, reserved_bytes=gram_reserved
        )
        cpw = chunks_per_window or 4
        while True:
            m_plan = build_bucket_window_plan(mb, ub.padded_entities,
                                              chunks_per_window=cpw)
            u_plan = build_bucket_window_plan(ub, mb.padded_entities,
                                              chunks_per_window=cpw)
            worst = max(
                p.staged_bytes_per_window(config.rank, cell_bytes,
                                          row_overhead_bytes=row_overhead)
                for p in (m_plan, u_plan)
            )
            if worst <= per_window_budget or cpw == 1:
                break
            cpw = max(1, cpw // 2)
        if worst > per_window_budget:
            raise ValueError(
                f"one staged window needs {worst / 1e6:.1f} MB but the "
                f"per-window budget is {per_window_budget / 1e6:.1f} MB "
                f"((device_budget · RESIDENT_FRACTION − "
                f"{gram_reserved / 1e6:.2f} MB global-Gram accumulator "
                "reserve) / WINDOW_BUFFERS) — lower hbm_chunk_elems so "
                "single chunks fit the budget, or raise the device budget"
            )
        staging = resolve_staging(
            staging if staging is not None
            else getattr(config, "staging", "auto"),
        )
        if pool_depth is None:
            pool_depth = (getattr(config, "staging_pool_depth", None)
                          or DEFAULT_POOL_DEPTH)
        pool_depth = max(1, min(
            int(pool_depth),
            _budget.max_pool_depth(device_budget_bytes, worst,
                                   reserved_bytes=gram_reserved),
        ))
        # Skew-aware hot-row cache resolution (ISSUE 15), unchanged
        # machinery against the width-class plans: one plan per side
        # covers every shard (absolute entity ids), so the helpers run
        # at shard=0 / local=padded_entities.
        from cfk_tpu.offload import hot as _hotmod

        requested = (hot_rows if hot_rows is not None
                     else getattr(config, "hot_rows", None))
        schedules = {("m", 0): m_plan.schedule(),
                     ("u", 0): u_plan.schedule()}
        hot_note = None
        f_u = f_m = 0
        if requested != 0:
            row_b = _budget.stage_row_bytes(config.rank, stage_name)
            arena = max(p.window_rows * row_b for p in (m_plan, u_plan))
            live = (pool_depth + 1 if staging == "pool"
                    else _budget.WINDOW_BUFFERS)
            live = max(live, _budget.WINDOW_BUFFERS)
            hot_reserved = gram_reserved + live * worst + arena
            admit = _budget.max_hot_rows(
                device_budget_bytes, config.rank, stage_name,
                reserved_bytes=hot_reserved,
            )
            counts_u = _hotmod.reference_counts(
                [m_plan], _fixed_rows_of(m_plan)
            )
            counts_m = _hotmod.reference_counts(
                [u_plan], _fixed_rows_of(u_plan)
            )
            solved_u = _hotmod.solved_rows_of(u_plan, 0,
                                              ub.padded_entities)
            solved_m = _hotmod.solved_rows_of(m_plan, 0,
                                              mb.padded_entities)
            mask_u = np.zeros(counts_u.shape, bool)
            mask_u[solved_u] = True
            counts_u[~mask_u] = 0
            mask_m = np.zeros(counts_m.shape, bool)
            mask_m[solved_m] = True
            counts_m[~mask_m] = 0
            slots_u = int(counts_u.sum())
            slots_m = int(counts_m.sum())
            if requested is None:
                f_u = _hotmod.knee_hot_rows(counts_u)
                f_m = _hotmod.knee_hot_rows(counts_m)
                total = f_u + f_m
                if total > admit:
                    f_u = f_u * admit // max(total, 1)
                    f_m = min(admit - f_u, f_m)
                    hot_note = (f"knee clamped by budget headroom "
                                f"({admit} rows admitted)")
                else:
                    hot_note = "coverage-curve knee within headroom"
            else:
                req = int(requested)
                if not _budget.hot_reservation_fits(
                    req, config.rank, stage_name, device_budget_bytes,
                    reserved_bytes=hot_reserved,
                ):
                    need = _budget.hot_reservation_bytes(
                        req, config.rank, stage_name
                    )
                    raise ValueError(
                        f"hot_rows={req} pinned but its reservation "
                        f"({need / 1e6:.2f} MB at the {stage_name!r} "
                        f"staging dtype) exceeds the headroom left by "
                        f"the Gram/window/delta-arena terms "
                        f"({admit * row_b / 1e6:.2f} MB ≈ {admit} rows) "
                        "— lower hot_rows, raise the device budget, or "
                        "use hot_rows=0 (the full-staging engine)"
                    )
                denom = max(slots_u + slots_m, 1)
                f_u = req * slots_u // denom
                f_m = req - f_u
                hot_note = f"pinned total {req}"
            f_u = min(f_u, int((counts_u > 0).sum()))
            f_m = min(f_m, int((counts_m > 0).sum()))
            if f_u + f_m == 0:
                hot_note = (hot_note or "") + "; resolved 0 (off)"
        hot_ctx = None
        if f_u + f_m > 0:
            rows_hot_u = _hotmod.select_hot_rows(counts_u, f_u)
            rows_hot_m = _hotmod.select_hot_rows(counts_m, f_m)
            hmaps = {
                ("m", 0): _hotmod.build_hot_map(
                    m_plan, schedules[("m", 0)], rows_hot_u),
                ("u", 0): _hotmod.build_hot_map(
                    u_plan, schedules[("u", 0)], rows_hot_m),
            }
            hot_ctx = {"rows_u": rows_hot_u, "rows_m": rows_hot_m,
                       "maps": hmaps, "note": hot_note}
    metrics.gauge("offload_windows_m", m_plan.num_windows)
    metrics.gauge("offload_windows_u", u_plan.num_windows)
    metrics.gauge("offload_window_rows_m", m_plan.window_rows)
    metrics.gauge("offload_window_rows_u", u_plan.window_rows)
    metrics.gauge("offload_chunks_per_window", cpw)
    metrics.gauge("offload_shards", s)
    metrics.gauge(
        "offload_plan_held_mb",
        round((m_plan.plan_held_bytes()
               + u_plan.plan_held_bytes()) / 1e6, 3),
    )
    metrics.gauge("offload_gram_reserved_mb",
                  round(gram_reserved / 1e6, 3))
    metrics.note("offload_optimizer",
                 "ials++" if pp else "ials")
    metrics.note("offload_staging", staging)
    if staging == "pool":
        metrics.gauge("offload_pool_depth", pool_depth)
        metrics.gauge("offload_pool_workers",
                      pool_workers_for(pool_depth))
    metrics.note("offload_hot", "on" if hot_ctx is not None else "off")
    if hot_note:
        metrics.note("offload_hot_decision", hot_note)
    if hot_ctx is not None:
        maps_all = hot_ctx["maps"].values()
        slots_total = sum(m.slots_total for m in maps_all)
        metrics.gauge("offload_hot_rows", f_u + f_m)
        metrics.gauge("offload_hot_rows_u", f_u)
        metrics.gauge("offload_hot_rows_m", f_m)
        if slots_total:
            metrics.gauge("offload_hot_coverage", round(
                sum(m.slots_hot for m in hot_ctx["maps"].values())
                / slots_total, 4))
            metrics.gauge("offload_delta_coverage", round(
                sum(m.slots_kept for m in hot_ctx["maps"].values())
                / slots_total, 4))

    # Init: identical to the resident trainer — init_factors_stats over
    # the bucketed per-entity stats (drawn at the real entity count, the
    # shard-count-invariant init), zero movie seed.
    key = jax.random.PRNGKey(config.seed)
    u0 = jax.jit(
        init_factors_stats, static_argnames=("rank", "num_entities")
    )(
        key, jnp.asarray(ub.rating_sum), jnp.asarray(ub.count),
        rank=config.rank, num_entities=ub.num_entities,
    ).astype(jnp.dtype(config.dtype))
    u_store = HostFactorStore.from_array(np.asarray(u0),
                                         dtype=config.dtype,
                                         num_shards=s)
    m_store = HostFactorStore(mb.padded_entities, config.rank,
                              dtype=config.dtype, num_shards=s)

    # Hot partitions + per-side contexts: device copies gather from the
    # just-initialized masters; only the cold delta crosses PCIe per
    # window from here on.
    hot_u_part = hot_m_part = None
    hot_halves: dict = {}
    if hot_ctx is not None:
        hot_u_part = HotPartition(hot_ctx["rows_u"], stage_name)
        hot_m_part = HotPartition(hot_ctx["rows_m"], stage_name)
        hot_u_part.rebuild(u_store)
        hot_m_part.rebuild(m_store)
        sb_m = _hotmod.scatter_back_maps(m_plan, 0, mb.padded_entities,
                                         hot_m_part.rows)
        sb_u = _hotmod.scatter_back_maps(u_plan, 0, ub.padded_entities,
                                         hot_u_part.rows)
        hot_halves[("m", 0)] = _HotHalf(
            hot_u_part, hot_m_part, hot_ctx["maps"][("m", 0)], sb_m)
        hot_halves[("u", 0)] = _HotHalf(
            hot_m_part, hot_u_part, hot_ctx["maps"][("u", 0)], sb_u)
        metrics.gauge("offload_hot_resident_mb", round(
            (hot_u_part.nbytes + hot_m_part.nbytes) / 1e6, 3))

    policy = policy_from_config(config)
    base_ov = Overrides(lam=config.lam,
                        fused_epilogue=config.fused_epilogue)
    ov = base_ov
    norm_limit = (config.health_norm_limit
                  if config.health_check_every is not None else None)
    probe_every = config.health_check_every or 1
    stats = StagingStats()
    if verify_windows is None:
        verify_windows = window_faults is not None

    def half(side, fixed_store, solve_store, plan, it, gram):
        """One bucketed half-iteration: stage the fixed side's windows
        (pool or serial), sweep/solve them against the shared Gram
        accumulator, return the solved host buffer (committed by the
        caller — the same solve-all-then-commit structure as the tiled
        driver)."""
        algo = ov.reg_solve_algo or config.reg_solve_algo
        hot_half = hot_halves.get((side, 0))
        if hot_half is not None and window_faults is not None:
            part = hot_half.fixed
            pois = (window_faults.apply_hot(it, side, part.num_rows)
                    if hasattr(window_faults, "apply_hot") else None)
            if pois is not None:
                record_event("fault", "hot_cache_corruption",
                             iteration=it, side=side, rows=len(pois))
                part.poison(pois)
        x_prev = solve_store.as_array() if pp else None
        stager = _bucket_stager(
            fixed_store, plan, plan.schedule(),
            table_dtype=config.table_dtype, faults=window_faults,
            iteration=it, side=side, shard=0,
            verify_windows=verify_windows, stats=stats, ici_group=1,
            hot=hot_half, x_prev=x_prev, mode=staging, depth=pool_depth,
        )
        try:
            with span("train/iter/half_step", side=side, shard=0,
                      iteration=it, tier="host_window"):
                rows = bucket_windowed_half_step(
                    fixed_store, plan, gram=gram, lam=ov.lam,
                    alpha=config.alpha, algorithm=config.algorithm,
                    block_size=config.block_size, sweeps=config.sweeps,
                    x_prev=x_prev, out_dtype=config.dtype,
                    solver=config.solver, overlap=bool(config.overlap),
                    fused_epilogue=ov.fused_epilogue,
                    in_kernel_gather=config.in_kernel_gather,
                    reg_solve_algo=algo, table_dtype=config.table_dtype,
                    faults=window_faults, iteration=it, side=side,
                    stats=stats, verify_windows=verify_windows,
                    shard=0, stager=stager, hot=hot_half,
                )
        finally:
            stager.close()
        return rows

    armed = (config.health_check_every is not None
             or verify_windows or window_faults is not None)
    snap = (u_store.copy(), m_store.copy()) if armed else (None, None)
    snap_iter = 0
    trips = 0
    it = 0
    degraded = False
    traces0 = trace_count()
    train_t0 = time.time()
    first_step_s = None

    def _rebuild_hot() -> None:
        if hot_u_part is not None:
            hot_u_part.rebuild(u_store)
            hot_m_part.rebuild(m_store)

    def trip(reason: str) -> bool:
        """Rollback + ladder climb (the tiled driver's ladder verbatim):
        restore the last-good stores, rebuild the hot partitions from
        them, and recompute the Gram accumulator on the next half — the
        accumulator has no snapshot because it needs none."""
        nonlocal u_store, m_store, it, trips, ov
        trips += 1
        metrics.incr("health_trips")
        metrics.note(f"health_trip_{trips}", f"iteration {it}: {reason}")
        record_event("fault", "health_trip", iteration=it, trip=trips,
                     reason=reason)
        dump_flight(f"health_trip_{trips}")
        if trips > policy.max_recoveries:
            detail = (
                f"recovery exhausted after {policy.max_recoveries} "
                f"trips; last: {reason}"
            )
            if policy.on_unrecoverable == "raise":
                record_event("fault", "unrecoverable", detail=detail)
                dump_flight("unrecoverable")
                raise TrainingDivergedError(detail)
            metrics.note("degraded", detail)
            record_event("fault", "degraded", detail=detail)
            dump_flight("degraded")
            u_store, m_store = snap
            it = snap_iter
            _rebuild_hot()
            return False
        u_store, m_store = snap[0].copy(), snap[1].copy()
        it = snap_iter
        _rebuild_hot()
        metrics.incr("rollbacks")
        new_ov = policy.escalate(ov, trips)
        detail = (
            f"rung {trips}: rollback to iter {snap_iter}, "
            f"lam={new_ov.lam}, fused={new_ov.fused_epilogue}, "
            f"algo={new_ov.reg_solve_algo or config.reg_solve_algo}"
        )
        if new_ov != ov:
            metrics.gauge("escalation_level", trips)
            metrics.note(f"escalation_{trips}", detail)
            record_event("fault", "escalation", rung=trips,
                         detail=detail)
        ov = new_ov
        if plan_provenance is not None:
            t = plan_provenance.record_transition(
                "recovery_escalation", detail
            )
            metrics.note(f"plan_transition_{trips}", str(t))
        return True

    with metrics.phase("train"):
        while it < config.num_iterations:
            try:
                with span("train/iter", i=it, tier="host_window",
                          optimizer="ials++" if pp else "ials"):
                    # Per-half Gram over the CURRENT fixed masters —
                    # exactly the resident iteration body's order (the
                    # u-half's Gram reads the freshly committed m).
                    gram_u = windowed_store_gram(
                        u_store, table_dtype=config.table_dtype,
                        stats=stats)
                    m_new = half("m", u_store, m_store, m_plan, it,
                                 gram_u)
                    m_store.write_range(0, m_new)
                    gram_m = windowed_store_gram(
                        m_store, table_dtype=config.table_dtype,
                        stats=stats)
                    u_new = half("u", m_store, u_store, u_plan, it,
                                 gram_m)
                    u_store.write_range(0, u_new)
                record_event("train", "iter", i=it, tier="host_window")
            except WindowIntegrityError as e:
                if not trip(f"window integrity: {e}"):
                    degraded = True
                    break
                continue
            it += 1
            metrics.incr("iterations")
            if first_step_s is None:
                first_step_s = time.time() - train_t0
            if not armed:
                continue
            if it % probe_every != 0 and it < config.num_iterations:
                continue
            reason = _probe(u_new, m_new, norm_limit)
            if reason is None:
                snap = (u_store.copy(), m_store.copy())
                snap_iter = it
                continue
            if not trip(reason):
                degraded = True
                break
    metrics.gauge("offload_windows_staged",
                  stats.get("windows_staged", 0))
    metrics.gauge("offload_staged_mb",
                  round(stats.get("staged_bytes", 0) / 1e6, 3))
    metrics.gauge("offload_staged_cold_mb",
                  round(stats.get("staged_cold_bytes", 0) / 1e6, 3))
    metrics.gauge("offload_gram_staged_mb",
                  round(stats.get("gram_staged_bytes", 0) / 1e6, 3))
    for key_ in ("rows_staged", "rows_delta_skipped", "rows_hot_device",
                 "gram_blocks_staged"):
        if key_ in stats:
            metrics.gauge(f"offload_{key_}", stats[key_])
    busy = float(stats.get("stage_busy_s", 0.0))
    stall = float(stats.get("stage_stall_s", 0.0))
    metrics.gauge("offload_stage_busy_s", round(busy, 4))
    metrics.gauge("offload_stage_stall_s", round(stall, 4))
    if busy > 0:
        metrics.gauge("offload_stage_hidden_frac",
                      round(max(0.0, 1.0 - stall / busy), 4))
        metrics.gauge("offload_staged_mb_per_s",
                      round(stats.get("staged_bytes", 0) / 1e6 / busy, 2))
    if staging == "pool":
        metrics.gauge("offload_pool_peak_inflight",
                      stats.get("pool_peak_inflight", 0))
        metrics.gauge("offload_pool_worker_stagings",
                      stats.get("pool_worker_stagings", 0))
    metrics.gauge("offload_trace_count", trace_count() - traces0)
    if first_step_s is not None:
        metrics.gauge("time_to_first_step_s", round(first_step_s, 4))
    if degraded:
        metrics.gauge("iterations_completed", snap_iter)

    from cfk_tpu.models.als import ALSModel

    return ALSModel(
        user_factors=u_store.as_array(),
        movie_factors=m_store.as_array(),
        num_users=dataset.user_map.num_entities,
        num_movies=dataset.movie_map.num_entities,
    )
