"""Two-stage clustered retrieval: centroid probe + exact shortlist rescore.

The exact serve path's cost is one full item-table scan per batch — the
O(users × catalog) floor ISSUE 16 breaks.  This module is the probe side:

- COARSE stage (``serve/candidate``): score the [B, k] user batch against
  the ``[C, k]`` cluster centroids (optionally over the int8/bf16
  quantized view — the canonical ``ops.quant`` dequant placement, same as
  the kernel's in-register rule) and take each user's top ``probe``
  clusters.
- SHORTLIST: the batch-union of selected clusters, gathered from the
  CLUSTER-MAJOR table (``serving.cluster``) as contiguous row ranges and
  padded to a pow2 multiple of ``tile_m`` — the same shape-bucketing
  trick the engine uses for batch sizes, so live traffic converges onto a
  handful of rescore programs.
- RESCORE stage (``serve/rescore``): the EXISTING Pallas top-K kernel
  over the gathered shortlist, with the same seen-item exclusion masks
  remapped to shortlist-local coordinates.  Scores of surviving rows are
  bit-identical to the exact path (same ``_score_tile_fold`` math, same
  k-order contraction); ties resolve to the earlier SHORTLIST position,
  i.e. cluster-major order of the gathered set — pinned by
  ``tests/test_twostage.py`` as "identical to the exact kernel run over
  the same gathered subtable".

The shortlist width is dynamic per batch, but the kernel's ``num_movies``
mask is jit-static — so the padded width is the static shape and the
ACTUAL row count rides the kernel's scalar-prefetched ``row_offset``:
with ``row_offset = rows_padded − rows`` and ``num_movies = rows_padded``
the kernel masks exactly the padding tail (global id ≥ num_movies), and
returned ids map back as ``shortlist_pos = id − row_offset``.  No
re-trace per distinct union size, only per pow2 bucket.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

from cfk_tpu.serving.cluster import ClusterIndex
from cfk_tpu.serving.topk_kernel import (
    _pow2_ceil,
    group_seen_cells,
    serve_compute_dtype,
    topk_scores_pallas,
)

# Trace counter for the two-stage programs (coarse + rescore), summed into
# ``serving.engine.trace_count`` so the prewarm zero-new-traces contract
# (PR 12) covers two_stage mode too.
_TRACES = [0]


def trace_count() -> int:
    """Coarse + rescore program traces this process."""
    return _TRACES[0]


def default_two_stage_params(num_movies: int, *,
                             min_recall: float | None = None
                             ) -> tuple[int, int]:
    """(clusters, probe_clusters) for a catalog size, sized like the plan
    resolver would: ~√M clusters (pow2), and the smallest probe count the
    recall model (``plan.cost.estimated_recall``) accepts at the plan
    recall constraint — the IVF nprobe ≈ √nlist rule of thumb."""
    from cfk_tpu.plan.cost import SERVE_MIN_RECALL, estimated_recall

    floor = SERVE_MIN_RECALL if min_recall is None else float(min_recall)
    m = max(int(num_movies), 1)
    clusters = min(_pow2_ceil(max(int(round(math.sqrt(m))), 1)), m)
    probe = 1
    while probe < clusters and estimated_recall(clusters, probe) < floor:
        probe += 1
    return clusters, probe


def _coarse_call(u, centroids, scale, *, probe):
    """Centroid score + per-user top-``probe`` clusters — the candidate
    stage, scored in the kernel's arithmetic (``serve_compute_dtype``'s
    compute dtype and precision; int8 centroids are dequantized here,
    code × scale, before the one matmul: they are few)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    _TRACES[0] += 1
    ct, prec = serve_compute_dtype(centroids.dtype)
    if centroids.dtype == jnp.int8:
        cent_f = centroids.astype(jnp.float32) * scale[:, None]
    else:
        cent_f = centroids.astype(ct)
    scores = jax.lax.dot_general(
        u.astype(ct), cent_f,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=prec,
    )  # [B, C]
    return lax.top_k(scores, probe)


@functools.lru_cache(maxsize=1)
def coarse_jit_fn():
    """Jitted coarse entry — one program per (B, C, probe, dtype) class."""
    import jax

    return jax.jit(_coarse_call, static_argnames=("probe",))


def _rescore_call(u, indices, table, scale, seen_tiles, offset, *,
                  k_top, tile_m):
    """Gather the shortlist rows from the resident cluster-major table and
    run the EXISTING streaming top-K kernel over them.  ``indices`` is the
    jit-static-width [R_pad] position vector; ``offset = R_pad − R`` is the
    traced scalar that masks the padding tail (module docstring)."""
    import jax.numpy as jnp

    _TRACES[0] += 1
    sub = jnp.take(table, indices, axis=0)
    sub_scale = None if scale is None else jnp.take(scale, indices)
    return topk_scores_pallas(
        u, sub, sub_scale, seen_tiles, k_top=k_top,
        num_movies=indices.shape[0], tile_m=tile_m, row_offset=offset,
    )


@functools.lru_cache(maxsize=1)
def rescore_jit_fn():
    """Jitted rescore entry — with pow2 shortlist-width and batch
    bucketing, live traffic converges onto a handful of programs."""
    import jax

    return jax.jit(_rescore_call, static_argnames=("k_top", "tile_m"))


@dataclasses.dataclass
class Shortlist:
    """One batch's gathered candidate set (host-side bookkeeping).

    ``indices [R_pad]`` are cluster-major TABLE positions (padding slots
    repeat position 0 — masked by the kernel, never selected);
    ``global_ids [R]`` maps shortlist position → global movie row;
    ``cluster_ids``/``starts``/``ends``/``local_starts`` describe the
    contiguous ranges for the seen-mask remap."""

    cluster_ids: np.ndarray  # [S] int64 sorted selected clusters
    starts: np.ndarray  # [S] int64 cluster-major range starts
    ends: np.ndarray  # [S] int64 range ends
    local_starts: np.ndarray  # [S] int64 shortlist-local range starts
    indices: np.ndarray  # [R_pad] int32 table positions
    global_ids: np.ndarray  # [R] int64 shortlist pos -> global movie row
    rows: int  # R — real candidate rows
    rows_padded: int  # R_pad — pow2 multiple of tile_m

    @property
    def offset(self) -> int:
        """The kernel's ``row_offset`` (= padding-tail mask, module doc)."""
        return self.rows_padded - self.rows


def build_shortlist(index: ClusterIndex, cluster_ids, *, tile_m: int,
                    min_rows: int = 1) -> Shortlist:
    """The batch-union shortlist for the selected clusters.

    Rows come out in cluster-major order (ascending cluster, ascending
    global row within — the tie-order contract).  When the union holds
    fewer than ``min_rows`` rows (a tiny catalog or degenerate probe set
    cannot cover K), the shortlist WIDENS to every cluster — full
    coverage through the same code path, never a short answer."""
    cids = np.unique(np.asarray(cluster_ids, np.int64))
    if cids.size and (cids[0] < 0 or cids[-1] >= index.num_clusters):
        raise ValueError(
            f"cluster ids out of range [0, {index.num_clusters})"
        )
    starts, ends = index.ranges(cids)
    rows = int((ends - starts).sum())
    if rows < min_rows:
        cids = np.arange(index.num_clusters, dtype=np.int64)
        starts, ends = index.ranges(cids)
        rows = int((ends - starts).sum())
    lens = ends - starts
    local_starts = np.zeros(cids.size, np.int64)
    if cids.size > 1:
        np.cumsum(lens[:-1], out=local_starts[1:])
    positions = (
        np.concatenate([np.arange(s, e, dtype=np.int64)
                        for s, e in zip(starts, ends)])
        if rows else np.zeros(0, np.int64)
    )
    rows_padded = _pow2_ceil(max(rows, 1), tile_m)
    indices = np.zeros(rows_padded, np.int32)
    indices[:rows] = positions
    return Shortlist(
        cluster_ids=cids, starts=starts, ends=ends,
        local_starts=local_starts, indices=indices,
        global_ids=index.perm[positions], rows=rows,
        rows_padded=rows_padded,
    )


def shortlist_seen(index: ClusterIndex, shortlist: Shortlist,
                   seen_movies, seen_indptr):
    """Remap a batch seen-CSR (GLOBAL movie rows, sorted per user) to
    SHORTLIST-LOCAL positions, dropping entries outside the shortlist (an
    unselected seen item is not a candidate, so it needs no mask).  Local
    positions are re-sorted per user — ``group_seen_cells``'s contract."""
    movies = np.asarray(seen_movies, np.int64)
    indptr = np.asarray(seen_indptr, np.int64)
    if movies.size:
        pos = index.inv_perm[movies]
        j = np.searchsorted(shortlist.starts, pos, side="right") - 1
        j = np.clip(j, 0, max(shortlist.starts.size - 1, 0))
        inside = ((pos >= shortlist.starts[j]) & (pos < shortlist.ends[j])
                  if shortlist.starts.size else np.zeros(pos.shape, bool))
        local = np.where(
            inside, shortlist.local_starts[j] + (pos - shortlist.starts[j]),
            -1,
        )
    else:
        local = np.zeros(0, np.int64)
    out_indptr = np.zeros(indptr.shape[0], np.int64)
    segs = []
    for i in range(indptr.shape[0] - 1):
        seg = local[indptr[i]: indptr[i + 1]]
        seg = np.sort(seg[seg >= 0])
        segs.append(seg)
        out_indptr[i + 1] = out_indptr[i] + seg.size
    out_movies = (np.concatenate(segs).astype(np.int32)
                  if out_indptr[-1] else np.zeros(0, np.int32))
    return out_movies, out_indptr


def shortlist_seen_cells(index: ClusterIndex, shortlist: Shortlist,
                         seen_movies, seen_indptr, batch: int, *,
                         tile_m: int):
    """The [NT_local, B, W] exclusion rectangle's cells and shape in
    shortlist coordinates — ``group_seen_cells`` over the remapped CSR (W
    pow2-bucketed as ever)."""
    movies_l, indptr_l = shortlist_seen(
        index, shortlist, seen_movies, seen_indptr
    )
    return group_seen_cells(
        movies_l, indptr_l, np.arange(batch),
        num_movies=max(shortlist.rows, 1), tile_m=tile_m,
        num_tiles=shortlist.rows_padded // tile_m,
    )


def map_shortlist_ids(ids: np.ndarray, shortlist: Shortlist) -> np.ndarray:
    """Kernel ids (``row_offset``-shifted shortlist positions, −1 empty)
    → GLOBAL movie rows."""
    ids = np.asarray(ids, np.int64)
    pos = np.clip(ids - shortlist.offset, 0,
                  max(shortlist.rows - 1, 0))
    mapped = (shortlist.global_ids[pos] if shortlist.rows
              else np.zeros_like(ids))
    return np.where(ids >= 0, mapped, -1).astype(np.int32)


def recall_at_k(ids: np.ndarray, oracle_ids: np.ndarray) -> float:
    """Mean per-user fraction of the exact oracle's top-K recovered —
    the first-class quality metric of the two-stage contract (every bench
    row carries it; the plan constraint is ≥ ``plan.cost.SERVE_MIN_RECALL``).
    −1 slots (fewer than K candidates) are ignored on both sides."""
    ids = np.asarray(ids)
    oracle_ids = np.asarray(oracle_ids)
    if ids.shape[0] != oracle_ids.shape[0]:
        raise ValueError(f"batch mismatch {ids.shape} vs {oracle_ids.shape}")
    hits = total = 0
    for got, want in zip(ids, oracle_ids):
        oracle = {int(x) for x in want if x >= 0}
        if not oracle:
            continue
        hits += len(oracle & {int(x) for x in got if x >= 0})
        total += len(oracle)
    return hits / total if total else 1.0
