"""The jax seams every sharded call site shares, and the kernels' XLA twins.

``shard_map``/``typeof_vma``/``to_varying`` are thin names over the installed
jax (0.9: top-level ``jax.shard_map`` with ``check_vma``, ``jax.typeof``,
``lax.pcast``) so the ring bodies and kernels spell them one way.  The
``emulate_*`` functions are the plain-XLA twins of the Pallas kernels: the
route interpret-mode (CPU) runs take, and the references the kernel tests
compare against.
"""

from __future__ import annotations

import jax
from jax import lax


def shard_map(f, *, mesh, in_specs, out_specs, check=True):
    """``jax.shard_map`` with the vma checker toggled by ``check``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def typeof_vma(x):
    """``jax.typeof(x).vma`` (None for values jax cannot type)."""
    try:
        return getattr(jax.typeof(x), "vma", None)
    except TypeError:  # pragma: no cover - non-typeable values
        return None


def to_varying(x, axis):
    """Mark x device-varying over ``axis``."""
    return lax.pcast(x, axis, to="varying")


def match_varying(z, ref):
    """Give constant ``z`` the same device-varying axes as traced ``ref``.

    Inside ``shard_map`` (with vma checking) a scan carry initialized from
    constants must be explicitly pcast to the mesh axes the body's data is
    varying over; outside shard_map this is the identity.
    """
    have = typeof_vma(z) or ()
    missing = tuple(a for a in (typeof_vma(ref) or ()) if a not in have)
    return to_varying(z, missing) if missing else z


def emulate_in_kernel_gather(table, nb, wt, ct):
    """XLA twin of the gather-fused Gram kernels' in-kernel row fetch —
    the interpret route, so CPU CI exercises the same code shape the
    Mosaic DMA gather runs.

    The Mosaic kernels (``ops.pallas.gram_kernel`` ``*_gather_pallas``)
    keep the RAW fixed table in HBM/ANY memory, DMA each tile's indexed
    rows into VMEM (indices clamped to the last real row), and apply the
    per-entry premultiply ``wt`` in-register — ``wt`` is the 0/1 validity
    mask for unit-weight callers (which is what realizes the zero-appended
    padding row without materializing it) or √aw·mask for the weighted
    (iALS) stream.  This twin runs the numerically identical ops the
    XLA-gather path runs: append the zero row, gather, cast to the
    compute dtype, multiply — so fused-gather and XLA-gather factors are
    BIT-IDENTICAL on this route (``tests/test_in_kernel_gather.py`` pins
    it).  Index convention: ``nb == table.shape[0]`` is the virtual zero
    row; larger indices are invalid.
    """
    import jax.numpy as jnp

    k = table.shape[-1]
    zrow = jnp.zeros((1, k), table.dtype)
    vma = typeof_vma(table)
    if vma:  # mark the zero row varying like the table under shard_map
        zrow = to_varying(zrow, tuple(vma))
    fz = jnp.concatenate([table, zrow])
    g = fz[nb].astype(ct)
    if wt is not None:
        g = g * wt.astype(ct)[:, None]
    return g


def emulate_topk_scores(u, table, scale, seen_tiles, *, k_top, num_movies,
                        tile_m, row_offset=0):
    """XLA twin of the serving score+top-K kernel — the sharded-interpret
    route, so CPU CI exercises the same code shape the Mosaic kernel runs:
    ``emulate_topk_counted`` without the counts, as
    ``serving.topk_kernel.topk_scores_pallas`` is of its counted form."""
    return emulate_topk_counted(
        u, table, scale, seen_tiles, k_top=k_top, num_movies=num_movies,
        tile_m=tile_m, row_offset=row_offset,
    )[:2]


def emulate_topk_counted(u, table, scale, seen_tiles, *, k_top, num_movies,
                         tile_m, row_offset=0, rows=None, grid_tiles=None):
    """XLA twin of ``serving.topk_kernel.topk_scores_counted``: (scores,
    movie rows, [selection rounds, tiles that ran one, exclusion chunks,
    tiles that ran one, tiles completed]).

    Scans the SAME per-tile fold the kernel body runs
    (``serving.topk_kernel._score_tile_fold`` — one shared function, the
    same twin discipline as the Gram kernels) over the same movie tiles in
    the same order, carrying the same sorted [K, B] selection, gating
    each tile's selection rounds on the carry's K-th score and taking the
    fold's branch with or without the masks by the same per-tile scalar
    (``SeenTiles.hits``) as the kernel does — so kernel and twin are
    BIT-IDENTICAL on this route, counts included (``tests/test_serving.py``
    pins it).  Where the fold defers passes (an int8 or a float32 table)
    the tiles it completes depend on the K-th scores its first gate reads,
    and the kernel reads them once a group of tiles: the scan carries that
    row and renews it at the same tiles.  Crucially the scan's
    per-step block is [B, tile_m]: no [B, num_movies] score matrix is ever
    materialized here either (the emulation-path memory check in the tests
    compiles this and bounds its temp memory below B·M·4 bytes).

    A ranged scan (``rows`` = ``(row_lo, row_hi)``, ``grid_tiles``: see
    ``topk_scores_counted``) scans the ``grid_tiles`` tiles the kernel's
    grid runs, from the first tile of the range's first slab, and folds
    those that hold a row of the range, the range's bounds in the masks'
    compares: the kernel's tiles, order, gates and counts.
    """
    import jax.numpy as jnp

    from cfk_tpu.serving import topk_kernel

    b = u.shape[0]
    m_pad = table.shape[0]
    nt = m_pad // tile_m
    tbl = table.reshape(nt, tile_m, -1)
    # what the fold reads as u, made once a call as the kernel's is
    u = topk_kernel.resident_operand(u, table.dtype)
    # one dense [tile_m] row a tile, a column only inside the step: a
    # trailing axis of 1 is padded to 128 lanes in the chip's HBM
    sc = (None if scale is None
          else scale.astype(jnp.float32).reshape(nt, tile_m))
    seen_tiles = topk_kernel.as_seen_tiles(seen_tiles, tile_m)
    if seen_tiles is None:
        seen = hits = None
        width = 0
    else:
        # slot-major, like the kernel's block: one exclusion slot = one
        # row; the hits are the kernel's second scalar-prefetch operand
        seen, hits = jnp.swapaxes(seen_tiles.slots, 1, 2), seen_tiles.hits
        width = seen.shape[1]
    # the tiles the kernel scores ahead of their gates (its P): a deferring
    # fold's first gate reads the K-th scores as of the group's start
    slab = topk_kernel.slab_tiles(
        nt, b, width, table.shape[1], table.dtype, tile_m=tile_m,
        k_top=k_top)
    group = min(topk_kernel._GROUP_TILES, slab)
    carry0 = jax.tree.map(
        lambda z: match_varying(z, table),
        (jnp.full((k_top, b), -jnp.inf, jnp.float32),
         jnp.full((k_top, b), -1, jnp.int32),
         jnp.zeros(topk_kernel.NUM_COUNTS, jnp.int32),
         jnp.full((1, b), -jnp.inf, jnp.float32)),
    )

    off = jnp.asarray(row_offset, jnp.int32)
    if rows is not None:
        row_lo, row_hi = (jnp.asarray(r, jnp.int32) for r in rows)
        # the table's tile of the rectangle's (and the scan's) tile 0
        first = topk_kernel.range_slabs(row_lo, row_hi, slab, tile_m)[0] * slab
        # the range's bounds take the table's end's place in the masks
        bounds = {"num_movies": row_hi, "row_lo": row_lo}
    else:
        bounds = {"num_movies": num_movies}

    def step(carry, i):
        # ``i`` is the tile's place in the scan, ``n`` in the table
        n = i if rows is None else jnp.minimum(first + i, nt - 1)
        at = lambda a, j: lax.dynamic_index_in_dim(a, j, 0, keepdims=False)
        seen_i = None if seen is None else at(seen, i)
        gate_kth = jnp.where(i % group == 0, carry[0][k_top - 1:], carry[3])
        tile_base = off + (i if rows is None else first + i) * tile_m

        def fold():
            v, ids, counts = topk_kernel._score_tile_fold(
                carry[0], carry[1], u, at(tbl, n),
                None if sc is None else at(sc, n)[:, None],
                None if seen is None else (
                    lambda j: lax.dynamic_slice_in_dim(seen_i, j, 1, 0)
                ),
                width, None if seen is None else at(hits, i), tile_base,
                tile_m=tile_m, k_top=k_top, gate_kth=gate_kth, **bounds,
            )
            return v, ids, carry[2] + jnp.stack(counts)

        if rows is None:
            out = fold()
        else:
            # a tile that holds no row of the range is not there
            out = lax.cond(
                (tile_base + tile_m > row_lo) & (tile_base < row_hi),
                fold, lambda: carry[:3])
        return (*out, gate_kth), None

    (vals, ids, counts, _), _ = lax.scan(
        step, carry0,
        jnp.arange(nt if rows is None else grid_tiles, dtype=jnp.int32))
    return vals.T, ids.T, counts


def emulate_fused_gram_solve(a, b, reg, *, reg_mode, lam, lseg):
    """XLA twin of the fused Gram+solve epilogue — the interpret route, so
    CPU CI exercises the same code shape the Mosaic kernel runs.

    Given the chunk's emulated (A [S, k, k], b [S, k]) normal-equation
    sums, return exactly what ``gram_solve_tiles_pallas`` returns:

        (x [S, k], carry_a [k, k], carry_b [k])

    — the carry row extracted RAW (pre-ridge) at ``lseg``, and the whole
    batch regularized + solved by the same fused reg+solve elimination the
    kernel's epilogue runs (``gauss_solve_reg_pallas``, which interprets
    off-TPU).  Because the split chunk path computes the identical
    segment-sum (A, b) and calls the identical reg+solve on it, fused and
    split factors are BIT-IDENTICAL on this route — the equivalence the
    fused/split regression tests pin.
    """
    from cfk_tpu.ops.pallas.solve_kernel import gauss_solve_reg_pallas

    x = gauss_solve_reg_pallas(a, b, reg, reg_mode=reg_mode, lam=lam)
    ca = lax.dynamic_index_in_dim(a, lseg, 0, keepdims=False)
    cb = lax.dynamic_index_in_dim(b, lseg, 0, keepdims=False)
    return x, ca, cb
