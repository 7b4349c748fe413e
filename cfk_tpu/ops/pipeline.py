"""Double-buffered chunk pipelining shared by the streaming half-steps.

Every tiled/bucketed half-iteration streams its work through fixed-size
chunks inside one XLA loop; executed naively, each loop step SERIALIZES its
memory phase (the neighbor-factor gather / chunk operand fetch) against its
compute phase (Gram GEMM + solve), so the gather engine idles during
compute and the MXU idles during the fetch.  ``prefetch_scan`` restructures
the loop as a classic software pipeline: two chunk buffers are alive at any
time, the fetch for chunk ``c+1`` is ISSUED (in program order) before the
compute for chunk ``c`` consumes the other buffer, and XLA's async
scheduler is free to overlap the two — the fetch has no data dependence on
the compute.  The math is unchanged: same fetches, same computes, same
order per chunk, so results are bit-identical to the serial loop
(``tests/test_overlap.py`` pins this).

The same shape serves the ring exchanges in ``cfk_tpu.parallel.spmd``
(there the "fetch" is a ``lax.ppermute`` over ICI), the tiled chunk scans
in ``cfk_tpu.ops.tiled``, and the bucketed chunk walks in
``cfk_tpu.ops.solve.walk_buckets`` / ``cfk_tpu.ops.subspace``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def default_overlap() -> bool:
    """Process-wide default for comm/compute overlap (the production mode).

    A patch point no tool patches any more (ROADMAP D13); per-call
    ``overlap=`` and ``ALSConfig.overlap`` override it explicitly."""
    return True


def resolve_overlap(overlap) -> bool:
    """Per-call override if given, else the process default."""
    return default_overlap() if overlap is None else bool(overlap)


def index_fetch(flat, cap):
    """A ``prefetch_scan`` fetch that slices chunk ``i``'s [cap] window
    out of a flat array — the in-kernel-gather pipelines' fetch phase.

    When the neighbor gather is fused into the Gram kernels
    (``ops.tiled`` ``in_kernel_gather``), the expensive memory phase the
    pipeline used to hide (the [cap, k] factor gather) moves inside the
    kernel's own DMA double buffer; what the scan prefetches is just the
    index chunk.  Keeping the prefetch_scan structure (rather than
    collapsing to a plain lax.scan) preserves the overlap on/off
    bit-equality contract and keeps the slice itself off the compute
    phase's critical path."""
    def fetch(i):
        return lax.dynamic_slice(flat, (i * cap,), (cap,))

    return fetch


def prefetch_scan(fetch, compute, num_chunks, init, xs=None):
    """Software-pipelined chunk scan with a one-chunk prefetch distance.

    ``fetch(i) -> buf`` produces chunk ``i``'s input buffer (a pytree; the
    expensive memory phase — a big gather, a dynamic slice, a permuted
    block).  ``compute(carry, buf, x, i) -> (carry, y)`` consumes it
    (``x`` is chunk ``i``'s slice of ``xs``, or None).  The schedule is::

        buf0 = fetch(0)                       # prologue
        step i: fetch(i+1)  ||  compute(buf_i)  # double buffer
        (the last step's prefetch index clamps to num_chunks-1; its result
         is dead and XLA removes nothing real with it)

    Returns ``(carry, ys)`` exactly like ``lax.scan`` over the chunks.
    """
    if xs is None:
        xs_leaves = jnp.arange(num_chunks)
        take = lambda s: None
        idx_of = lambda s: s
    else:
        xs_leaves = (jnp.arange(num_chunks), xs)
        take = lambda s: s[1]
        idx_of = lambda s: s[0]

    buf0 = fetch(jnp.asarray(0, jnp.int32))

    def step(carry, scanned):
        buf, inner = carry
        i = idx_of(scanned)
        nxt = fetch(jnp.minimum(i + 1, num_chunks - 1).astype(jnp.int32))
        inner, y = compute(inner, buf, take(scanned), i)
        return (nxt, inner), y

    (_, carry), ys = lax.scan(step, (buf0, init), xs_leaves)
    return carry, ys


def chunk_map(piece, arrs, num_chunks, *, overlap=None):
    """``lax.map(piece, arrs)`` over the leading chunk axis, pipelined.

    ``arrs`` is a tuple of [num_chunks, ...] arrays.  With overlap on, the
    read of chunk ``c+1``'s operands is issued before ``piece`` runs on
    chunk ``c`` (double buffer); with overlap off this is exactly
    ``lax.map`` (the serial reference schedule).  Used by the bucketed
    chunk walks, where ``piece`` is opaque (full iALS solve or a subspace
    sweep) and the operand fetch is the part worth hiding.
    """
    if not resolve_overlap(overlap):
        return lax.map(lambda c: piece(*c), arrs)

    def fetch(i):
        return tuple(
            lax.dynamic_index_in_dim(a, i, 0, keepdims=False) for a in arrs
        )

    def compute(carry, buf, _x, _i):
        return carry, piece(*buf)

    _, ys = prefetch_scan(fetch, compute, num_chunks, init=None)
    return ys
