"""In-kernel neighbor gather: the gather-fused Gram kernel variants
(cfk_tpu/ops/pallas/gram_kernel.py ``*_gather_pallas``) DMA the indexed
factor rows straight from the HBM-resident table instead of consuming a
materialized [C, k] gathered stream.

Equivalence contract pinned here: on the interpret/XLA-emulation route
the fused gather runs the numerically identical append-zero-row + gather
+ premultiply the XLA-gather path runs (``compat.emulate_in_kernel_gather``)
— for the kernel wrappers (padding rows, bf16 and f32 tables, the weighted
√aw premultiply, carries) and for the stream/dense/accum/ring half-step
bodies, overlap on and off, with the support-gate fallbacks exercised.

What "identical" means depends on what the two sides run on the CPU:

- same program two ways — the gather wrappers' XLA twin against another
  twin (the fused-epilogue and dense wrappers always take theirs), or one
  route with the knob resolving the same way: BIT-IDENTICAL
  (``assert_array_equal``);
- twin against kernel body — the knob-off side of a split-epilogue stream
  or accum half-step calls ``gram_tiles_pallas``, whose interpret route
  outside shard_map is the kernel body under the Pallas interpreter, while
  the knob-on side is the gather wrapper's twin.  The body sums each
  16-row tile with one MXU-shaped dot and walks tiles in order; the twin
  is an einsum + segment-sum that XLA:CPU is free to reassociate.  Same
  products, different float32 summation order: a few ulp on (A, b)
  (≤ 4.6e-7 relative observed on jax 0.9.0), amplified by the ridge
  system's conditioning once solved.  ``_ULP`` / ``_SOLVED`` below.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from cfk_tpu.config import ALSConfig
from cfk_tpu.data.blocks import Dataset, build_tiled_blocks
from cfk_tpu.data.synthetic import synthetic_netflix_coo
from cfk_tpu.models.als import _tiled_to_device, train_als
from cfk_tpu.ops.pallas.gram_kernel import (
    gram_solve_tiles_gather_pallas,
    gram_solve_tiles_pallas,
    gram_tiles_gather_pallas,
    gram_tiles_pallas,
    in_kernel_gather_supported,
)
from cfk_tpu.ops.tiled import ials_tiled_half_step, tiled_half_step


# kernel body vs XLA twin (module docstring): float32 summation order only.
_ULP = dict(rtol=2e-6, atol=0)  # (A, b) sums; 4.6e-7 relative observed
_SOLVED = dict(rtol=1e-5, atol=1e-5)  # solved rank-8 factors; 7.2e-7 abs seen


@pytest.fixture(scope="module")
def synth():
    coo = synthetic_netflix_coo(3000, 400, 60_000, seed=1)
    return Dataset.from_coo(coo)


def _kernel_inputs(rng, *, f=37, k=8, t=16, nt=12, s=5, dtype=np.float32):
    """A stream-mode kernel problem with real padding: some indices hit
    the virtual zero row (== f) and their mask/rt entries are zero."""
    table = rng.standard_normal((f, k)).astype(dtype)
    nb = rng.integers(0, f, nt * t).astype(np.int32)
    pad = rng.random(nt * t) < 0.2
    nb[pad] = f  # the virtual zero row
    mask = (~pad).astype(np.float32)
    rt = (rng.standard_normal(nt * t) * mask).astype(np.float32)
    seg = np.sort(rng.integers(0, s, nt)).astype(np.int32)
    return (jnp.asarray(table), jnp.asarray(nb), jnp.asarray(mask),
            jnp.asarray(rt), jnp.asarray(seg))


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_kernel_gather_matches_materialized_stream(dtype):
    """Unit-weight contract: gather-fused (A, b) == the split Gram fed the
    materialized zero-row-appended stream, f32 AND bf16 tables, padding
    rows contributing exact zeros.  Twin vs twin is bit-exact; twin vs the
    split kernel's body is float32 summation order apart (``_ULP``)."""
    from cfk_tpu.ops.pallas.gram_kernel import _emulate_gram_tiles

    rng = np.random.default_rng(0)
    dt = jnp.bfloat16 if dtype == "bfloat16" else np.float32
    table, nb, mask, rt, seg = _kernel_inputs(rng)
    table = table.astype(dt)
    fz = jnp.concatenate([table, jnp.zeros((1, 8), table.dtype)])
    g = fz[nb]  # the materialized stream the XLA schedule builds
    a, b = gram_tiles_gather_pallas(table, nb, mask, rt, seg,
                                    num_segments=5, tile_rows=16)
    a_twin, b_twin = _emulate_gram_tiles(g, rt, seg, num_segments=5,
                                         tile_rows=16, carry=None)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_twin))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(b_twin))
    a_ref, b_ref = gram_tiles_pallas(g, rt, seg, num_segments=5,
                                     tile_rows=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref), **_ULP)
    # b: the body feeds the b coefficient to the MXU in the stream dtype
    # (``r_i.astype(g_i.dtype)``), the twin keeps it float32 — on a bf16
    # stream that is 2⁻⁹ relative rounding per term (2.5e-2 observed on
    # sums that cancel), not summation order.
    b_tol = _ULP if dtype == np.float32 else dict(
        rtol=0, atol=1e-2 * float(np.abs(np.asarray(b_ref)).max()))
    np.testing.assert_allclose(np.asarray(b), np.asarray(b_ref), **b_tol)


def test_kernel_gather_weighted_premultiply():
    """The √aw premultiply applied in-register == the XLA path's
    pre-multiplied stream (iALS's sqrt reparameterization), bit-exact."""
    rng = np.random.default_rng(1)
    table, nb, mask, rt, seg = _kernel_inputs(rng)
    aw = (rng.random(nb.shape[0]).astype(np.float32) + 0.5) * np.asarray(
        mask
    )
    fz = jnp.concatenate([table, jnp.zeros((1, 8), table.dtype)])
    g = fz[nb] * jnp.asarray(aw)[:, None]
    a_ref, b_ref = gram_tiles_pallas(g, rt, seg, num_segments=5,
                                     tile_rows=16)
    a, b = gram_tiles_gather_pallas(table, nb, jnp.asarray(aw), rt, seg,
                                    num_segments=5, tile_rows=16)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_ref))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(b_ref))


def test_kernel_gather_fused_solve_with_carry():
    """The gather + in-VMEM ridge+solve composition: (x, carry) of the
    gather-fused wrapper == the stream-fed fused wrapper, diag and matrix
    reg modes, with a chunk-boundary carry folded in."""
    rng = np.random.default_rng(2)
    table, nb, mask, rt, seg = _kernel_inputs(rng)
    k = 8
    fz = jnp.concatenate([table, jnp.zeros((1, k), table.dtype)])
    g = fz[nb]
    cnt = jnp.asarray(rng.integers(1, 50, 5).astype(np.int32))
    carry = (jnp.asarray(rng.standard_normal((k, k)).astype(np.float32)),
             jnp.asarray(rng.standard_normal(k).astype(np.float32)),
             jnp.asarray(1.0, jnp.float32))
    lseg = jnp.asarray(3, jnp.int32)
    kw = dict(num_segments=5, tile_rows=16, lam=0.05, carry=carry)
    x_ref, ca_ref, cb_ref = gram_solve_tiles_pallas(
        g, rt, seg, cnt, lseg, reg_mode="diag", **kw)
    x, ca, cb = gram_solve_tiles_gather_pallas(
        table, nb, mask, rt, seg, cnt, lseg, reg_mode="diag", **kw)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x_ref))
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(ca_ref))
    np.testing.assert_array_equal(np.asarray(cb), np.asarray(cb_ref))

    reg = jnp.asarray(np.eye(k, dtype=np.float32) * 0.1 + 0.01)
    xm_ref, _, _ = gram_solve_tiles_pallas(
        g, rt, seg, reg, lseg, reg_mode="matrix", **kw)
    xm, _, _ = gram_solve_tiles_gather_pallas(
        table, nb, mask, rt, seg, reg, lseg, reg_mode="matrix", **kw)
    np.testing.assert_array_equal(np.asarray(xm), np.asarray(xm_ref))


def test_support_gate():
    """Rank/dtype (what Mosaic's one-row DMA lowers — tests/
    test_chip_compile.py holds the gate to the chip's compiler), SMEM
    budget and tile/block alignment gates; refused shapes keep the
    XLA-gather path (exercised end-to-end below)."""
    f32 = dict(k=128, table_dtype=jnp.float32)
    assert in_kernel_gather_supported(65_536, 20_480, 128, **f32)
    assert not in_kernel_gather_supported(65_536, 20_480, 8, **f32)  # tile
    assert not in_kernel_gather_supported(
        65_536, 20_480, 128, block_rows=24, **f32
    )  # block align
    assert not in_kernel_gather_supported(1 << 21, 0, 128, **f32)  # SMEM
    for k, dt in ((64, jnp.float32), (128, jnp.bfloat16), (128, jnp.int8)):
        assert not in_kernel_gather_supported(
            65_536, 20_480, 128, k=k, table_dtype=dt), (k, dt)
        # ... where Mosaic lowers the DMA; the interpret route's twin has
        # no such limit, only the shape gates
        assert in_kernel_gather_supported(
            65_536, 20_480, 128, k=k, table_dtype=dt, lowered=False)
    assert not in_kernel_gather_supported(
        65_536, 20_480, 8, lowered=False, **f32)


def _half(blocks, fixed, lam, ikg, weighted=False, **kw):
    return np.asarray(tiled_half_step(
        fixed, _tiled_to_device(blocks, weighted),
        ("tiled", blocks.mode) + blocks.statics,
        blocks.padded_entities, lam, solver="pallas",
        in_kernel_gather=ikg, **kw,
    ))


@pytest.mark.parametrize("overlap", [True, False])
def test_stream_fused_gather_matches_xla_bitexact(synth, overlap):
    d = synth.coo_dense
    rng = np.random.default_rng(0)
    M = jnp.asarray(rng.standard_normal((400, 8)).astype(np.float32))
    ub = build_tiled_blocks(
        d.user_raw, d.movie_raw, d.rating, 3000, 400,
        accum_max_entities=16, chunk_elems=2048, tile_rows=16,
    )
    assert ub.mode == "stream"
    on = _half(ub, M, 0.05, True, overlap=overlap)
    off = _half(ub, M, 0.05, False, overlap=overlap)
    np.testing.assert_array_equal(on, off)


@pytest.mark.parametrize("overlap", [True, False])
def test_dense_stream_fused_gather_matches_xla_bitexact(synth, overlap):
    d = synth.coo_dense
    rng = np.random.default_rng(2)
    M = jnp.asarray(rng.standard_normal((400, 8)).astype(np.float32))
    ub = build_tiled_blocks(
        d.user_raw, d.movie_raw, d.rating, 3000, 400,
        accum_max_entities=0, chunk_elems=256, tile_rows=16,
        dense_stream=True,
    )
    assert ub.mode == "dstream"
    on = _half(ub, M, 0.05, True, overlap=overlap)
    off = _half(ub, M, 0.05, False, overlap=overlap)
    np.testing.assert_array_equal(on, off)


@pytest.mark.parametrize("overlap", [True, False])
def test_accum_fused_gather_matches_xla(synth, overlap):
    """Accum mode rebases slice-local indices to absolute table rows and
    skips the hoisted window stack entirely.  Accum has no fused epilogue,
    so knob-off runs the split kernel's body against knob-on's twin:
    ``_SOLVED``, not bits."""
    d = synth.coo_dense
    rng = np.random.default_rng(4)
    U = jnp.asarray(rng.standard_normal((3000, 8)).astype(np.float32))
    mb = build_tiled_blocks(
        d.movie_raw, d.user_raw, d.rating, 400, 3000,
        slice_rows=128, chunk_elems=2048, tile_rows=16,
    )
    assert mb.mode == "accum"
    on = _half(mb, U, 0.05, True, overlap=overlap)
    off = _half(mb, U, 0.05, False, overlap=overlap)
    np.testing.assert_allclose(on, off, **_SOLVED)


@pytest.mark.parametrize("dense", [False, True])
def test_ials_fused_gather_matches_xla_bitexact(synth, dense):
    """Weighted (iALS) premultiply through the gather kernels: the
    ε-clamped √aw stream re-masked by the validity channel — both tiled
    stream layouts, bit-exact across the knob."""
    d = synth.coo_dense
    rng = np.random.default_rng(3)
    M = jnp.asarray(rng.standard_normal((400, 8)).astype(np.float32))
    ub = build_tiled_blocks(
        d.user_raw, d.movie_raw, d.rating, 3000, 400,
        accum_max_entities=0, chunk_elems=256, tile_rows=16,
        dense_stream=dense,
    )
    outs = {}
    for ikg in (False, True):
        outs[ikg] = np.asarray(ials_tiled_half_step(
            M, _tiled_to_device(ub, weighted=dense),
            ("tiled", ub.mode) + ub.statics,
            ub.padded_entities, 0.1, 2.0, solver="pallas",
            in_kernel_gather=ikg,
        ))
    np.testing.assert_array_equal(outs[True], outs[False])


def test_unaligned_tiles_fall_back_to_xla_gather(synth):
    """tile_rows=8 fails the 16-alignment gate: in_kernel_gather=True
    must silently keep the XLA-gather path — bit-identical to off."""
    d = synth.coo_dense
    rng = np.random.default_rng(5)
    M = jnp.asarray(rng.standard_normal((400, 8)).astype(np.float32))
    ub = build_tiled_blocks(
        d.user_raw, d.movie_raw, d.rating, 3000, 400,
        accum_max_entities=16, chunk_elems=2048, tile_rows=8,
    )
    on = _half(ub, M, 0.05, True)
    off = _half(ub, M, 0.05, False)
    np.testing.assert_array_equal(on, off)


def test_gather_with_split_epilogue(synth):
    """The fused gather composes with fused_epilogue=False (gather-fused
    Gram, split HBM solve).  Knob-off is the split kernel's body, knob-on
    the gather twin: ``_SOLVED`` vs the all-XLA-gather schedule."""
    d = synth.coo_dense
    rng = np.random.default_rng(6)
    M = jnp.asarray(rng.standard_normal((400, 8)).astype(np.float32))
    ub = build_tiled_blocks(
        d.user_raw, d.movie_raw, d.rating, 3000, 400,
        accum_max_entities=16, chunk_elems=2048, tile_rows=16,
    )
    on = _half(ub, M, 0.05, True, fused_epilogue=False)
    off = _half(ub, M, 0.05, False, fused_epilogue=False)
    np.testing.assert_allclose(on, off, **_SOLVED)


def test_rank_above_solve_cap_keeps_gather(synth):
    """rank > the fused elimination's cap: the fused SOLVE falls back to
    the split schedule while the fused GATHER stays active.  The split
    schedule's knob-off side is the kernel body (twin vs body), and at
    rank 136 every system has far more unknowns than the ~20 ratings
    behind it, so only the λ·n ridge conditions it: the few-ulp (A, b)
    difference reaches 1.3e-5 absolute in the solved rows — 1e-4."""
    from cfk_tpu.ops.pallas.solve_kernel import LU_MAX_RANK

    d = synth.coo_dense
    rng = np.random.default_rng(7)
    k = LU_MAX_RANK + 8
    M = jnp.asarray(rng.standard_normal((400, k)).astype(np.float32))
    ub = build_tiled_blocks(
        d.user_raw, d.movie_raw, d.rating, 3000, 400,
        accum_max_entities=16, chunk_elems=2048, tile_rows=16,
    )
    on = _half(ub, M, 0.05, True)
    off = _half(ub, M, 0.05, False)
    np.testing.assert_allclose(on, off, rtol=1e-4, atol=1e-4)


def test_trainer_gather_matches_xla_bitexact(synth):
    """End-to-end: the tiled trainer with in_kernel_gather on == off."""
    ds = Dataset.from_coo(synth.coo_dense, layout="tiled", chunk_elems=2048,
                          accum_max_entities=16)
    base = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                     layout="tiled", solver="pallas")
    on = train_als(
        ds, dataclasses.replace(base, in_kernel_gather=True)
    ).predict_dense()
    off = train_als(
        ds, dataclasses.replace(base, in_kernel_gather=False)
    ).predict_dense()
    np.testing.assert_array_equal(on, off)


@pytest.mark.parametrize("exchange,layout", [("ring", "tiled"),
                                             ("ring", "padded")])
def test_sharded_ring_gather_matches_xla(synth, exchange, layout):
    """Both SPMD ring paths across the knob: the tiled ring gathers
    in-kernel from the rotated factor block (bit-exact on/off); the
    padded ring has no tiled kernel, so the knob is inert there — pinned
    so a future wiring mistake cannot silently change it."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import train_als_sharded

    coo = synthetic_netflix_coo(3000, 400, 60_000, seed=1)
    ds4 = Dataset.from_coo(coo, layout=layout, num_shards=4,
                           ring=layout == "tiled", ring_warn=False)
    base = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                     layout=layout, solver="pallas", num_shards=4,
                     exchange=exchange)
    outs = {}
    for ikg in (True, False):
        cfg = dataclasses.replace(base, in_kernel_gather=ikg)
        outs[ikg] = train_als_sharded(ds4, cfg, make_mesh(4)).predict_dense()
    np.testing.assert_array_equal(outs[True], outs[False])


def test_config_validates_gather_and_algo_knobs():
    assert ALSConfig(in_kernel_gather=True).in_kernel_gather is True
    assert ALSConfig().in_kernel_gather is None
    assert ALSConfig(reg_solve_algo="gj").reg_solve_algo == "gj"
    assert ALSConfig().reg_solve_algo == "auto"
    with pytest.raises(ValueError, match="in_kernel_gather"):
        ALSConfig(in_kernel_gather="yes")
    with pytest.raises(ValueError, match="reg_solve_algo"):
        ALSConfig(reg_solve_algo="cholesky")


def test_reg_solve_algo_threads_to_same_factors(synth):
    """The threaded elimination parameter: lu and gj run different
    kernels but solve the same systems — factors agree to tight
    tolerance, and both accept the knob end-to-end."""
    ds = Dataset.from_coo(synth.coo_dense, layout="tiled", chunk_elems=2048,
                          accum_max_entities=16)
    base = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                     layout="tiled", solver="pallas")
    lu = train_als(
        ds, dataclasses.replace(base, reg_solve_algo="lu")
    ).predict_dense()
    gj = train_als(
        ds, dataclasses.replace(base, reg_solve_algo="gj")
    ).predict_dense()
    np.testing.assert_allclose(lu, gj, rtol=2e-5, atol=2e-5)
