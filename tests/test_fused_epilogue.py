"""Fused Gram+solve epilogue: each chunk's normal equations solved inside
the Gram kernel's VMEM residency (cfk_tpu/ops/pallas/gram_kernel.py
``gram_solve_tiles_pallas`` / ``gram_solve_tiles_dense_pallas``).

Equivalence contract pinned here: on the interpret/XLA-emulation route the
fused path is BIT-IDENTICAL to the split Gram→HBM→solve schedule with the
pallas solver (both run the same segment-sum Gram + the same fused
reg+solve elimination), for the stream, dense-stream, and ring-tiled
bodies, both weight modes, with the rank>cap automatic fallback; the accum
body's knob (which swaps the final batched solve's algorithm, not a
per-chunk round-trip) is equivalent to tight tolerance.
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
from cfk_tpu.ops.tiled import ials_tiled_half_step, tiled_half_step


@pytest.fixture(scope="module")
def synth():
    coo = synthetic_netflix_coo(3000, 400, 60_000, seed=1)
    return Dataset.from_coo(coo)


def _half(blocks, fixed, lam, fused, **kw):
    return np.asarray(tiled_half_step(
        fixed, _tiled_to_device(blocks),
        ("tiled", blocks.mode) + blocks.statics,
        blocks.padded_entities, lam, solver="pallas",
        fused_epilogue=fused, **kw,
    ))


def test_stream_fused_matches_split(synth):
    d = synth.coo_dense
    rng = np.random.default_rng(0)
    M = jnp.asarray(rng.standard_normal((400, 8)).astype(np.float32))
    ub = build_tiled_blocks(
        d.user_raw, d.movie_raw, d.rating, 3000, 400,
        accum_max_entities=16, chunk_elems=2048, tile_rows=8,
    )
    assert ub.mode == "stream"
    fused = _half(ub, M, 0.05, True)
    split = _half(ub, M, 0.05, False)
    # tile_rows=8 refuses the in-kernel gather, so the split side calls
    # gram_tiles_pallas — on the CPU the kernel BODY under the Pallas
    # interpreter — while the fused wrapper runs its XLA twin (einsum +
    # segment-sum).  Same products, different float32 summation order: a
    # few ulp on (A, b), 3.6e-7 absolute observed in the solved rank-8
    # rows on jax 0.9.0.  The bit-exact leg (twin vs the XLA split
    # schedule) is the next test.
    np.testing.assert_allclose(fused, split, rtol=1e-5, atol=1e-5)


def test_stream_fused_matches_xla_split_bitexact(synth):
    """The emulation twin runs the identical segment-sum + fused reg+solve
    the split XLA gram backend runs — bit-exact on ANY jax version."""
    from cfk_tpu.ops.tiled import als_half_step_tiled

    d = synth.coo_dense
    rng = np.random.default_rng(1)
    M = jnp.asarray(rng.standard_normal((400, 8)).astype(np.float32))
    ub = build_tiled_blocks(
        d.user_raw, d.movie_raw, d.rating, 3000, 400,
        accum_max_entities=16, chunk_elems=2048, tile_rows=8,
    )
    blk = _tiled_to_device(ub)
    fused = _half(ub, M, 0.05, True)
    xla_split = np.asarray(als_half_step_tiled(
        M, blk["neighbor_idx"], blk["rating"], blk["weight"],
        blk["tile_seg"], blk["chunk_entity"], blk["chunk_count"],
        blk["carry_in"], blk["last_seg"], ub.padded_entities, 0.05,
        statics=ub.statics, solver="pallas", gram_backend="xla",
        fused_epilogue=False,
    ))
    np.testing.assert_array_equal(fused, xla_split)


def test_dense_stream_fused_matches_split_bitexact(synth):
    d = synth.coo_dense
    rng = np.random.default_rng(2)
    M = jnp.asarray(rng.standard_normal((400, 8)).astype(np.float32))
    ub = build_tiled_blocks(
        d.user_raw, d.movie_raw, d.rating, 3000, 400,
        accum_max_entities=0, chunk_elems=256, tile_rows=16,
        dense_stream=True,
    )
    assert ub.mode == "dstream"
    fused = _half(ub, M, 0.05, True)
    split = _half(ub, M, 0.05, False)
    np.testing.assert_array_equal(fused, split)


@pytest.mark.parametrize("dense", [False, True])
def test_ials_fused_matches_split_bitexact(synth, dense):
    """The matrix-reg (YᵀY+λI) fused mode, both tiled stream layouts."""
    d = synth.coo_dense
    rng = np.random.default_rng(3)
    M = jnp.asarray(rng.standard_normal((400, 8)).astype(np.float32))
    ub = build_tiled_blocks(
        d.user_raw, d.movie_raw, d.rating, 3000, 400,
        accum_max_entities=0, chunk_elems=256, tile_rows=16,
        dense_stream=dense,
    )
    outs = {}
    for fused in (False, True):
        outs[fused] = np.asarray(ials_tiled_half_step(
            M, _tiled_to_device(ub, weighted=dense),
            ("tiled", ub.mode) + ub.statics,
            ub.padded_entities, 0.1, 2.0, solver="pallas",
            fused_epilogue=fused,
        ))
    np.testing.assert_array_equal(outs[True], outs[False])


def test_accum_fused_knob_tight_tolerance(synth):
    """Accum mode has no per-chunk residency to fuse into; the knob swaps
    the final batched solve between the fused reg+solve kernel and the
    split ridge-add + dispatch — different elimination order, same math."""
    d = synth.coo_dense
    rng = np.random.default_rng(4)
    U = jnp.asarray(rng.standard_normal((3000, 8)).astype(np.float32))
    mb = build_tiled_blocks(
        d.movie_raw, d.user_raw, d.rating, 400, 3000,
        slice_rows=128, chunk_elems=2048,
    )
    assert mb.mode == "accum"
    fused = _half(mb, U, 0.05, True)
    split = _half(mb, U, 0.05, False)
    np.testing.assert_allclose(fused, split, rtol=2e-5, atol=2e-5)


def test_rank_above_cap_falls_back_to_split(synth):
    """rank > the fused elimination's cap must silently take the split
    path — bit-identical to fused_epilogue=False."""
    from cfk_tpu.ops.pallas.solve_kernel import LU_MAX_RANK

    d = synth.coo_dense
    rng = np.random.default_rng(5)
    k = LU_MAX_RANK + 8
    M = jnp.asarray(rng.standard_normal((400, k)).astype(np.float32))
    ub = build_tiled_blocks(
        d.user_raw, d.movie_raw, d.rating, 3000, 400,
        accum_max_entities=16, chunk_elems=2048, tile_rows=8,
    )
    fused = _half(ub, M, 0.05, True)
    split = _half(ub, M, 0.05, False)
    np.testing.assert_array_equal(fused, split)


def test_kernel_fused_vs_split_with_carry():
    """Kernel-level contract: (x, carry) of the fused wrapper equals the
    split gram + fused reg+solve + lseg extraction, diag and matrix."""
    from cfk_tpu.ops.pallas.gram_kernel import (
        fused_gram_solve_supported,
        gram_solve_tiles_pallas,
        gram_tiles_pallas,
    )
    from cfk_tpu.ops.solve import regularized_solve, regularized_solve_matrix

    rng = np.random.default_rng(0)
    k, t, nt, S = 8, 16, 12, 5
    g = jnp.asarray(rng.standard_normal((nt * t, k)).astype(np.float32))
    rt = jnp.asarray(rng.standard_normal(nt * t).astype(np.float32))
    seg = jnp.asarray(np.sort(rng.integers(0, S, nt)).astype(np.int32))
    cnt = jnp.asarray(rng.integers(1, 50, S).astype(np.int32))
    carry = (jnp.asarray(rng.standard_normal((k, k)).astype(np.float32)),
             jnp.asarray(rng.standard_normal(k).astype(np.float32)),
             jnp.asarray(1.0, jnp.float32))
    lseg = jnp.asarray(3, jnp.int32)

    a, b = gram_tiles_pallas(g, rt, seg, num_segments=S, tile_rows=t,
                             carry=carry)
    x, ca, cb = gram_solve_tiles_pallas(
        g, rt, seg, cnt, lseg, num_segments=S, tile_rows=t,
        reg_mode="diag", lam=0.05, carry=carry,
    )
    np.testing.assert_array_equal(
        np.asarray(x),
        np.asarray(regularized_solve(a, b, cnt, 0.05, solver="pallas")),
    )
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(a)[3])
    np.testing.assert_array_equal(np.asarray(cb), np.asarray(b)[3])

    reg = jnp.asarray(np.eye(k, dtype=np.float32) * 0.1 + 0.01)
    xm, _, _ = gram_solve_tiles_pallas(
        g, rt, seg, reg, lseg, num_segments=S, tile_rows=t,
        reg_mode="matrix", carry=carry,
    )
    np.testing.assert_array_equal(
        np.asarray(xm),
        np.asarray(regularized_solve_matrix(a, b, reg, solver="pallas")),
    )

    assert fused_gram_solve_supported(2000, 64)
    assert not fused_gram_solve_supported(2000, 129)


def test_trainer_fused_matches_split_bitexact(synth):
    """End-to-end: the tiled trainer with fused_epilogue on == off."""
    ds = Dataset.from_coo(synth.coo_dense, layout="tiled", chunk_elems=2048,
                          accum_max_entities=16)
    base = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                     layout="tiled", solver="pallas")
    on = train_als(
        ds, dataclasses.replace(base, fused_epilogue=True)
    ).predict_dense()
    off = train_als(
        ds, dataclasses.replace(base, fused_epilogue=False)
    ).predict_dense()
    np.testing.assert_array_equal(on, off)


def test_ring_tiled_fused_matches_single(synth):
    """The ring half-step's fused knob: 4-way ring with fused on matches
    the single-device split reference (the knob gates the ring's final
    reg+solve pass; the accumulation itself is unchanged)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import train_als_sharded

    coo = synthetic_netflix_coo(3000, 400, 60_000, seed=1)
    cfg1 = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                     layout="tiled", solver="cholesky")
    ref = train_als(
        Dataset.from_coo(coo, layout="tiled"), cfg1
    ).predict_dense()
    ds4 = Dataset.from_coo(coo, layout="tiled", num_shards=4, ring=True,
                           ring_warn=False)
    cfg4 = dataclasses.replace(cfg1, num_shards=4, exchange="ring",
                               solver="pallas", fused_epilogue=True)
    got = train_als_sharded(ds4, cfg4, make_mesh(4)).predict_dense()
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("overlap", [True, False])
def test_sharded_tiled_matches_single_overlap_axis(synth, overlap):
    """The 4-shard tiled SPMD equivalence (the pre-existing mismatch fixed
    by the padding-invariant init) holds with overlap on AND off."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import train_als_sharded

    coo = synthetic_netflix_coo(3000, 400, 60_000, seed=1)
    cfg1 = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                     layout="tiled", solver="cholesky", overlap=overlap)
    ref = train_als(
        Dataset.from_coo(coo, layout="tiled"), cfg1
    ).predict_dense()
    cfg4 = dataclasses.replace(cfg1, num_shards=4)
    got = train_als_sharded(
        Dataset.from_coo(coo, layout="tiled", num_shards=4), cfg4,
        make_mesh(4),
    ).predict_dense()
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


def test_config_validates_fused_epilogue():
    assert ALSConfig(fused_epilogue=True).fused_epilogue is True
    assert ALSConfig().fused_epilogue is None
    with pytest.raises(ValueError, match="fused_epilogue"):
        ALSConfig(fused_epilogue="yes")
