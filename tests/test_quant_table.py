"""Quantized HBM gather tables (ops.quant) + the bucketed/iALS++ kernel port.

Contracts pinned here (ISSUE 7):

- f32 default is BIT-IDENTICAL to pre-quantization behavior everywhere.
- The in-kernel-gather knob is bit-exact for every table dtype (the
  canonical scale-fold-then-one-multiply order every route shares).
- bf16 table: held-out RMSE ≤ 1.01× the f32 run on the planted fixture.
- int8 table: documented tolerance (≤ 1.10× on the planted fixture —
  measured ~1.00; the bound is deliberately loose, per-row symmetric
  quantization is ~0.4% relative per gather).
- Bucketed port: all four (gather, fused) knob combinations bit-exact,
  and the ported f32 explicit path bit-identical to the legacy schedule
  (one tile per entity makes the emulation einsum the legacy einsum).
- iALS++ block_size=k exactness anchor preserved under both new knobs
  and every table dtype — which also pins the score-stream consistency
  bugfix (scores recomputed from the f32 masters instead of the
  dequantized table would break the anchor under int8).

Fast representatives run in tier-1; the exhaustive sweeps are slow-marked
(scripts/tier1.sh budget).
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from cfk_tpu.config import ALSConfig
from cfk_tpu.data.blocks import Dataset, RatingsCOO
from cfk_tpu.ops import quant


def _coo(seed=0, nm=48, nu=80, nnz=1800, planted=True):
    rng = np.random.default_rng(seed)
    if planted:
        u0 = rng.standard_normal((nu, 4))
        m0 = rng.standard_normal((nm, 4))
        mi = rng.integers(0, nm, nnz)
        ui = rng.integers(0, nu, nnz)
        r = np.clip((u0[ui] * m0[mi]).sum(1) * 0.5 + 3.0
                    + 0.2 * rng.standard_normal(nnz), 1, 5)
    else:
        mi = rng.integers(0, nm, nnz)
        ui = rng.integers(0, nu, nnz)
        r = rng.integers(1, 6, nnz).astype(np.float64)
    return RatingsCOO(
        movie_raw=(mi + 1).astype(np.int64),
        user_raw=(ui + 1).astype(np.int64),
        rating=r.astype(np.float32),
    )


@pytest.fixture(scope="module")
def tiled_ds():
    # accum_max_entities=0 forces stream mode on both halves (the chunk
    # bodies with carries — the representative tiled path).
    return Dataset.from_coo(_coo(), layout="tiled", chunk_elems=1024,
                            tile_rows=16, accum_max_entities=0)


@pytest.fixture(scope="module")
def bucketed_ds():
    return Dataset.from_coo(_coo(), layout="bucketed")


# ---- ops.quant unit contracts ---------------------------------------------


def test_int8_quantize_roundtrip_and_symmetry():
    rng = np.random.default_rng(1)
    t = jnp.asarray(rng.standard_normal((37, 8)).astype(np.float32))
    t = t.at[5].set(0.0)  # all-zero row
    data, scale = quant.quantize_table(t, "int8")
    assert data.dtype == jnp.int8 and scale.shape == (37,)
    dq = quant.dequantize_table(data, scale)
    amax = np.abs(np.asarray(t)).max(axis=1)
    # half-step of the per-row grid, plus exact zeros for the zero row
    assert np.all(np.abs(np.asarray(dq - t)) <= amax[:, None] / 127 * 0.51)
    assert np.all(np.asarray(dq[5]) == 0.0)
    # sign symmetry: -x quantizes to -q exactly (127-level grid)
    dneg, sneg = quant.quantize_table(-t, "int8")
    np.testing.assert_array_equal(np.asarray(dneg), -np.asarray(data))
    np.testing.assert_array_equal(np.asarray(sneg), np.asarray(scale))


def test_fold_scale_canonical_order():
    rng = np.random.default_rng(2)
    scale = jnp.asarray(rng.random(10).astype(np.float32) + 0.1)
    wt = jnp.asarray(rng.random(32).astype(np.float32))
    nb = jnp.asarray(rng.integers(0, 11, 32).astype(np.int32))  # 10 = zero row
    got = quant.fold_scale(wt, scale, nb)
    sz = np.concatenate([np.asarray(scale), [0.0]]).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(wt) * sz[np.asarray(nb)])
    # identity without a scale
    assert quant.fold_scale(wt, None, nb) is wt


def test_table_dtype_validation():
    with pytest.raises(ValueError, match="table_dtype"):
        quant.resolve_table_dtype("float16")
    with pytest.raises(ValueError, match="int8"):
        quant.validate_table_dtype_layout("int8", "padded")
    quant.validate_table_dtype_layout("bfloat16", "padded")  # fine
    with pytest.raises(ValueError, match="int8"):
        ALSConfig(layout="segment", table_dtype="int8")
    with pytest.raises(ValueError, match="table_dtype"):
        ALSConfig(table_dtype="fp8")
    ALSConfig(layout="tiled", table_dtype="int8")  # fine


def test_gather_operand_view():
    t = jnp.asarray(np.random.default_rng(0).standard_normal((9, 4)),
                    dtype=jnp.float32)
    assert quant.gather_operand_view(t, None) is t
    assert quant.gather_operand_view(t, "bfloat16").dtype == jnp.bfloat16
    v = quant.gather_operand_view(t, "int8")
    assert v.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(v - t))) < 0.05


def test_roofline_table_bytes():
    from cfk_tpu.utils.roofline import (
        als_iteration_cost,
        roofline_row,
        table_gather_bytes_per_row,
    )

    assert table_gather_bytes_per_row(128, "float32") == 512
    assert table_gather_bytes_per_row(128, "bfloat16") == 256
    assert table_gather_bytes_per_row(128, "int8") == 132
    # f32 table_dtype is the identity — bf16 STORAGE still gathers 2B cells
    assert table_gather_bytes_per_row(128, "float32", factor_bytes=2) == 256
    # quantization halves the bytes floor but not the row-slot floor
    c_f = als_iteration_cost(10**7, 10**5, 10**4, 128, factor_bytes=4,
                             table_dtype="float32")
    c_b = als_iteration_cost(10**7, 10**5, 10**4, 128, factor_bytes=4,
                             table_dtype="bfloat16")
    assert c_b.gather_bytes == c_f.gather_bytes / 2
    assert c_b.gather_rows == c_f.gather_rows
    row = roofline_row(c_b, 1.0, table_dtype="bfloat16",
                       device_kind="TPU v5 lite")
    assert row["table_dtype"] == "bfloat16"
    assert row["vs_gather_roofline"] > 0 and "roofline" not in row
    # a device without published peaks gets the counts and a note, never
    # another chip's peak
    cpu = roofline_row(c_b, 1.0, device_kind="cpu")
    assert "not measured" in cpu["roofline"] and "mfu" not in cpu
    assert cpu["gather_gb_per_iter"] == row["gather_gb_per_iter"]
    # layout-aware rows: bucketed counts padded cells, sweeps multiply
    c_r = als_iteration_cost(10**7, 10**5, 10**4, 128, gather_rows=3.1e7,
                             sweeps=2)
    assert c_r.gather_rows == pytest.approx(6.2e7)


# ---- tiled layout: default identity + knob/dtype contracts -----------------


def test_tiled_f32_default_bit_identical(tiled_ds):
    from cfk_tpu.models.als import train_als

    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                    layout="tiled")
    base = train_als(tiled_ds, cfg).predict_dense()
    f32 = train_als(
        tiled_ds, dataclasses.replace(cfg, table_dtype="float32")
    ).predict_dense()
    np.testing.assert_array_equal(base, f32)


def test_tiled_int8_gather_knob_bit_exact(tiled_ds):
    """The canonical dequant order: XLA gather and in-kernel gather (its
    emulation twin on CPU) produce bit-identical factors for int8 tables."""
    from cfk_tpu.ops.tiled import tiled_half_step

    from cfk_tpu.models.als import _tiled_device_setup

    mb, ub, _stats, kw = _tiled_device_setup(tiled_ds, weighted=True)
    rng = np.random.default_rng(1)
    fixed = jnp.asarray(rng.standard_normal(
        (tiled_ds.movie_blocks.padded_entities, 8)).astype(np.float32))
    on = tiled_half_step(fixed, ub, kw["u_chunks"], kw["u_entities"], 0.05,
                         solver="cholesky", table_dtype="int8")
    off = tiled_half_step(fixed, ub, kw["u_chunks"], kw["u_entities"], 0.05,
                          solver="cholesky", table_dtype="int8",
                          in_kernel_gather=False)
    np.testing.assert_array_equal(np.asarray(on), np.asarray(off))


def test_quantized_rmse_contract_planted(tiled_ds):
    """bf16 table RMSE ≤ 1.01× f32 on the planted fixture; the int8 ratio
    is the documented (loose) bound."""
    from cfk_tpu.eval.metrics import mse_rmse_from_blocks
    from cfk_tpu.models.als import train_als

    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=4, seed=0,
                    layout="tiled")
    rmse = {}
    for td in ("float32", "bfloat16", "int8"):
        m = train_als(tiled_ds, dataclasses.replace(cfg, table_dtype=td))
        _, rmse[td] = mse_rmse_from_blocks(m.predict_dense(), tiled_ds)
    assert rmse["bfloat16"] <= rmse["float32"] * 1.01, rmse
    assert rmse["int8"] <= rmse["float32"] * 1.10, rmse


@pytest.mark.slow
@pytest.mark.parametrize("mode_kw", [
    dict(),  # accum (default gates at this shape)
    dict(accum_max_entities=0),  # stream
    dict(accum_max_entities=0, dense_stream=True),  # dstream
])
@pytest.mark.parametrize("td", ["bfloat16", "int8"])
def test_tiled_all_modes_knob_bit_exact(mode_kw, td):
    """Exhaustive (slow): every tiled mode × table dtype keeps the gather
    knob and the overlap knob bit-exact."""
    from cfk_tpu.models.als import _tiled_device_setup
    from cfk_tpu.ops.tiled import tiled_half_step

    ds = Dataset.from_coo(_coo(), layout="tiled", chunk_elems=1024,
                          tile_rows=16, **mode_kw)
    mb, ub, _stats, kw = _tiled_device_setup(ds, weighted=True)
    rng = np.random.default_rng(1)
    fixed = jnp.asarray(rng.standard_normal(
        (ds.movie_blocks.padded_entities, 8)).astype(np.float32))
    ref = tiled_half_step(fixed, ub, kw["u_chunks"], kw["u_entities"], 0.05,
                          solver="cholesky", table_dtype=td)
    for knobs in (dict(in_kernel_gather=False), dict(overlap=False)):
        got = tiled_half_step(fixed, ub, kw["u_chunks"], kw["u_entities"],
                              0.05, solver="cholesky", table_dtype=td,
                              **knobs)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_ials_tiled_quantized_gram_consistency(tiled_ds):
    """iALS under a quantized table computes YᵀY from the SAME dequantized
    rows the kernels gather — the shared implicit_reg term and the
    per-entity Grams must agree on what the fixed factors are."""
    from cfk_tpu.models.als import _tiled_device_setup
    from cfk_tpu.ops.solve import global_gram
    from cfk_tpu.ops.tiled import ials_tiled_half_step

    mb, ub, _stats, kw = _tiled_device_setup(tiled_ds, weighted=True)
    rng = np.random.default_rng(2)
    fixed = jnp.asarray(rng.standard_normal(
        (tiled_ds.movie_blocks.padded_entities, 8)).astype(np.float32))
    auto = ials_tiled_half_step(
        fixed, ub, kw["u_chunks"], kw["u_entities"], 0.1, 2.0,
        solver="cholesky", table_dtype="int8",
    )
    explicit = ials_tiled_half_step(
        fixed, ub, kw["u_chunks"], kw["u_entities"], 0.1, 2.0,
        solver="cholesky", table_dtype="int8",
        gram=global_gram(quant.gather_operand_view(fixed, "int8")),
    )
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(explicit))


# ---- bucketed kernel port ---------------------------------------------------


# The bucketed port on the CPU runs one of two programs per width class.
# gather=fused (the default) takes the gather wrappers' XLA twin, whose
# one-tile-per-entity einsum is the legacy whole-rectangle einsum.
# gather=xla with the split epilogue feeds the materialized stream to
# ``gram_tiles_pallas``, whose CPU route is the kernel BODY under the
# Pallas interpreter (ops/pallas/interpret.py): the same float32 math
# summed in another order.  Measured on jax 0.9.0 at this fixture, one
# half-step, factors up to 2.1 in magnitude: 7.2e-7 absolute (explicit,
# cholesky), 8.3e-7 (explicit, pallas solver), 1.2e-7 (implicit float32),
# 1.0e-7 (implicit int8).  The bound is 6 x the largest.
_BODY_VS_TWIN_ATOL = 5e-6
# On a bfloat16 table the body also feeds the b coefficient to the MXU in
# the stream dtype where the twin keeps it float32 (ROADMAP D8): 2^-9
# relative on b, 7.1e-4 absolute measured on factors up to 0.51.  7 x.
_BODY_VS_TWIN_BF16_ATOL = 5e-3


def _bucketed_half(bucketed_ds, seed):
    from cfk_tpu.models.als import _bucketed_device_setup

    mblocks, _u, _s, kw = _bucketed_device_setup(bucketed_ds)
    rng = np.random.default_rng(seed)
    fixed = jnp.asarray(rng.standard_normal(
        (bucketed_ds.user_blocks.padded_entities, 8)).astype(np.float32))
    return fixed, mblocks, kw["m_chunks"], kw["m_entities"]


def test_bucketed_port_f32_default_knobs_are_the_pinned_program(bucketed_ds):
    """The f32 explicit port with its knobs left free is the program that
    pinning what they resolve to gives (gather fused; the cholesky solver
    always splits the epilogue): one program twice, so bit-identical."""
    from cfk_tpu.ops.solve import als_half_step_bucketed

    args = _bucketed_half(bucketed_ds, 3)
    free = als_half_step_bucketed(*args, 0.05, solver="cholesky")
    pinned = als_half_step_bucketed(
        *args, 0.05, solver="cholesky", in_kernel_gather=True,
        fused_epilogue=False,
    )
    np.testing.assert_array_equal(np.asarray(free), np.asarray(pinned))


def test_bucketed_port_f32_close_to_knobs_off(bucketed_ds, monkeypatch):
    """The port against the knobs-off route, and against the legacy
    whole-rectangle schedule refused width classes keep: different
    programs, equal to float32 round-off (``_BODY_VS_TWIN_ATOL``)."""
    from cfk_tpu.ops import bucketed as bport
    from cfk_tpu.ops.solve import als_half_step_bucketed

    args = _bucketed_half(bucketed_ds, 3)
    port = np.asarray(als_half_step_bucketed(*args, 0.05, solver="cholesky"))
    off = als_half_step_bucketed(
        *args, 0.05, solver="cholesky", in_kernel_gather=False,
        fused_epilogue=False,
    )
    np.testing.assert_allclose(np.asarray(off), port, rtol=0,
                               atol=_BODY_VS_TWIN_ATOL)
    # Every width class refused: the legacy gather + einsum + solve batch
    # (equal to the twin to the bit here, but another program).
    monkeypatch.setattr(bport, "bucket_port_supported", lambda *a: False)
    legacy = als_half_step_bucketed(*args, 0.05, solver="cholesky")
    np.testing.assert_allclose(np.asarray(legacy), port, rtol=0,
                               atol=_BODY_VS_TWIN_ATOL)


def test_bucketed_port_knob_combos_bit_exact(bucketed_ds):
    """gather {fused, xla} × epilogue {fused, split} all bit-exact under
    the pallas solver (fast representative: one combo pair per axis; the
    full cross product is the slow sweep below)."""
    from cfk_tpu.models.als import _bucketed_device_setup
    from cfk_tpu.ops.solve import als_half_step_bucketed

    mblocks, _u, _s, kw = _bucketed_device_setup(bucketed_ds)
    rng = np.random.default_rng(3)
    fixed = jnp.asarray(rng.standard_normal(
        (bucketed_ds.user_blocks.padded_entities, 8)).astype(np.float32))
    ref = als_half_step_bucketed(
        fixed, mblocks, kw["m_chunks"], kw["m_entities"], 0.05,
        solver="pallas", in_kernel_gather=True, fused_epilogue=True,
    )
    for knobs in (dict(in_kernel_gather=False, fused_epilogue=True),
                  dict(in_kernel_gather=True, fused_epilogue=False)):
        got = als_half_step_bucketed(
            fixed, mblocks, kw["m_chunks"], kw["m_entities"], 0.05,
            solver="pallas", **knobs,
        )
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.parametrize("td", [None, "bfloat16", "int8"])
def test_bucketed_ials_port_default_gather_is_the_pinned_program(
    bucketed_ds, td
):
    """Implicit port (the tiled iALS sqrt reparameterization at bucket
    granularity): the gather knob left free is the program that pinning it
    on gives, at every table dtype."""
    from cfk_tpu.ops.solve import ials_half_step_bucketed

    args = _bucketed_half(bucketed_ds, 4)
    free = ials_half_step_bucketed(*args, 0.1, 2.0, solver="cholesky",
                                   table_dtype=td)
    pinned = ials_half_step_bucketed(*args, 0.1, 2.0, solver="cholesky",
                                     table_dtype=td, in_kernel_gather=True)
    np.testing.assert_array_equal(np.asarray(free), np.asarray(pinned))


def test_bucketed_ials_port_gather_knob_and_quant_close(bucketed_ds):
    """Implicit port across the gather knob: twin against kernel body, so
    float32 round-off (and the bfloat16 b coefficient: see the bounds
    above); quantized tables stay close to the f32 port."""
    from cfk_tpu.ops.solve import ials_half_step_bucketed

    args = _bucketed_half(bucketed_ds, 4)
    ref = np.asarray(ials_half_step_bucketed(*args, 0.1, 2.0,
                                             solver="cholesky"))
    for td, atol in ((None, _BODY_VS_TWIN_ATOL),
                     ("bfloat16", _BODY_VS_TWIN_BF16_ATOL),
                     ("int8", _BODY_VS_TWIN_ATOL)):
        q = np.asarray(ials_half_step_bucketed(
            *args, 0.1, 2.0, solver="cholesky", table_dtype=td,
        ))
        qx = ials_half_step_bucketed(
            *args, 0.1, 2.0, solver="cholesky", table_dtype=td,
            in_kernel_gather=False,
        )
        np.testing.assert_allclose(np.asarray(qx), q, rtol=0, atol=atol)
        assert float(np.max(np.abs(q - ref))) < 0.5


@pytest.mark.slow
def test_bucketed_port_full_cross_product():
    """Exhaustive (slow): all four knob combos × explicit/implicit on a
    power-law corpus (many width classes, incl. chunked and narrow
    (< 16) legacy-fallback buckets).  With the gather fused the epilogue
    knob is bit-exact (twin against twin); gather=xla is another program
    under either epilogue (the materialized stream through the fused
    kernel's twin, or through the split kernel's body) and is held to
    ``_BODY_VS_TWIN_ATOL`` (9.5e-7 and 1.6e-6 measured here)."""
    from cfk_tpu.models.als import _bucketed_device_setup
    from cfk_tpu.ops.solve import als_half_step_bucketed, ials_half_step_bucketed

    rng = np.random.default_rng(5)
    nm, nu, nnz = 100, 160, 4000
    mp = (1.0 / np.arange(1, nm + 1)) ** 1.2
    up = (1.0 / np.arange(1, nu + 1)) ** 1.2
    coo = RatingsCOO(
        movie_raw=(rng.choice(nm, nnz, p=mp / mp.sum()) + 1).astype(np.int64),
        user_raw=(rng.choice(nu, nnz, p=up / up.sum()) + 1).astype(np.int64),
        rating=rng.integers(1, 6, nnz).astype(np.float32),
    )
    ds = Dataset.from_coo(coo, layout="bucketed", chunk_elems=2048)
    mblocks, _u, _s, kw = _bucketed_device_setup(ds)
    fixed = jnp.asarray(rng.standard_normal(
        (ds.user_blocks.padded_entities, 8)).astype(np.float32))
    for fn, args in ((als_half_step_bucketed, (0.05,)),
                     (ials_half_step_bucketed, (0.1, 2.0))):
        outs = [
            np.asarray(fn(
                fixed, mblocks, kw["m_chunks"], kw["m_entities"], *args,
                solver="pallas", in_kernel_gather=g, fused_epilogue=f,
            ))
            for g in (True, False) for f in (True, False)
        ]
        np.testing.assert_array_equal(outs[0], outs[1])
        for o in outs[2:]:
            np.testing.assert_allclose(o, outs[0], rtol=0,
                                       atol=_BODY_VS_TWIN_ATOL)


# ---- iALS++ / ALS++ subspace port ------------------------------------------


def _rect(seed=0, F=50, E=40, P=12, k=16):
    rng = np.random.default_rng(seed)
    fixed = jnp.asarray(rng.standard_normal((F, k)).astype(np.float32))
    nb = jnp.asarray(rng.integers(0, F, (E, P)).astype(np.int32))
    mask = jnp.asarray((rng.random((E, P)) < 0.7).astype(np.float32))
    rt = jnp.asarray(rng.integers(1, 6, (E, P)).astype(np.float32)) * mask
    x0 = jnp.asarray(rng.standard_normal((E, k)).astype(np.float32))
    return fixed, nb, rt, mask, x0


@pytest.mark.parametrize("td", ["float32", "bfloat16", "int8"])
def test_ialspp_block_k_anchor_under_knobs(td):
    """The exactness anchor (block_size = k ⇒ one sweep = the full solve)
    holds under the in-kernel gather, the fused b×b epilogue, AND every
    table dtype — the full solve is evaluated on the SAME dequantized
    table the sweep gathers, which is also what pins the score-stream
    consistency bugfix (scores from the f32 masters would break this
    anchor for int8)."""
    from cfk_tpu.ops.solve import ials_half_step
    from cfk_tpu.ops.subspace import ials_pp_half_step

    fixed, nb, rt, mask, x0 = _rect()
    # The sweep gathers the quantized rows and computes in f32, so the
    # equivalent full solve runs f32 arithmetic on the dequantized VALUES
    # (ials_half_step on a raw bf16 table would switch to bf16 compute —
    # a different arithmetic, not the anchor).
    view = quant.gather_operand_view(fixed, td).astype(jnp.float32)
    full = ials_half_step(view, nb, rt, mask, 0.1, 2.0)
    pp = ials_pp_half_step(
        fixed, x0, nb, rt, mask, 0.1, 2.0, block_size=x0.shape[1], sweeps=1,
        table_dtype=td, in_kernel_gather=True,
    )
    np.testing.assert_allclose(np.asarray(pp), np.asarray(full), atol=2e-4)
    # gather knob bit-exact at every dtype
    pp_x = ials_pp_half_step(
        fixed, x0, nb, rt, mask, 0.1, 2.0, block_size=x0.shape[1], sweeps=1,
        table_dtype=td, in_kernel_gather=False,
    )
    np.testing.assert_array_equal(np.asarray(pp), np.asarray(pp_x))


def test_alspp_anchor_and_fused_b_epilogue():
    from cfk_tpu.ops.solve import als_half_step
    from cfk_tpu.ops.subspace import als_pp_half_step

    fixed, nb, rt, mask, x0 = _rect()
    cnt = mask.sum(axis=1).astype(jnp.int32)
    full = als_half_step(fixed, nb, rt, mask, cnt, 0.05)
    pp = als_pp_half_step(
        fixed, x0, nb, rt, mask, cnt, 0.05, block_size=x0.shape[1], sweeps=1,
    )
    np.testing.assert_allclose(np.asarray(pp), np.asarray(full), atol=2e-4)
    # the b×b fused epilogue (pallas lanes at block rank) stays within
    # elimination-algorithm tolerance of the split dispatch
    pp_f = als_pp_half_step(
        fixed, x0, nb, rt, mask, cnt, 0.05, block_size=4, sweeps=1,
        solver="pallas", fused_epilogue=True,
    )
    pp_s = als_pp_half_step(
        fixed, x0, nb, rt, mask, cnt, 0.05, block_size=4, sweeps=1,
        solver="pallas", fused_epilogue=False,
    )
    np.testing.assert_allclose(np.asarray(pp_f), np.asarray(pp_s), atol=1e-4)


def test_ialspp_bucketed_trained_quant_close(bucketed_ds):
    """End-to-end: iALS++ on the bucketed layout trains to near-identical
    factors under a bf16 table (the headline ialspp_ml25m stack)."""
    from cfk_tpu.models.ials import IALSConfig, train_ials

    cfg = IALSConfig(rank=8, lam=0.1, alpha=4.0, num_iterations=2, seed=0,
                     layout="bucketed", algorithm="ials++", block_size=4,
                     sweeps=1)
    base = train_ials(bucketed_ds, cfg).predict_dense()
    f32 = train_ials(
        bucketed_ds, dataclasses.replace(cfg, table_dtype="float32")
    ).predict_dense()
    np.testing.assert_array_equal(base, f32)
    bf = train_ials(
        bucketed_ds, dataclasses.replace(cfg, table_dtype="bfloat16")
    ).predict_dense()
    assert float(np.max(np.abs(bf - base))) < 0.2


# ---- SPMD ------------------------------------------------------------------


def test_tiled_ring_int8_payload_matches_single_device():
    """The tiled ring rotates the (int8 codes, f32 scales) pair and folds
    each block's scales locally — factors match the single-device int8
    run (fast representative: 2 shards; 4-shard + bf16 are slow)."""
    from cfk_tpu.models.als import train_als
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import train_als_sharded

    coo = _coo(seed=7, nm=40, nu=64, nnz=1200)
    ds1 = Dataset.from_coo(coo, layout="tiled", chunk_elems=512,
                           tile_rows=16)
    ds2 = Dataset.from_coo(coo, num_shards=2, layout="tiled",
                           chunk_elems=512, tile_rows=16, ring=True)
    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=1,
                    layout="tiled", table_dtype="int8")
    single = train_als(ds1, cfg).predict_dense()
    sharded = train_als_sharded(
        ds2, dataclasses.replace(cfg, num_shards=2, exchange="ring"),
        make_mesh(2),
    ).predict_dense()
    np.testing.assert_allclose(sharded, single, atol=5e-3, rtol=5e-3)


def test_int8_quantize_corrupt_row_poisons_scale():
    """A NaN/Inf row must surface in the per-row SCALE: the int8 codes are
    finite by construction, so the scale is the only payload leaf an
    ``isfinite`` probe (the tiled ring's in-carry sentinel) can see.  The
    `amax > 0` predicate would launder NaN into finite codes × scale 1.0
    — pinned here so the where-condition never regresses."""
    rng = np.random.default_rng(3)
    t = rng.standard_normal((9, 8)).astype(np.float32)
    t[2, 5] = np.nan
    t[6, 0] = np.inf
    t[4] = 0.0  # all-zero row keeps its exact-zero dequant contract
    data, scale = quant.quantize_table(jnp.asarray(t), "int8")
    s = np.asarray(scale)
    assert np.isnan(s[2])
    assert np.isinf(s[6])
    assert s[4] == 1.0
    finite = [0, 1, 3, 5, 7, 8]
    np.testing.assert_array_equal(
        s[finite], np.abs(t[finite]).max(axis=1) / 127.0
    )
    assert np.all(np.isfinite(np.asarray(data, np.float32)))


def test_tiled_ring_int8_sentinel_detects_corruption(tmp_path):
    """NaN factor rows under table_dtype='int8' must TRIP the health
    sentinel and recover: quantize_table poisons the corrupt rows' scales
    and the tiled ring's carry probe checks the scales leaf of the
    rotating (codes, scales) payload.  Before the fix the NaN quantized
    to finite codes × scale 1.0 and the run silently produced garbage
    with zero health trips."""
    import warnings

    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import train_als_sharded
    from cfk_tpu.resilience.faults import FactorCorruption, FaultInjector
    from cfk_tpu.transport.checkpoint import CheckpointManager
    from cfk_tpu.utils.metrics import Metrics

    coo = _coo(seed=11, nm=40, nu=64, nnz=1200)
    ds = Dataset.from_coo(coo, num_shards=2, layout="tiled",
                          chunk_elems=512, tile_rows=16, ring=True)
    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=3, seed=1,
                    layout="tiled", table_dtype="int8", num_shards=2,
                    exchange="ring", health_check_every=1)
    mesh = make_mesh(2)
    base = train_als_sharded(ds, cfg, mesh).host_factors()

    inj = FaultInjector(
        FactorCorruption(iteration=1, side="u", value=float("nan"))
    )
    metrics = Metrics()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rec = train_als_sharded(
            ds, cfg, mesh,
            checkpoint_manager=CheckpointManager(str(tmp_path)),
            metrics=metrics, fault_injector=inj,
        ).host_factors()
    assert metrics.counters["health_trips"] >= 1
    np.testing.assert_allclose(rec[0], base[0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(rec[1], base[1], atol=1e-5, rtol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("shards", [2, 4])
@pytest.mark.parametrize("td", ["bfloat16", "int8"])
def test_bucketed_sharded_quant_matches_single(shards, td):
    """Exhaustive (slow): quantized all_gather payloads at 2/4 shards on
    the bucketed iALS++ stack reproduce the single-device run."""
    from cfk_tpu.models.ials import IALSConfig, train_ials, train_ials_sharded
    from cfk_tpu.parallel.mesh import make_mesh

    coo = _coo(seed=8, nm=48, nu=80, nnz=1500)
    ds1 = Dataset.from_coo(coo, layout="bucketed")
    dsn = Dataset.from_coo(coo, num_shards=shards, layout="bucketed")
    cfg = IALSConfig(rank=8, lam=0.1, alpha=4.0, num_iterations=2, seed=0,
                     layout="bucketed", algorithm="ials++", block_size=4,
                     sweeps=1, table_dtype=td)
    single = train_ials(ds1, cfg).predict_dense()
    sharded = train_ials_sharded(
        dsn, dataclasses.replace(cfg, num_shards=shards), make_mesh(shards)
    ).predict_dense()
    np.testing.assert_allclose(sharded, single, atol=5e-3, rtol=5e-3)
