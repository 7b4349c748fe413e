"""Kernel body vs XLA twin, on the CPU.

Plain interpret mode (``interpret=True``, what a CPU backend resolves to)
routes every Gram kernel but the first (``gram_tiles_pallas``, whose body
has always run interpreted outside shard_map) to its XLA twin.  THIS file is
where the other bodies execute off the chip: ``interpret="kernel"`` runs
each ``pallas_call`` — row DMAs, semaphores, VMEM scratch, the owner-run
walk, the in-VMEM ridge+solve epilogue — under the Pallas interpreter, and
the result is held to the twin's.

Kernel ≠ twin bit for bit, by construction, so each comparison carries a
tolerance with its reason:

- float32 streams: same products, other summation order (the body walks
  tiles accumulating per owner run, the twin segment-sums per-tile Grams; the
  elimination is the same code but sees those ulp-different sums) — float32
  round-off, ``1e-5`` of the operand scale.
- bfloat16 / int8 streams: A as above (bf16 products are exact in float32).
  The b side differs MORE: the body feeds the b coefficient to the MXU in
  the stream dtype (``r_i.astype(g_i.dtype)``: 2⁻⁹ relative rounding per
  term) where the twin keeps it float32 — ``1e-2`` of the operand scale for b
  and for the solved rows.

Rows of segments that own no tile are unspecified in the bodies (never
written) and zero in the twins: only owned rows are compared.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from cfk_tpu.ops.pallas import gram_kernel as gk  # noqa: E402

T, NT, K, SEGS, F = 16, 12, 8, 6, 40
C = NT * T
LAM = 0.05


def _tol(dtype, side):
    if dtype == "float32" or side == "a":
        return 1e-5
    return 1e-2


def _close(got, want, tol):
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=tol,
        atol=tol * max(float(np.abs(want).max()), 1e-30))


def _table(rng, dtype):
    """(table, per-row scale or None) — int8 codes carry their dequant scale
    separately, to be folded into the weight stream (``quant.fold_scale``)."""
    from cfk_tpu.ops.quant import quantize_table

    tbl = jnp.asarray(rng.standard_normal((F, K)).astype(np.float32))
    return quantize_table(tbl, dtype)


def _tile_problem(dtype, seed=0):
    rng = np.random.default_rng(seed)
    table, scale = _table(rng, dtype)
    nb = rng.integers(0, F + 1, size=C).astype(np.int32)  # F = zero row
    wt = (nb < F).astype(np.float32)
    if scale is not None:
        wt = wt * np.asarray(scale)[np.minimum(nb, F - 1)]
    rt = rng.random(C).astype(np.float32) * (nb < F)
    seg = np.sort(rng.integers(0, SEGS - 1, size=NT)).astype(np.int32)
    reg = rng.integers(1, 9, size=SEGS).astype(np.float32)
    carry = (jnp.asarray(rng.standard_normal((K, K)).astype(np.float32)),
             jnp.asarray(rng.standard_normal(K).astype(np.float32)),
             jnp.float32(1.0))
    return dict(table=table, nb=jnp.asarray(nb), wt=jnp.asarray(wt),
                rt=jnp.asarray(rt), seg=jnp.asarray(seg),
                reg=jnp.asarray(reg), lseg=jnp.int32(seg[-1]), carry=carry,
                owned=np.unique(seg))


def _both(fn):
    """(body, twin) outputs as numpy trees."""
    return tuple(jax.tree.map(np.asarray, fn(mode))
                 for mode in ("kernel", True))


def _check_gram(body, twin, owned, dtype):
    _close(body[0][owned], twin[0][owned], _tol(dtype, "a"))
    _close(body[1][owned], twin[1][owned], _tol(dtype, "b"))


def _check_solve(body, twin, owned, dtype):
    _close(body[0][owned], twin[0][owned], _tol(dtype, "x"))
    _close(body[1], twin[1], _tol(dtype, "a"))  # raw carry A row
    _close(body[2], twin[2], _tol(dtype, "b"))  # raw carry b row


def _stream(p, dtype):
    """The materialized gathered stream the non-gather kernels consume."""
    from cfk_tpu.compat import emulate_in_kernel_gather
    from cfk_tpu.ops.solve import _gram_compute_dtype

    ct, _ = _gram_compute_dtype(p["table"])
    return emulate_in_kernel_gather(p["table"], p["nb"], p["wt"], ct)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gram_tiles_body(dtype):
    p = _tile_problem(dtype)
    g = _stream(p, dtype)
    body = jax.tree.map(np.asarray, gk.gram_tiles_pallas(
        g, p["rt"], p["seg"], num_segments=SEGS, tile_rows=T,
        group_tiles=4, interpret="kernel", carry=p["carry"]))
    # this wrapper takes its twin only under shard_map: call it directly
    twin = jax.tree.map(np.asarray, gk._emulate_gram_tiles(
        g, p["rt"], p["seg"], num_segments=SEGS, tile_rows=T,
        carry=p["carry"]))
    _check_gram(body, twin, p["owned"], dtype)


@pytest.mark.parametrize("algo", ["lu", "gj"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gram_solve_tiles_body(dtype, algo):
    p = _tile_problem(dtype, seed=1)
    body, twin = _both(lambda m: gk.gram_solve_tiles_pallas(
        _stream(p, dtype), p["rt"], p["seg"], p["reg"], p["lseg"],
        num_segments=SEGS, tile_rows=T, group_tiles=4, reg_mode="diag",
        lam=LAM, interpret=m, carry=p["carry"], algo=algo))
    _check_solve(body, twin, p["owned"], dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_gram_tiles_gather_body(dtype):
    p = _tile_problem(dtype, seed=2)
    body, twin = _both(lambda m: gk.gram_tiles_gather_pallas(
        p["table"], p["nb"], p["wt"], p["rt"], p["seg"], num_segments=SEGS,
        tile_rows=T, group_tiles=4, interpret=m, carry=p["carry"]))
    _check_gram(body, twin, p["owned"], dtype)


@pytest.mark.parametrize("reg_mode", ["diag", "matrix"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gram_solve_tiles_gather_body(dtype, reg_mode):
    p = _tile_problem(dtype, seed=3)
    reg = p["reg"]
    if reg_mode == "matrix":  # iALS: one shared SPD term, λ rides inside it
        x = np.random.default_rng(3).standard_normal((K, K)).astype(np.float32)
        reg = jnp.asarray(x @ x.T + np.eye(K, dtype=np.float32))
    body, twin = _both(lambda m: gk.gram_solve_tiles_gather_pallas(
        p["table"], p["nb"], p["wt"], p["rt"], p["seg"], reg, p["lseg"],
        num_segments=SEGS, tile_rows=T, group_tiles=4, reg_mode=reg_mode,
        lam=LAM if reg_mode == "diag" else 0.0, interpret=m,
        carry=p["carry"]))
    _check_solve(body, twin, p["owned"], dtype)


@pytest.mark.parametrize("dtype,weighted", [
    ("float32", False), ("float32", True), ("bfloat16", False),
    ("bfloat16", True), ("int8", True),  # int8 rows need the scale in wt
])
def test_gather_rows_body(dtype, weighted):
    """Pure data movement + one multiply: the body's rows ARE the twin's."""
    p = _tile_problem(dtype, seed=4)
    # wt=None callers annihilate padding downstream: keep indices in range
    nb = p["nb"] if weighted else jnp.minimum(p["nb"], F - 1)
    body, twin = _both(lambda m: gk.gather_rows_pallas(
        p["table"], nb, p["wt"] if weighted else None, block_rows=64,
        interpret=m))
    np.testing.assert_array_equal(body, twin)


# -- dense-stream kernels: real metadata from the production builder ---------

@pytest.fixture(scope="module")
def dense():
    """One chunk of genuine dense-stream blocks (16-aligned windows, LPT
    entity order, trash slots) + a small factor table."""
    from cfk_tpu.data.blocks import build_tiled_blocks, index_entities
    from cfk_tpu.data.synthetic import synthetic_netflix_coo

    coo = synthetic_netflix_coo(400, 60, 5_000, seed=4)
    umap, u_dense = index_entities(coo.user_raw)
    mmap, m_dense = index_entities(coo.movie_raw)
    ub = build_tiled_blocks(
        u_dense, m_dense, coo.rating, umap.num_entities, mmap.num_entities,
        accum_max_entities=0, chunk_elems=2_048, dense_stream=True,
        tile_rows=16,
    )
    assert ub.mode == "dstream" and ub.num_chunks >= 2
    return ub, mmap.num_entities


def _dense_problem(dense, dtype, chunk=1):
    from cfk_tpu.ops.quant import quantize_table

    ub, f_rows = dense
    nc, cap, e_c, t, nt, ng, bg = ub.statics
    rng = np.random.default_rng(7)
    table, scale = quantize_table(
        jnp.asarray(rng.standard_normal((f_rows, K)).astype(np.float32) * .3),
        dtype)
    nb = ub.neighbor_idx.reshape(nc, cap)[chunk]
    meta = ub.tile_meta.reshape(nc, ng + 4 * nt)[chunk]
    seg = meta[ng + 3 * nt:]
    # the weighted stream: √aw for iALS, the folded dequant scale for int8
    wt = np.sqrt(rng.random(cap).astype(np.float32) + 0.1)
    if scale is not None:
        wt = wt * np.concatenate([np.asarray(scale), [0.0]])[nb]
    return dict(
        table=table, nb=jnp.asarray(nb), wt=jnp.asarray(wt.astype(np.float32)),
        rt=jnp.asarray(ub.rating.reshape(nc, nt * t)[chunk]),
        meta=jnp.asarray(meta),
        reg=jnp.asarray(rng.integers(1, 9, size=e_c + 1).astype(np.float32)),
        lseg=jnp.int32(ub.last_seg.reshape(nc)[chunk]),
        carry=(jnp.asarray(rng.standard_normal((K, K)).astype(np.float32)),
               jnp.asarray(rng.standard_normal(K).astype(np.float32)),
               jnp.float32(ub.carry_in.reshape(nc)[chunk])),
        owned=np.unique(seg[seg < e_c]),
        kw=dict(num_segments=e_c + 1, tile_rows=t, num_tiles=nt,
                num_groups=ng, block_rows=bg),
    )


def _dense_stream(p, weighted):
    from cfk_tpu.compat import emulate_in_kernel_gather
    from cfk_tpu.ops.solve import _gram_compute_dtype

    ct, _ = _gram_compute_dtype(p["table"])
    return emulate_in_kernel_gather(
        p["table"], p["nb"], p["wt"] if weighted else None, ct)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gram_tiles_dense_body(dense, dtype):
    p = _dense_problem(dense, dtype)
    body, twin = _both(lambda m: gk.gram_tiles_dense_pallas(
        _dense_stream(p, False), p["rt"], p["meta"], interpret=m,
        carry=p["carry"], **p["kw"]))
    _check_gram(body, twin, p["owned"], dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gram_solve_tiles_dense_body(dense, dtype):
    p = _dense_problem(dense, dtype)
    body, twin = _both(lambda m: gk.gram_solve_tiles_dense_pallas(
        _dense_stream(p, False), p["rt"], p["meta"], p["reg"], p["lseg"],
        reg_mode="diag", lam=LAM, interpret=m, carry=p["carry"], **p["kw"]))
    _check_solve(body, twin, p["owned"], dtype)


@pytest.mark.parametrize("dtype,weighted", [
    ("float32", False), ("bfloat16", False), ("float32", True),
    ("int8", True),
])
def test_gram_tiles_dense_gather_body(dense, dtype, weighted):
    p = _dense_problem(dense, dtype)
    body, twin = _both(lambda m: gk.gram_tiles_dense_gather_pallas(
        p["table"], p["nb"], p["wt"] if weighted else None, p["rt"],
        p["meta"], interpret=m, carry=p["carry"], **p["kw"]))
    _check_gram(body, twin, p["owned"], dtype)


@pytest.mark.parametrize("dtype,weighted", [
    ("float32", False), ("bfloat16", True),
])
def test_gram_solve_tiles_dense_gather_body(dense, dtype, weighted):
    p = _dense_problem(dense, dtype)
    body, twin = _both(lambda m: gk.gram_solve_tiles_dense_gather_pallas(
        p["table"], p["nb"], p["wt"] if weighted else None, p["rt"],
        p["meta"], p["reg"], p["lseg"], reg_mode="diag", lam=LAM,
        interpret=m, carry=p["carry"], **p["kw"]))
    _check_solve(body, twin, p["owned"], dtype)
