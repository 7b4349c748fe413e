"""Compiled-on-real-TPU pallas kernel correctness (VERDICT r1 item #8).

Interpret mode (the CPU tests) accepts programs Mosaic rejects and its
numerics differ from the compiled kernel, so the kernels are also verified
compiled on hardware.  Every test skips unless the default backend is a TPU:

    CFK_TPU_TESTS=1 python -m pytest tests/test_pallas_tpu.py -q

(tests/conftest.py forces the CPU platform unless CFK_TPU_TESTS=1.)  The
backend is asked inside a fixture, after collection — never while the module
is imported, which would load libtpu in every pytest-xdist worker.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


@pytest.fixture(autouse=True)
def _needs_tpu():
    if jax.default_backend() != "tpu":
        pytest.skip("needs a real TPU backend (run with CFK_TPU_TESTS=1)")


def _spd_batch(rng, e, k, dtype=np.float32):
    x = rng.standard_normal((e, k, max(k // 8, 2))).astype(dtype)
    a = np.einsum("ekr,elr->ekl", x, x) + 3.0 * np.eye(k, dtype=dtype)
    b = rng.standard_normal((e, k)).astype(dtype)
    return a, b


# k = 5 (reference parity rank), 32, and 64 including a non-multiple-of-128
# batch so the padded-lane edge (identity-padded systems) is exercised.
@pytest.mark.parametrize("k,e", [(5, 77), (32, 300), (64, 257)])
def test_gauss_solve_compiled_matches_cholesky(k, e):
    from cfk_tpu.ops.solve import batched_spd_solve
    from cfk_tpu.ops.pallas import gauss_solve_pallas

    rng = np.random.default_rng(k)
    a, b = _spd_batch(rng, e, k)
    want = np.asarray(batched_spd_solve(jnp.asarray(a), jnp.asarray(b)))
    got = np.asarray(
        gauss_solve_pallas(
            jnp.asarray(np.transpose(a, (1, 2, 0))), jnp.asarray(b.T),
            interpret=False,
        )
    ).T
    resid = np.einsum("ekl,el->ek", a, got) - b
    assert np.abs(resid).max() < 1e-3, "kernel solution does not satisfy Ax=b"
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("k,e", [(128, 256), (128, 8), (64, 200), (8, 300)])
def test_cholesky_lanes_compiled_matches_float64(k, e):
    """What ``batched_spd_solve`` runs here (float32, k % 8 == 0, k <= 128):
    the lane-batched Cholesky, compiled, against numpy's float64 solve of
    ALS-WR systems as the fold-in makes them (an all-padding system and one
    at the ridge's floor among them), and no further from it than XLA's
    own calls on the same systems."""
    from cfk_tpu.ops.solve import batched_spd_solve, spd_solve_route
    from tests.test_pallas_solve import (_normal_equations,
                                         _xla_cholesky_solve)

    rng = np.random.default_rng(1000 * k + e)
    a, b = _normal_equations(rng, e, k)
    assert spd_solve_route(a, b) == "lanes"
    got = np.asarray(batched_spd_solve(jnp.asarray(a), jnp.asarray(b)))
    xla = np.asarray(_xla_cholesky_solve(jnp.asarray(a), jnp.asarray(b)))
    want = np.linalg.solve(a.astype(np.float64),
                           b.astype(np.float64)[..., None])[..., 0]
    err = lambda x: (np.abs(x - want) / np.maximum(np.abs(want), 1.0)).max()
    assert np.all(got[0] == 0.0)
    assert err(got) < 1e-5 and err(got) < 4 * err(xla) + 1e-6, (
        err(got), err(xla))


@pytest.mark.parametrize("k", [96, 128])
def test_blocked_solve_compiled_matches_cholesky(k):
    from cfk_tpu.ops.solve import batched_spd_solve, dispatch_spd_solve

    rng = np.random.default_rng(k)
    a, b = _spd_batch(rng, 200, k)
    want = np.asarray(batched_spd_solve(jnp.asarray(a), jnp.asarray(b)))
    got = np.asarray(dispatch_spd_solve(jnp.asarray(a), jnp.asarray(b), "pallas"))
    np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("unit_weights", [False, True])
def test_gram_tiles_kernel_compiled(unit_weights):
    """The fused grouped-Gram kernel, compiled: must match the XLA path.

    Covers both weight modes through the ONE stream: unit (explicit ALS)
    and the sqrt-reparameterized weighted form (iALS streams g = √w·f
    with rt rescaled by 1/√w; the reference applies raw weights)."""
    from cfk_tpu.ops.pallas.gram_kernel import gram_tiles_pallas

    rng = np.random.default_rng(0)
    t, nt, k, segs = 64, 64, 32, 17
    g = rng.standard_normal((nt * t, k)).astype(np.float32)
    wt = (
        np.ones(nt * t, np.float32) if unit_weights
        else rng.random(nt * t).astype(np.float32)
    )
    rt = rng.random(nt * t).astype(np.float32)
    seg = np.sort(rng.integers(0, segs - 1, size=nt)).astype(np.int32)
    gs = g if unit_weights else g * np.sqrt(wt)[:, None]
    rts = rt if unit_weights else rt / np.sqrt(wt)
    a, b = gram_tiles_pallas(
        jnp.asarray(gs), jnp.asarray(rts), jnp.asarray(seg),
        num_segments=segs, tile_rows=t, interpret=False,
    )
    a, b = np.asarray(a), np.asarray(b)
    for s in np.unique(seg):
        rows = np.repeat(seg == s, t)
        gws = g[rows] * wt[rows][:, None]
        np.testing.assert_allclose(a[s], gws.T @ g[rows], rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(
            b[s], g[rows].T @ rt[rows], rtol=2e-3, atol=2e-3
        )


@pytest.mark.parametrize("reg_mode,k,e", [
    ("diag", 64, 257), ("diag", 5, 77), ("matrix", 64, 300),
    ("diag", 128, 200), ("matrix", 128, 137),  # LU path above the GJ cap
])
def test_gauss_solve_reg_compiled(reg_mode, k, e):
    """The fused batch-first reg+solve kernel, compiled: ragged last grid
    block (e not a multiple of 128) and both regularizer modes."""
    from cfk_tpu.ops.pallas import gauss_solve_reg_pallas
    from cfk_tpu.ops.solve import batched_spd_solve

    rng = np.random.default_rng(e)
    a, b = _spd_batch(rng, e, k)
    if reg_mode == "diag":
        cnt = rng.integers(0, 50, size=e).astype(np.int32)
        lam = 0.05
        reg = lam * np.maximum(cnt.astype(np.float32), 1.0)
        a_reg = a + reg[:, None, None] * np.eye(k, dtype=np.float32)
        got = np.asarray(gauss_solve_reg_pallas(
            jnp.asarray(a), jnp.asarray(b), jnp.asarray(cnt),
            reg_mode="diag", lam=lam, interpret=False,
        ))
    else:
        r = rng.standard_normal((k, 4)).astype(np.float32)
        rm = r @ r.T + 0.1 * np.eye(k, dtype=np.float32)
        a_reg = a + rm[None]
        got = np.asarray(gauss_solve_reg_pallas(
            jnp.asarray(a), jnp.asarray(b), jnp.asarray(rm),
            reg_mode="matrix", interpret=False,
        ))
    want = np.asarray(
        batched_spd_solve(jnp.asarray(a_reg), jnp.asarray(b))
    )
    resid = np.einsum("ekl,el->ek", a_reg, got) - b
    assert np.abs(resid).max() < 1e-3
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_gram_tiles_kernel_carry_compiled():
    """The in-kernel chunk-boundary carry fold: cin scales the carried
    (a0, b0) into segment 0's sums; cin=0 is a no-op."""
    from cfk_tpu.ops.pallas.gram_kernel import gram_tiles_pallas

    rng = np.random.default_rng(7)
    t, nt, k, segs = 64, 64, 32, 17
    g = rng.standard_normal((nt * t, k)).astype(np.float32)
    rt = rng.random(nt * t).astype(np.float32)
    seg = np.sort(rng.integers(0, segs - 1, size=nt)).astype(np.int32)
    seg[0] = 0  # carry semantics: segment 0 owns the first tile
    a0 = rng.standard_normal((k, k)).astype(np.float32)
    b0 = rng.standard_normal(k).astype(np.float32)
    base_a, base_b = gram_tiles_pallas(
        jnp.asarray(g), jnp.asarray(rt), jnp.asarray(seg),
        num_segments=segs, tile_rows=t, interpret=False,
    )
    for cin in (0.0, 1.0):
        a, b = gram_tiles_pallas(
            jnp.asarray(g), jnp.asarray(rt), jnp.asarray(seg),
            num_segments=segs, tile_rows=t, interpret=False,
            carry=(jnp.asarray(a0), jnp.asarray(b0), jnp.float32(cin)),
        )
        np.testing.assert_allclose(
            np.asarray(a[0]), np.asarray(base_a[0]) + cin * a0,
            rtol=2e-3, atol=2e-3,
        )
        np.testing.assert_allclose(
            np.asarray(b[0]), np.asarray(base_b[0]) + cin * b0,
            rtol=2e-3, atol=2e-3,
        )
        # Only rows of segments that own a tile are specified; compare
        # exactly those (minus segment 0, which carries the fold).
        owned = np.unique(seg)
        owned = owned[owned != 0]
        np.testing.assert_allclose(
            np.asarray(a)[owned], np.asarray(base_a)[owned],
            rtol=1e-5, atol=1e-5,
        )


def _dense_blocks(seed=4, dtype=np.float32):
    """Real dense-stream blocks from the production builder (forced
    dstream), so the compiled kernel sees genuine metadata: 16-aligned
    window offsets, LPT entity order, trash slots, carry chains."""
    from cfk_tpu.data.blocks import build_tiled_blocks
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.data.blocks import index_entities

    coo = synthetic_netflix_coo(3000, 400, 60_000, seed=seed)
    umap, u_dense = index_entities(coo.user_raw)
    mmap, m_dense = index_entities(coo.movie_raw)
    ub = build_tiled_blocks(
        u_dense, m_dense, coo.rating, umap.num_entities, mmap.num_entities,
        accum_max_entities=0, chunk_elems=16_384, dense_stream=True,
    )
    assert ub.mode == "dstream"
    rng = np.random.default_rng(seed)
    table = rng.standard_normal(
        (mmap.num_entities, 64)
    ).astype(dtype) * 0.3
    return ub, table


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gram_dense_kernel_compiled(weighted, dtype):
    """VERDICT r4 #5: the dense-stream kernel's Mosaic-only contracts
    (``pl.multiple_of`` 16-alignment hints, bf16 dynamic sublane windows)
    regression-tested on real hardware against the interpret-mode oracle.
    ``weighted`` runs the production sqrt-reparameterized stream
    (gs = √aw·g) through the same unit-weight kernel form."""
    import jax.numpy as jnp
    from cfk_tpu.ops.pallas.gram_kernel import gram_tiles_dense_pallas

    ub, table = _dense_blocks()
    nc, cap, e_c, t, nt, ng, bg = ub.statics
    k = table.shape[1]
    fz = np.concatenate([table, np.zeros((1, k), table.dtype)])
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(11)
    tol = 3e-2 if dt == jnp.bfloat16 else 3e-3  # bf16 stream rounding
    for c in range(min(nc, 3)):
        nb = ub.neighbor_idx.reshape(nc, cap)[c]
        rt = ub.rating.reshape(nc, nt * t)[c].astype(np.float32)
        meta = ub.tile_meta.reshape(nc, ng + 4 * nt)[c]
        g = fz[nb]
        if weighted:
            aw = np.sqrt(rng.random(cap).astype(np.float32) + 0.1)
            g = g * aw[:, None]
        gj = jnp.asarray(g).astype(dt)
        args = (gj, jnp.asarray(rt), jnp.asarray(meta))
        kw = dict(num_segments=e_c + 1, tile_rows=t, num_tiles=nt,
                  num_groups=ng, block_rows=bg)
        a_c, b_c = gram_tiles_dense_pallas(*args, **kw, interpret=False)
        a_i, b_i = gram_tiles_dense_pallas(*args, **kw, interpret=True)
        # Absent segments' rows are unspecified in the compiled kernel;
        # compare only rows that own tiles.
        seg = meta[ng + 3 * nt:]
        owned = np.unique(seg[seg < e_c])
        np.testing.assert_allclose(
            np.asarray(a_c)[owned], np.asarray(a_i)[owned],
            rtol=tol, atol=tol)
        np.testing.assert_allclose(
            np.asarray(b_c)[owned], np.asarray(b_i)[owned],
            rtol=tol, atol=tol)


def test_gram_dense_kernel_carry_compiled():
    """The dense kernel's chunk-boundary carry fold, compiled: cin scales
    (a0, b0) into segment 0; cin=0 is a no-op."""
    import jax.numpy as jnp
    from cfk_tpu.ops.pallas.gram_kernel import gram_tiles_dense_pallas

    ub, table = _dense_blocks(seed=6)
    nc, cap, e_c, t, nt, ng, bg = ub.statics
    k = table.shape[1]
    fz = np.concatenate([table, np.zeros((1, k), table.dtype)])
    nb = ub.neighbor_idx.reshape(nc, cap)[1]
    rt = ub.rating.reshape(nc, nt * t)[1].astype(np.float32)
    meta = ub.tile_meta.reshape(nc, ng + 4 * nt)[1]
    g = jnp.asarray(fz[nb]).astype(jnp.bfloat16)
    rng = np.random.default_rng(3)
    a0 = rng.standard_normal((k, k)).astype(np.float32)
    b0 = rng.standard_normal(k).astype(np.float32)
    kw = dict(num_segments=e_c + 1, tile_rows=t, num_tiles=nt,
              num_groups=ng, block_rows=bg)
    base_a, base_b = gram_tiles_dense_pallas(
        g, jnp.asarray(rt), jnp.asarray(meta), **kw, interpret=False)
    for cin in (0.0, 1.0):
        a, b = gram_tiles_dense_pallas(
            g, jnp.asarray(rt), jnp.asarray(meta), **kw, interpret=False,
            carry=(jnp.asarray(a0), jnp.asarray(b0), jnp.float32(cin)))
        np.testing.assert_allclose(
            np.asarray(a[0]), np.asarray(base_a[0]) + cin * a0,
            rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(
            np.asarray(b[0]), np.asarray(base_b[0]) + cin * b0,
            rtol=2e-2, atol=2e-2)


# -- kernels first compiled for the chip in PR 21 ----------------------------

def _dense_chunk(ub, table, chunk=1):
    nc, cap, e_c, t, nt, ng, bg = ub.statics
    meta = ub.tile_meta.reshape(nc, ng + 4 * nt)[chunk]
    seg = meta[ng + 3 * nt:]
    return dict(
        nb=jnp.asarray(ub.neighbor_idx.reshape(nc, cap)[chunk]),
        rt=jnp.asarray(ub.rating.reshape(nc, nt * t)[chunk]),
        meta=jnp.asarray(meta),
        reg=jnp.asarray(np.concatenate(
            [ub.chunk_count.reshape(nc, e_c)[chunk], [1]]).astype(np.float32)),
        lseg=jnp.int32(ub.last_seg.reshape(nc)[chunk]),
        owned=np.unique(seg[seg < e_c]),
        kw=dict(num_segments=e_c + 1, tile_rows=t, num_tiles=nt,
                num_groups=ng, block_rows=bg),
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gram_solve_dense_fused_compiled(dtype):
    """The fused Gram+ridge+LU epilogue (the headline user half's kernel),
    compiled, against its XLA twin: x of owned rows and the raw carry row.
    Tolerance: MXU passes + the elimination's float32 round-off; bf16 adds
    the b-coefficient's stream-dtype rounding (tests/test_kernel_bodies)."""
    from cfk_tpu.ops.pallas.gram_kernel import gram_solve_tiles_dense_pallas

    ub, table = _dense_blocks(seed=8)
    p = _dense_chunk(ub, table)
    fz = jnp.concatenate([jnp.asarray(table), jnp.zeros((1, 64))])
    g = fz[p["nb"]].astype(jnp.dtype(dtype))
    tol = 3e-2 if dtype == "bfloat16" else 3e-3
    out = [gram_solve_tiles_dense_pallas(
        g, p["rt"], p["meta"], p["reg"], p["lseg"], reg_mode="diag",
        lam=0.05, interpret=mode, **p["kw"]) for mode in (False, True)]
    (x_c, ca_c, cb_c), (x_i, ca_i, cb_i) = out
    np.testing.assert_allclose(np.asarray(x_c)[p["owned"]],
                               np.asarray(x_i)[p["owned"]],
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(ca_c), np.asarray(ca_i),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(cb_c), np.asarray(cb_i),
                               rtol=tol, atol=tol)


def test_dense_gather_f32_rank128_compiled():
    """The one in-kernel gather shape the gate admits (float32 rows of 128
    lanes): row DMAs + padding mask compiled, against the twin — including
    the alignment pads inside tile windows that need the mask."""
    from cfk_tpu.ops.pallas.gram_kernel import (
        gather_rows_pallas,
        gram_tiles_dense_gather_pallas,
    )

    ub, _ = _dense_blocks(seed=9)
    rng = np.random.default_rng(9)
    f_rows = int(ub.neighbor_idx.max())  # pads index the virtual zero row
    table = jnp.asarray(
        rng.standard_normal((f_rows, 128)).astype(np.float32) * 0.3)
    p = _dense_chunk(ub, table)
    a_c, b_c = gram_tiles_dense_gather_pallas(
        table, p["nb"], None, p["rt"], p["meta"], interpret=False, **p["kw"])
    a_i, b_i = gram_tiles_dense_gather_pallas(
        table, p["nb"], None, p["rt"], p["meta"], interpret=True, **p["kw"])
    for got, want in ((a_c, a_i), (b_c, b_i)):
        np.testing.assert_allclose(np.asarray(got)[p["owned"]],
                                   np.asarray(want)[p["owned"]],
                                   rtol=3e-3, atol=3e-3)
    wt = (p["nb"] < f_rows).astype(jnp.float32)
    rows_c = gather_rows_pallas(table, p["nb"], wt, interpret=False)
    rows_i = gather_rows_pallas(table, p["nb"], wt, interpret=True)
    np.testing.assert_array_equal(np.asarray(rows_c), np.asarray(rows_i))


@pytest.mark.parametrize("cells", ["none", "one_tile", "every_tile"])
@pytest.mark.parametrize("order", ["random", "ascending", "equal"])
@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16", "int8"])
def test_topk_compiled_matches_twin(table_dtype, order, cells):
    _topk_compiled_against_twin(table_dtype, order, cells, m=2_000)


@pytest.mark.parametrize("cells", ["one_tile", "every_tile"])
@pytest.mark.parametrize("order", ["random", "ascending", "equal"])
@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16", "int8"])
def test_topk_compiled_ragged_slab_matches_twin(table_dtype, order, cells):
    """The same orders over 2 G + 3 tiles: two whole slabs and a ragged
    last step of three tiles, whose blocks the pipeline clips at the
    table's end, the last tile reaching past ``num_movies``."""
    from cfk_tpu.serving.topk_kernel import _SLAB_LADDER, slab_tiles

    nt = 2 * _SLAB_LADDER[0] + 3
    assert nt % slab_tiles(nt, 64, 16, 128, jnp.float32, tile_m=512,
                           k_top=10) == 3
    _topk_compiled_against_twin(table_dtype, order, cells, m=nt * 512 - 48)


def _topk_compiled_against_twin(table_dtype, order, cells, m):
    """The serve scorer compiled (movie-major fold, selection rounds gated
    on the carry's K-th score, the masks on the tiles that hold a cell or
    cross ``num_movies``: ``cells`` puts the seen rows nowhere, in the
    third of the tiles, or anywhere) against its XLA twin: the same
    ids wherever scores are not within round-off of each other, the same
    scores to MXU tolerance.  ``ascending`` makes every tile enter every
    user's top-K (the most rounds the gate can ask for) with scores exact in
    a float table, so compiled and twin agree to the bit; ``equal``
    makes every score of a user the same, so the ids are its K lowest
    unseen rows whatever the precision."""
    from cfk_tpu.compat import emulate_topk_counted
    from cfk_tpu.ops.quant import quantize_table
    from cfk_tpu.serving.topk_kernel import (
        build_seen_tiles,
        topk_scores_counted,
    )

    rng = np.random.default_rng(3)
    k, b, k_top, tile = 128, 64, 10, 512
    m_pad = -(-m // tile) * tile
    nt = m_pad // tile
    tbl = np.zeros((m_pad, k), np.float32)
    u = rng.standard_normal((b, k)).astype(np.float32)
    if order == "random":
        tbl[:m] = rng.standard_normal((m, k)).astype(np.float32)
    elif order == "ascending":
        # row r scores 2^e_b · (r + 1): two small integers a row, one
        # power of two a user — products and the one sum are exact
        # (both under 256: exact in a bfloat16 table too)
        base = 64 if m < 64 * 256 else 128
        tbl[:m, 0] = np.arange(1, m + 1) // base
        tbl[:m, 1] = np.arange(1, m + 1) % base
        u = np.zeros((b, k), np.float32)
        u[:, 1] = 2.0 ** rng.integers(-3, 4, b)
        u[:, 0] = base * u[:, 1]
    else:
        tbl[:m] = rng.standard_normal(k).astype(np.float32)
    data, scale = quantize_table(jnp.asarray(tbl), table_dtype)
    u = jnp.asarray(u)
    lo, hi = {"none": (0, m), "one_tile": (2 * tile, 3 * tile),
              "every_tile": (0, m)}[cells]
    seen = [lo + np.sort(rng.choice(
        hi - lo, size=0 if cells == "none" else int(rng.integers(0, 40)),
        replace=False)).astype(np.int32) for _ in range(b)]
    indptr = np.zeros(b + 1, np.int64)
    indptr[1:] = np.cumsum([s.size for s in seen])
    st = jnp.asarray(build_seen_tiles(
        np.concatenate(seen), indptr, np.arange(b), num_movies=m,
        tile_m=tile))
    # a tile a user has rated into runs the rectangle's width, 16 slots a
    # chunk; the others none
    hit = {"none": 0, "one_tile": 1, "every_tile": nt}[cells]
    assert hit == len(np.unique(np.concatenate(seen) // tile))
    chunks = [hit * (st.shape[2] // 16), hit]
    kw = dict(k_top=k_top, num_movies=m, tile_m=tile)
    v_c, i_c, n_c = topk_scores_counted(u, data, scale, st, interpret=False,
                                        **kw)
    v_t, i_t, n_t = emulate_topk_counted(u, data, scale, st, **kw)
    v_c, i_c, v_t, i_t = map(np.asarray, (v_c, i_c, v_t, i_t))
    for n in map(np.asarray, (n_c, n_t)):
        # an int8 or a float32 tile's masks wait behind its first gate
        # with its deferred passes: of the hit tiles, those that gate let
        # through
        assert n[2] == n[3] * (st.shape[2] // 16) and n[3] <= hit
        assert n[1] <= n[4] <= nt
        if table_dtype == "bfloat16" or n[4] == nt:
            assert n[2:].tolist() == chunks + [nt]
    tol = 2e-2 if table_dtype == "bfloat16" else 2e-3
    np.testing.assert_allclose(v_c, v_t, rtol=tol, atol=tol)
    assert (np.diff(v_c, axis=1) <= 0).all()  # descending
    for row in range(b):
        assert not set(i_c[row].tolist()) & set(seen[row].tolist())
        assert (i_c[row] >= 0).all() and (i_c[row] < m).all()
    if order == "random":
        # ids agree except where two candidates are within the tolerance
        assert (i_c == i_t).mean() > 0.95
        return
    unseen = [np.setdiff1d(np.arange(m), s) for s in seen]
    if order == "ascending":
        # every tile enters: K rounds each (the last has 464 real rows)
        assert np.asarray(n_c)[:2].tolist() == [k_top * nt, nt]
        assert np.asarray(n_t)[:2].tolist() == [k_top * nt, nt]
        if table_dtype == "int8":
            # the codes round: neither exact sums nor the strict order hold
            assert (i_c == i_t).mean() > 0.95
            return
        want = np.stack([x[::-1][:k_top] for x in unseen])
        np.testing.assert_array_equal(i_c, want)
        np.testing.assert_array_equal(v_c, v_t)
    else:
        want = np.stack([x[:k_top] for x in unseen])
        np.testing.assert_array_equal(i_c, want)
        assert np.asarray(n_c)[:2].tolist() == [k_top, 1]
    np.testing.assert_array_equal(i_c, i_t)
    assert np.asarray(n_c).tolist() == np.asarray(n_t).tolist()


def test_topk_int8_at_a_size_where_the_scale_layout_shows():
    """2.1 M rows of int8 codes with exclusion on: the scales reach the kernel
    as a lane-dense [NT, 1, T] view (a bitcast), so the compiled call needs
    no temporary — as a [M_pad, 1] operand they were copied out to 128 lanes
    a row on every call, 1.07 GB here (ISSUE 32) — and the turn in register
    (broadcast down the sublanes, transposed) gives the twin's answers and
    numpy's scores."""
    from cfk_tpu.compat import emulate_topk_counted
    from cfk_tpu.serving.topk_kernel import (
        chunk_seen_cells, group_seen_cells, scatter_seen_cells,
        topk_scores_counted)

    rng = np.random.default_rng(32)
    m, k, b, k_top, tile = 2_100_000, 128, 64, 16, 512
    m_pad = -(-m // tile) * tile
    nt = m_pad // tile
    codes = rng.integers(-127, 128, (m_pad, k), dtype=np.int8)
    codes[m:] = 0
    scales = rng.uniform(1e-3, 2e-3, m_pad).astype(np.float32)
    u_host = ((rng.random((b, k), dtype=np.float32) - 0.5) * 0.35)
    seen = [np.sort(rng.choice(m, size=int(rng.integers(1, 40)),
                               replace=False)).astype(np.int32)
            for _ in range(b)]
    indptr = np.zeros(b + 1, np.int64)
    indptr[1:] = np.cumsum([s.size for s in seen])
    cells, shape = group_seen_cells(
        np.concatenate(seen), indptr, np.arange(b), num_movies=m,
        tile_m=tile, num_tiles=nt)
    (piece,) = chunk_seen_cells(cells, cells.shape[1], nt)
    st = jax.jit(scatter_seen_cells, static_argnames=("shape", "tile_m"))(
        jnp.asarray(piece), shape=shape, tile_m=tile)
    u, data, scale = map(jnp.asarray, (u_host, codes, scales))
    kw = dict(k_top=k_top, num_movies=m, tile_m=tile)
    fn = jax.jit(lambda *a: topk_scores_counted(*a, interpret=False, **kw))
    mem = fn.lower(u, data, scale, st).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20, mem
    v_c, i_c, n_c = map(np.asarray, fn(u, data, scale, st))
    v_t, i_t, n_t = map(np.asarray, jax.jit(
        lambda *a: emulate_topk_counted(*a, **kw))(u, data, scale, st))
    assert n_c.tolist() == n_t.tolist() and n_c[3] > 0
    assert (i_c == i_t).mean() > 0.99
    np.testing.assert_allclose(v_c, v_t, rtol=2e-6, atol=2e-6)
    assert (np.diff(v_c, axis=1) <= 0).all()
    for row in range(b):
        assert not set(i_c[row].tolist()) & set(seen[row].tolist())
        assert (i_c[row] >= 0).all() and (i_c[row] < m).all()
    # numpy's float32 scores of the dequantized rows at the served ids
    deq = codes[i_c].astype(np.float32) * scales[i_c][..., None]
    want = np.einsum("bjk,bk->bj", deq.astype(np.float64),
                     u_host.astype(np.float64))
    assert np.abs(v_c - want).max() <= 2e-6


def _device_table(key, m_pad, tile, rank, dtype):
    """[m_pad, rank] made on the device, a few tiles at a time, in place:
    int8 codes uniform in ±127 (what a uniform ±0.175 table quantizes to),
    or uniform ±0.175 floats."""
    nt = m_pad // tile
    d = max(x for x in range(1, 65) if nt % x == 0)

    def block(k):
        if dtype == jnp.int8:
            return jax.random.randint(k, (tile * d, rank), -127, 128, jnp.int8)
        return ((jax.random.uniform(k, (tile * d, rank), jnp.float32) - 0.5)
                * 0.35).astype(dtype)

    return jax.jit(lambda: jax.lax.fori_loop(
        0, nt // d,
        lambda i, buf: jax.lax.dynamic_update_slice(
            buf, block(jax.random.fold_in(key, i)), (i * tile * d, 0)),
        jnp.zeros((m_pad, rank), dtype)))()


def _device_seen(key, nt, b, tile, hit_share):
    """A ``SeenTiles`` made on the device: ``hit_share`` of the tiles hold
    one cell of ~3 of the batch's rows each, W = 16."""
    from cfk_tpu.serving.topk_kernel import SeenTiles

    k1, k2, k3 = jax.random.split(key, 3)
    hits = (jax.random.uniform(k1, (nt,)) < hit_share).astype(jnp.int32)
    row = jax.random.randint(k2, (nt, b), 0, tile, jnp.int32)
    who = jax.random.uniform(k3, (nt, b)) < 3.0 / b
    slots = jax.jit(lambda: jnp.full((nt, b, 16), tile, jnp.int32).at[
        :, :, 0].set(jnp.where((hits[:, None] > 0) & who, row, tile)))()
    return SeenTiles(slots, hits)


@pytest.mark.parametrize("b", [128, 256])
def test_topk_int8_cell_shape_matches_twin_with_its_counts(b):
    """The int8 cells' call (48.19 M x 128 codes, 94,122 tiles, K 16, W 16,
    a fifth of the tiles hit) compiled against the twin scanning the same
    tiles: the ids, the scores to float32 round-off, and all five counts —
    the tiles completed among them, a tenth to a fifth of the table's,
    which is what passes 1 and 2, the masks and the rounds now cost."""
    from cfk_tpu.compat import emulate_topk_counted
    from cfk_tpu.serving.topk_kernel import topk_scores_counted

    m, k, k_top, tile = 48_190_000, 128, 16, 512
    nt = -(-m // tile)
    key = jax.random.PRNGKey(48)
    data = _device_table(key, nt * tile, tile, k, jnp.int8)
    scale = jax.random.uniform(jax.random.fold_in(key, 1), (nt * tile,),
                               jnp.float32, 0.170, 0.175) / 127.0
    u = (jax.random.uniform(jax.random.fold_in(key, 2), (b, k), jnp.float32)
         - 0.5) * 0.35
    st = _device_seen(jax.random.fold_in(key, 3), nt, b, tile, 0.21)
    kw = dict(k_top=k_top, num_movies=m, tile_m=tile)
    v_c, i_c, n_c = map(np.asarray, jax.jit(
        lambda *a: topk_scores_counted(*a, interpret=False, **kw))(
            u, data, scale, st))
    v_t, i_t, n_t = map(np.asarray, jax.jit(
        lambda *a: emulate_topk_counted(*a, **kw))(u, data, scale, st))
    assert n_c.tolist() == n_t.tolist()
    rounds, select, chunks, hit, completed = n_c.tolist()
    assert select <= completed <= 0.25 * nt
    assert 0 < chunks == hit <= completed
    assert hit < 0.3 * int(np.asarray(st.hits).sum())
    np.testing.assert_array_equal(i_c, i_t)
    np.testing.assert_allclose(v_c, v_t, rtol=2e-6, atol=2e-6)
    assert (np.diff(v_c, axis=1) <= 0).all() and (i_c < m).all()


def test_float32_cell_shape_matches_twin_and_takes_less_than_it_took():
    """The float32 cells' call alone at the one-chip cell's size (9.35 M
    x 128, B 256, K 16, 8 % of the tiles hit), compiled, against the twin
    scanning the same tiles: the ids, the scores to float32 round-off, the
    counts (the kernel's block and the twin's differ in the order of their
    sums, so a round or a gate in a few thousand may fall the other way);
    the first gate shuts on the tiles no row of which can enter, and a
    call takes less than the body that ran six passes on every tile took
    on this data (PERF.md section 6, PRs 48 and 50)."""
    import time

    from cfk_tpu.compat import emulate_topk_counted
    from cfk_tpu.serving.topk_kernel import topk_scores_counted

    m, k, b, k_top, tile = 9_350_000, 128, 256, 16, 512
    nt = -(-m // tile)
    key = jax.random.PRNGKey(48)
    data = _device_table(key, nt * tile, tile, k, jnp.float32)
    u = (jax.random.uniform(jax.random.fold_in(key, 2), (b, k), jnp.float32)
         - 0.5) * 0.35
    st = _device_seen(jax.random.fold_in(key, 3), nt, b, tile, 0.08)
    fn = jax.jit(lambda *a: topk_scores_counted(
        *a, k_top=k_top, num_movies=m, tile_m=tile, interpret=False))
    out = jax.block_until_ready(fn(u, data, None, st))
    t0 = time.perf_counter()
    for _ in range(20):
        out = fn(u, data, None, st)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / 20 * 1e3
    print(f"float32 scorer alone: {ms:.2f} ms a call")
    v_c, i_c, n_c = map(np.asarray, out)
    v_t, i_t, n_t = map(np.asarray, jax.jit(
        lambda *a: emulate_topk_counted(
            *a, k_top=k_top, num_movies=m, tile_m=tile))(u, data, None, st))
    print("counts, kernel and twin:", n_c.tolist(), n_t.tolist())
    rounds, select, chunks, hit, completed = n_c.tolist()
    assert select <= completed <= 0.7 * nt  # the first gate does shut
    assert 0 < chunks == hit <= completed
    assert hit < 0.8 * int(np.asarray(st.hits).sum())
    assert (np.abs(n_c - n_t) <= 0.005 * np.maximum(n_t, 200)).all()
    assert (i_c == i_t).mean() > 0.999
    np.testing.assert_allclose(v_c, v_t, rtol=2e-6, atol=2e-6)
    assert (np.diff(v_c, axis=1) <= 0).all() and (i_c < m).all()
    assert ms <= _F32_ALONE_MS, ms


# the body that ran the block on every tile, on this data (PR 48)
_F32_ALONE_MS = 28.7


def test_sliced_upload_on_the_chip_is_the_whole_table_quantizer(monkeypatch):
    """2 M x 128 rows handed to the engine as a row reader and uploaded in
    300,000-row slices of codes (the last short, none a multiple of the
    tile): the device's table is numpy's by the written rule to the bit, and
    no float32 slice of it was ever on the device."""
    from cfk_tpu.ops.quant import quantize_table
    from cfk_tpu.serving import engine as engine_mod
    from tests.serve_reference import quantize_rows

    rng = np.random.default_rng(33)
    m, k = 2_000_123, 128
    mf = ((rng.random((m, k), dtype=np.float32) - 0.5) * 0.35)
    mf[7] = 0.0
    monkeypatch.setattr(engine_mod, "_SLICE_BYTES", 300_000 * k * 4)
    put = []
    real_put = jax.device_put
    monkeypatch.setattr(
        jax, "device_put",
        lambda x, *a, **kw: put.append((np.shape(x), np.asarray(x).dtype))
        or real_put(x, *a, **kw))
    eng = engine_mod.ServeEngine(
        np.zeros((8, k), np.float32), lambda lo, hi: mf[lo:hi], num_users=8,
        num_movies=m, table_dtype="int8", tile_m=512)
    assert {dt for shape, dt in put if len(shape) == 2} == {np.dtype(np.int8)}
    assert max(shape[0] for shape, _ in put) == 300_000
    data, scale = map(np.asarray, eng._table)
    want_codes, want_scales = quantize_rows(mf)
    np.testing.assert_array_equal(data[:m], want_codes)
    np.testing.assert_array_equal(scale[:m], want_scales)
    assert not data[m:].any() and (scale[m:] == 1.0).all()
    # the chip's own quantizer, for the record: where it differs from the
    # rule (its float32 divide is not IEEE's) it differs by one step
    codes_d, scales_d = map(np.asarray, quantize_table(jnp.asarray(mf), "int8"))
    off = codes_d.astype(np.int16) - want_codes
    assert np.abs(off).max() <= 1
    print(f"chip quantize_table against the rule: {int((off != 0).sum())} of "
          f"{off.size} codes, {int((scales_d != want_scales).sum())} of {m} "
          "scales differ")


def _seen_problem(rng, users, movies, longest):
    seen = [np.sort(rng.choice(movies, size=int(rng.integers(0, longest)),
                               replace=False)).astype(np.int32)
            for _ in range(users)]
    indptr = np.zeros(users + 1, np.int64)
    indptr[1:] = np.cumsum([s.size for s in seen])
    return np.concatenate(seen), indptr


def _chip_built_rectangle(monkeypatch, movies, indptr, rows, capacity, **kw):
    """(the rectangle ``engine._seen_chunks`` + the engine's jitted scatter
    build on the chip, the arrays handed over, numpy's) for one batch."""
    from cfk_tpu.serving.engine import _seen_tiles_jit_fn
    from cfk_tpu.serving.topk_kernel import (
        build_seen_tiles,
        group_seen_cells,
        seen_cell_capacity,
    )
    from tests.test_serving import (
        assert_one_program_shape,
        one_program_runs,
    )

    want = build_seen_tiles(movies, indptr, rows, **kw)
    cells, shape = group_seen_cells(movies, indptr, rows, **kw)
    capacity = capacity or seen_cell_capacity(len(rows))
    runs, attrs = one_program_runs(monkeypatch, cells, shape, capacity)
    assert_one_program_shape(runs, attrs, cells.shape[1], capacity)
    got = None
    for run in runs:
        got = _seen_tiles_jit_fn()(jnp.asarray(run), got, shape=shape,
                                   tile_m=kw["tile_m"])
    return got, runs, want


@pytest.mark.parametrize("capacity", [None, 300, 60],
                         ids=["two_pieces", "five_pieces", "past_the_top"])
def test_seen_rectangle_built_on_the_chip_equals_host_oracle(
        capacity, monkeypatch):
    """The scatter the chip's compiler makes of ``scatter_seen_cells``
    (dropped fill columns, no sorted or unique hint; one run over the whole
    list padded to a rung, a donated rectangle where the list passes the top
    rung) against numpy's, bit for bit."""
    rng = np.random.default_rng(11)
    m, tile, b = 200_000, 512, 64
    movies, indptr = _seen_problem(rng, b, m + 5_000, 40)
    rows = rng.integers(0, b, size=b)  # users repeat within the batch
    got, runs, want = _chip_built_rectangle(
        monkeypatch, movies, indptr, rows, capacity, num_movies=m,
        tile_m=tile)
    assert len(runs) == (1 if capacity != 60 else 2)
    np.testing.assert_array_equal(np.asarray(got.slots), want)
    np.testing.assert_array_equal(np.asarray(got.hits),
                                  (want != tile).any(axis=(1, 2)))


@pytest.mark.parametrize("heavy, longest, rung, programs", [
    (0, 0, 1, 1), (18, 1_300, 8, 1), (8, 10_000, 16, 2)],
    ids=["the_control's_batch", "the_skew_cell's_batch", "past_the_top"])
def test_seen_rectangle_at_the_skew_cell_shape_equals_host_oracle(
        heavy, longest, rung, programs, monkeypatch):
    """The one-program build at the stream cells' real shape, [18,262, 256,
    16] int32 (299 MB): a batch of short lists (one piece, today's program),
    a batch with eighteen follow-ups of users drawn by activity (~23 k
    cells: six pieces in one run of the rung of eight, where the parent ran
    its program six times) and one past the top rung (~78 k cells: the top
    rung's program, whose scatter the compiler sorts, and again on its own
    result), each against numpy's rectangle to the bit."""
    rng = np.random.default_rng(42)
    m, tile, b = 9_350_000, 512, 256
    lists = [np.sort(rng.choice(m, size=int(rng.integers(0, 15)),
                                replace=False)) for _ in range(b - heavy)]
    lists += [np.sort(rng.choice(m, size=int(rng.integers(longest * 9 // 10,
                                                         longest + 1)),
                                 replace=False)) for _ in range(heavy)]
    indptr = np.zeros(b + 1, np.int64)
    indptr[1:] = np.cumsum([x.size for x in lists])
    movies = np.concatenate(lists).astype(np.int32)
    got, runs, want = _chip_built_rectangle(
        monkeypatch, movies, indptr, rng.permutation(b), None, num_movies=m,
        tile_m=tile)
    assert want.shape == (18_262, 256, 16)
    assert [r.shape for r in runs] == [(4, rung * 16 * b)] * programs
    np.testing.assert_array_equal(np.asarray(got.slots), want)
    np.testing.assert_array_equal(np.asarray(got.hits),
                                  (want != tile).any(axis=(1, 2)))


@pytest.mark.parametrize("caller", ["exact", "one_device_mesh"])
def test_serve_callers_same_answers_from_the_chip_built_rectangle(
        caller, monkeypatch):
    """Each caller of the device-built rectangle — the one-device scan and
    the item-sharded scan (a mesh of this one chip) — answers as it did
    over a rectangle built on the host and uploaded whole: ids and scores
    bit for bit, one piece or several."""
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.serving import engine as engine_mod
    from tests.test_serving import host_built_seen_tiles

    rng = np.random.default_rng(5)
    users, m, rank, tile = 300, 20_000, 128, 512
    uf = rng.standard_normal((users, rank)).astype(np.float32)
    mf = rng.standard_normal((m, rank)).astype(np.float32)
    movies, indptr = _seen_problem(rng, users, m, 60)
    eng = engine_mod.ServeEngine(
        uf, mf, num_users=users, num_movies=m, seen_movies=movies,
        seen_indptr=indptr, tile_m=tile,
        mesh=make_mesh(1) if caller == "one_device_mesh" else None,
    )
    rows = rng.integers(0, users, size=50)
    # one piece; a few (one run of a higher rung); past the top rung (the
    # top rung's program run again on its own result)
    for capacity in (engine_mod.seen_cell_capacity, lambda b: 512,
                     lambda b: 48):
        with monkeypatch.context() as mp:
            mp.setattr(engine_mod, "seen_cell_capacity", capacity)
            vals, ids = eng.topk(rows, 10)
            mp.setattr(engine_mod.ServeEngine, "_seen_tiles",
                       host_built_seen_tiles)
            want_vals, want_ids = eng.topk(rows, 10)
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(vals, want_vals)
    for row, got in zip(rows, ids):
        assert (got >= 0).all() and (got < m).all()
        mine = movies[indptr[row]: indptr[row + 1]]
        assert not set(got.tolist()) & set(mine.tolist())


@pytest.mark.parametrize("capacity", [None, 512, 48],
                         ids=["two_pieces", "four_pieces", "past_the_top"])
def test_four_chip_sharded_engine_equals_one_device(capacity, monkeypatch):
    """``ServeEngine(shards=4)`` on four real chips, at a size one chip
    holds too: the table lies a quarter on each chip, each chip's slice of
    the exclusion rectangle is the host oracle's, and ids and scores are the
    one-device engine's bit for bit, the cell list of one run of the shard
    program or, past the top rung, of several."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four chips (the chip tool's --chips 4)")
    from cfk_tpu.parallel.spmd import serve_seen_tiles_sharded
    from cfk_tpu.serving import engine as engine_mod
    from cfk_tpu.serving.topk_kernel import (
        build_seen_tiles,
        group_seen_cells,
        seen_cell_capacity,
    )
    from tests.test_serving import one_program_runs

    rng = np.random.default_rng(23)
    users, m, rank, tile, b = 300, 201_000, 128, 512, 64
    uf = rng.standard_normal((users, rank)).astype(np.float32)
    mf = rng.standard_normal((m, rank)).astype(np.float32)
    movies, indptr = _seen_problem(rng, users, m, 60)
    kw = dict(num_users=users, num_movies=m, seen_movies=movies,
              seen_indptr=indptr, tile_m=tile)
    one = engine_mod.ServeEngine(uf, mf, **kw)
    four = engine_mod.ServeEngine(uf, mf, shards=4, **kw)
    table = four._table[0]
    assert [s.device for s in table.addressable_shards] == jax.devices()[:4]
    assert {s.data.shape[0] for s in table.addressable_shards} == {
        four.table_rows // 4}

    rows = rng.integers(0, users, size=b)
    nt = four.table_rows // tile
    skw = dict(num_movies=m, tile_m=tile, num_tiles=nt)
    want = build_seen_tiles(movies, indptr, rows, **skw)
    cells, shape = group_seen_cells(movies, indptr, rows, **skw)
    runs, attrs = one_program_runs(
        monkeypatch, cells, shape, capacity or seen_cell_capacity(b))
    assert attrs["programs"] == len(runs) == (1 if capacity != 48 else 3)
    got = None
    for run in runs:
        got = serve_seen_tiles_sharded(
            four.mesh, engine_mod._put(run, four.mesh), got, shape=shape,
            tile_m=tile)
    for part, oracle in ((got.slots, want),
                         (got.hits, (want != tile).any(axis=(1, 2)))):
        for shard in part.addressable_shards:
            np.testing.assert_array_equal(np.asarray(shard.data),
                                          oracle[shard.index])

    if capacity is not None:
        monkeypatch.setattr(engine_mod, "seen_cell_capacity",
                            lambda b: capacity)
    for take in (rows, rows[:50], rows[:5]):
        want_vals, want_ids = one.topk(take, 10)
        vals, ids = four.topk(take, 10)
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(vals, want_vals)
