"""Top-K serving contracts (ISSUE 8): the score+top-K kernel against the
dense oracle, kernel↔twin bit-equality, quantized-table self-consistency,
the no-dense-score-matrix memory bound, multi-shard == single-shard, the
request server round trip, and the hot-user cache's fold-in freshness."""

import functools
import re
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cfk_tpu.compat import emulate_topk_scores
from cfk_tpu.serving.topk_kernel import (
    build_seen_tiles,
    chunk_seen_cells,
    group_seen_cells,
    topk_scores_pallas,
)


def _problem(rng, b=8, m=50, k=16, tile=16, seen_max=10):
    u = rng.standard_normal((b, k)).astype(np.float32)
    mf = rng.standard_normal((m, k)).astype(np.float32)
    m_pad = -(-m // tile) * tile
    tbl = np.zeros((m_pad, k), np.float32)
    tbl[:m] = mf
    seen = [
        np.sort(rng.choice(m, size=int(rng.integers(0, seen_max)),
                           replace=False)).astype(np.int32)
        for _ in range(b)
    ]
    indptr = np.zeros(b + 1, np.int64)
    indptr[1:] = np.cumsum([s.size for s in seen])
    movies = (np.concatenate(seen) if indptr[-1]
              else np.zeros(0, np.int32))
    return u, mf, tbl, seen, movies, indptr


# Kernel vs an independent dense matmul (numpy / XLA): the kernel contracts
# tile·uᵀ (movie-major, the orientation Mosaic lowers), the oracles u·tileᵀ —
# the same k products summed in another order.  Scores therefore agree to
# float32 round-off of a k ≤ 16 term dot at |score| ≲ 10 (k·2⁻²³·Σ|uᵢmᵢ| ≈
# 1e-5 worst case, ~1e-6 seen), not bit for bit; ids are equal because no two
# candidates of these random problems are that close.  Kernel vs its own twin
# (same fold function) and single- vs multi-shard stay exact below.
_DOT_ATOL = 1e-5


def _dense_oracle(u, mf, seen, k_top):
    """Reference selection from the materialized score matrix — what the
    kernel must reproduce without ever materializing it."""
    sc = u @ mf.T
    for b, s in enumerate(seen):
        sc[b, s] = -np.inf
    ids = np.argsort(-sc, axis=1, kind="stable")[:, :k_top]
    return np.take_along_axis(sc, ids, 1).astype(np.float32), ids


def test_kernel_matches_dense_oracle(rng):
    u, mf, tbl, seen, movies, indptr = _problem(rng)
    st = build_seen_tiles(movies, indptr, np.arange(8), num_movies=50,
                          tile_m=16)
    vals, ids = topk_scores_pallas(
        jnp.asarray(u), jnp.asarray(tbl), None, jnp.asarray(st),
        k_top=5, num_movies=50, tile_m=16,
    )
    ov, oi = _dense_oracle(u, mf, seen, 5)
    np.testing.assert_allclose(np.asarray(vals), ov, rtol=0, atol=_DOT_ATOL)
    np.testing.assert_array_equal(np.asarray(ids), oi)
    for b in range(8):  # exclusion: no already-rated movie in the top-K
        assert not set(np.asarray(ids)[b].tolist()) & set(seen[b].tolist())


def test_kernel_bit_equals_emulation_twin(rng):
    u, mf, tbl, seen, movies, indptr = _problem(rng)
    st = build_seen_tiles(movies, indptr, np.arange(8), num_movies=50,
                          tile_m=16)
    args = (jnp.asarray(u), jnp.asarray(tbl), None, jnp.asarray(st))
    kw = dict(k_top=7, num_movies=50, tile_m=16)
    v1, i1 = topk_scores_pallas(*args, **kw)
    v2, i2 = emulate_topk_scores(*args, **kw)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_kernel_matches_eval_ranking_oracle(rng):
    # the eval-side oracle: the held-out item's rank from eval.ranking
    # must agree with membership in the kernel's top-K (the serving path
    # and the offline evaluator must never disagree about what the top-K
    # IS).  Build a tiny model-ish problem with no ties.
    from cfk_tpu.data.blocks import RatingsCOO
    from cfk_tpu.eval.ranking import Heldout, _ranks

    u, mf, tbl, seen, movies, indptr = _problem(rng, seen_max=6)
    train = RatingsCOO(
        movie_raw=movies.astype(np.int64),
        user_raw=np.repeat(np.arange(8), np.diff(indptr)).astype(np.int64),
        rating=np.ones(movies.shape[0], np.float32),
    )
    scores = u @ mf.T
    held = Heldout(
        user_dense=np.arange(8, dtype=np.int64),
        movie_dense=np.asarray(
            [next(m for m in range(50) if m not in set(s.tolist()))
             for s in seen], np.int64,
        ),
    )
    ranks = _ranks(scores, train, held)
    st = build_seen_tiles(movies, indptr, np.arange(8), num_movies=50,
                         tile_m=16)
    k_top = 5
    _, ids = topk_scores_pallas(
        jnp.asarray(u), jnp.asarray(tbl), None, jnp.asarray(st),
        k_top=k_top, num_movies=50, tile_m=16,
    )
    ids = np.asarray(ids)
    for b in range(8):
        in_topk = int(held.movie_dense[b]) in ids[b].tolist()
        assert in_topk == (ranks[b] < k_top), (b, ranks[b], ids[b])


@pytest.mark.parametrize("table_dtype", ["bfloat16", "int8"])
def test_quantized_table_self_consistency(rng, table_dtype):
    # the quantization metric contract: the kernel on a quantized table
    # returns EXACTLY the top-K of the dequantized-table scores —
    # quantization error lives in the table, the kernel adds none
    # (bit-pinned against the twin scoring the dequantized view; an int8
    # tile's block is the same float32 sum with the row's scale taken out
    # of it, three exact bfloat16 passes and one multiply (ISSUE 35), so
    # its scores sit within the sum's own rounding of the view's, not on
    # its bits: tests/test_serve_q8.py holds that arithmetic to float64).
    from cfk_tpu.ops.quant import dequantize_table, quantize_table

    u, mf, tbl, *_ = _problem(rng)
    data, scale = quantize_table(jnp.asarray(tbl), table_dtype)
    v1, i1 = topk_scores_pallas(
        jnp.asarray(u), data, scale, None, k_top=5, num_movies=50,
        tile_m=16,
    )
    dq = dequantize_table(data, scale)
    v2, i2 = emulate_topk_scores(
        jnp.asarray(u), dq, None, None, k_top=5, num_movies=50, tile_m=16,
    )
    v1, v2 = np.asarray(v1), np.asarray(v2)
    if table_dtype == "int8":
        assert (np.abs(v1 - v2) <= 4 * np.spacing(np.abs(v2))).all()
    else:
        np.testing.assert_array_equal(v1, v2)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_no_dense_score_matrix_materialized():
    # the memory contract behind the whole design: compiled temp memory
    # stays far below one [B, num_movies] f32 block (the emulation twin
    # is the compiled route on CPU; the Mosaic kernel's out_specs bound
    # HBM writes to [B, K] by construction)
    b, m, k, k_top, tile = 8, 8192, 16, 5, 128
    fn = functools.partial(
        emulate_topk_scores, k_top=k_top, num_movies=m, tile_m=tile,
    )
    compiled = jax.jit(
        lambda u, t: fn(u, t, None, None)
    ).lower(jnp.zeros((b, k)), jnp.zeros((m, k))).compile()
    stats = compiled.memory_analysis()
    dense_bytes = b * m * 4
    assert stats.temp_size_in_bytes < dense_bytes // 4, (
        stats.temp_size_in_bytes, dense_bytes,
    )
    assert stats.output_size_in_bytes <= 4 * b * k_top * 8


def test_row_offset_split_merges_to_whole(rng):
    # the sharded merge protocol in miniature: two half-tables scored with
    # their global row offsets, concat + one top_k == the whole table
    u, mf, tbl, *_ = _problem(rng, m=60, tile=16)
    u, tbl = jnp.asarray(u), jnp.asarray(tbl)
    kw = dict(k_top=6, num_movies=60, tile_m=16)
    v, i = topk_scores_pallas(u, tbl, None, None, **kw)
    v1, i1 = topk_scores_pallas(u, tbl[:32], None, None, row_offset=0, **kw)
    v2, i2 = topk_scores_pallas(u, tbl[32:], None, None, row_offset=32, **kw)
    mv, pos = jax.lax.top_k(jnp.concatenate([v1, v2], 1), 6)
    mi = jnp.take_along_axis(jnp.concatenate([i1, i2], 1), pos, 1)
    np.testing.assert_array_equal(np.asarray(mv), np.asarray(v))
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(i))


# -- the gated selection on the orders that stress the gate ------------------
#
# Every score is ONE exact product: the table's only non-zero column holds a
# small integer g(row) (exact in bfloat16; an int8 row dequantizes to one f32
# value, which the oracle reads back), the user's entry in that column is a
# power of two.  So the numpy oracle — a stable sort by score descending, row
# ascending — gives the answer to the bit in every table dtype, and nothing
# of the fold is used to compute it.

_GATE_M, _GATE_TILE, _GATE_RANK = 70, 16, 16  # 5 tiles, the last 6 real rows
GATE_ORDERS = ("ascending", "descending", "equal", "few", "seen_entrants",
               "last_tile")


def _gate_problem(order, b, k_top):
    """(u, table, seen lists) of one adversarial order."""
    m, tile = _GATE_M, _GATE_TILE
    rng = np.random.default_rng(b * 131 + k_top)
    rows = np.arange(m)
    g = {"ascending": rows + 1.0,  # every tile enters every top-K
         "descending": m - rows + 0.0,  # only the first tile does
         "equal": np.ones(m),  # ties: the K lowest unseen rows
         "few": rows % 7 + 1.0,
         "seen_entrants": rows + 1.0,
         # one tile of winners early on, then nothing until the last two
         # real rows of the padded last tile
         "last_tile": np.where(rows >= m - 2, 150.0 + rows,
                               np.where(rows < tile, 60.0 - rows, 1.0)),
         }[order]
    m_pad = -(-m // tile) * tile
    tbl = np.zeros((m_pad, _GATE_RANK), np.float32)
    tbl[:m, 3] = g
    tbl[m:, 3] = 200.0  # padding rows would win if the mask let them
    u = rng.standard_normal((b, _GATE_RANK)).astype(np.float32)
    u[:, 3] = 2.0 ** rng.integers(-2, 3, b)
    if order == "few":
        # user i keeps i % (K + 1) candidates: from none to exactly K
        seen = [np.sort(rng.permutation(m)[i % (k_top + 1):])
                for i in range(b)]
    elif order == "seen_entrants":
        # the best rows of every tile are seen cells, and a few more
        best = rows[rows % tile >= tile - 3]
        seen = [np.union1d(best, rng.choice(m, size=i % 5, replace=False))
                for i in range(b)]
    else:
        seen = [np.sort(rng.choice(m, size=int(rng.integers(0, 6)),
                                   replace=False)) for _ in range(b)]
    return u, tbl, [x.astype(np.int32) for x in seen]


def _gate_oracle(u, deq, seen, k_top, m=_GATE_M):
    """Stable sort by score descending, row ascending; −inf / −1 where a
    user has fewer than K candidates."""
    vals = np.full((len(seen), k_top), -np.inf, np.float32)
    ids = np.full((len(seen), k_top), -1, np.int32)
    for i, s in enumerate(seen):
        sc = u[i, 3] * deq[:m, 3]  # exact: power of two × one value
        cand = np.setdiff1d(np.arange(m), s)
        order = cand[np.lexsort((cand, -sc[cand]))][:k_top]
        vals[i, :order.size], ids[i, :order.size] = sc[order], order
    return vals, ids


@functools.lru_cache(maxsize=None)
def _counted_programs(k_top, num_movies, tile_m):
    """(kernel on the interpret path, twin), jitted: one compile per shape
    class of the operands."""
    from cfk_tpu.compat import emulate_topk_counted
    from cfk_tpu.serving.topk_kernel import topk_scores_counted

    kw = dict(k_top=k_top, num_movies=num_movies, tile_m=tile_m)
    return (jax.jit(functools.partial(topk_scores_counted, **kw)),
            jax.jit(functools.partial(emulate_topk_counted, **kw)))


def _gate_run(order, table_dtype, b, k_top, exclude=True):
    from cfk_tpu.ops.quant import dequantize_table, quantize_table

    u, tbl, seen = _gate_problem(order, b, k_top)
    if not exclude:
        seen = [np.zeros(0, np.int32)] * b
    data, scale = quantize_table(jnp.asarray(tbl), table_dtype)
    deq = np.asarray(dequantize_table(data, scale), np.float32)
    indptr = np.zeros(b + 1, np.int64)
    indptr[1:] = np.cumsum([x.size for x in seen])
    st = jnp.asarray(build_seen_tiles(
        np.concatenate(seen), indptr, np.arange(b), num_movies=_GATE_M,
        tile_m=_GATE_TILE))
    assert st.shape == (5, b, 16)  # one compiled shape per (dtype, B, K)
    outs = [tuple(map(np.asarray, fn(jnp.asarray(u), data, scale, st)))
            for fn in _counted_programs(k_top, _GATE_M, _GATE_TILE)]
    return outs, _gate_oracle(u, deq, seen, k_top)


@pytest.mark.parametrize("k_top", [1, 10, 16])
@pytest.mark.parametrize("b", [8, 64, 256])
@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("order", GATE_ORDERS)
def test_gated_fold_equals_stable_sort_oracle(order, table_dtype, b, k_top):
    """Kernel (interpret path) and twin against the numpy oracle, to the
    bit: values, ids and their order."""
    (kernel, twin), (want_v, want_i) = _gate_run(order, table_dtype, b, k_top)
    for got_v, got_i, _ in (kernel, twin):
        np.testing.assert_array_equal(got_i, want_i)
        np.testing.assert_array_equal(got_v, want_v)
    np.testing.assert_array_equal(kernel[2], twin[2])
    if order == "few":  # the −1 tail sits behind the candidates there are
        have = np.arange(b) % (k_top + 1)
        np.testing.assert_array_equal(
            kernel[1] >= 0, np.arange(k_top)[None, :] < have[:, None])


@pytest.mark.parametrize("k_top", [1, 10, 16, 24])
@pytest.mark.parametrize("order", ["ascending", "descending", "equal"])
def test_selection_counts_are_what_the_order_implies(order, k_top):
    """[rounds run, tiles that ran any, exclusion chunks run, tiles that
    ran any, tiles completed] with no cell in the rectangle: ascending
    scores make every tile replace the whole carry (min(K, real rows)
    rounds each), descending or equal ones only fill it (K rounds over the
    first ceil(K / T) tiles, then every gate stays shut), no tile runs an
    exclusion chunk, a float32 table completes all five tiles — and kernel
    and twin count alike."""
    (kernel, twin), _ = _gate_run(order, "float32", 8, k_top, exclude=False)
    real = [16, 16, 16, 16, 6]  # rows of each tile below num_movies
    want = ([sum(min(k_top, r) for r in real), 5] if order == "ascending"
            else [k_top, -(-k_top // 16)])  # the tiles that fill the carry
    assert kernel[2].tolist() == want + [0, 0, 5]
    assert twin[2].tolist() == want + [0, 0, 5]


# -- the gated masks: a tile runs the exclusion chunks it holds --------------

_MASK_TILE, _MASK_NT, _MASK_B, _MASK_K = 512, 20, 8, 10
_MASK_M = _MASK_NT * _MASK_TILE - 300  # the last tile holds 212 real rows
_MASK_WIDTH = 32  # two chunks of slots in every case: one compiled shape
MASK_CASES = ("no_cell", "every_tile", "one_tile_in_ten", "two_chunks",
              "padded_last_tile", "rows_0_and_511")


def _mask_seen(case):
    """Seen lists (global rows, sorted) of the ``_MASK_B`` batch rows."""
    t, b = _MASK_TILE, _MASK_B
    rng = np.random.default_rng(sum(map(ord, case)))
    if case == "no_cell":
        lists = [[] for _ in range(b)]
    elif case == "every_tile":
        lists = [[j * t + (37 * i + 11 * j) % 212 for j in range(_MASK_NT)]
                 for i in range(b)]
    elif case == "one_tile_in_ten":
        lists = [[3 * t + int(x) for x in rng.choice(t, 3, replace=False)]
                 + [13 * t + int(x) for x in rng.choice(t, i % 3, False)]
                 for i in range(b)]
        lists[1] += [13 * t + 7]
    elif case == "two_chunks":
        # row 0 has 20 cells in tile 5: the second chunk of 16 slots holds
        # cells there, and nowhere else
        lists = [[5 * t + int(x) for x in rng.choice(t, 20, replace=False)]]
        lists += [[1 * t + i, 5 * t + 2 * i, 9 * t + 3 * i][:i % 4]
                  for i in range(1, b)]
    elif case == "padded_last_tile":
        lists = [[19 * t + int(x) for x in rng.choice(212, i, replace=False)]
                 for i in range(b)]
    elif case == "rows_0_and_511":
        lists = [[4 * t, 4 * t + 511], [0, 511], [511], [4 * t]]
        lists += [[] for _ in range(b - 4)]
    else:
        raise KeyError(case)
    return [np.sort(np.asarray(x, np.int32)) for x in lists]


def _mask_counts_implied(seen):
    """[exclusion chunks run, tiles that ran them] by the cell lists: a
    tile any batch row has rated into runs the rectangle's whole width."""
    hit = np.zeros((_MASK_NT,), bool)
    for s in seen:
        hit[s // _MASK_TILE] = True
    return [int(hit.sum()) * (_MASK_WIDTH // 16), int(hit.sum())]


@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("case", MASK_CASES)
def test_gated_masks_equal_oracle_and_full_width_run(case, table_dtype):
    """Kernel (interpret path) and twin, masking only the tiles that hold
    a cell or cross ``num_movies``, against the numpy stable-sort oracle
    and against a run told that every tile holds one — to the bit — with
    the hits made from the cell list (``scatter_seen_cells``, the
    server's) and by reading a bare rectangle; the counts are what the
    cell lists imply — on an int8 table, whose masks wait with passes 1
    and 2 behind the first gate, of the hit tiles that gate let through."""
    from cfk_tpu.ops.quant import dequantize_table, quantize_table
    from cfk_tpu.serving.topk_kernel import SeenTiles, scatter_seen_cells

    seen = _mask_seen(case)
    rng = np.random.default_rng(5)
    m, m_pad = _MASK_M, _MASK_NT * _MASK_TILE
    g = rng.permutation(m) % 251 + 1.0
    # a seen row would top its user's list if the mask let it through
    g[np.concatenate(seen).astype(np.int64)] = 300.0
    tbl = np.zeros((m_pad, _GATE_RANK), np.float32)
    tbl[:m, 3] = g
    tbl[m:, 3] = 400.0  # and so would a padding row
    u = rng.standard_normal((_MASK_B, _GATE_RANK)).astype(np.float32)
    u[:, 3] = 2.0 ** rng.integers(-2, 3, _MASK_B)
    data, scale = quantize_table(jnp.asarray(tbl), table_dtype)
    deq = np.asarray(dequantize_table(data, scale), np.float32)
    movies, indptr = _csr(seen)
    kw = dict(num_movies=m, tile_m=_MASK_TILE, min_width=_MASK_WIDTH)
    bare = jnp.asarray(build_seen_tiles(
        movies, indptr, np.arange(_MASK_B), **kw))
    cells, shape = group_seen_cells(movies, indptr, np.arange(_MASK_B), **kw)
    assert shape == bare.shape == (_MASK_NT, _MASK_B, _MASK_WIDTH)
    built = None
    for piece in chunk_seen_cells(cells, 64, _MASK_NT):
        built = scatter_seen_cells(jnp.asarray(piece), built, shape=shape,
                                   tile_m=_MASK_TILE)
    np.testing.assert_array_equal(built.slots, bare)
    every_tile = SeenTiles(bare, jnp.ones(_MASK_NT, jnp.int32))
    want_v, want_i = _gate_oracle(u, deq, seen, _MASK_K, m=m)
    runs = {name: [tuple(map(np.asarray, fn(jnp.asarray(u), data, scale, st)))
                   for fn in _counted_programs(_MASK_K, _MASK_M, _MASK_TILE)]
            for name, st in (("cell_list", built), ("bare", bare),
                             ("every_tile", every_tile))}
    for kernel, twin in runs.values():
        for got_v, got_i, _ in (kernel, twin):
            np.testing.assert_array_equal(got_i, want_i)
            np.testing.assert_array_equal(got_v, want_v)
        np.testing.assert_array_equal(kernel[2], twin[2])
    counts = {name: kernel[2].tolist() for name, (kernel, _) in runs.items()}
    chunks = _MASK_WIDTH // 16
    if table_dtype != "bfloat16":
        # passes deferred: a mask runs on a hit tile that was completed,
        # and only there
        for c in counts.values():
            assert c[2] == c[3] * chunks and c[1] <= c[4] <= _MASK_NT
        assert counts["cell_list"][3] <= _mask_counts_implied(seen)[1]
        assert counts["every_tile"][3] == counts["every_tile"][4]
    else:
        assert counts["cell_list"][2:] == _mask_counts_implied(seen) + [
            _MASK_NT]
        assert counts["every_tile"][2:] == [_MASK_NT * chunks, _MASK_NT,
                                            _MASK_NT]
    assert counts["bare"] == counts["cell_list"]
    # the same rounds on the same scores: only the masks differ
    assert counts["every_tile"][:2] == counts["cell_list"][:2]
    assert counts["every_tile"][4] == counts["cell_list"][4]


# -- a grid step streams a slab of G tiles and folds them one by one ---------

_SLAB_TILE, _SLAB_B, _SLAB_K, _SLAB_RANK = 16, 8, 10, 16


def _slab_num_tiles(which):
    from cfk_tpu.serving.topk_kernel import _SLAB_LADDER

    g = _SLAB_LADDER[0]
    return {"1": 1, "G-1": g - 1, "G": g, "G+1": g + 1,
            "2G+3": 2 * g + 3}[which]


@pytest.mark.parametrize("exclude", [True, False], ids=["seen", "no_seen"])
@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("which", ["1", "G-1", "G", "G+1", "2G+3"])
def test_slab_kernel_equals_twin_to_the_bit(which, table_dtype, exclude):
    """The kernel on the interpret path, G tiles a grid step and the last
    step ragged, against the twin that scans tile by tile: scores, ids and
    all five counts to the bit, at NT on both sides of the ladder's top.
    The table's last tile reaches past ``num_movies``, holds user 0's best
    row (an entrant in the last slab's last tile) and user 1's best row,
    which user 1 has rated (a hit there)."""
    from cfk_tpu.ops.quant import quantize_table
    from cfk_tpu.serving.topk_kernel import slab_tiles

    t, b, k_top = _SLAB_TILE, _SLAB_B, _SLAB_K
    nt = _slab_num_tiles(which)
    m = nt * t - 5  # the last tile's last five rows are padding
    rng = np.random.default_rng(nt)
    u = rng.standard_normal((b, _SLAB_RANK)).astype(np.float32)
    tbl = np.zeros((nt * t, _SLAB_RANK), np.float32)
    tbl[:m] = rng.standard_normal((m, _SLAB_RANK))
    tbl[m - 1], tbl[m - 2] = 4 * u[0], 4 * u[1]
    seen = [np.sort(rng.choice(m - 2, size=min(int(rng.integers(0, 9)), m - 2),
                               replace=False)).astype(np.int32)
            for _ in range(b)]
    seen[1] = np.append(seen[1], m - 2).astype(np.int32)
    indptr = np.zeros(b + 1, np.int64)
    indptr[1:] = np.cumsum([x.size for x in seen])
    st = jnp.asarray(build_seen_tiles(
        np.concatenate(seen), indptr, np.arange(b), num_movies=m,
        tile_m=t)) if exclude else None
    data, scale = quantize_table(jnp.asarray(tbl), table_dtype)
    g = slab_tiles(nt, b, 16 if exclude else 0, _SLAB_RANK, data.dtype,
                   tile_m=t, k_top=k_top)
    assert 1 <= g <= nt and (nt % g != 0) == (which in ("G-1", "G+1", "2G+3"))
    kernel, twin = (
        tuple(map(np.asarray, fn(jnp.asarray(u), data, scale, st)))
        for fn in _counted_programs(k_top, m, t))
    for got, want in zip(kernel, twin):
        np.testing.assert_array_equal(got, want)
    vals, ids, counts = kernel
    assert ids[0, 0] == m - 1  # the entrant of the last tile
    assert (ids[1, 0] == m - 2) == (not exclude)  # and the hit there
    real = [row[row >= 0] for row in ids]  # the -1 tail where m < K
    assert ids.max() < m and all(np.unique(r).size == r.size for r in real)
    if exclude:
        assert not any(np.isin(ids[i], seen[i]).any() for i in range(b))
        hit = np.unique(np.concatenate(seen) // t).size
        # W = 16: a chunk a tile that is hit (and, where passes are
        # deferred, completed)
        assert counts[2] == counts[3] <= hit
        assert counts[3] == hit or table_dtype != "bfloat16"
    else:
        assert counts[2:4].tolist() == [0, 0]
    assert 1 <= counts[1] <= nt and counts[1] <= counts[0]
    # every pass ran on every tile, but on an int8 or a float32 table's
    # behind a shut gate
    assert counts[1] <= counts[4] <= nt
    assert counts[4] == nt or table_dtype != "bfloat16"


def test_slab_tiles_fits_the_table_and_the_budget():
    """G is the ladder's largest rung that the table has tiles for and the
    VMEM budget room for: the top at every cell's shape, NT's own rung on a
    shortlist's few tiles, 1 where a wide rectangle leaves room for one."""
    from cfk_tpu.serving.topk_kernel import (
        _SLAB_LADDER, _VMEM_CAP, _vmem_bytes, slab_tiles)

    assert _SLAB_LADDER[-1] == 1 and list(_SLAB_LADDER) == sorted(
        _SLAB_LADDER, reverse=True)
    shapes = [(nt, b, w, dt) for nt in (1, 2, 3, 5, 15, 16, 17, 100, 18_262,
                                        23_531, 94_122)
              for b in (8, 256) for w in (0, 16, 64, 1024, 32_768)
              for dt in (jnp.float32, jnp.bfloat16, jnp.int8)]
    for nt, b, w, dt in shapes:
        g = slab_tiles(nt, b, w, 128, dt, tile_m=512, k_top=16)
        need = functools.partial(_vmem_bytes, batch=b, seen_width=w,
                                 rank=128, table_dtype=dt, tile_m=512,
                                 k_top=16)
        assert g in _SLAB_LADDER and g <= nt
        assert g == 1 or need(g) <= _VMEM_CAP
        bigger = [r for r in _SLAB_LADDER if g < r <= nt]
        assert all(need(r) > _VMEM_CAP for r in bigger), (nt, b, w, dt)
    for nt, dt in ((18_262, jnp.float32), (23_531, jnp.float32),
                   (94_122, jnp.int8)):  # the cells' calls: B 256, W 16
        assert slab_tiles(nt, 256, 16, 128, dt, tile_m=512,
                          k_top=16) == _SLAB_LADDER[0]
    # a rectangle 32,768 slots wide is 33.5 MB a tile: no room for two
    assert slab_tiles(18_262, 256, 32_768, 128, jnp.float32, tile_m=512,
                      k_top=16) == 1
    assert slab_tiles(0, 8, 0, 16, jnp.float32, tile_m=16, k_top=4) == 1


@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_serve_equals_single_shard(rng, shards):
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import serve_topk_sharded

    tile = 16
    m = 100
    m_pad = -(-m // (4 * tile)) * (4 * tile)
    u = rng.standard_normal((8, 16)).astype(np.float32)
    tbl = np.zeros((m_pad, 16), np.float32)
    tbl[:m] = rng.standard_normal((m, 16)).astype(np.float32)
    seen = [np.sort(rng.choice(m, size=5, replace=False)).astype(np.int32)
            for _ in range(8)]
    indptr = np.zeros(9, np.int64)
    indptr[1:] = np.cumsum([5] * 8)
    st = jnp.asarray(build_seen_tiles(
        np.concatenate(seen), indptr, np.arange(8), num_movies=m,
        tile_m=tile, num_tiles=m_pad // tile,
    ))
    u, tbl = jnp.asarray(u), jnp.asarray(tbl)
    kw = dict(k_top=7, num_movies=m, tile_m=tile)
    v1, i1 = topk_scores_pallas(u, tbl, None, st, **kw)
    v2, i2, _ = serve_topk_sharded(make_mesh(shards), u, tbl, None, st, **kw)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_build_seen_tiles_brute_force(rng):
    m, tile = 77, 16
    nt = -(-m // tile)
    seen = [np.sort(rng.choice(m, size=int(rng.integers(0, 30)),
                               replace=False)).astype(np.int32)
            for _ in range(5)]
    indptr = np.zeros(6, np.int64)
    indptr[1:] = np.cumsum([s.size for s in seen])
    movies = (np.concatenate(seen) if indptr[-1]
              else np.zeros(0, np.int32))
    st = build_seen_tiles(movies, indptr, np.arange(5), num_movies=m,
                          tile_m=tile)
    assert st.shape[0] == nt and st.shape[1] == 5
    assert st.shape[2] % 16 == 0 and st.shape[2] & (st.shape[2] - 1) == 0
    for t in range(nt):
        for b in range(5):
            want = sorted(x % tile for x in seen[b]
                          if t * tile <= x < (t + 1) * tile)
            got = sorted(x for x in st[t, b].tolist() if x != tile)
            assert got == want, (t, b)


def _csr(lists):
    indptr = np.zeros(len(lists) + 1, np.int64)
    indptr[1:] = np.cumsum([len(x) for x in lists])
    movies = (np.concatenate([np.asarray(x, np.int32) for x in lists])
              if indptr[-1] else np.zeros(0, np.int32))
    return movies, indptr


def _seen_case(name):
    """(seen lists by user row, batch rows, num_movies, tile_m, capacity,
    pieces expected, width expected) of one case of the device build."""
    rng = np.random.default_rng(sum(map(ord, name)))
    pick = lambda m, n: np.sort(rng.choice(m, size=n, replace=False))
    if name == "random":
        lists = [pick(77, int(rng.integers(0, 30))) for _ in range(8)]
        return lists, np.arange(8), 77, 16, 128, None, 16
    if name == "all_empty":
        return [[]] * 8, np.arange(8), 50, 16, 128, 1, 16
    if name == "one_user_many_times":
        lists = [pick(200, 40), pick(200, 3), []]
        rows = np.array([0] * 13 + [1, 2, 0])
        return lists, rows, 200, 16, 16 * 16, 3, 16
    if name == "cells_past_num_movies":
        # rows 50..63 lie in the padded table, 64.. past it: all dropped
        lists = [np.array([3, 49, 50, 63, 64, 900]), np.array([50, 51]),
                 np.array([0, 48])] + [[]] * 5
        return lists, np.arange(8), 50, 16, 128, 1, 16
    if name == "pad_slots_empty":
        # the engine's padding: slots past n select the empty last list
        lists = [pick(50, 9), pick(50, 4), pick(50, 7), []]
        return lists, np.array([0, 1, 2, 3, 3, 3, 3, 3]), 50, 16, 128, 1, 16
    if name == "over_capacity":
        lists = [pick(300, 25) for _ in range(8)]
        return lists, np.arange(8), 300, 16, 64, 4, 16
    if name == "exactly_capacity":
        lists = [pick(300, 8) for _ in range(8)]
        return lists, np.arange(8), 300, 16, 64, 1, 16
    if name == "wide":
        # 40 and 70 seen items inside one 128-row tile: W 64 and 128
        lists = [pick(128, 40), 128 + pick(128, 70), pick(256, 5), []]
        return lists, np.arange(4), 256, 128, 64, 2, 128
    raise KeyError(name)


SEEN_CASES = ("random", "all_empty", "one_user_many_times",
              "cells_past_num_movies", "pad_slots_empty", "over_capacity",
              "exactly_capacity", "wide")


def one_program_runs(monkeypatch, cells, shape, capacity, *, warm=False):
    """The batch's cell list as the server hands it to the device
    (``engine._seen_chunks`` under a piece of ``capacity`` cells) and what
    its ``serve/batch/seen_tiles`` span says of it."""
    import types

    from cfk_tpu.serving import engine as engine_mod

    attrs = {}
    with monkeypatch.context() as mp:
        mp.setattr(engine_mod, "seen_cell_capacity", lambda b: capacity)
        runs = engine_mod._seen_chunks(
            types.SimpleNamespace(set=attrs.update), cells, shape, warm)
    return runs, attrs


def assert_one_program_shape(runs, attrs, cells: int, capacity: int):
    """One array a rung of the ladder wide under the top rung, under twice
    the cells past one piece; past the top, arrays of the top rung's."""
    from cfk_tpu.serving.topk_kernel import SEEN_PIECE_RUNGS

    top = SEEN_PIECE_RUNGS[-1]
    pieces = max(-(-cells // capacity), 1)
    assert attrs["cells"] == cells and attrs["capacity"] == capacity
    assert attrs["programs"] == len(runs) == max(-(-pieces // top), 1)
    width = runs[0].shape[1]
    assert width // capacity in SEEN_PIECE_RUNGS and width % capacity == 0
    for run in runs:
        assert run.shape == (4, width) and run.dtype == np.int32
    if pieces <= top:
        assert attrs["chunks"] == pieces
        assert width >= cells and (pieces == 1 or width < 2 * cells)
    else:
        assert width == top * capacity and len(runs) * width < 2 * cells


@pytest.mark.parametrize("name", SEEN_CASES)
def test_device_built_rectangle_bit_equals_host_oracle(name, monkeypatch):
    # the serve path's rectangle: host grouping, the list padded to a rung
    # of pieces of a fixed capacity, ONE run of the engine's jitted scatter
    from cfk_tpu.serving.engine import _seen_tiles_jit_fn

    lists, rows, m, tile, capacity, pieces, width = _seen_case(name)
    movies, indptr = _csr(lists)
    kw = dict(num_movies=m, tile_m=tile, num_tiles=-(-m // tile) + 1)
    want = build_seen_tiles(movies, indptr, rows, **kw)
    cells, shape = group_seen_cells(movies, indptr, rows, **kw)
    assert shape == want.shape == (kw["num_tiles"], len(rows), width)
    assert cells.shape[1] == int((want != tile).sum())
    runs, attrs = one_program_runs(monkeypatch, cells, shape, capacity)
    assert_one_program_shape(runs, attrs, cells.shape[1], capacity)
    if pieces is not None:
        assert attrs["chunks"] == pieces
    (run,) = runs
    got = _seen_tiles_jit_fn()(jnp.asarray(run), None, shape=shape,
                               tile_m=tile)
    assert got.slots.dtype == got.hits.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got.slots), want)
    # a tile is hit where the rectangle holds a cell, and nowhere else
    np.testing.assert_array_equal(np.asarray(got.hits),
                                  (want != tile).any(axis=(1, 2)))


# pieces of eight cells a list is made to hold: none, one, every rung of the
# ladder and between two, one past the top rung (the top program twice, the
# second nearly empty), three times the top; a last piece one cell short,
# full, and one cell into the next; prewarm's (an array of nothing but fill)
PIECE_CASES = {
    "no_cells": (0, False), "one_piece": (8, False),
    "two_pieces": (16, False), "three_pieces": (24, False),
    "four_pieces": (32, False), "five_pieces": (40, False),
    "eight_pieces": (64, False), "sixteen_pieces": (128, False),
    "top_rung_and_one": (136, False), "three_times_the_top": (384, False),
    "last_piece_one_short": (31, False), "last_piece_one_over": (129, False),
    "warm_few_cells": (5, True), "warm_past_the_top": (200, True),
}


def piece_case_lists(cells: int, users: int = 8, movies: int = 600):
    """Seen lists of ``users`` users over ``movies`` movies with ``cells``
    cells in all, and as many again at or past ``movies``, which the
    grouping drops."""
    rng = np.random.default_rng(cells)
    sizes = np.full(users, cells // users)
    sizes[:cells % users] += 1
    return [np.concatenate([
        np.sort(rng.choice(movies, size=int(n), replace=False)),
        movies + np.arange(int(n))]) for n in sizes]


@pytest.mark.parametrize("name", PIECE_CASES)
def test_one_program_build_bit_equals_host_oracle(name, monkeypatch):
    """However many pieces the batch's cell list holds, the rectangle is
    ``build_seen_tiles``' to the bit: from one run of one program up to
    the top rung, from the top rung's program run again on its own result
    past it."""
    from cfk_tpu.serving.engine import _seen_tiles_jit_fn
    from cfk_tpu.serving.topk_kernel import SEEN_PIECE_RUNGS

    n, warm = PIECE_CASES[name]
    movies, indptr = _csr(piece_case_lists(n))
    kw = dict(num_movies=600, tile_m=16, num_tiles=39)
    want = build_seen_tiles(movies, indptr, np.arange(8), **kw)
    cells, shape = group_seen_cells(movies, indptr, np.arange(8), **kw)
    assert cells.shape[1] == n == int((want != 16).sum())
    runs, attrs = one_program_runs(monkeypatch, cells, shape, 8, warm=warm)
    if warm:  # both programs of the top rung, whatever the cells
        top = SEEN_PIECE_RUNGS[-1]
        assert [r.shape for r in runs] == [(4, top * 8)] * max(
            2, -(-n // (top * 8)))
    else:
        assert_one_program_shape(runs, attrs, n, 8)
    got = None
    for run in runs:
        got = _seen_tiles_jit_fn()(jnp.asarray(run), got, shape=shape,
                                   tile_m=16)
    np.testing.assert_array_equal(np.asarray(got.slots), want)
    np.testing.assert_array_equal(np.asarray(got.hits),
                                  (want != 16).any(axis=(1, 2)))


@pytest.mark.parametrize("name", SEEN_CASES)
def test_engine_answers_equal_dense_oracle_through_device_rectangle(
        name, monkeypatch):
    from cfk_tpu.serving import engine as engine_mod

    lists, rows, m, tile, capacity, pieces, _ = _seen_case(name)
    rng = np.random.default_rng(7)
    uf = rng.standard_normal((len(lists), 8)).astype(np.float32)
    mf = rng.standard_normal((m, 8)).astype(np.float32)
    movies, indptr = _csr(lists)
    eng = engine_mod.ServeEngine(
        uf, mf, num_users=len(lists), num_movies=m, seen_movies=movies,
        seen_indptr=indptr, tile_m=tile, batch_quantum=4,
    )
    monkeypatch.setattr(engine_mod, "seen_cell_capacity", lambda b: capacity)
    seen_chunks = []
    real = engine_mod._seen_chunks
    monkeypatch.setattr(
        engine_mod, "_seen_chunks",
        lambda *a: seen_chunks.append(real(*a)) or seen_chunks[-1])
    k = 6
    vals, ids = eng.topk(rows, k)
    # one array, one run of the program, whatever the pieces
    assert len(seen_chunks) == 1 and len(seen_chunks[0]) == 1
    if pieces is not None:
        assert seen_chunks[0][0].shape == (
            4, engine_mod.seen_piece_rung(pieces) * capacity)
    seen = [np.asarray(lists[r], np.int64) for r in rows]
    seen = [x[x < m] for x in seen]
    ov, oi = _dense_oracle(uf[rows], mf, seen, k)
    np.testing.assert_array_equal(ids, oi)
    np.testing.assert_allclose(vals, ov, rtol=0, atol=_DOT_ATOL)
    # and bit for bit what the kernel gives over the host-built rectangle
    b = engine_mod._pow2_ceil(len(rows), 4)
    m2, i2 = _csr([lists[r] for r in rows] + [[]] * (b - len(rows)))
    st = build_seen_tiles(m2, i2, np.arange(b), num_movies=m, tile_m=tile,
                          num_tiles=eng.table_rows // tile)
    u = np.zeros((b, 8), np.float32)
    u[: len(rows)] = uf[rows]
    kv, ki = topk_scores_pallas(
        jnp.asarray(u), eng._table[0], None, jnp.asarray(st), k_top=k,
        num_movies=m, tile_m=tile,
    )
    np.testing.assert_array_equal(vals, np.asarray(kv)[: len(rows)])
    np.testing.assert_array_equal(ids, np.asarray(ki)[: len(rows)])


def host_built_seen_tiles(engine, chunks, shape):
    """``ServeEngine._seen_tiles`` as the serve path had it before the
    device built the rectangle: numpy fills it, the whole of it is
    uploaded."""
    if chunks is None:
        return None
    rect = np.full(shape, engine.tile_m, np.int32)
    for cells in map(np.asarray, chunks):
        cells = cells[:, cells[0] < shape[0]]
        rect[cells[0], cells[1], cells[2]] = cells[3]
    return jnp.asarray(rect)


@pytest.mark.parametrize("pieces", [None, 3, 40],
                         ids=["one_piece", "three_pieces", "past_the_top"])
@pytest.mark.parametrize("caller", ["exact", "item_sharded"])
def test_every_caller_serves_from_the_device_built_rectangle(
        caller, pieces, rng, monkeypatch):
    # one grouping, one device builder: the one-device scan and the sharded
    # scan give, bit for bit, the answers they give over a rectangle the
    # host built from the same cell list — also
    # for a batch of three pieces (one run of the rung of four) and of forty
    # (three runs of the top rung's program)
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.serving import engine as engine_mod

    users, movies = 24, 300
    uf = rng.standard_normal((users, 8)).astype(np.float32)
    mf = rng.standard_normal((movies, 8)).astype(np.float32)
    movies_csr, indptr = _csr([
        np.sort(rng.choice(movies, size=int(rng.integers(0, 40)),
                           replace=False)) for _ in range(users)])
    eng = engine_mod.ServeEngine(
        uf, mf, num_users=users, num_movies=movies, seen_movies=movies_csr,
        seen_indptr=indptr, tile_m=16, batch_quantum=8,
        mesh=make_mesh(2) if caller == "item_sharded" else None,
    )
    rows = rng.integers(0, users, size=13)
    if pieces is not None:
        cells = int(np.diff(indptr)[rows].sum())
        monkeypatch.setattr(engine_mod, "seen_cell_capacity",
                            lambda b: -(-cells // pieces))
    programs = []
    real = engine_mod._seen_chunks

    def counting(*a):
        runs = real(*a)
        programs.append(len(runs))
        return runs

    monkeypatch.setattr(engine_mod, "_seen_chunks", counting)
    vals, ids = eng.topk(rows, 5)
    assert programs == [{None: 1, 3: 1, 40: 3}[pieces]]
    monkeypatch.setattr(engine_mod.ServeEngine, "_seen_tiles",
                        host_built_seen_tiles)
    want_vals, want_ids = eng.topk(rows, 5)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(vals, want_vals)
    for row, got in zip(rows, ids):
        mine = movies_csr[indptr[row]: indptr[row + 1]]
        assert not set(got.tolist()) & set(mine.tolist())


def test_int8_engine_bit_identical_to_kernel(rng):
    # the engine's int8 answers are the kernel's own over the same table,
    # quantized and assembled by hand
    from cfk_tpu.ops.quant import quantize_table
    from cfk_tpu.serving import ServeEngine, pad_table

    users, movies, rank, per_user = 48, 512, 16, 6
    uf = rng.standard_normal((users, rank)).astype(np.float32)
    mf = rng.standard_normal((movies, rank)).astype(np.float32)
    sm = np.sort(rng.integers(0, movies, size=(users, per_user)),
                 axis=1).astype(np.int32).ravel()
    si = np.arange(users + 1, dtype=np.int64) * per_user
    eng = ServeEngine(
        uf, mf, num_users=users, num_movies=movies, seen_movies=sm,
        seen_indptr=si, table_dtype="int8", tile_m=64, batch_quantum=8)
    vals, ids = eng.topk(np.arange(8), 10)
    data, scale = quantize_table(jnp.asarray(pad_table(mf, 64, 1)), "int8")
    st = build_seen_tiles(sm, si[:9], np.arange(8), num_movies=movies,
                          tile_m=64, num_tiles=data.shape[0] // 64)
    ev, ei = topk_scores_pallas(
        jnp.asarray(uf[:8]), data, scale, jnp.asarray(st), k_top=10,
        num_movies=movies, tile_m=64,
    )
    np.testing.assert_array_equal(vals, np.asarray(ev))
    np.testing.assert_array_equal(ids, np.asarray(ei))


def _tiny_model(seed=0):
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models.als import train_als

    ds = Dataset.from_coo(synthetic_netflix_coo(60, 30, 900, seed=seed))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        model = train_als(ds, ALSConfig(rank=4, num_iterations=3))
    return ds, model


def test_engine_matches_recommend_oracle(rng):
    from cfk_tpu.eval.recommend import recommend_top_k
    from cfk_tpu.serving import engine_from_model

    ds, model = _tiny_model()
    eng = engine_from_model(model, ds, tile_m=16)
    rows = np.arange(12)
    s1, i1 = eng.topk(rows, 5)
    s2, i2 = recommend_top_k(model, rows, 5, dataset=ds)
    np.testing.assert_allclose(s1, s2, rtol=0, atol=_DOT_ATOL)
    np.testing.assert_array_equal(i1, i2)


def test_engine_validation():
    from cfk_tpu.serving import ServeEngine

    eng = ServeEngine(np.zeros((4, 8), np.float32),
                      np.zeros((20, 8), np.float32),
                      num_users=4, num_movies=20, tile_m=16)
    with pytest.raises(ValueError, match="out of range"):
        eng.topk(np.asarray([7]), 3)
    with pytest.raises(ValueError, match="k must be"):
        eng.topk(np.asarray([1]), 21)
    with pytest.raises(ValueError, match="scale required"):
        topk_scores_pallas(jnp.zeros((4, 8)), jnp.zeros((16, 8)),
                           jnp.zeros((16,)), None, k_top=2, num_movies=16,
                           tile_m=16)


def test_server_round_trip_and_coalescing():
    from cfk_tpu.serving import (
        RecommendServer,
        ServeClient,
        engine_from_model,
        ensure_serve_topics,
    )
    from cfk_tpu.transport import InMemoryBroker

    ds, model = _tiny_model()
    eng = engine_from_model(model, ds, tile_m=16)
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(eng, broker)
    client = ServeClient(broker)
    got = client.ask([3, 5, 9, 2], 4, server=server)
    assert len(got) == 4
    # everything pending coalesced into ONE scoring batch
    assert server.batches == 1
    s, i = eng.topk(np.asarray([5]), 4)
    # req_ids are monotone per client, so sorted(got) is request order
    resp = got[sorted(got)[1]]
    np.testing.assert_array_equal(resp.movie_rows, i[0])
    np.testing.assert_array_equal(resp.scores, s[0])
    # per-request k is honored inside a shared batch
    mixed = client.ask([1], 2, server=server)
    assert next(iter(mixed.values())).movie_rows.shape == (2,)
    # an out-of-range user gets an error response, co-batched neighbors
    # still succeed
    bad = client.request(10_000, 4)
    good = client.request(3, 4)
    client.flush()
    server.step()
    by_id = {r.req_id: r for r in client.poll_responses()}
    assert by_id[bad].error and by_id[bad].movie_rows.size == 0
    assert not by_id[good].error and by_id[good].movie_rows.size == 4


def test_serve_frames_round_trip():
    from cfk_tpu.transport.serdes import (
        ScoreRequest,
        ScoreResponse,
        decode_score_request,
        decode_score_response,
        encode_score_request,
        encode_score_response,
    )

    req = ScoreRequest(req_id=7, user=123, k=10, reply_partition=3)
    assert decode_score_request(encode_score_request(req)) == req
    resp = ScoreResponse(
        req_id=7, movie_rows=np.asarray([4, -1], np.int32),
        scores=np.asarray([1.5, -np.inf], np.float32), error="",
    )
    back = decode_score_response(encode_score_response(resp))
    assert back.req_id == 7 and back.error == ""
    np.testing.assert_array_equal(back.movie_rows, resp.movie_rows)
    np.testing.assert_array_equal(back.scores, resp.scores)
    with pytest.raises(ValueError):
        decode_score_request(b"\x00" * 3)
    with pytest.raises(ValueError):
        decode_score_response(b"\x00" * 20)


def test_hot_user_cache_reserves_foldin_commits(tmp_path):
    # the tier-1 single-threaded version of chaos_lab's serve_under_foldin:
    # after a StreamSession commit, the attached engine serves scores
    # bit-identical to scoring the committed factors, and the just-rated
    # movie disappears from that user's top-K
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.serving import ServeEngine, engine_from_model
    from cfk_tpu.streaming import StreamConfig, StreamProducer, StreamSession
    from cfk_tpu.transport import InMemoryBroker
    from cfk_tpu.transport.checkpoint import CheckpointManager

    ds, model = _tiny_model()
    cfg = ALSConfig(rank=4, num_iterations=3, health_check_every=1)
    broker = InMemoryBroker()
    prod = StreamProducer(broker)
    victim_raw = int(ds.user_map.raw_ids[0])
    vrow = int(ds.user_map.to_dense(np.asarray([victim_raw]))[0])
    rated_raw = int(ds.movie_map.raw_ids[4])
    rated_row = int(ds.movie_map.to_dense(np.asarray([rated_raw]))[0])
    prod.send(victim_raw, rated_raw, 5.0)
    eng = engine_from_model(model, ds, tile_m=16)
    before, _ = eng.topk(np.asarray([vrow]), 5)
    sess = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=8), base_model=model,
    )
    eng.attach_session(sess)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        sess.run()
    assert eng.invalidations >= 1
    after_s, after_i = eng.topk(np.asarray([vrow]), 5)
    # freshness: bit-identical to a fresh engine over the live factors
    live = ServeEngine(
        sess.user_factors, np.asarray(sess.movie_factors),
        num_users=sess.state.num_users, num_movies=eng.num_movies,
        seen_movies=eng._seen_movies, seen_indptr=eng._seen_indptr,
        tile_m=16,
    )
    live._extend_seen([(vrow, rated_row)])
    want_s, want_i = live.topk(np.asarray([vrow]), 5)
    np.testing.assert_array_equal(after_s, want_s)
    np.testing.assert_array_equal(after_i, want_i)
    # the factors actually moved and the just-rated movie is excluded
    assert not np.array_equal(after_s, before)
    assert rated_row not in after_i[0].tolist()


def test_loadgen_open_loop_report():
    from cfk_tpu.serving import (
        RecommendServer,
        ServeClient,
        engine_from_model,
        ensure_serve_topics,
        run_open_loop,
        zipf_user_rows,
    )
    from cfk_tpu.transport import InMemoryBroker

    ds, model = _tiny_model()
    eng = engine_from_model(model, ds, tile_m=16)
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(eng, broker, max_batch=8)
    client = ServeClient(broker)
    client.ask([0], 3, server=server)  # warm
    rep = run_open_loop(
        client, rate_qps=2000.0, num_requests=20,
        user_rows=zipf_user_rows(eng.num_users, 20, seed=3), k=3,
        server=server, drive_server=True,
    )
    row = rep.as_row()
    assert row["answered"] == 20
    assert row["qps"] > 0
    assert row["p50_ms"] <= row["p99_ms"] <= row["max_ms"]
    assert rep.batches >= 1


def test_serve_roofline_row_fields():
    from cfk_tpu.utils.roofline import serve_batch_cost, serve_roofline_row

    cost = serve_batch_cost(59_047, 128, 256, 100, table_dtype="int8",
                            m_pad=59_392)
    row = serve_roofline_row(cost, 0.01, table_dtype="int8",
                             device_kind="TPU v5 lite")
    assert row["vs_roofline"] > 0
    assert row["table_dtype"] == "int8"
    # int8 quarters the table scan vs f32 (+ the per-row scale)
    f32 = serve_batch_cost(59_047, 128, 256, 100, table_dtype="float32",
                           m_pad=59_392)
    assert cost.hbm_bytes < 0.3 * f32.hbm_bytes


def test_cli_serve_loadgen_mode(tmp_path, capsys):
    # self-contained `cfk_tpu serve` (no --broker): restore factors from a
    # checkpoint, run the built-in open-loop loadgen against the in-memory
    # log, print the QPS/p50/p99 row — no reference data needed
    import json

    from cfk_tpu.cli import main
    from cfk_tpu.transport.checkpoint import CheckpointManager

    ds, model = _tiny_model()
    csv = tmp_path / "ratings.csv"
    coo = ds.coo_dense
    with open(csv, "w") as f:
        f.write("userId,movieId,rating,timestamp\n")
        for u, m, r in zip(ds.user_map.raw_ids[coo.user_raw],
                           ds.movie_map.raw_ids[coo.movie_raw],
                           coo.rating):
            f.write(f"{u},{m},{r},0\n")
    ck = tmp_path / "ck"
    ck.mkdir()
    mgr = CheckpointManager(str(ck))
    mgr.save(3, model.user_factors, model.movie_factors,
             meta={"model": "als", "rank": 4, "num_shards": 1})
    mgr.wait_pending()
    rc = main([
        "serve", "--data", str(csv), "--format", "movielens",
        "--checkpoint-dir", str(ck), "--tile-m", "16", "-k", "5",
        "--loadgen-qps", "500", "--loadgen-requests", "16",
    ])
    assert rc == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["answered"] == 16
    assert row["k"] == 5
    assert row["p50_ms"] >= 0


def test_malformed_request_frame_skipped_not_wedged():
    # review fix: a poison frame must be skipped WITH the cursor advanced
    # — re-raising before the cursor moved would wedge every restart on
    # the same offset, denying service to all clients forever
    from cfk_tpu.serving import (
        RecommendServer,
        ServeClient,
        engine_from_model,
        ensure_serve_topics,
    )
    from cfk_tpu.transport import InMemoryBroker

    ds, model = _tiny_model()
    eng = engine_from_model(model, ds, tile_m=16)
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(eng, broker)
    client = ServeClient(broker)
    broker.produce("serve-requests", key=0, value=b"\x01\x02\x03",
                   partition=0)
    got = client.ask([3], 4, server=server)
    assert len(got) == 1 and not next(iter(got.values())).error
    assert server.malformed_requests == 1
    # the poison offset is consumed: an idle step re-reads nothing
    assert server.step() == 0
    assert server.malformed_requests == 1


def test_commit_event_carries_committed_dtype_rows(tmp_path):
    # review fix: a bf16-dtype session's commit events must publish the
    # COMMITTED (dtype-rounded) rows — a listener caching the pre-cast
    # f32 solve would serve scores no post-crash engine could reproduce
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.streaming import StreamConfig, StreamProducer, StreamSession
    from cfk_tpu.transport import InMemoryBroker
    from cfk_tpu.transport.checkpoint import CheckpointManager

    ds, model = _tiny_model()
    cfg = ALSConfig(rank=4, num_iterations=3, dtype="bfloat16")
    broker = InMemoryBroker()
    prod = StreamProducer(broker)
    prod.send(int(ds.user_map.raw_ids[0]), int(ds.movie_map.raw_ids[1]), 5.0)
    sess = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=8), base_model=model,
    )
    events = []
    sess.add_commit_listener(events.append)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        sess.run()
    assert len(events) == 1
    rows = events[0]["rows"]
    touched = events[0]["touched_rows"]
    # bit-identical to the committed factor table (bf16 round-trip), i.e.
    # every published row survives the cast unchanged
    np.testing.assert_array_equal(
        rows, np.asarray(sess.user_factors[np.asarray(touched)], np.float32)
    )
    assert rows.dtype == np.float32


def test_hostile_request_frames_fuzz_batch_isolation():
    # serdes fuzz (ISSUE 18): malformed, truncated, and oversized request
    # frames co-batched with valid ones — every valid request is answered,
    # every hostile frame is counted + skipped, and the serve loop stays
    # alive (no exception, no wedged cursor)
    from cfk_tpu.serving import (
        RecommendServer,
        ServeClient,
        engine_from_model,
        ensure_serve_topics,
    )
    from cfk_tpu.transport import InMemoryBroker
    from cfk_tpu.transport.serdes import ScoreRequest, encode_score_request

    ds, model = _tiny_model()
    eng = engine_from_model(model, ds, tile_m=16)
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(eng, broker)
    client = ServeClient(broker)
    good = encode_score_request(ScoreRequest(req_id=1, user=3, k=4))
    hostile = [
        b"",                      # empty
        b"\x00",                  # 1 byte
        good[:11],                # truncated header
        good + b"\xff" * 9,       # oversized (trailing junk)
        bytes(255 for _ in range(len(good))),  # right length, hostile bits
        b"\x00" * 1024,           # oversized zeros
    ]
    rng = np.random.default_rng(7)
    hostile += [bytes(rng.integers(0, 256, size=int(n), dtype=np.uint8))
                for n in rng.integers(1, 64, size=10) if int(n) != 24]
    valid_ids = []
    for i, frame in enumerate(hostile):
        valid_ids.append(client.request(i % eng.num_users, 3))
        broker.produce("serve-requests", key=0, value=frame, partition=0)
    client.flush()
    while server.step():
        pass
    by_id = {r.req_id: r for r in client.poll_responses()}
    # every VALID co-batched request answered, no errors
    assert set(valid_ids) <= set(by_id)
    assert all(not by_id[rid].error for rid in valid_ids)
    # every hostile frame skipped and counted, none re-read
    assert server.malformed_requests == len(hostile)
    assert server.step() == 0
    assert server.malformed_requests == len(hostile)
    # the "right length, hostile bits" frame may have decoded into an
    # insane ScoreRequest — that one gets a per-request ERROR response
    # (validation), which must not have poisoned anything above


def test_hostile_frame_fuzz_decoders_raise_value_error_only():
    # every truncation/corruption of a valid frame either round-trips or
    # raises ValueError — never struct.error/IndexError/segfault-bait —
    # for all three serving codecs (request, response, factor delta)
    from cfk_tpu.transport.serdes import (
        ScoreRequest,
        ScoreResponse,
        decode_factor_delta,
        decode_score_request,
        decode_score_response,
        encode_factor_delta,
        encode_score_request,
        encode_score_response,
        make_factor_delta,
    )

    rng = np.random.default_rng(11)
    frames = [
        (decode_score_request,
         encode_score_request(ScoreRequest(req_id=9, user=4, k=7))),
        (decode_score_response,
         encode_score_response(ScoreResponse(
             req_id=9, movie_rows=np.arange(5, dtype=np.int32),
             scores=np.arange(5, dtype=np.float32), error="x",
             retriable=True, epoch=3, staleness=2))),
        (decode_factor_delta,
         encode_factor_delta(make_factor_delta(
             1, 4, "rows", num_users=8, user_rows=[2, 5],
             user_factors=np.ones((2, 3), np.float32),
             lazy_user_rows=[7], cells=[(2, 1)], rank=3))),
    ]
    for decode, frame in frames:
        for cut in range(len(frame)):
            try:
                decode(frame[:cut])
            except ValueError:
                pass
        for _ in range(50):
            mutated = bytearray(frame)
            for pos in rng.integers(0, len(frame), size=3):
                mutated[pos] ^= int(rng.integers(1, 256))
            try:
                decode(bytes(mutated))
            except ValueError:
                pass
        with pytest.raises(ValueError):
            decode(frame + b"\x01")


def test_factor_delta_round_trip():
    from cfk_tpu.transport.serdes import (
        decode_factor_delta,
        encode_factor_delta,
        make_factor_delta,
    )

    rng = np.random.default_rng(5)
    d = make_factor_delta(
        2, 17, "rows", num_users=100, user_rows=[3, 9, 41],
        user_factors=rng.standard_normal((3, 6)).astype(np.float32),
        lazy_user_rows=[55, 60], cells=[(3, 7), (9, 1)],
        movie_rows=[4], movie_factors=rng.standard_normal((1, 6)),
    )
    back = decode_factor_delta(encode_factor_delta(d))
    assert (back.epoch, back.seq, back.kind) == (2, 17, "rows")
    assert back.num_users == 100
    np.testing.assert_array_equal(back.user_rows, d.user_rows)
    np.testing.assert_array_equal(back.user_factors, d.user_factors)
    np.testing.assert_array_equal(back.lazy_user_rows, d.lazy_user_rows)
    np.testing.assert_array_equal(back.cells, d.cells)
    np.testing.assert_array_equal(back.movie_rows, d.movie_rows)
    np.testing.assert_array_equal(back.movie_factors, d.movie_factors)
    # epoch announcement: no factors in-frame (snapshot lives in the store)
    e = make_factor_delta(3, 18, "epoch", num_users=100)
    back = decode_factor_delta(encode_factor_delta(e))
    assert back.kind == "epoch" and back.user_rows.size == 0
    with pytest.raises(ValueError, match="kind"):
        encode_factor_delta(make_factor_delta(1, 1, "nope"))


# --- one loop for requests and the stream (PR 34) ---------------------------


def _stream_stack(tmp_path, *, max_batch=8, batch_records=8, table_dtype=None,
                  seed=5):
    """A seeded catalogue (300 users, 200 items, rank 8) served by an engine
    whose request server also drives a stream session built from the seen
    lists' CSR and folding in against the engine's own table."""
    import types

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.serving import (
        RecommendServer,
        ServeClient,
        ServeEngine,
        ensure_serve_topics,
    )
    from cfk_tpu.streaming import (
        StreamConfig,
        StreamProducer,
        StreamSession,
        StreamState,
    )
    from cfk_tpu.transport import InMemoryBroker
    from cfk_tpu.transport.checkpoint import CheckpointManager

    rng = np.random.default_rng(seed)
    users_n, items_n, rank = 300, 200, 8
    lens = rng.integers(1, 9, users_n)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    items = np.concatenate([np.sort(rng.choice(items_n, n, replace=False))
                            for n in lens]).astype(np.int32)
    values = rng.integers(1, 6, items.size).astype(np.float32)
    u_tab = ((rng.random((users_n, rank)) - 0.5) * 0.35).astype(np.float32)
    m_tab = ((rng.random((items_n, rank)) - 0.5) * 0.35).astype(np.float32)
    engine = ServeEngine(
        u_tab, m_tab, num_users=users_n, num_movies=items_n,
        seen_movies=items, seen_indptr=indptr, tile_m=64,
        table_dtype=table_dtype)
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    s = types.SimpleNamespace(
        indptr=indptr, items=items, values=values, u_tab=u_tab, m_tab=m_tab,
        engine=engine, broker=broker, lam=0.05, k=5,
        producer=StreamProducer(broker), client=ServeClient(broker))
    s.session = StreamSession(
        StreamState.from_csr(indptr, items, values, num_movies=items_n),
        ALSConfig(rank=rank, lam=s.lam, health_check_every=1), broker,
        CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=batch_records),
        base_model=types.SimpleNamespace(user_factors=u_tab), engine=engine)
    s.events = []
    s.session.add_commit_listener(s.events.append)
    s.server = RecommendServer(engine, broker, max_batch=max_batch,
                               session=s.session)
    return s


def test_one_item_table_on_the_device_with_a_session_attached(tmp_path):
    import jax

    # arrays of the table's extent: another test's may linger in the process
    # (``foldin._zero_systems`` keeps a [256, 8] of zeros for good)
    tables = lambda: sum(a.shape == (256, 8) for a in jax.live_arrays())
    before = tables()
    s = _stream_stack(tmp_path)
    table = s.engine._table[0]
    # the session keeps none of its own: it folds in against the engine's
    assert s.session._m is None and s.session.movie_factors is table
    assert s.engine.fold_table() is s.engine._table
    assert table.shape == (256, 8) and tables() == before + 1
    s.producer.send(3, 7, 5.0)
    assert s.server.step() == 0 and s.session.stream_step == 1
    while s.session.in_flight:  # published once the store has renamed it
        s.server.step()
    assert s.engine.commit_ordinal == 1 and s.engine._table[0] is table
    assert tables() == before + 1
    # the base user table is the caller's, shared by engine and session
    assert s.session._users.base is s.u_tab
    assert np.shares_memory(s.engine._u_base, s.u_tab)
    # a quantized table is folded in against as the engine holds it (ISSUE
    # 47; tests/test_foldin_q8.py has the mesh's refusal)
    q = _stream_stack(tmp_path / "q", table_dtype="int8")
    assert q.session._fixed() is q.engine._table


def _as_of(s, user, ordinal, sent):
    """(the user's vector, its list) as of a commit ordinal, from what the
    commit listener saw and the plain reference."""
    from benchmarks.harness import reference_foldin

    vec = s.u_tab[user]
    for e in s.events:
        if e["stream_step"] <= ordinal and user in e["touched_rows"]:
            vec = e["rows"][e["touched_rows"].index(user)]
    committed = {(row, mv): e["stream_step"] for e in s.events
                 for row, mv in e["cells"]}
    lo, hi = s.indptr[user], s.indptr[user + 1]
    mine = [(mv, rt, committed.get((u, mv), np.inf))
            for u, mv, rt in sent if u == user]
    return vec, reference_foldin.list_as_of(
        s.items[lo:hi], s.values[lo:hi], mine, ordinal)


def _against_the_reference(s, asked, got, sent):
    """Every answer against the plain reference as of the ordinal it names:
    (worst rank gap, worst score error, worst folded-in row error, answers
    scored with a folded-in row)."""
    from benchmarks.harness import reference_foldin

    worst_gap = worst_score = worst_row = 0.0
    touched_answers = 0
    for rid, resp in got.items():
        assert not resp.error
        user = asked[rid]
        vec, (mine, ratings) = _as_of(s, user, resp.ordinal, sent)
        assert reference_foldin.invalid_id_sets(
            [resp.movie_rows], [mine], 200, s.k) == 0
        best, scores = reference_foldin.exact_topk(vec[None], s.m_tab,
                                                   [mine], s.k)
        gap, err = reference_foldin.topk_gaps(
            resp.movie_rows[None], resp.scores[None], best, scores)
        worst_gap, worst_score = max(worst_gap, gap), max(worst_score, err)
        if vec is not s.u_tab[user] and not np.array_equal(vec,
                                                           s.u_tab[user]):
            touched_answers += 1
            exact = reference_foldin.solve_row(s.m_tab, mine, ratings, s.lam)
            worst_row = max(worst_row, reference_foldin.row_err(vec, exact))
    return worst_gap, worst_score, worst_row, touched_answers


def test_answers_served_during_a_stream_against_the_plain_reference(tmp_path):
    """Every answer of a server that folds a stream in between its batches
    is the exact top-K of the user's vector as of the commit ordinal it
    names, with the list as of that ordinal excluded; every folded-in row is
    the reference's solve."""
    from benchmarks.harness import reference_foldin

    s = _stream_stack(tmp_path)
    rng = np.random.default_rng(9)
    sent, asked, got = [], {}, {}
    for round_ in range(12):
        for _ in range(6):
            u, mv = int(rng.integers(0, 300)), int(rng.integers(0, 200))
            rt = float(rng.integers(1, 6))
            s.producer.send(u, mv, rt)
            sent.append((u, mv, rt))
        # more than max_batch requests a round: a backlog, a batch in
        # flight; a third of them from users who have just rated
        for i in range(12):
            u = sent[-1 - i][0] if i < 4 else int(rng.integers(0, 300))
            asked[s.client.request(u, s.k)] = u
        s.server.step()
        got.update((r.req_id, r) for r in s.client.poll_responses())
    while len(got) < len(asked) or s.session.backlog() or s.session.in_flight:
        s.server.step()
        got.update((r.req_id, r) for r in s.client.poll_responses())
    assert s.session.stream_step == len(s.events) >= 9
    assert s.server.metrics.counters.get("serve_batches_overlapped", 0) > 0
    ordinals = [got[rid].ordinal for rid in sorted(got)]
    assert ordinals == sorted(ordinals) and len(set(ordinals)) > 3
    assert ordinals[-1] <= s.session.stream_step
    worst_gap, worst_score, worst_row, touched_answers = \
        _against_the_reference(s, asked, got, sent)
    assert touched_answers >= 20
    assert worst_gap <= 1e-5 and worst_score <= 2e-5 and worst_row < 1e-5


def test_a_burst_of_ratings_is_folded_in_several_batches_a_step(tmp_path):
    """Five micro-batches' worth of ratings arrive while a request batch is
    on the device: the server's steps hand over three fold-ins at once, each
    staged over the ones before (the same users rate again and again), wait
    behind no scorer, and every answer in between is exact as of the
    ordinal it names."""
    s = _stream_stack(tmp_path)
    rng = np.random.default_rng(2)
    sent, asked, got = [], {}, {}

    def ask(n):
        for _ in range(n):
            u = int(rng.integers(0, 12))
            asked[s.client.request(u, s.k)] = u

    ask(16)
    assert s.server.step() == 0 and s.server._in_flight.on_device
    for _ in range(40):
        u, mv = int(rng.integers(0, 12)), int(rng.integers(0, 200))
        rt = float(rng.integers(1, 6))
        s.producer.send(u, mv, rt)
        sent.append((u, mv, rt))
    depth, commits = [], []
    while len(got) < len(asked) or s.session.backlog() or s.session.in_flight:
        if len(asked) < 64:
            ask(8)
        s.server.step()
        depth.append(len(s.session._in_flight))
        commits.append(s.session.stream_step)
        got.update((r.req_id, r) for r in s.client.poll_responses())
    # three handed over in the first step, committed in the second with the
    # last two handed over, those committed in the third
    assert depth[:3] == [3, 2, 0] and commits[:3] == [0, 3, 5]
    assert s.server.metrics.counters.get("serve_batches_overlapped", 0) > 2
    # a unit is published when the store has renamed it, whichever step
    # that is: the answers in between name ordinals in order, and one asked
    # after the last publication names the last
    ask(8)
    while len(got) < len(asked):
        s.server.step()
        got.update((r.req_id, r) for r in s.client.poll_responses())
    ordinals = [got[rid].ordinal for rid in sorted(got)]
    assert ordinals == sorted(ordinals) and {0, 5} <= set(ordinals)
    assert s.engine.commit_ordinal == s.session.published_step == 5
    gap, err, row, touched = _against_the_reference(s, asked, got, sent)
    assert touched >= 20 and gap <= 1e-5 and err <= 2e-5 and row < 1e-5


def test_ordinal_and_read_your_writes_with_a_batch_in_flight(tmp_path):
    """A batch names the ordinal it was STAGED against, captured with its
    rows and seen lists under the engine's lock: one in flight across a
    commit is answered as of the ordinal before; a request polled after the
    commit was published sees the rating."""
    s = _stream_stack(tmp_path)
    user = 11
    lo, hi = s.indptr[user], s.indptr[user + 1]
    unseen = [m for m in range(200) if m not in set(s.items[lo:hi])]
    base_s, base_i = s.engine.topk(np.asarray([user]), s.k)
    rated = int(base_i[0, 0])  # the user's best item: rating it hides it
    assert rated in unseen
    # a backlog: 8 requests polled and handed over, 8 waiting
    first = [s.client.request(user if i == 0 else 20 + i, s.k)
             for i in range(16)]
    assert s.server.step() == 0 and s.server._in_flight.on_device
    # the user's rating arrives while that batch is on the device
    s.producer.send(user, rated, 1.0)
    # the next step hands the fold-in over and answers the batch staged
    # before the rating was sent: as of ordinal 0, the base answer
    assert s.server.step() == 8
    early = {r.req_id: r for r in s.client.poll_responses()}
    assert early[first[0]].ordinal == 0
    np.testing.assert_array_equal(early[first[0]].movie_rows, base_i[0])
    # the commit lands a step later at the latest (the fold-in is fetched
    # once it is ready, never waited for behind the scorer in flight)
    extra = [s.client.request(30 + i, s.k) for i in range(16)]
    steps = 0
    while s.session.stream_step < 1:
        s.server.step()
        steps += 1
    assert steps <= 2
    while s.engine.commit_ordinal < 1:  # published once it is renamed
        s.server.step()
    assert s.session.stream_step == 1
    # a request polled after the commit's listener returned sees the rating
    late_req = s.client.request(user, s.k)
    while late_req not in early or len(early) < 33:
        s.server.step()
        early.update((r.req_id, r) for r in s.client.poll_responses())
    assert early[late_req].ordinal == 1
    assert rated not in early[late_req].movie_rows.tolist()
    assert not np.array_equal(early[late_req].scores, base_s[0])
    seen = [early[rid].ordinal for rid in first + extra + [late_req]]
    assert seen == sorted(seen) and set(seen) == {0, 1}
    assert not s.session.in_flight and s.session.backlog() == 0


# -- the names the benchmark finds the programs by --------------------------
# A device trace names an XLA module after its jitted entry, and the
# benchmark's readers find the serve and fold-in programs by those names
# alone: renamed, a per-layer metric reads ``null`` on the ledger and
# nothing else notices.  Each entry lowered at toy size; the shapes are no
# other test's, so no other test's trace count sees these.

def _lowered_program(entry):
    from jax import ShapeDtypeStruct as S

    from cfk_tpu.parallel import spmd
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.serving import engine as engine_mod
    from cfk_tpu.serving.topk_kernel import SeenTiles
    from cfk_tpu.streaming import foldin

    f32, i32 = jnp.float32, jnp.int32
    b, k, tile, nt, w, k_top = 8, 24, 16, 6, 16, 7
    m, e = nt * tile, 8
    seen = SeenTiles(S((nt, b, w), i32), S((nt,), i32))
    fold = dict(lam=0.0625, solver="cholesky", reg_solve_algo="auto")
    if entry == "_topk_call":
        return engine_mod._topk_jit_fn().lower(
            S((b, k), f32), S((m, k), f32), None, seen, k_top=k_top,
            num_movies=m - 3, tile_m=tile)
    if entry == "_seen_tiles_call":
        return engine_mod._seen_tiles_jit_fn().lower(
            S((4, 32), i32), None, shape=(nt, b, w), tile_m=tile)
    if entry == "_topk_shard_call":
        return spmd._serve_topk_sharded_fn(
            make_mesh(2), m // 2, False, True, k_top, m - 3, tile,
        ).lower(S((b, k), f32), S((m, k), f32), seen)
    if entry == "_seen_tiles_shard_call":
        return spmd._serve_seen_tiles_sharded_fn(
            make_mesh(2), (nt, b, w), tile, True).lower(S((4, 32), i32))
    if entry == "_padded_fold":
        return foldin._padded_fold.lower(
            S((m, k), f32), S((e, 8), i32), S((e, 8), f32), S((e, 8), f32),
            S((e,), f32), np.int32(3), np.float32(1e3), **fold)
    if entry == "_cells_fold_gram":
        return foldin._cells_fold_gram.lower(
            S((m, k), f32), S((64, 2 * foldin.CHUNK + 2), i32),
            S((e, k, k), f32), S((e, k), f32))
    assert entry == "_cells_fold_solve"
    return foldin._cells_fold_solve.lower(
        S((e, k, k), f32), S((e, k), f32), S((e,), f32), np.int32(3),
        np.float32(1e3), **fold)


@pytest.mark.parametrize("entry", [
    # benchmarks/layer_metrics/serve_seen_device_ms.py (SCORE_PROGRAM) and
    # benchmarks/harness/shard_trace.py (SCORER: the Mosaic call's name)
    "_topk_call",
    # benchmarks/layer_metrics/serve_seen_device_ms.py (BUILD_PROGRAM)
    "_seen_tiles_call",
    # benchmarks/harness/shard_trace.py (SCORE_PROGRAM, and SCORER for the
    # call inside it, which the named scope names)
    "_topk_shard_call",
    # benchmarks/harness/shard_trace.py (BUILD_PROGRAM)
    "_seen_tiles_shard_call",
    # benchmarks/layer_metrics/foldin_device_ms.py (PROGRAM) and
    # benchmarks/harness/foldin_modules.py (FOLD_MODULES)
    "_padded_fold",
    # benchmarks/harness/foldin_modules.py (FOLD_MODULES: ``_cells_fold``)
    "_cells_fold_gram",
    "_cells_fold_solve",
])
def test_programs_carry_the_names_the_benchmark_reads(entry):
    text = _lowered_program(entry).as_text(debug_info=True)
    assert re.search(rf"module @jit_{entry}\b", text), text[:200]
    if entry == "_topk_shard_call":
        # the scorer inside the shard program runs under a scope of the
        # same name: its custom call reads ``_topk_shard_call.<n>`` in a
        # device trace, not ``shard_map.<n>``
        assert re.search(r'"_topk_shard_call/', text)


# -- departments on the request path (PR 52) ----------------------------------

def test_request_frames_are_versioned_by_length():
    """A frame without a department is the 24 bytes it always was and an old
    frame decodes as 'no department'; a department rides in 4 more."""
    import struct

    from cfk_tpu.transport.serdes import (
        ScoreRequest,
        decode_score_request,
        encode_score_request,
    )

    old = struct.pack(">qqii", 7, 123, 10, 3)  # what a client of PR 51 sends
    assert decode_score_request(old) == ScoreRequest(
        req_id=7, user=123, k=10, reply_partition=3, department=None)
    assert encode_score_request(ScoreRequest(7, 123, 10, 3)) == old
    req = ScoreRequest(req_id=7, user=123, k=10, reply_partition=3,
                       department=5)
    frame = encode_score_request(req)
    assert len(frame) == 28 and frame[:24] == old
    assert decode_score_request(frame) == req
    assert decode_score_request(encode_score_request(
        ScoreRequest(1, 2, 3, department=0))).department == 0
    with pytest.raises(ValueError, match="non-negative"):
        encode_score_request(ScoreRequest(1, 2, 3, department=-1))
    with pytest.raises(ValueError, match="department"):
        decode_score_request(struct.pack(">qqiii", 7, 123, 10, 3, -2))
    for n in (23, 25, 27, 29):
        with pytest.raises(ValueError, match="24 or 28"):
            decode_score_request(b"\x00" * n)


class _CountingBroker:
    """An ``InMemoryBroker`` that counts the reads of each requests
    partition."""

    def __init__(self):
        from cfk_tpu.transport import InMemoryBroker

        self._b = InMemoryBroker()
        self.reads: dict[int, int] = {}

    def consume(self, topic, partition, start_offset=0):
        if topic == "serve-requests":
            self.reads[partition] = self.reads.get(partition, 0) + 1
        return self._b.consume(topic, partition, start_offset)

    def __getattr__(self, name):
        return getattr(self._b, name)


def _dept_serving(*, departments=3, max_batch=4, route=True, broker=None,
                  seed=3):
    """(engine, server, client, department of each item) over a small
    catalogue whose departments come sorted."""
    from cfk_tpu.serving import (
        RecommendServer, ServeClient, ServeEngine, ensure_serve_topics)
    from cfk_tpu.transport import InMemoryBroker

    rng = np.random.default_rng(seed)
    users, items, rank = 30, 200, 8
    dept = np.sort(rng.integers(0, departments, size=items))
    eng = ServeEngine(
        rng.standard_normal((users, rank)).astype(np.float32),
        rng.standard_normal((items, rank)).astype(np.float32),
        num_users=users, num_movies=items, tile_m=16, item_department=dept)
    broker = broker if broker is not None else InMemoryBroker()
    ensure_serve_topics(broker, departments=departments if route else None)
    server = RecommendServer(eng, broker, max_batch=max_batch)
    client = ServeClient(broker, route="department" if route else "req")
    return eng, server, client, dept


def _drive(server, client):
    """Step until nothing is answered any more: {req_id: response}, and the
    answers each step returned together."""
    got, steps = {}, []
    idle = 0
    while idle < 3:
        served = server.step()
        new = client.poll_responses()
        got.update({r.req_id: r for r in new})
        if new:
            steps.append([r.req_id for r in new])
        idle = 0 if served or new else idle + 1
    return got, steps


@pytest.mark.parametrize("route", [True, False],
                         ids=["keyed_by_department", "one_partition"])
def test_a_batch_never_mixes_departments(route):
    """Requests of three departments and of none, interleaved: every step's
    answers name one department, on a topic keyed by department (full
    batches) and on one partition (the runs the log holds); every answer is
    of the department asked for."""
    eng, server, client, dept = _dept_serving(route=route)
    asked = {}
    for i in range(36):
        d = (None, 0, 1, 2)[(i // 2) % 4] if not route else (None, 0, 1, 2)[i % 4]
        asked[client.request(i % 30, 3, d)] = d
    client.flush()
    got, steps = _drive(server, client)
    assert set(got) == set(asked) and not any(r.error for r in got.values())
    for step in steps:
        assert len({asked[rid] for rid in step}) == 1
    for rid, r in got.items():
        if asked[rid] is not None:
            assert (dept[r.movie_rows] == asked[rid]).all()
    if route:  # the log did the grouping: full batches
        # nine requests of each: 4 + 4 + 1, never topped up from another
        assert max(map(len, steps)) == 4 and server.batches == 12
    else:  # runs of two
        assert max(map(len, steps)) == 2
    # a whole-catalogue answer is what an engine without departments gives
    whole = [rid for rid, d in asked.items() if d is None]
    users = [i for i, rid in enumerate(asked) if asked[rid] is None]
    vals, ids = eng.topk(np.asarray(users) % 30, 3)
    for j, rid in enumerate(whole):
        np.testing.assert_array_equal(got[rid].movie_rows, ids[j])


def test_a_topic_without_the_departments_partition_refuses_the_request():
    """Keyed by department means a partition for each and one for none:
    nothing wraps onto another department's."""
    from cfk_tpu.serving import ServeClient
    from cfk_tpu.serving.server import department_partition

    assert department_partition(None, 1) == 0
    assert [department_partition(d, 4) for d in range(3)] == [1, 2, 3]
    for department, partitions in ((3, 4), (0, 1), (-1, 4)):
        with pytest.raises(ValueError, match="has no partition"):
            department_partition(department, partitions)
    _, _, client, _ = _dept_serving(departments=3)
    with pytest.raises(ValueError, match="department 3 has no partition"):
        client.request(0, 5, department=3)
    with pytest.raises(ValueError, match="'req', 'user' or 'department'"):
        ServeClient(client.transport, route="item")


def test_the_longest_waiting_department_is_served_first():
    eng, server, client, _ = _dept_serving()
    order = [2, 0, None, 1]  # the order their first requests arrived in
    asked = {}
    for d in order:
        asked[client.request(1, 3, d)] = d
    for d in (1, None, 0, 2) * 2:
        asked[client.request(2, 3, d)] = d
    client.flush()
    _, steps = _drive(server, client)
    assert [asked[s[0]] for s in steps] == order
    assert all(len(s) == 3 for s in steps)


def test_a_department_never_asked_costs_nothing():
    broker = _CountingBroker()
    eng, server, client, _ = _dept_serving(broker=broker)
    for i in range(10):
        client.request(i, 3, 1)
    client.flush()
    got, _ = _drive(server, client)
    assert len(got) == 10
    # partition 2 (department 1) alone was ever read: no poll, no batch and
    # no program for the others
    assert set(broker.reads) == {2}
    assert server.metrics.counters["serve_dept_batches_1"] == server.batches
    assert "serve_dept_batches_0" not in server.metrics.counters


def test_an_unknown_department_is_refused_in_words_not_served_whole():
    from cfk_tpu.serving import (
        RecommendServer, ServeClient, ServeEngine, ensure_serve_topics)
    from cfk_tpu.transport import InMemoryBroker

    # (a topic not keyed by department: on a keyed one the client has no
    # partition to send department 9 to)
    eng, server, client, _ = _dept_serving(route=False)
    rid = client.request(1, 3, 9)
    big = client.request(1, 150, 0)  # more than department 0 holds
    client.flush()
    got, _ = _drive(server, client)
    assert "no department 9" in got[rid].error and not got[rid].retriable
    assert "outside [1, " in got[big].error
    # an engine that was given no departments says so
    plain = ServeEngine(np.zeros((4, 8), np.float32),
                        np.ones((40, 8), np.float32), num_users=4,
                        num_movies=40, tile_m=16)
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server, client = RecommendServer(plain, broker), ServeClient(broker)
    rid, ok = client.request(1, 3, 0), client.request(1, 3)
    client.flush()
    got, _ = _drive(server, client)
    assert "no item_department" in got[rid].error and not got[ok].error


def test_cursors_committed_per_partition_survive_a_restart():
    """At least once, as before departments: a server that dies with a batch
    in flight leaves its cursor uncommitted, and its heir, adopting every
    partition at its committed cursor, answers that batch again and the
    rest, none twice but the uncommitted one."""
    from cfk_tpu.serving import RecommendServer

    eng, server, client, _ = _dept_serving(max_batch=2)
    asked = {}
    for i in range(12):
        asked[client.request(i, 3, i % 3)] = i % 3
    client.flush()
    answered = {}
    for _ in range(3):  # two batches answered, a third in flight
        server.step()
        answered.update({r.req_id: r for r in client.poll_responses()})
    assert len(answered) == 4 and server._in_flight is not None
    committed = dict(server.committed_cursors)
    assert sum(committed.values()) == 4 and sum(server._cursors.values()) == 6
    heir = RecommendServer(eng, server.transport, max_batch=2)
    for p, cursor in committed.items():
        heir.adopt_partition(p, cursor)
    again, _ = _drive(heir, client)
    assert set(again) | set(answered) == set(asked)
    assert not set(again) & set(answered)  # what was committed is not re-served
    assert sum(heir.committed_cursors.values()) == 12


def test_two_partitions_under_a_backlog_take_turns():
    """The repair for servers without departments too: a batch is one
    partition's, the one whose head has waited longest, so partition 0
    cannot starve partition 1 whatever it holds."""
    from cfk_tpu.serving import (
        RecommendServer, ServeClient, ServeEngine, ensure_serve_topics)
    from cfk_tpu.transport import InMemoryBroker

    rng = np.random.default_rng(0)
    eng = ServeEngine(rng.standard_normal((8, 8)).astype(np.float32),
                      rng.standard_normal((60, 8)).astype(np.float32),
                      num_users=8, num_movies=60, tile_m=16)
    broker = InMemoryBroker()
    ensure_serve_topics(broker, request_partitions=2)
    server = RecommendServer(eng, broker, max_batch=4)
    client = ServeClient(broker, route="user")
    part_of = {}
    for i in range(40):  # partition 0 holds 32 requests, partition 1 eight
        user = 0 if i % 5 else 1
        part_of[client.request(user, 3)] = user % 2
    client.flush()
    _, steps = _drive(server, client)
    served = [part_of[s[0]] for s in steps]
    assert all(len({part_of[r] for r in s}) == 1 for s in steps)
    # by the arrival of each batch's head: partition 1's first request came
    # first, its fifth after partition 0's sixteenth.  The parent filled
    # every batch from partition 0 until that was empty: [0] * 8 + [1] * 2
    assert served == [1, 0, 0, 0, 0, 1, 0, 0, 0, 0]


def test_two_ranged_batches_in_flight_scan_their_own_ranges():
    """The overlap of PR 33 under departments: batch n + 1 (another
    department) is staged and handed over while batch n is on the device."""
    from cfk_tpu import telemetry

    eng, server, client, dept = _dept_serving(max_batch=4)
    asked = {}
    for i in range(24):
        asked[client.request(i, 3, i % 3)] = i % 3
    client.flush()
    tracer = telemetry.configure(None)
    try:
        got, steps = _drive(server, client)
        events = tracer.events()
    finally:
        telemetry.shutdown(write=False)
    assert len(got) == 24 and server.metrics.counters[
        "serve_batches_overlapped"] >= 4
    for rid, r in got.items():
        assert (dept[r.movie_rows] == asked[rid]).all()
    batches = [e["args"] for e in events if e.get("name") == "serve/batch"
               and "department" in e.get("args", {})]
    assert len(batches) == 6 and all(
        b["range_rows"] == int((dept == b["department"]).sum())
        and b["range_tiles"] >= 1 for b in batches)
    polls = [e["args"] for e in events if e.get("name") == "serve/poll"
             and "department" in e.get("args", {})]
    assert polls and set(polls[0]["pending_by_department"]) == {0, 1, 2}
    computes = [e["args"] for e in events
                if e.get("name") == "serve/batch/compute"
                and "tiles" in e.get("args", {})]  # those that fetched
    assert len(computes) == 6
    assert all(c["grid_tiles"] >= c["tiles"] for c in computes)


@pytest.mark.parametrize("kind", ["text", "npy"])
def test_cli_serve_takes_the_departments_from_a_file(tmp_path, capsys,
                                                     monkeypatch, kind):
    """``cfk_tpu serve --item-departments FILE``: one int an item row, read
    with numpy; the engine it builds was given them (and prewarms their
    rungs), and without the file the command serves as it did."""
    import json

    from cfk_tpu.cli import main
    from cfk_tpu.serving import engine as engine_mod
    from cfk_tpu.transport.checkpoint import CheckpointManager

    ds, model = _tiny_model()
    csv = tmp_path / "ratings.csv"
    coo = ds.coo_dense
    with open(csv, "w") as f:
        f.write("userId,movieId,rating,timestamp\n")
        for u, m, r in zip(ds.user_map.raw_ids[coo.user_raw],
                           ds.movie_map.raw_ids[coo.movie_raw],
                           coo.rating):
            f.write(f"{u},{m},{r},0\n")
    ck = tmp_path / "ck"
    ck.mkdir()
    mgr = CheckpointManager(str(ck))
    mgr.save(3, model.user_factors, model.movie_factors,
             meta={"model": "als", "rank": 4, "num_shards": 1})
    mgr.wait_pending()
    dept = np.arange(model.num_movies) % 3  # not sorted: a permuted layout
    path = tmp_path / ("dept.npy" if kind == "npy" else "dept.txt")
    np.save(path, dept) if kind == "npy" else np.savetxt(path, dept, fmt="%d")
    built = []
    real = engine_mod.ServeEngine.__init__

    def spy(self, *a, **kw):
        real(self, *a, **kw)
        built.append(self)

    monkeypatch.setattr(engine_mod.ServeEngine, "__init__", spy)
    rc = main([
        "serve", "--data", str(csv), "--format", "movielens",
        "--checkpoint-dir", str(ck), "--tile-m", "16", "-k", "5",
        "--loadgen-qps", "500", "--loadgen-requests", "16",
        "--item-departments", str(path),
    ])
    assert rc == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["answered"] == 16
    (eng,) = built
    assert sorted(eng.departments) == [0, 1, 2] and eng._to_item is not None
    vals, ids = eng.topk(np.arange(4), 3, department=2)
    assert (dept[ids] == 2).all()
