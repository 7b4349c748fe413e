"""Department pages: a request names a department and is answered with the
exact top-K among that department's items (PR 52).

The ranged scan is the scorer's own kernel handed a range of rows and a rung
of steps (``topk_scores_counted(rows=, grid_tiles=)``); its twin scans the
same tiles (``compat.emulate_topk_counted``): bit for bit on the interpret
path, counts included.  The engine lays its table out by department, answers
in the caller's item rows, and refuses in words what it cannot serve.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from cfk_tpu.compat import emulate_topk_counted
from cfk_tpu.ops.quant import quantize_table
from cfk_tpu.serving import engine as engine_mod
from cfk_tpu.serving.engine import ServeEngine
from cfk_tpu.serving.topk_kernel import (
    build_seen_tiles,
    range_slabs,
    slab_tiles,
    topk_scores_counted,
)

T, NT, RANK, B, K = 16, 40, 24, 8, 7
M = NT * T - 5  # the table's last tile is ragged


def _table(rng, dtype):
    full = np.zeros((NT * T, RANK), np.float32)
    full[:M] = rng.standard_normal((M, RANK)).astype(np.float32)
    data, scale = quantize_table(jnp.asarray(full), dtype)
    dense = np.asarray(data, np.float32)
    if scale is not None:
        dense = dense * np.asarray(scale)[:, None]
    return data, scale, dense


def _ranged_inputs(rng, lo, hi, dtype):
    """(u, table, scale, dense table, rectangle of the range's rung, the
    users' seen rows, grid_tiles) for a scan of rows [lo, hi)."""
    data, scale, dense = _table(rng, dtype)
    u = rng.standard_normal((B, RANK)).astype(np.float32)
    g = slab_tiles(NT, B, 16, RANK, data.dtype, tile_m=T, k_top=K)
    first, last = range_slabs(lo, hi, g, T)
    grid_tiles = engine_mod._range_rung(last - first + 1) * g
    seen = [np.sort(rng.choice(np.arange(lo, hi), size=min(5, hi - lo),
                               replace=False)) if hi > lo
            else np.zeros(0, np.int64) for _ in range(B)]
    indptr = np.concatenate([[0], np.cumsum([len(s) for s in seen])])
    rows = (np.concatenate(seen) - first * g * T).astype(np.int32)
    rect = build_seen_tiles(rows, indptr, np.arange(B),
                            num_movies=grid_tiles * T, tile_m=T,
                            num_tiles=grid_tiles)
    return u, data, scale, dense, rect, seen, grid_tiles


RANGES = {
    "inside_tiles": (37, 300),  # starts and ends inside a tile
    "one_tile": (16, 32),
    "inside_one_tile": (250, 253),
    "the_table": (0, M),
    "to_the_ragged_end": (17, M),
    "empty": (100, 100),
    "first_slab": (0, 16),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("name", list(RANGES))
def test_ranged_kernel_equals_twin_and_the_dense_oracle(rng, name, dtype):
    """Kernel body under the interpreter against the twin, bit for bit,
    counts included; both against a dense top-K over the range's rows."""
    lo, hi = RANGES[name]
    u, data, scale, dense, rect, seen, grid_tiles = _ranged_inputs(
        rng, lo, hi, dtype)
    kw = dict(k_top=K, num_movies=M, tile_m=T,
              rows=(jnp.int32(lo), jnp.int32(hi)), grid_tiles=grid_tiles)
    got = topk_scores_counted(jnp.asarray(u), data, scale,
                              jnp.asarray(rect), **kw)
    twin = emulate_topk_counted(jnp.asarray(u), data, scale,
                                jnp.asarray(rect), **kw)
    for a, b in zip(got, twin):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    vals, ids, counts = map(np.asarray, got)
    # the tiles counted are those that hold a row of the range
    range_tiles = -(-hi // T) - lo // T if hi > lo else 1
    assert counts[4] <= range_tiles and counts[1] <= range_tiles
    uq = u.astype(jnp.bfloat16).astype(np.float32) if dtype == "bfloat16" else u
    scores = uq @ dense.T
    scores[:, :lo] = -np.inf
    scores[:, hi:] = -np.inf
    for i, s in enumerate(seen):
        scores[i, s] = -np.inf
    order = np.argsort(-scores, axis=1, kind="stable")[:, :K]
    want = np.take_along_axis(scores, order, axis=1)
    real = np.isfinite(want)
    assert (ids[~real] == -1).all() and np.isneginf(vals[~real]).all()
    assert ((ids[real] >= lo) & (ids[real] < hi)).all()
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" else dict(
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(vals[real], want[real], **tol)
    if dtype != "bfloat16":
        np.testing.assert_array_equal(ids[real], order[real])


@pytest.mark.parametrize("lo, hi", [(32, 320), (16, 32), (0, M - M % T)])
def test_ranged_answers_equal_the_unranged_kernel_on_the_rows_alone(rng, lo, hi):
    """A range that starts and ends on tiles: the same kernel, unranged, over
    a table that holds those rows and nothing else."""
    u, data, _, _, rect, seen, grid_tiles = _ranged_inputs(
        rng, lo, hi, "float32")
    got = topk_scores_counted(
        jnp.asarray(u), data, None, jnp.asarray(rect), k_top=K, num_movies=M,
        tile_m=T, rows=(lo, hi), grid_tiles=grid_tiles)
    indptr = np.concatenate([[0], np.cumsum([len(s) for s in seen])])
    alone = build_seen_tiles(
        (np.concatenate(seen) - lo).astype(np.int32), indptr, np.arange(B),
        num_movies=hi - lo, tile_m=T)
    want = topk_scores_counted(
        jnp.asarray(u), data[lo:hi], None, jnp.asarray(alone), k_top=K,
        num_movies=hi - lo, tile_m=T)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]) + lo)
    # the selection's counts are the data's, whatever the grid around it
    np.testing.assert_array_equal(np.asarray(got[2])[:2],
                                  np.asarray(want[2])[:2])


def test_a_ranged_scan_is_refused_without_its_rung_and_over_a_mesh():
    u, table = jnp.zeros((8, RANK)), jnp.zeros((NT * T, RANK))
    with pytest.raises(ValueError, match="grid_tiles"):
        topk_scores_counted(u, table, None, None, k_top=K, num_movies=M,
                            tile_m=T, rows=(0, 16))
    with pytest.raises(ValueError, match="no multiple"):
        topk_scores_counted(u, table, None, None, k_top=K, num_movies=M,
                            tile_m=T, rows=(0, 16), grid_tiles=24,
                            interpret=True)


# -- the engine ---------------------------------------------------------------

USERS, ITEMS = 40, 1000


def _catalogue(rng, sort):
    uf = rng.standard_normal((USERS, RANK)).astype(np.float32)
    mf = rng.standard_normal((ITEMS, RANK)).astype(np.float32)
    dept = rng.integers(0, 4, size=ITEMS)
    if sort:
        dept = np.sort(dept)
    lens = rng.integers(1, 9, size=USERS)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    seen = np.concatenate([np.sort(rng.choice(ITEMS, size=n, replace=False))
                           for n in lens]).astype(np.int32)
    return uf, mf, dept, seen, indptr


def _engine(cat, **kw):
    uf, mf, dept, seen, indptr = cat
    return ServeEngine(uf, mf, num_users=USERS, num_movies=ITEMS,
                       seen_movies=seen, seen_indptr=indptr, tile_m=16,
                       item_department=dept, **kw)


def _oracle(cat, rows, k, department):
    uf, mf, dept, seen, indptr = cat
    scores = uf[rows] @ mf.T
    for i, r in enumerate(rows):
        scores[i, seen[indptr[r]:indptr[r + 1]]] = -np.inf
    if department is not None:
        scores[:, dept != department] = -np.inf
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(scores, order, axis=1), order


@pytest.mark.parametrize("sort", [True, False], ids=["sorted", "shuffled"])
@pytest.mark.parametrize("table_dtype", ["float32", "int8"])
def test_engine_answers_a_department_in_item_rows(rng, sort, table_dtype):
    cat = _catalogue(rng, sort)
    eng = _engine(cat, table_dtype=table_dtype)
    # sorted input: the identity, detected; shuffled: a stable permutation
    assert (eng._to_item is None) == sort
    assert sorted(eng.departments) == [0, 1, 2, 3]
    rows = np.arange(20)
    for department in (None, 0, 1, 2, 3):
        vals, ids = eng.topk(rows, 5, department=department)
        want_v, want_i = _oracle(cat, rows, 5, department)
        if table_dtype == "float32":
            np.testing.assert_array_equal(np.sort(ids, 1), np.sort(want_i, 1))
            np.testing.assert_allclose(vals, want_v, rtol=1e-5, atol=1e-5)
        if department is not None:
            assert (cat[2][ids] == department).all()
        for i, r in enumerate(rows):
            mine = cat[3][cat[4][r]:cat[4][r + 1]]
            assert not np.isin(ids[i], mine).any()


def test_a_shuffled_catalogue_answers_as_the_sorted_one(rng):
    """The same items under two numberings: one whose departments come
    sorted, one shuffled.  Mapped back, the answers are the same items."""
    uf, mf, dept, seen, indptr = _catalogue(rng, True)
    perm = rng.permutation(ITEMS)  # new item row i is old row perm[i]
    inv = np.argsort(perm)
    shuffled_seen = np.concatenate([
        np.sort(inv[seen[indptr[r]:indptr[r + 1]]]) for r in range(USERS)
    ]).astype(np.int32)
    a = _engine((uf, mf, dept, seen, indptr))
    b = _engine((uf, mf[perm], dept[perm], shuffled_seen, indptr))
    assert a._to_item is None and b._to_item is not None
    rows = np.arange(USERS)
    for department in (None, 0, 3):
        va, ia = a.topk(rows, 6, department=department)
        vb, ib = b.topk(rows, 6, department=department)
        np.testing.assert_array_equal(va, vb)
        np.testing.assert_array_equal(np.sort(ia, 1), np.sort(perm[ib], 1))


def test_seen_cells_at_a_ranges_edges(rng):
    """A user who has rated the first and the last item of a department, and
    the items on either side of it: the two inside are excluded, the two
    outside could never enter."""
    uf, mf, dept, _, _ = _catalogue(rng, True)
    lo, hi = np.flatnonzero(dept == 1)[[0, -1]] + [0, 1]
    seen = np.asarray([lo - 1, lo, hi - 1, hi], np.int32)
    indptr = np.concatenate([[0], np.full(USERS, 4)])  # user 0 alone
    cat = (uf, mf, dept, seen, indptr)
    eng = _engine(cat)
    k = int(hi - lo) - 2  # every item of the department the user may get
    vals, ids = eng.topk(np.asarray([0, 1]), k, department=1)
    assert sorted(ids[0]) == list(range(lo + 1, hi - 1))
    assert np.isfinite(vals[0]).all()
    # user 1 has rated nothing: the edges are its to get
    assert {lo, hi - 1} <= set(ids[1]) or k < hi - lo
    with pytest.raises(ValueError, match="k must be|outside"):
        eng.topk(np.asarray([0]), ITEMS + 1, department=1)


def test_a_stream_commit_is_seen_by_a_department_request(rng):
    cat = _catalogue(rng, False)
    eng = _engine(cat)
    user, department = 3, 2
    _, before = eng.topk(np.asarray([user]), 4, department=department)
    new_row = rng.standard_normal(RANK).astype(np.float32)
    eng.on_commit({"rows": new_row[None], "touched_rows": [user],
                   "cells": [(user, int(before[0, 0]))], "stream_step": 9})
    stamp = {}
    vals, after = eng.topk(np.asarray([user]), 4, department=department,
                           stamp=stamp)
    assert stamp["ordinal"] == 9
    assert before[0, 0] not in after[0]  # the item just rated is gone
    uf = cat[0].copy()
    uf[user] = new_row
    seen = np.append(cat[3][cat[4][user]:cat[4][user + 1]], before[0, 0])
    scores = uf[user] @ cat[1].T
    scores[seen] = -np.inf
    scores[cat[2] != department] = -np.inf
    np.testing.assert_array_equal(
        np.sort(after[0]), np.sort(np.argsort(-scores, kind="stable")[:4]))
    # an item delta lands on the item's row of the layout
    eng.apply_movie_deltas([int(after[0, 1])], np.zeros((1, RANK), np.float32))
    vals2, _ = eng.topk(np.asarray([user]), 4, department=department)
    assert 0.0 in vals2[0] or vals2[0].min() > 0


def test_what_an_engine_cannot_serve_is_refused_in_words(rng):
    cat = _catalogue(rng, False)
    eng = _engine(cat)
    with pytest.raises(ValueError, match="no department 7"):
        eng.topk(np.arange(3), 3, department=7)
    with pytest.raises(ValueError, match="laid out by department"):
        eng.fold_table()
    plain = ServeEngine(cat[0], cat[1], num_users=USERS, num_movies=ITEMS,
                        tile_m=16)
    with pytest.raises(ValueError, match="was given no item_department"):
        plain.topk(np.arange(3), 3, department=0)
    with pytest.raises(ValueError, match="over a mesh"):
        _engine(cat, shards=2)
    with pytest.raises(ValueError, match="one non-negative int an item row"):
        ServeEngine(cat[0], cat[1], num_users=USERS, num_movies=ITEMS,
                    tile_m=16, item_department=np.zeros(ITEMS - 1, np.int64))
    with pytest.raises(ValueError, match="item order"):
        ServeEngine(cat[0], lambda lo, hi: cat[1][lo:hi], num_users=USERS,
                    num_movies=ITEMS, tile_m=16, item_department=cat[2])
    # sorted departments: the table is in item order, a fold-in may gather
    sorted_cat = _catalogue(rng, True)
    assert _engine(sorted_cat).fold_table()[0].shape == (1008, RANK)


def test_prewarm_grows_ranged_rungs_only_for_an_engine_with_departments(rng):
    cat = _catalogue(rng, True)
    plain = ServeEngine(cat[0], cat[1], num_users=USERS, num_movies=ITEMS,
                        seen_movies=cat[3], seen_indptr=cat[4], tile_m=16)
    assert plain.prewarm(8, max_batch=32)["programs"] == 3  # 8, 16, 32
    eng = _engine(cat)
    warm = eng.prewarm(8, max_batch=32)
    rungs = {engine_mod._range_rung(
        range_slabs(lo, hi, 16, 16)[1] - range_slabs(lo, hi, 16, 16)[0] + 1)
        for lo, hi in eng._ranges.values()}
    assert warm["programs"] == 3 * (1 + len(rungs))
    before = engine_mod.trace_count()
    for department in eng.departments:
        for n in (1, 9, 20):
            eng.topk(np.arange(n), 8, department=department)
    assert engine_mod.trace_count() == before


def test_prewarm_warms_the_departments_it_is_told_alone(rng):
    """A deployment whose traffic names some departments pays for their
    rungs, and a whole-table program a size; a department left out is
    traced when it is first asked for."""
    cat = _catalogue(rng, True)
    eng = _engine(cat)
    first = sorted(eng.departments)[0]
    lo, hi = eng.department_range(first)
    warm = eng.prewarm(8, max_batch=32, departments=[first])
    assert warm["programs"] == 3 * 2  # 8, 16, 32: whole and the one rung
    before = engine_mod.trace_count()
    eng.topk(np.arange(9), 8, department=first)
    eng.topk(np.arange(9), 8)
    assert engine_mod.trace_count() == before


def test_a_ranged_batch_says_what_it_scanned(rng):
    from cfk_tpu import telemetry

    cat = _catalogue(rng, True)
    eng = _engine(cat)
    tracer = telemetry.configure(None)
    try:
        eng.topk(np.arange(5), 5, department=1)
        eng.topk(np.arange(5), 5)
        events = [e for e in tracer.events()
                  if e.get("name") == "serve/batch/compute"]
    finally:
        telemetry.shutdown(write=False)
    ranged, whole = (e["args"] for e in events)
    lo, hi = eng.department_range(1)
    assert ranged["department"] == 1 and ranged["range_rows"] == hi - lo
    assert ranged["tiles"] == -(-hi // 16) - lo // 16
    assert ranged["grid_tiles"] >= ranged["tiles"]
    assert ranged["grid_steps"] * ranged["slab_tiles"] == ranged["grid_tiles"]
    assert ranged["completed_tiles"] <= ranged["tiles"]
    assert ranged["scan_bytes"] == ranged["tiles"] * 16 * RANK * 4
    # a batch without a department writes what it wrote
    assert whole["tiles"] == 63 and "grid_tiles" not in whole
    assert "department" not in whole and "range_rows" not in whole
