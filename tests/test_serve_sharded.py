"""A catalogue row-sharded over a mesh (ISSUE 26): ``ServeEngine(shards=n)``
on the 8-device CPU mesh at toy size — answers against a numpy reference
that imports nothing of the program and against the one-device engine bit
for bit, where the table and the exclusion rectangle live, what the spans
say, and the table's life under ``load_state`` / ``apply_movie_deltas``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cfk_tpu import telemetry
from cfk_tpu.serving import engine as engine_mod
from cfk_tpu.serving.topk_kernel import (
    build_seen_tiles,
    group_seen_cells,
)
from tests.serve_reference import exact_topk_blocks, topk_gaps
from tests.test_serving import (
    PIECE_CASES,
    assert_one_program_shape,
    one_program_runs,
    piece_case_lists,
)

RANK, TILE = 8, 16
SHARDS = (1, 2, 4)


def _csr(lists):
    indptr = np.zeros(len(lists) + 1, np.int64)
    indptr[1:] = np.cumsum([len(x) for x in lists])
    movies = (np.concatenate([np.asarray(x, np.int32) for x in lists])
              if indptr[-1] else np.zeros(0, np.int32))
    return movies, indptr


def _problem(case):
    """(user factors, item factors, seen lists, batch rows, K) of a case."""
    rng = np.random.default_rng(sum(map(ord, case)))
    pick = lambda lo, hi, n: lo + np.sort(rng.choice(hi - lo, n, replace=False))
    users = 24
    if case == "ragged":  # 1,000 rows: not a multiple of shards x tile
        m, k = 1000, 7
        lists = [pick(0, m, int(rng.integers(0, 40))) for _ in range(users)]
    elif case == "exact_multiple":  # 1,024 = 4 x 16 x 16
        m, k = 1024, 5
        lists = [pick(0, m, int(rng.integers(0, 30))) for _ in range(users)]
    elif case == "seen_in_one_shard":
        # every seen item of every user lies in the second of four shards
        m, k = 1000, 7
        lists = [pick(256, 512, int(rng.integers(1, 60)))
                 for _ in range(users)]
    elif case == "k_over_a_shard":
        # 60 rows over four shards: 16 a shard, most of them seen, K = 20
        m, k = 60, 20
        lists = [pick(0, 32, 25) for _ in range(users)]
    elif case == "last_shard_all_padding":
        # 130 rows pad to 4 x 48: the last shard holds no real row
        m, k = 130, 6
        lists = [pick(0, m, int(rng.integers(0, 20))) for _ in range(users)]
    else:
        raise KeyError(case)
    uf = rng.standard_normal((users, RANK)).astype(np.float32)
    mf = rng.standard_normal((m, RANK)).astype(np.float32)
    rows = rng.integers(0, users, size=13)
    return uf, mf, lists, rows, k


CASES = ("ragged", "exact_multiple", "seen_in_one_shard", "k_over_a_shard",
         "last_shard_all_padding")


def _engine(uf, mf, lists, **kw):
    movies, indptr = _csr(lists)
    kw.setdefault("tile_m", TILE)
    return engine_mod.ServeEngine(
        uf, mf, num_users=uf.shape[0], num_movies=mf.shape[0],
        seen_movies=movies, seen_indptr=indptr, batch_quantum=8, **kw)


@pytest.mark.parametrize("pieces", ["one_piece", "several_pieces",
                                    "past_the_top_rung"])
@pytest.mark.parametrize("shards", SHARDS)
@pytest.mark.parametrize("case", CASES)
def test_sharded_answers_equal_reference_and_one_device(
        case, shards, pieces, monkeypatch):
    uf, mf, lists, rows, k = _problem(case)
    if pieces != "one_piece":
        # three pieces: one run of the rung of four; forty: the top rung's
        # program run again on its own result
        cells = int(sum(len(lists[r]) for r in rows))
        cut = {"several_pieces": 3, "past_the_top_rung": 40}[pieces]
        monkeypatch.setattr(engine_mod, "seen_cell_capacity",
                            lambda b: max(-(-cells // cut), 1))
    want_vals, want_ids = _engine(uf, mf, lists).topk(rows, k)
    vals, ids = _engine(uf, mf, lists, shards=shards).topk(rows, k)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(vals, want_vals)
    best, best_ids, at = exact_topk_blocks(
        uf[rows], mf, [lists[r] for r in rows], k, ids, block=97)
    np.testing.assert_array_equal(ids, best_ids)
    rank_gap, score_err = topk_gaps(vals, best, at)
    assert rank_gap == 0.0 and score_err <= 2e-6
    for row, got in zip(rows, ids):
        assert not set(got.tolist()) & set(np.asarray(lists[row]).tolist())


@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("shards", SHARDS)
def test_table_is_placed_shard_by_shard_never_whole(shards, table_dtype,
                                                    monkeypatch):
    uf, mf, lists, rows, k = _problem("ragged")
    put = []
    real_put = jax.device_put
    monkeypatch.setattr(
        jax, "device_put",
        lambda x, *a, **kw: put.append(np.shape(x)) or real_put(x, *a, **kw))
    before = {id(a) for a in jax.live_arrays()}
    eng = _engine(uf, mf, lists, shards=shards, table_dtype=table_dtype)
    per = -(-mf.shape[0] // (shards * TILE)) * TILE
    assert eng.table_rows == per * shards
    for part in eng._table:
        if part is None:
            continue
        assert part.shape[0] == per * shards
        assert [s.data.shape[0] for s in part.addressable_shards] == (
            [per] * shards)
        assert [s.device for s in part.addressable_shards] == list(
            eng.mesh.devices.flat)
    # what went up went up a shard at a time ...
    assert [s for s in put if len(s) == 2] == [(per, RANK)] * shards
    # ... and no array the engine made holds the table's rows on one device
    for a in jax.live_arrays():
        if id(a) not in before and a.ndim and a.shape[0] >= per * shards:
            assert len(a.sharding.device_set) == shards, (a.shape, a.sharding)
    want_vals, want_ids = _engine(uf, mf, lists,
                                  table_dtype=table_dtype).topk(rows, k)
    vals, ids = eng.topk(rows, k)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(vals, want_vals)


@pytest.mark.parametrize("name", PIECE_CASES)
@pytest.mark.parametrize("shards", SHARDS)
def test_each_chip_builds_its_slice_of_the_rectangle(shards, name,
                                                     monkeypatch):
    """The one-program build over a mesh: each chip's slice of the
    rectangle and of its hits is the numpy oracle's to the bit however
    many pieces the cell list holds (``tests/test_serving.py``'s cases: up
    to the top rung one run of one shard program, past it the top rung's
    run again on its own result), cells past ``num_movies`` dropped."""
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import serve_seen_tiles_sharded

    n, warm = PIECE_CASES[name]
    movies, indptr = _csr(piece_case_lists(n))
    nt = -(-600 // (shards * TILE)) * shards
    kw = dict(num_movies=600, tile_m=TILE, num_tiles=nt)
    want = build_seen_tiles(movies, indptr, np.arange(8), **kw)
    cells, shape = group_seen_cells(movies, indptr, np.arange(8), **kw)
    assert cells.shape[1] == n
    runs, attrs = one_program_runs(monkeypatch, cells, shape, 8, warm=warm)
    if not warm:
        assert_one_program_shape(runs, attrs, n, 8)
    mesh = make_mesh(shards)
    got = None
    for run in runs:
        got = serve_seen_tiles_sharded(mesh, jnp.asarray(run), got,
                                       shape=shape, tile_m=TILE)
    assert got.slots.shape == want.shape and got.hits.shape == (nt,)
    # the rectangle and its hits (a tile that holds a cell) lie together
    for part, oracle in ((got.slots, want),
                         (got.hits, (want != TILE).any(axis=(1, 2)))):
        assert len(part.addressable_shards) == shards
        for shard, device in zip(part.addressable_shards, mesh.devices.flat):
            assert shard.device == device
            assert shard.data.shape == (nt // shards,) + oracle.shape[1:]
            np.testing.assert_array_equal(np.asarray(shard.data),
                                          oracle[shard.index])


def test_a_cell_of_an_earlier_shard_is_dropped_not_wrapped_round():
    """Rebased below zero a tile index would wrap round in ``.at[]`` and
    land in the shard's last tiles."""
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import serve_seen_tiles_sharded

    cells = np.array([[0], [2], [0], [5]], np.int32)  # tile 0 only
    got = serve_seen_tiles_sharded(make_mesh(4), jnp.asarray(cells), None,
                                   shape=(8, 4, 16), tile_m=TILE)
    want = np.full((8, 4, 16), TILE, np.int32)
    want[0, 2, 0] = 5
    np.testing.assert_array_equal(np.asarray(got.slots), want)
    np.testing.assert_array_equal(np.asarray(got.hits), [1] + [0] * 7)


@pytest.mark.parametrize("shards", SHARDS)
def test_table_stays_sharded_through_deltas_and_load_state(shards):
    uf, mf, lists, rows, k = _problem("ragged")
    rng = np.random.default_rng(9)
    one, eng = _engine(uf, mf, lists), _engine(uf, mf, lists, shards=shards)
    placed = eng._table[0].sharding
    delta_rows = np.array([0, 255, 256, 999, 4000, -1])
    delta = rng.standard_normal((6, RANK)).astype(np.float32)
    for e in (one, eng):
        assert e.apply_movie_deltas(delta_rows, delta) == 4
    assert eng._table[0].sharding == placed
    for a, b in zip(one.topk(rows, k), eng.topk(rows, k)):
        np.testing.assert_array_equal(a, b)
    uf2 = rng.standard_normal(uf.shape).astype(np.float32)
    mf2 = rng.standard_normal(mf.shape).astype(np.float32)
    for e in (one, eng):
        e.load_state(uf2, mf2, epoch=3)
    assert eng._table[0].sharding == placed and eng.table_swaps == 1
    assert len(eng._table[0].addressable_shards) == shards
    for a, b in zip(one.topk(rows, k), eng.topk(rows, k)):
        np.testing.assert_array_equal(a, b)


def test_shards_and_mesh_are_one_choice():
    from cfk_tpu.parallel.mesh import make_mesh

    uf, mf, lists, *_ = _problem("ragged")
    with pytest.raises(ValueError, match="mesh/shards"):
        _engine(uf, mf, lists, shards=2, mesh=make_mesh(2))
    with pytest.raises(ValueError, match="devices"):
        _engine(uf, mf, lists, shards=64)
    assert _engine(uf, mf, lists).mesh is None


@pytest.mark.parametrize("table_dtype,operands", [("float32", 3), ("int8", 4)])
def test_shard_program_takes_the_operands_there_are(table_dtype, operands):
    """No scales placeholder: a float table's shard program has ``u``, the
    table and the rectangle; an int8 table's has its scales too."""
    from cfk_tpu.parallel import spmd

    uf, mf, lists, rows, k = _problem("ragged")
    eng = _engine(uf, mf, lists, shards=2, table_dtype=table_dtype)
    seen = []
    real = spmd._serve_topk_sharded_fn

    def spy(*key):
        fn = real(*key)
        return lambda *ops: seen.append(len(ops)) or fn(*ops)

    spmd_fn = spmd._serve_topk_sharded_fn
    try:
        spmd._serve_topk_sharded_fn = spy
        eng.topk(rows, k)
        eng.topk(rows, k, exclude_seen=False)
    finally:
        spmd._serve_topk_sharded_fn = spmd_fn
    assert seen == [operands, operands - 1]


@pytest.mark.parametrize("pieces", [1, 3, 9, 17])
def test_prewarm_counts_the_shard_programs_and_closes_the_set(pieces):
    uf, mf, lists, rows, k = _problem("ragged")
    # one user whose list makes a batch of its rows hold `pieces` pieces of
    # 16 x b cells at every batch size
    lists = list(lists)
    lists[7] = np.sort(np.random.default_rng(pieces).choice(
        mf.shape[0], 16 * pieces - 8, replace=False))
    eng = _engine(uf, mf, lists, shards=4, tile_m=32)  # shapes of its own
    warm = eng.prewarm(8, max_batch=16)
    # per batch size: the scorer, the slice build at each of the five rungs
    # of pieces and the top rung's build onto a donated slice (all of them
    # new in the first case, none in the others: the counter is the
    # process's)
    assert warm["programs"] == 2 and warm["new_traces"] in (0, 14)
    before = engine_mod.trace_count()
    eng.topk(rows, 8)
    eng.topk(rows[:5], 8)
    for n in (8, 16):
        vals, ids = eng.topk(np.full(n, 7), 8)
        assert not set(ids.ravel().tolist()) & set(lists[7].tolist())
    assert engine_mod.trace_count() == before


def test_spans_of_a_sharded_engine():
    uf, mf, lists, rows, k = _problem("ragged")
    tracer = telemetry.configure()
    try:
        eng = _engine(uf, mf, lists, shards=4)
        eng.topk(rows, k)
        events = {e["name"]: e for e in tracer.events() if e.get("ph") == "X"}
    finally:
        telemetry.shutdown(write=False)
    up = events["serve/engine/table_upload"]["args"]
    assert up == {"shards": 4, "rows_per_shard": 256, "rows_per_slice": 256,
                  "slices": 4, "bytes": 1024 * RANK * 4,
                  "table_dtype": "float32", "quantized_on": "none"}
    cells = int(sum(len(lists[r]) for r in rows))
    seen = events["serve/batch/seen_tiles"]["args"]
    assert seen["cells"] == cells == sum(seen["shard_cells"])
    assert len(seen["shard_cells"]) == 4
    batch = events["serve/batch/upload"]["args"]
    assert batch["shards"] == 4
    assert batch["bytes"] == 16 * RANK * 4 + 4 * 16 * 16 * 4
    assert batch["replicated_bytes"] == 4 * batch["bytes"]
    compute = events["serve/batch/compute"]["args"]
    assert compute["shards"] == 4 and compute["merge_candidates"] == 4 * k
    # the gated selection's counts, added up over the four shards' tiles
    assert compute["tiles"] == 1024 // eng.tile_m
    assert 4 <= compute["select_tiles"] <= compute["tiles"]
    assert compute["select_tiles"] <= compute["select_rounds"]
    # and the gated exclusion's: chunks run, tiles that ran any
    assert 1 <= compute["seen_hit_tiles"] <= compute["tiles"]
    assert compute["seen_hit_tiles"] <= compute["seen_chunks"]
    # a float32 table completes the tiles whose first gate opened, shard
    # by shard: at least those that ran a round or a mask
    assert (max(compute["select_tiles"], compute["seen_hit_tiles"])
            <= compute["completed_tiles"] <= compute["tiles"])
    assert compute["score_passes"] == 7
    # what the scorer streams for the batch, all four shards'
    assert compute["table_dtype"] == "float32"
    assert compute["scan_bytes"] == 1024 * RANK * 4
    # 16 tiles a shard, one grid step of 16 tiles on each
    assert (compute["slab_tiles"], compute["grid_steps"]) == (16, 4)
    # a one-device engine uploads through the same span (PR 32) and its
    # batch spans carry nothing of the mesh
    tracer = telemetry.configure()
    try:
        _engine(uf, mf, lists).topk(rows, k)
        events = {e["name"]: e for e in tracer.events() if e.get("ph") == "X"}
    finally:
        telemetry.shutdown(write=False)
    assert events["serve/engine/table_upload"]["args"] == {
        "shards": 1, "rows_per_shard": 1008, "rows_per_slice": 1008,
        "slices": 1, "bytes": 1008 * RANK * 4, "table_dtype": "float32",
        "quantized_on": "none"}
    assert "shards" not in events["serve/batch/upload"]["args"]
    assert "shard_cells" not in events["serve/batch/seen_tiles"]["args"]
    assert set(events["serve/batch/compute"]["args"]) == {
        "n", "b", "k", "select_rounds", "select_tiles", "seen_chunks",
        "seen_hit_tiles", "completed_tiles", "tiles", "table_dtype",
        "scan_bytes",
        "score_passes", "slab_tiles", "grid_steps"}
    # a shard's first gate reads its own K-th scores, which lie no higher
    # than one device's at the same tile: it opens, and masks, no fewer
    one = events["serve/batch/compute"]["args"]
    for x in ("seen_chunks", "seen_hit_tiles", "completed_tiles"):
        assert 1 <= one[x] <= compute[x]
    assert one["completed_tiles"] < one["tiles"]
    # 63 tiles on one device: three steps of 16 and a ragged one of 15
    assert events["serve/batch/compute"]["args"]["tiles"] == 63
    assert events["serve/batch/compute"]["args"]["slab_tiles"] == 16
    assert events["serve/batch/compute"]["args"]["grid_steps"] == 4


@pytest.mark.parametrize("shards", SHARDS)
@pytest.mark.parametrize("case", ["ragged", "k_over_a_shard",
                                  "last_shard_all_padding"])
def test_slab_counts_on_the_span_over_shards(case, shards):
    """``slab_tiles`` is the G the scorer takes for one shard's tiles and
    ``grid_steps`` the steps that makes of the table, the ragged last step
    of every shard counted, added up over the shards; the counts of the
    fold stay counts of tiles, and the answers the one-device engine's
    (whose kernel body runs the slabs) to the bit."""
    from cfk_tpu.serving.topk_kernel import slab_tiles

    uf, mf, lists, rows, k = _problem(case)
    one_vals, one_ids = _engine(uf, mf, lists).topk(rows, k)
    tracer = telemetry.configure()
    try:
        eng = _engine(uf, mf, lists, shards=shards)
        vals, ids = eng.topk(rows, k)
        compute = next(e["args"] for e in tracer.events()
                       if e["name"] == "serve/batch/compute")
    finally:
        telemetry.shutdown(write=False)
    np.testing.assert_array_equal(ids, one_ids)
    np.testing.assert_array_equal(vals, one_vals)
    per = eng.table_rows // shards // TILE  # tiles a shard
    assert compute["tiles"] == per * shards
    g = slab_tiles(per, compute["b"], 16, RANK, jnp.float32, tile_m=TILE,
                   k_top=compute["k"])
    assert compute["slab_tiles"] == g <= per
    assert compute["grid_steps"] == shards * -(-per // g)
    assert compute["tiles"] / compute["grid_steps"] <= g
    assert compute["seen_hit_tiles"] <= compute["tiles"]


@pytest.mark.parametrize("shards", SHARDS)
@pytest.mark.parametrize("data_case", ["lattice", "late_ulp"])
def test_float_gate_over_shards_answers_as_one_device(data_case, shards):
    """A float32 table's first gate (ISSUE 50) shard by shard: each shard
    reads its own K-th scores and its own tiles' largest entries, no
    collective is added, and the merged answer is the one-device engine's
    and the dense float32 oracle's to the bit — on a table whose later
    tiles score lower, so that gates shut on every shard, and on one whose
    only entrant past the first rows lies one ulp over the K-th score in
    the last shard, where pass 0 cannot see it."""
    from tests.test_serve_f32_gate import _dense_oracle, _lattice

    rng = np.random.default_rng(50 + shards)
    users, m, k = 24, 64 * TILE - 5, 9
    uf, mf = _lattice(rng, (users, 16)), _lattice(rng, (m, 16))
    if data_case == "lattice":
        mf *= (2.0 ** -(np.arange(m) // (4 * TILE) % 4)).astype(
            np.float32)[:, None]
    else:
        mf[:], uf[:] = 0.0, 0.0
        mf[:, 0], uf[:, 0] = 0.5, 1.0
        mf[:, 1], uf[:, 1] = 512 / 1024.0, 2.0 ** -14
        mf[60 * TILE + 3, 1] = 513 / 1024.0
    lists = [np.sort(rng.choice(m, int(rng.integers(0, 30)), replace=False))
             for _ in range(users)]
    rows = rng.integers(0, users, size=13)
    want_v, want_i = _dense_oracle(uf[rows], mf, [lists[r] for r in rows], m,
                                   k)
    computes = []
    for n in (None, shards):
        tracer = telemetry.configure()
        try:
            vals, ids = _engine(uf, mf, lists, shards=n).topk(rows, k)
            computes.append(next(e["args"] for e in tracer.events()
                                 if e["name"] == "serve/batch/compute"))
        finally:
            telemetry.shutdown(write=False)
        np.testing.assert_array_equal(ids, want_i)
        np.testing.assert_array_equal(vals, want_v)
    one, over = computes
    assert one["score_passes"] == over["score_passes"] == 7
    for c in (one, over):
        assert (max(c["select_tiles"], c["seen_hit_tiles"])
                <= c["completed_tiles"] <= c["tiles"] == 64)
    # a shard's K-th scores lie no higher than one device's at the same
    # tile, so its gate opens no fewer
    assert one["completed_tiles"] <= over["completed_tiles"]
    if data_case == "lattice":
        assert over["completed_tiles"] < 64
    else:
        late = 60 * TILE + 3
        assert all(late in row for row, r in zip(ids, rows)
                   if late not in lists[r])


@pytest.mark.parametrize("shards", SHARDS)
def test_exclusion_chunks_of_cells_in_one_shard(shards):
    """Every cell lies in the second of four shards: the other shards run
    no exclusion chunk at all, the answers are the one-device engine's to
    the bit, and the span's counts, added up over the shards, are what the
    cell lists imply (a tile a batch row has rated into runs the
    rectangle's whole width, 16 slots a chunk)."""
    uf, mf, lists, rows, k = _problem("seen_in_one_shard")
    want_vals, want_ids = _engine(uf, mf, lists).topk(rows, k)
    tracer = telemetry.configure()
    try:
        vals, ids = _engine(uf, mf, lists, shards=shards).topk(rows, k)
        compute = next(e["args"] for e in tracer.events()
                       if e["name"] == "serve/batch/compute")
    finally:
        telemetry.shutdown(write=False)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(vals, want_vals)
    hit = np.zeros(1024 // TILE, bool)
    most = 0
    for r in rows:
        n = np.bincount(np.asarray(lists[r]) // TILE, minlength=hit.size)
        hit |= n > 0
        most = max(most, int(n.max()))
    assert not hit[:256 // TILE].any() and not hit[512 // TILE:].any()
    width = max(16, 1 << (most - 1).bit_length())
    assert compute["seen_hit_tiles"] == hit.sum() > 0
    assert compute["seen_chunks"] == hit.sum() * (width // 16)
