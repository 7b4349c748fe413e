"""Two-stage clustered retrieval (ISSUE 16): the seeded k-means index,
the cluster-major layout round-trip, measured recall@K against the
bit-exact scan across the table-dtype × shard × K matrix, exact-mode
bit-identity (the PR 8 contract must survive the new code path), fold-in
deltas landing inside their cluster rows, the fault→exact fallback, and
the prewarm zero-new-traces contract in two_stage mode."""

import itertools

import numpy as np
import pytest

import jax.numpy as jnp

from cfk_tpu.serving import ServeEngine, pad_table, recall_at_k
from cfk_tpu.serving.cluster import build_cluster_index, kmeans_item_clusters
from cfk_tpu.serving.twostage import (
    build_shortlist,
    default_two_stage_params,
    map_shortlist_ids,
)

USERS, MOVIES, RANK = 48, 512, 16


def _clustered(rng, comps=8):
    """Mixture-of-Gaussians factors — the structure the index exploits."""
    cent = rng.standard_normal((comps, RANK)).astype(np.float32) * 2.0
    mf = (cent[rng.integers(0, comps, size=MOVIES)]
          + rng.standard_normal((MOVIES, RANK)).astype(np.float32) * 0.2)
    uf = (cent[rng.integers(0, comps, size=USERS)]
          + rng.standard_normal((USERS, RANK)).astype(np.float32) * 0.2)
    return uf, mf


def _seen(rng, per_user=6):
    seen = np.sort(rng.integers(0, MOVIES, size=(USERS, per_user)),
                   axis=1).astype(np.int32)
    indptr = np.arange(USERS + 1, dtype=np.int64) * per_user
    return seen, seen.ravel(), indptr


def _engine(uf, mf, *, dtype="float32", shards=1, mode="two_stage",
            seen=None, **kw):
    mesh = None
    if shards > 1:
        from cfk_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(shards)
    sm, si = (None, None) if seen is None else seen
    return ServeEngine(
        uf, mf, num_users=USERS, num_movies=MOVIES, seen_movies=sm,
        seen_indptr=si, table_dtype=dtype, tile_m=64, batch_quantum=8,
        mesh=mesh, serve_mode=mode, clusters=16, probe_clusters=8, **kw,
    )


# -- k-means / cluster-major layout -----------------------------------------

def test_kmeans_deterministic(rng):
    _, mf = _clustered(rng)
    c1, a1 = kmeans_item_clusters(mf, 16, seed=3)
    c2, a2 = kmeans_item_clusters(mf, 16, seed=3)
    np.testing.assert_array_equal(c1, c2)  # bit-identical, same seed
    np.testing.assert_array_equal(a1, a2)
    c3, _ = kmeans_item_clusters(mf, 16, seed=4)
    assert not np.array_equal(c1, c3)  # the seed is the only entropy
    assert a1.min() >= 0 and a1.max() < 16
    assert len(np.unique(a1)) == 16  # empty clusters were reseeded


def test_cluster_major_permutation_round_trip(rng):
    _, mf = _clustered(rng)
    index = build_cluster_index(mf, 16, seed=0)
    perm, inv = index.perm, index.inv_perm
    np.testing.assert_array_equal(np.sort(perm), np.arange(MOVIES))
    np.testing.assert_array_equal(perm[inv], np.arange(MOVIES))
    np.testing.assert_array_equal(mf[perm][inv], mf)  # layout round-trip
    assert index.offsets[0] == 0 and index.offsets[-1] == MOVIES
    for c in range(16):  # every cluster-major range holds its own rows
        rows = perm[index.offsets[c]:index.offsets[c + 1]]
        assert (index.assign[rows] == c).all()
        # stable argsort keeps ascending global order inside a cluster —
        # the shortlist tie contract depends on it
        np.testing.assert_array_equal(rows, np.sort(rows))
    assert index.quick_check() is None
    index.validate()


def test_shortlist_maps_ids_back_and_widens(rng):
    _, mf = _clustered(rng)
    index = build_cluster_index(mf, 16, seed=0)
    sl = build_shortlist(index, np.array([3, 1, 3, 7]), tile_m=64)
    assert sl.rows == sl.global_ids.shape[0]
    assert sl.rows_padded % 64 == 0 and sl.rows_padded >= sl.rows
    # gathered ids map back through the offset trick
    local = np.arange(sl.rows, dtype=np.int32) + sl.offset
    back = map_shortlist_ids(local[None, :], sl)[0]
    np.testing.assert_array_equal(back, sl.global_ids)
    # the union is exactly the probed clusters' rows, cluster-major
    assert set(np.unique(index.assign[sl.global_ids])) == {1, 3, 7}
    # a union smaller than min_rows widens to the whole catalog
    wide = build_shortlist(index, np.array([0]), tile_m=64,
                           min_rows=MOVIES)
    assert wide.rows == MOVIES


# -- recall matrix -----------------------------------------------------------

def _recall_case(rng, dtype, shards, k_top):
    uf, mf = _clustered(rng)
    seen_m, sm, si = _seen(rng)
    eng = _engine(uf, mf, dtype=dtype, shards=shards, seen=(sm, si))
    rows = np.arange(24)
    vals, ids = eng.topk(rows, k_top)
    assert eng.last_scan["serve_mode"] == "two_stage"
    _, oracle = eng.topk(rows, k_top, force_exact=True)
    r = float(recall_at_k(ids, oracle))
    assert r >= 0.95, (dtype, shards, k_top, r)
    for i, u in enumerate(rows):  # seen-exclusion holds on the shortlist
        assert not set(ids[i][ids[i] >= 0].tolist()) & set(
            seen_m[u].tolist())
    assert vals.shape == (24, k_top) and ids.shape == (24, k_top)


# one representative per axis value keeps tier-1 cheap while every axis
# is still exercised; the slow matrix below is exhaustive
@pytest.mark.parametrize("dtype,shards,k_top", [
    ("float32", 1, 10),
    ("bfloat16", 1, 10),
    ("int8", 1, 10),
    ("float32", 2, 10),
    ("float32", 1, 100),
])
def test_recall_representatives(rng, dtype, shards, k_top):
    _recall_case(rng, dtype, shards, k_top)


@pytest.mark.slow
@pytest.mark.parametrize("dtype,shards,k_top", list(itertools.product(
    ["float32", "bfloat16", "int8"], [1, 2], [10, 100])))
def test_recall_matrix_exhaustive(rng, dtype, shards, k_top):
    _recall_case(rng, dtype, shards, k_top)


# -- exact-mode bit-identity (the PR 8 contract survives) -------------------

def test_exact_mode_bit_identical_to_kernel(rng):
    from cfk_tpu.ops.quant import quantize_table
    from cfk_tpu.serving.topk_kernel import (
        build_seen_tiles,
        topk_scores_pallas,
    )

    uf, mf = _clustered(rng)
    _, sm, si = _seen(rng)
    eng = _engine(uf, mf, dtype="int8", mode="exact", seen=(sm, si))
    rows = np.arange(8)
    vals, ids = eng.topk(rows, 10)
    # the pre-ISSUE-16 serve path, assembled by hand
    data, scale = quantize_table(
        jnp.asarray(pad_table(mf, 64, 1)), "int8")
    st = build_seen_tiles(sm, si[:9], np.arange(8), num_movies=MOVIES,
                          tile_m=64, num_tiles=data.shape[0] // 64)
    ev, ei = topk_scores_pallas(
        jnp.asarray(uf[:8]), data, scale, jnp.asarray(st), k_top=10,
        num_movies=MOVIES, tile_m=64,
    )
    np.testing.assert_array_equal(vals, np.asarray(ev))
    np.testing.assert_array_equal(ids, np.asarray(ei))


def test_force_exact_bit_identical_to_exact_engine(rng):
    uf, mf = _clustered(rng)
    seen = _seen(rng)[1:]
    ts = _engine(uf, mf, dtype="bfloat16", seen=seen)
    ex = _engine(uf, mf, dtype="bfloat16", mode="exact", seen=seen)
    rows = np.arange(16)
    tv, ti = ts.topk(rows, 10, force_exact=True)
    ev, ei = ex.topk(rows, 10)
    np.testing.assert_array_equal(tv, ev)
    np.testing.assert_array_equal(ti, ei)


@pytest.mark.parametrize("rows", [65, 129, 191, 192])
def test_rescore_masks_its_tail_and_the_tiles_that_hold_a_cell(rng, rows):
    """The rescore over a gathered shortlist of ``rows`` of 192 padded
    rows against numpy: the padding mask runs only on the tiles the tail
    reaches (it starts at ``row_offset + rows``, not at a tile boundary),
    the exclusion mask only on the middle tile, the one that holds cells —
    and the answers are those of masking everything everywhere."""
    from cfk_tpu.serving.topk_kernel import build_seen_tiles
    from cfk_tpu.serving.twostage import _rescore_call

    b, k_top, tile = 8, 10, 64
    u = rng.standard_normal((b, RANK)).astype(np.float32)
    table = rng.standard_normal((MOVIES, RANK)).astype(np.float32)
    indices = rng.permutation(MOVIES)[:192].astype(np.int32)
    scores = u @ table[indices[:rows]].T
    # each user has seen the best rows of the middle tile it would get
    seen = [64 + np.sort(np.argsort(-scores[i, 64:min(rows, 128)])[:i % 4])
            for i in range(b)]
    indptr = np.zeros(b + 1, np.int64)
    indptr[1:] = np.cumsum([x.size for x in seen])
    st = build_seen_tiles(np.concatenate(seen).astype(np.int32), indptr,
                          np.arange(b), num_movies=192, tile_m=tile)
    vals, ids = _rescore_call(
        jnp.asarray(u), jnp.asarray(indices), jnp.asarray(table), None,
        jnp.asarray(st), np.int32(192 - rows), k_top=k_top, tile_m=tile)
    for i, x in enumerate(seen):
        scores[i, x] = -np.inf
    want = np.argsort(-scores, axis=1, kind="stable")[:, :k_top]
    np.testing.assert_array_equal(np.asarray(ids) - (192 - rows), want)
    np.testing.assert_allclose(
        np.asarray(vals), np.take_along_axis(scores, want, 1), atol=1e-5)


# -- fold-in deltas / fault fallback / prewarm ------------------------------

def test_movie_delta_lands_in_cluster_row(rng):
    from cfk_tpu.ops.quant import quantize_table

    uf, mf = _clustered(rng)
    eng = _engine(uf, mf, dtype="int8")
    drows = np.array([5, 99, 400])
    new = rng.standard_normal((3, RANK)).astype(np.float32)
    assert eng.apply_movie_deltas(drows, new) == 3
    index, ctable, cscale, _, _ = eng._cluster
    pos = index.positions_of(drows)
    qd, qs = quantize_table(jnp.asarray(new), "int8")
    # per-row quantization: the delta's codes+scale are bit-identical to
    # a full-table requantization, in BOTH table views
    np.testing.assert_array_equal(np.asarray(ctable[pos]), np.asarray(qd))
    np.testing.assert_array_equal(np.asarray(cscale[pos]), np.asarray(qs))
    np.testing.assert_array_equal(np.asarray(eng._table[0][drows]),
                                  np.asarray(qd))
    assert index.stale_rows == 3
    # past the staleness bound the engine degrades to exact (recorded)
    eng.max_stale_fraction = 0.0
    eng.topk(np.arange(8), 5)
    assert eng.two_stage_fallbacks == 1
    assert eng.last_scan["serve_mode"] == "exact"


def test_fault_falls_back_bit_exact_and_table_swap_recovers(rng):
    uf, mf = _clustered(rng)
    ts = _engine(uf, mf)
    ex = _engine(uf, mf, mode="exact")
    ts._cluster[0].centroids[2, :] = np.nan  # corrupt the index
    rows = np.arange(16)
    tv, ti = ts.topk(rows, 10)
    ev, ei = ex.topk(rows, 10)
    np.testing.assert_array_equal(tv, ev)  # degraded answer is bit-exact
    np.testing.assert_array_equal(ti, ei)
    assert ts.two_stage_fallbacks == 1 and ts._two_stage_disabled
    ts._set_table(mf)  # the next snapshot swap re-arms two_stage
    assert not ts._two_stage_disabled
    ts.topk(rows, 10)
    assert ts.last_scan["serve_mode"] == "two_stage"


def test_prewarm_zero_new_traces_in_two_stage_mode(rng):
    from cfk_tpu.serving.engine import trace_count

    uf, mf = _clustered(rng)
    seen = _seen(rng)[1:]
    eng = _engine(uf, mf, seen=seen)
    pool = np.arange(32)
    info = eng.prewarm(10, max_batch=16, user_rows=pool)
    assert info["programs"] == 2  # rungs 8, 16
    before = trace_count()
    eng.topk(pool[:16], 10)  # the first real batch traces nothing
    assert trace_count() - before == 0


def test_two_stage_engine_through_the_request_loop(rng):
    # The clustered candidate -> exact-rescore path behind the whole
    # request loop (transport log, RecommendServer coalescing, open-loop
    # client): every request answered from the two-stage scan, whose
    # shortlist is a real cut of the catalogue and whose answers overlap
    # the exact scan's.
    from cfk_tpu.serving import (
        RecommendServer,
        ServeClient,
        ensure_serve_topics,
        run_open_loop,
        zipf_user_rows,
    )
    from cfk_tpu.transport import InMemoryBroker

    uf, mf = _clustered(rng)
    _, sm, si = _seen(rng)
    eng = _engine(uf, mf, seen=(sm, si))
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(eng, broker, max_batch=8)
    client = ServeClient(broker)
    client.ask([0], 3, server=server)  # warm
    rep = run_open_loop(
        client, rate_qps=2000.0, num_requests=24,
        user_rows=zipf_user_rows(USERS, 24, seed=2), k=3, server=server,
        drive_server=True,
    )
    assert rep.as_row()["answered"] == 24 and rep.batches >= 1
    scan = dict(eng.last_scan)
    assert scan["serve_mode"] == "two_stage"
    assert scan["clusters"] == 16 and scan["probe_clusters"] >= 1
    assert 0 < scan["shortlist_rows"] <= MOVIES
    assert scan["bytes_scanned_per_batch"] > 0
    rows = zipf_user_rows(USERS, 8, seed=1)
    _, ids = eng.topk(rows, 3)
    _, oracle = eng.topk(rows, 3, force_exact=True)
    assert 0.0 < float(recall_at_k(ids, oracle)) <= 1.0


def test_default_params_meet_recall_floor():
    from cfk_tpu.plan.cost import SERVE_MIN_RECALL, estimated_recall

    for m in (1_000, 59_047, 500_000):
        c, p = default_two_stage_params(m)
        assert 2 <= c <= m and 1 <= p <= c
        assert estimated_recall(c, p) >= SERVE_MIN_RECALL


def test_roofline_two_stage_variant():
    from cfk_tpu.utils.roofline import (
        expected_shortlist_rows,
        serve_batch_cost,
        serve_roofline_row,
    )

    m, r, b, k = 59_047, 128, 16, 100
    # the expected batch union interpolates between one user's probe
    # share and the whole catalog as the batch grows
    one = expected_shortlist_rows(m, 1, 1024, 32)
    assert one == pytest.approx(m * 32 / 1024)
    assert expected_shortlist_rows(m, 100, 1024, 32) < m
    assert (expected_shortlist_rows(m, 64, 1024, 32)
            > expected_shortlist_rows(m, 8, 1024, 32))
    ex = serve_batch_cost(m, r, b, k, table_dtype="int8")
    ts = serve_batch_cost(m, r, b, k, table_dtype="int8",
                          serve_mode="two_stage", clusters=1024,
                          probe_clusters=32)
    assert ts.hbm_bytes < ex.hbm_bytes  # small batch: two_stage wins
    # a MEASURED union overrides the closed-form expectation
    meas = serve_batch_cost(m, r, b, k, table_dtype="int8",
                            serve_mode="two_stage", clusters=1024,
                            probe_clusters=32, shortlist_rows=2048)
    int8_row = r + 4  # codes + per-row f32 scale
    assert meas.hbm_bytes == pytest.approx(
        1024 * int8_row + 2048 * (int8_row + 4.0)
        + ex.hbm_bytes - m * int8_row, rel=0.05)
    row = serve_roofline_row(ts, 1.0, table_dtype="int8",
                             device_kind="cpu")
    assert "not measured" in row["roofline"] and "vs_roofline" not in row
    assert row["bytes_scanned_per_batch"] == round(ts.hbm_bytes)
    with pytest.raises(ValueError):
        serve_batch_cost(m, r, b, k, serve_mode="two_stage", clusters=0)


def test_similar_items_and_nearest_clusters(rng):
    _, mf = _clustered(rng)
    index = build_cluster_index(mf, 16, seed=0)
    row = 37
    sims = index.similar_items(row, 5)
    assert row not in sims.tolist()
    assert (index.assign[sims] == index.assign[row]).all()
    near = index.nearest_clusters(mf[row], 3)
    assert index.assign[row] in near.tolist()  # own cluster ranks first
