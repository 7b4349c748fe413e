"""Block-builder property tests: the padded rectangles must encode exactly the
same (entity, neighbor, rating) triples as the input COO — the invariant the
reference maintains incrementally in its *Ratings2BlocksProcessors."""

import numpy as np
import pytest

from cfk_tpu.data.blocks import (
    Dataset,
    IdMap,
    RatingsCOO,
    build_padded_blocks,
    build_ring_blocks,
)


def random_coo(rng, n_movies=37, n_users=23, nnz=400):
    # Sparse raw ids with gaps, duplicate (movie,user) pairs avoided.
    movies = rng.choice(np.arange(1, 1000, 3), size=n_movies, replace=False)
    users = rng.choice(np.arange(2, 2000, 5), size=n_users, replace=False)
    pairs = rng.choice(n_movies * n_users, size=nnz, replace=False)
    m = movies[pairs // n_users]
    u = users[pairs % n_users]
    r = rng.integers(1, 6, size=nnz).astype(np.float32)
    return RatingsCOO(movie_raw=m.astype(np.int64), user_raw=u.astype(np.int64), rating=r)


def blocks_to_triples(blocks, fixed_ids):
    """Recover (entity_dense, neighbor_dense, rating) triples from padding."""
    e_idx, p_idx = np.nonzero(blocks.mask)
    return set(
        zip(
            e_idx.tolist(),
            blocks.neighbor_idx[e_idx, p_idx].tolist(),
            blocks.rating[e_idx, p_idx].tolist(),
        )
    )


def test_idmap_roundtrip(rng):
    raw = rng.choice(10_000, size=200, replace=False).astype(np.int64)
    m = IdMap.from_raw(raw)
    assert np.all(np.diff(m.raw_ids) > 0)  # ascending
    dense = m.to_dense(raw)
    np.testing.assert_array_equal(m.raw_ids[dense], raw)


def test_idmap_unknown_raises(rng):
    m = IdMap.from_raw(np.array([3, 7, 11], dtype=np.int64))
    with pytest.raises(KeyError):
        m.to_dense(np.array([3, 8], dtype=np.int64))


@pytest.mark.parametrize("num_shards", [1, 4])
def test_blocks_encode_exact_triples(rng, num_shards):
    coo = random_coo(rng)
    ds = Dataset.from_coo(coo, num_shards=num_shards)

    m_dense = ds.movie_map.to_dense(coo.movie_raw)
    u_dense = ds.user_map.to_dense(coo.user_raw)

    want_movie_side = set(zip(m_dense.tolist(), u_dense.tolist(), coo.rating.tolist()))
    assert blocks_to_triples(ds.movie_blocks, ds.user_map) == want_movie_side

    want_user_side = set(zip(u_dense.tolist(), m_dense.tolist(), coo.rating.tolist()))
    assert blocks_to_triples(ds.user_blocks, ds.movie_map) == want_user_side


@pytest.mark.parametrize("num_shards", [1, 3, 8])
def test_padding_divisible(rng, num_shards):
    coo = random_coo(rng)
    ds = Dataset.from_coo(coo, num_shards=num_shards)
    assert ds.movie_blocks.padded_entities % num_shards == 0
    assert ds.user_blocks.padded_entities % num_shards == 0
    # Pad rows are fully masked with zero counts.
    mb = ds.movie_blocks
    assert np.all(mb.mask[mb.num_entities :] == 0)
    assert np.all(mb.count[mb.num_entities :] == 0)


def test_ring_blocks_cover_all_ratings(rng):
    """Every rating appears exactly once across the ring rectangles, with its
    global neighbor id recoverable as local + shard·Fs (pure numpy)."""
    coo = random_coo(rng)
    ds = Dataset.from_coo(coo, num_shards=4)
    dcoo = ds.coo_dense
    rb = build_ring_blocks(
        dcoo.movie_raw, dcoo.user_raw, dcoo.rating,
        ds.movie_map.num_entities, ds.user_map.num_entities, num_shards=4,
    )
    assert rb.mask.sum() == dcoo.num_ratings
    e_idx, t_idx, p_idx = np.nonzero(rb.mask)
    global_ids = rb.neighbor_local[e_idx, t_idx, p_idx] + t_idx * rb.fixed_shard_size
    got = set(zip(e_idx.tolist(), global_ids.tolist(),
                  rb.rating[e_idx, t_idx, p_idx].tolist()))
    want = set(zip(dcoo.movie_raw.tolist(), dcoo.user_raw.tolist(),
                   dcoo.rating.tolist()))
    assert got == want


def test_counts_match_bincount(rng):
    coo = random_coo(rng)
    ds = Dataset.from_coo(coo)
    m_dense = ds.movie_map.to_dense(coo.movie_raw)
    np.testing.assert_array_equal(
        ds.movie_blocks.count[: ds.movie_blocks.num_entities],
        np.bincount(m_dense, minlength=ds.movie_map.num_entities),
    )
    np.testing.assert_array_equal(
        ds.movie_blocks.count.sum() , coo.num_ratings
    )


def test_tiled_accum_chunk_elems_sizes_only_the_accum_half():
    """``accum_chunk_elems`` (the measured knees differ: 64k stream / 256k
    accum chunks at Netflix shape) applies to the half that resolves to
    accum mode; ``chunk_elems`` still sizes the streamed half."""
    from cfk_tpu.data.synthetic import synthetic_netflix_coo

    coo = synthetic_netflix_coo(3_000, 200, 40_000, seed=2)
    kw = dict(layout="tiled", tile_rows=16, accum_max_entities=1_000,
              chunk_elems=2_048)
    ds = Dataset.from_coo(coo, accum_chunk_elems=8_192, **kw)
    assert ds.movie_blocks.mode == "accum" and ds.user_blocks.mode == "stream"
    assert ds.movie_blocks.chunk_cap == 8_192
    assert ds.user_blocks.chunk_cap == 2_048
    # unset: one knob sizes both, as before
    same = Dataset.from_coo(coo, **kw)
    assert same.movie_blocks.chunk_cap == same.user_blocks.chunk_cap == 2_048
    np.testing.assert_array_equal(same.user_blocks.neighbor_idx,
                                  ds.user_blocks.neighbor_idx)


def test_cached_scale_dataset_builds_through_from_coo(tmp_path):
    """The build-or-load path ``chip_smoke.py`` builds through is
    ``Dataset.from_coo`` and nothing else: same blocks, same knobs."""
    from cfk_tpu.data.cache import cached_scale_dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo

    shape = dict(users=3_000, movies=200, nnz=40_000)
    kw = dict(tile_rows=16, slice_rows=1_024, accum_chunk_elems=8_192,
              dense_stream=True)
    got = cached_scale_dataset(
        **shape, seed=2, layout="tiled", chunk_elems=2_048,
        cache_root=str(tmp_path), log=lambda *a, **k: None, **kw)
    want = Dataset.from_coo(
        synthetic_netflix_coo(3_000, 200, 40_000, seed=2), layout="tiled",
        chunk_elems=2_048, **kw)
    for side in ("movie_blocks", "user_blocks"):
        g, w = getattr(got, side), getattr(want, side)
        assert (g.mode, g.statics) == (w.mode, w.statics)
        np.testing.assert_array_equal(g.neighbor_idx, w.neighbor_idx)
    assert got.movie_blocks.slice_rows == 1_024



def test_cached_scale_dataset_second_call_loads_what_the_first_built(tmp_path):
    """A first call builds and says nothing of a hit; the same arguments
    again load the cache (and say so) and give back the same ratings."""
    from cfk_tpu.data.cache import cached_scale_dataset

    said = []
    kw = dict(users=300, movies=80, nnz=2_000, seed=0, layout="tiled",
              chunk_elems=1_024, tile_rows=16, cache_root=str(tmp_path),
              log=lambda *a, **k: said.append(" ".join(map(str, a))))
    built = cached_scale_dataset(**kw)
    assert not any("cache hit" in line for line in said)
    loaded = cached_scale_dataset(**kw)
    assert any("cache hit" in line for line in said)
    np.testing.assert_array_equal(built.coo_dense.rating,
                                  loaded.coo_dense.rating)
    np.testing.assert_array_equal(built.user_blocks.neighbor_idx,
                                  loaded.user_blocks.neighbor_idx)
