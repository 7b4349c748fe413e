"""A float32 tile's one bfloat16 pass and the gate it decides (ISSUE 50):
the bound that gate reads never hides an entrant, on inputs built against
it; the fold that runs the six-pass block, the masks and the rounds only
behind that gate answers bit for bit what a fold that runs them on every
tile answers (the parent's arithmetic), kernel and twin alike with all five
counts, and what the dense float32 oracle answers wherever float32 sums are
exact; the gate does shut where it must."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cfk_tpu.serving import topk_kernel

# the midpoint between two bfloat16 neighbours, and a hair under it
_HALF = np.float32(1.0 + 2.0 ** -8)
_UNDER = np.nextafter(_HALF, np.float32(0))


def _csr(lists):
    indptr = np.zeros(len(lists) + 1, np.int64)
    indptr[1:] = np.cumsum([len(x) for x in lists])
    movies = (np.concatenate([np.asarray(x, np.int32) for x in lists])
              if indptr[-1] else np.zeros(0, np.int32))
    return movies, indptr


def _bf(x):
    return np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))


def _bound_case(name, t=64, k=128, b=16):
    """(u [b, k], tile [t, k]) built against the bound: what pass 0 loses
    of row r < b is as large as the operands allow for user r."""
    rng = np.random.default_rng(sum(map(ord, name)))
    u = ((rng.random((b, k), dtype=np.float32) - 0.5) * 0.35)
    tile = ((rng.random((t, k), dtype=np.float32) - 0.5) * 0.35)
    if name.startswith("midpoints"):
        # every entry of both operands a hair under a bfloat16 midpoint:
        # each rounds down by all but the whole half spacing, and with
        # equal signs everything pass 0 drops adds up
        sign = rng.choice([-1.0, 1.0], (b, k)).astype(np.float32)
        u = _UNDER * sign * (2.0 ** rng.integers(-6, 3, (b, k))).astype(
            np.float32)
        tile[:b] = _UNDER * sign * 2.0  # one magnitude: the tile's largest
        if name == "midpoints_over":
            # a hair OVER: everything rounds up, pass 0 lies above the block
            over = np.nextafter(_HALF, np.float32(2))
            u, tile[:b] = u / _UNDER * over, tile[:b] / _UNDER * over
        if name == "midpoints_tie":
            # exactly on the midpoint: ties go to even, half of them up
            u, tile[:b] = u / _UNDER * _HALF, tile[:b] / _UNDER * _HALF
    if name == "zeros":
        tile[:] = 0.0
    if name == "padded":
        tile[t // 2:] = 0.0  # the table's last tile: rows of padding
    if name == "huge_tiny_u":
        u = u * (2.0 ** rng.choice([-40, 0, 40], (b, k))).astype(np.float32)
    if name == "huge_tiny_tile":
        tile = tile * (2.0 ** rng.choice([-30, 0, 30], (t, 1))
                       ).astype(np.float32)
    if name == "top_entry_elsewhere":
        # the largest |entry| sits in a row that scores nothing
        tile[-1] = 0.0
        tile[-1, 0] = 64.0
        u[:, 0] = 0.0
    if name == "one_hot":
        # one coordinate carries everything: the 1-norm is no looser
        # than the sum it bounds
        u[:] = 0.0
        u[:, 5] = _UNDER
        tile[:b, 5] = _UNDER * 8
    return u, tile


BOUND_CASES = ("weights", "midpoints", "midpoints_over", "midpoints_tie",
               "zeros", "padded", "huge_tiny_u", "huge_tiny_tile",
               "top_entry_elsewhere", "one_hot")


@pytest.mark.parametrize("jitted", [False, True])
@pytest.mark.parametrize("case", BOUND_CASES)
def test_the_float_gate_never_hides_an_entrant(case, jitted):
    """``_pass0_bound`` from one bfloat16 pass is no smaller than the
    maximum of the block at ``Precision.HIGHEST``, for every user, on
    inputs built against it; so a K-th score one ulp under the exact
    maximum — which pass 0's own maximum may not reach — leaves the gate
    open."""
    u, tile = _bound_case(case)

    def both(u, tile):
        u, u0, slack = topk_kernel.resident_operand(u, jnp.float32)
        bound = topk_kernel._pass0_bound(tile, u0, slack)
        exact = topk_kernel._tile_scores(u, tile, None)
        first = topk_kernel._tile_max(topk_kernel._bf16_pass(
            tile.astype(jnp.bfloat16), u0))
        return bound, exact, first, u0, slack

    bound, exact, first, u0, slack = map(
        np.asarray, (jax.jit(both) if jitted else both)(
            jnp.asarray(u), jnp.asarray(tile)))
    np.testing.assert_array_equal(u0.astype(np.float32), _bf(u))
    assert slack.shape == (2, 1, u.shape[0])
    top = exact.max(axis=0)
    assert np.isfinite(bound).all() and np.isfinite(exact).all()
    assert (bound[0] >= top).all()
    if case != "zeros":
        one_ulp_under = np.nextafter(top, np.float32(-np.inf))[None]
        assert bool(topk_kernel._entrant(jnp.asarray(bound),
                                         jnp.asarray(one_ulp_under)))
        assert (bound[0] > one_ulp_under[0]).all()  # user by user
    else:
        # a tile of zeros scores 0 and is bounded by 0: it enters a
        # user's top-K exactly when that user's K-th score is negative
        assert not bound.any() and not exact.any()
    if case == "midpoints":
        # the case is worth its name: pass 0 alone misses the maximum by
        # a good part of the slack the bound allows for, so a gate read
        # from pass 0's maximum would have stayed shut
        lost = top - first[0]
        assert (lost >= 0.9 * (bound[0] - first[0])).all() and (lost > 0).all()
    if case == "midpoints_over":
        assert (first[0] > top).all()
    # against the dot product in float64 too, up to the block's own
    # float32 rounding
    real = (tile.astype(np.float64) @ u.astype(np.float64).T).max(axis=0)
    assert (bound[0] >= real - 1e-5 * np.abs(real)).all()


def test_half_the_bfloat16_spacing_is_read_from_the_exponent():
    """The h of ``_pass0_bound``: 2^(floor(log2 a) - 8) bounds what
    rounding to bfloat16 moves any entry no larger than a, and is attained
    just under a midpoint."""
    for a in (1.0, 1.5, 1.9999999, 0.175, 3e-5, 7e12):
        a = np.float32(a)
        h = np.float32(2.0 ** (np.floor(np.log2(a)) - 8))
        xs = np.float32(a) * np.linspace(0, 1, 4001, dtype=np.float32)
        assert (np.abs(xs - _bf(xs)) <= h).all()
        tile = np.zeros((8, 8), np.float32)
        tile[3, 2] = -a
        u0 = jnp.zeros((4, 8), jnp.bfloat16)
        slack = jnp.stack([jnp.ones((1, 4)), jnp.zeros((1, 4))])
        got = np.asarray(topk_kernel._pass0_bound(jnp.asarray(tile), u0,
                                                  slack))
        assert (got == h).all()
    e = np.float32(2.0) ** np.floor(np.log2(np.float32(0.175)))
    x = _UNDER * e
    assert abs(x - _bf(x)) > 0.99 * e * 2.0 ** -8


# -- the fold behind the gate ------------------------------------------------

_NT, _T, _K, _KTOP = 35, 16, 16, 10  # 2 G + 3 tiles of 16 rows


def _lattice(rng, shape, bits=10):
    """Multiples of 2^-bits in (-1, 1): ``bits`` significant bits at most,
    so a product of two is exact in float32 and so is a sum of 16, in any
    order — and bfloat16, which keeps 8, rounds most of them."""
    return (rng.integers(-(2 ** bits) + 1, 2 ** bits, shape)
            / 2.0 ** bits).astype(np.float32)


def _gate_problem(data_case, b, exclusion):
    """(u, table, seen rectangle or None, lists, num_movies, exact): 2 G +
    3 tiles, the last grid step a ragged slab of three, the last tile
    reaching past ``num_movies`` with padding rows that would win."""
    rng = np.random.default_rng(b + len(data_case) + len(exclusion))
    nt, t, k = _NT, _T, _K
    m = nt * t - 5
    u = _lattice(rng, (b, k))
    mf = _lattice(rng, (nt * t, k))
    decays, exact = True, True
    if data_case == "midpoints":
        # every entry of the table on or a step under a bfloat16
        # midpoint: pass 0 is as far off as 9 and 11 bits allow (and u
        # keeps 7, so that the sums stay exact)
        mag = np.where(rng.random((nt * t, k)) < 0.5, 1 + 2.0 ** -8,
                       1 + 2.0 ** -8 - 2.0 ** -10)
        mf = (mag * 2.0 ** rng.integers(-3, 0, (nt * t, k))
              * rng.choice([-1.0, 1.0], (nt * t, k))).astype(np.float32)
        u = _lattice(rng, (b, k), bits=7)
    if data_case in ("one_ulp", "late_ulp"):
        # a row scores 1/2 + r ulps for every other user, 1/2 - r ulps for
        # the rest, r < 1024 a 10-bit number that bfloat16 rounds
        decays = False
        mf[:], u[:] = 0.0, 0.0
        mf[:, 0], u[:, 0] = 0.5, 1.0
        u[0::2, 1], u[1::2, 1] = 2.0 ** -14, -2.0 ** -14
        if data_case == "one_ulp":
            # each row beats the one before by one ulp of the score
            mf[:, 1] = np.arange(nt * t) / 1024.0
        else:
            # every row scores the same but one in a late tile, one ulp
            # over: pass 0 rounds its 513 to the others' 512
            mf[:, 1] = 512 / 1024.0
            mf[20 * t + 3, 1] = 513 / 1024.0
    if data_case == "zeros":
        # whole tiles of zeros among the others; every other user scores
        # below zero on every other row, so the zero rows enter its top-K
        mf = np.abs(mf)
        mf[np.isin(np.arange(nt * t) // t, [1, 4, 5, 17, 30, 34])] = 0.0
        u[1::2] = -np.abs(u[1::2])
    if data_case == "ties":
        decays = False
        mf[:] = mf[:7][rng.integers(0, 7, nt * t)]  # seven distinct rows
    if data_case == "huge_tiny_u":
        # sums over 2^80 of range are not exact: held to the parent's fold
        u = u * (2.0 ** rng.choice([-40, 0, 40], (b, k))).astype(np.float32)
        exact = False
    if decays:
        # later tiles score lower, by powers of two (the lattice keeps its
        # bits), so that their gates shut for a full batch
        mf = mf * (2.0 ** -(np.arange(nt * t) // (3 * t))).astype(
            np.float32)[:, None]
    mf[m:] = 2.0 ** 12  # padding rows would win if the mask let them
    if exclusion == "none":
        return u, mf, None, [np.zeros(0, np.int64)] * b, m, exact
    if exclusion == "sparse":
        lists = [np.sort(rng.choice(m, int(rng.integers(0, 4)), False))
                 for _ in range(b)]
    elif exclusion == "few":
        # user i keeps i % (K + 1) candidates: from none to exactly K
        lists = [np.sort(rng.permutation(m)[i % (_KTOP + 1):])
                 for i in range(b)]
    else:  # every tile holds a cell of some user: the tile's best row
        lists = [np.zeros(0, np.int64) for _ in range(b)]
        best = (mf[:m].astype(np.float64) @ u[0].astype(np.float64))
        lists[0] = np.sort(np.asarray(
            [j * t + int(np.argmax(best[j * t:min((j + 1) * t, m)]))
             for j in range(nt)]))
        lists[1 % b] = np.union1d(lists[1 % b], rng.choice(m, 5, False))
    movies, indptr = _csr([x.astype(np.int32) for x in lists])
    st = topk_kernel.build_seen_tiles(
        movies, indptr, np.arange(b), num_movies=m, tile_m=t)
    return u, mf, jnp.asarray(st), lists, m, exact


def _dense_oracle(u, mf, lists, m, k_top):
    """Every score in float64 (exact on the lattice, so the float32 sum in
    any order), stable top-K by score descending, row ascending."""
    sc = (mf[:m].astype(np.float64) @ u.astype(np.float64).T).astype(
        np.float32)
    assert (sc.astype(np.float64) == mf[:m].astype(np.float64)
            @ u.astype(np.float64).T).all()
    vals = np.full((len(lists), k_top), -np.inf, np.float32)
    ids = np.full((len(lists), k_top), -1, np.int32)
    for i, seen in enumerate(lists):
        cand = np.setdiff1d(np.arange(m), seen)
        order = cand[np.lexsort((cand, -sc[cand, i]))][:k_top]
        vals[i, :order.size], ids[i, :order.size] = sc[order, i], order
    return vals, ids


@pytest.mark.parametrize("data_case", ["lattice", "midpoints", "one_ulp",
                                       "late_ulp", "zeros", "ties",
                                       "huge_tiny_u"])
@pytest.mark.parametrize("exclusion", ["none", "sparse", "few",
                                       "every_tile"])
@pytest.mark.parametrize("b", [8, 256])
def test_gated_float_fold_is_the_parents_fold_bit_for_bit(
        b, exclusion, data_case, monkeypatch):
    """Kernel (interpret path) and twin with the block behind the first
    gate, against the same fold with that gate held open on every tile —
    six passes, the masks and the exact gate everywhere: the parent's
    arithmetic — and against the dense float32 oracle where float32 sums
    are exact.  Scores, ids and the selection's counts to the bit; the
    exclusion counts and the completed tiles no larger."""
    from cfk_tpu.compat import emulate_topk_counted

    u, mf, st, lists, m, exact = _gate_problem(data_case, b, exclusion)
    args = (jnp.asarray(u), jnp.asarray(mf), None, st)
    kw = dict(k_top=_KTOP, num_movies=m, tile_m=_T)
    routes = (topk_kernel.topk_scores_counted, emulate_topk_counted)
    gated = [tuple(map(np.asarray, fn(*args, **kw))) for fn in routes]
    monkeypatch.setattr(
        topk_kernel, "_pass0_bound",
        lambda tile, u0, slack: jnp.full((1, u0.shape[0]), jnp.inf,
                                         jnp.float32))
    parents = [tuple(map(np.asarray, fn(*args, **kw))) for fn in routes]
    for got, want in zip(gated, parents):
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
        assert got[0].tobytes() == want[0].tobytes()
        assert got[2][:2].tolist() == want[2][:2].tolist()
        assert (got[2][2:] <= want[2][2:]).all()
        assert want[2][4] == _NT
        assert got[2][1] <= got[2][4]  # a tile that ran a round was completed
        assert got[2][3] <= got[2][4]  # and so was one that ran a mask
    # kernel == twin with all five counts, either way
    for kernel, twin in (gated, parents):
        for x, y in zip(kernel, twin):
            np.testing.assert_array_equal(x, y)
    if data_case in ("lattice", "midpoints", "zeros") and exclusion != "few":
        # the gate did shut somewhere (never for a user short of K
        # candidates, whose K-th score stays -inf)
        assert gated[0][2][4] < _NT
    if exact:
        want_v, want_i = _dense_oracle(u, mf, lists, m, _KTOP)
        np.testing.assert_array_equal(gated[0][1], want_i)
        np.testing.assert_array_equal(gated[0][0], want_v)
    if data_case == "one_ulp" and exclusion == "none":
        # the last K rows for the users whose scores ascend, the first K
        # for those whose scores descend
        assert gated[0][1][0].tolist() == list(range(m - 1, m - 1 - _KTOP,
                                                     -1))
        assert gated[0][1][1].tolist() == list(range(_KTOP))
    if data_case == "late_ulp" and exclusion == "none":
        # the late row first where it is one ulp over, never where under
        late = 20 * _T + 3
        assert gated[0][1][0].tolist() == [late] + list(range(_KTOP - 1))
        assert gated[0][1][1].tolist() == list(range(_KTOP))


def test_a_gate_read_from_pass_0_alone_would_lose_rows(monkeypatch):
    """The control of the test above: with the bound's slack left out the
    first gate shuts on the tile that holds an entrant one ulp over the
    K-th score, and the answer loses it — the slack is what makes one
    bfloat16 pass enough."""
    u, mf, st, lists, m, _ = _gate_problem("late_ulp", 8, "none")
    want_v, want_i = _dense_oracle(u, mf, lists, m, _KTOP)
    sound = topk_kernel._pass0_bound
    monkeypatch.setattr(
        topk_kernel, "_pass0_bound",
        lambda tile, u0, slack: sound(tile, u0, [0.0 * slack[0]] * 2))
    _, ids, counts = topk_kernel.topk_scores_counted(
        jnp.asarray(u), jnp.asarray(mf), None, st, k_top=_KTOP,
        num_movies=m, tile_m=_T)
    assert not np.array_equal(np.asarray(ids), want_i)
    assert int(counts[4]) < _NT


def test_a_float32_engine_counts_the_tiles_its_gate_opened():
    """``ServeEngine`` on a float32 table: the answers are the dense
    oracle's, ``serve/batch/compute`` says 7 passes a completed tile and
    fewer tiles completed than scanned."""
    from cfk_tpu import telemetry
    from cfk_tpu.serving import engine as engine_mod

    rng = np.random.default_rng(50)
    users, m, k = 24, 40 * _T - 3, _K
    uf, mf = _lattice(rng, (users, k)), _lattice(rng, (m, k))
    mf *= (2.0 ** -(np.arange(m) // (4 * _T))).astype(np.float32)[:, None]
    lists = [np.sort(rng.choice(m, int(rng.integers(0, 30)), replace=False))
             for _ in range(users)]
    movies, indptr = _csr(lists)
    rows = rng.integers(0, users, size=13)
    tracer = telemetry.configure()
    try:
        eng = engine_mod.ServeEngine(
            uf, mf, num_users=users, num_movies=m, seen_movies=movies,
            seen_indptr=indptr, batch_quantum=8, tile_m=_T,
            table_dtype="float32")
        vals, ids = eng.topk(rows, 7)
        (compute,) = [e["args"] for e in tracer.events()
                      if e.get("ph") == "X"
                      and e["name"] == "serve/batch/compute"]
    finally:
        telemetry.shutdown(write=False)
    want_v, want_i = _dense_oracle(uf[rows], mf, [lists[r] for r in rows], m,
                                   7)
    np.testing.assert_array_equal(ids, want_i)
    np.testing.assert_array_equal(vals, want_v)
    assert compute["score_passes"] == 7 and compute["tiles"] == 40
    assert (max(compute["select_tiles"], compute["seen_hit_tiles"])
            <= compute["completed_tiles"] < compute["tiles"])
