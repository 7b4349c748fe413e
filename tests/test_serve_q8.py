"""An int8 row-quantized item table on one device, uploaded in slices (ISSUE
32): answers against the plain numpy reference of the quantized semantics on
one device and over 2- and 4-device meshes; the sliced upload's codes and
scales against quantizing the whole table at once, to the bit; a control in
narrower arithmetic failing the stated limits; a table past the device's
budget going up in slices where it fits quantized and refused in words where
it does not; the spans.  And the arithmetic of an int8 tile's score block
(ISSUE 35): the three bfloat16 pieces of ``u`` sum to it bit for bit, every
code is exact in bfloat16, the three-pass block sits where a float32 sum
can, and each piece is needed.  And the gate its passes 1 and 2 wait behind
(ISSUE 48): the bound that decides it never hides an entrant, the fold that
defers them answers bit for bit what a fold that completes every tile
answers, kernel and twin alike with all five counts, float tables defer
nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cfk_tpu import telemetry
from cfk_tpu.ops.quant import quantize_rows_host, quantize_table
from cfk_tpu.serving import engine as engine_mod
from cfk_tpu.serving import topk_kernel
from tests.serve_reference import (
    exact_topk_blocks, quantize_rows, quantized_topk, topk_gaps)

RANK, TILE = 8, 16
# the benchmark's limits (benchmarks/configs/amazon23-serve-r128-int8.json)
RANK_GAP, SCORE_ERR = 1e-5, 2e-5


def _csr(lists):
    indptr = np.zeros(len(lists) + 1, np.int64)
    indptr[1:] = np.cumsum([len(x) for x in lists])
    movies = (np.concatenate([np.asarray(x, np.int32) for x in lists])
              if indptr[-1] else np.zeros(0, np.int32))
    return movies, indptr


def _problem(seed=0, m=1000, users=24, rank=RANK, scale=0.35):
    """Seeded weights shaped like the benchmark's: uniform in ±scale/2."""
    rng = np.random.default_rng(seed)
    uf = ((rng.random((users, rank), dtype=np.float32) - 0.5) * scale)
    mf = ((rng.random((m, rank), dtype=np.float32) - 0.5) * scale)
    mf[3] = 0.0  # an all-zero row: scale 1.0, codes 0
    lists = [np.sort(rng.choice(m, int(rng.integers(0, 40)), replace=False))
             for _ in range(users)]
    rows = rng.integers(0, users, size=13)
    return uf, mf, lists, rows


def _engine(uf, mf, lists, **kw):
    movies, indptr = _csr(lists)
    kw.setdefault("tile_m", TILE)
    kw.setdefault("table_dtype", "int8")
    return engine_mod.ServeEngine(
        uf, mf, num_users=uf.shape[0],
        num_movies=kw.pop("num_movies", None) or mf.shape[0],
        seen_movies=movies, seen_indptr=indptr, batch_quantum=8, **kw)


def _slices(monkeypatch, rows, rank=RANK):
    monkeypatch.setattr(engine_mod, "_SLICE_BYTES", rows * rank * 4)


def _table(eng):
    data, scale = eng._table
    return np.asarray(data), None if scale is None else np.asarray(scale)


@pytest.mark.parametrize("shards", [None, 2, 4])
@pytest.mark.parametrize("seed", [0, 1])
def test_int8_answers_equal_the_quantized_reference(seed, shards):
    uf, mf, lists, rows = _problem(seed)
    k = 7
    vals, ids = _engine(uf, mf, lists, shards=shards).topk(rows, k)
    best, best_ids, at = quantized_topk(
        uf[rows], mf, [lists[r] for r in rows], k, ids, block=97)
    np.testing.assert_array_equal(ids, best_ids)
    rank_gap, score_err = topk_gaps(vals, best, at)
    assert rank_gap == 0.0 and score_err <= 2e-6
    assert rank_gap <= RANK_GAP and score_err <= SCORE_ERR
    for row, got in zip(rows, ids):
        assert not set(got.tolist()) & set(np.asarray(lists[row]).tolist())
    # and it is the quantized table's answer, not the float32 table's
    exact, _, _ = exact_topk_blocks(uf[rows], mf, [lists[r] for r in rows], k)
    assert np.abs(exact - vals).max() > 1e-4


@pytest.mark.parametrize("reader", ["array", "callable"])
@pytest.mark.parametrize("shards", [None, 2, 4])
@pytest.mark.parametrize("slice_rows", [100, 256, 5000])
def test_sliced_upload_is_the_whole_table_quantizer_to_the_bit(
        slice_rows, shards, reader, monkeypatch):
    """100 rows a slice is no multiple of the 16-row tile and leaves a last
    short slice on every device; 5,000 is the table in one piece."""
    uf, mf, lists, rows = _problem(2)
    _slices(monkeypatch, slice_rows)
    asked = []

    def read(lo, hi):
        asked.append((lo, hi))
        return mf[lo:hi]

    eng = _engine(uf, read if reader == "callable" else mf, lists,
                  shards=shards, num_movies=mf.shape[0])
    data, scale = _table(eng)
    per = -(-1000 // ((shards or 1) * TILE)) * TILE
    assert data.shape == (per * (shards or 1), RANK) and data.dtype == np.int8
    padded = np.zeros((data.shape[0], RANK), np.float32)
    padded[:1000] = mf
    # the rule three ways: the plain reference, the program's device
    # quantizer run op by op on the CPU (IEEE), the program's host quantizer
    for codes, scales in (quantize_rows(padded),
                          quantize_table(jnp.asarray(padded), "int8"),
                          quantize_rows_host(padded, threads=3)):
        np.testing.assert_array_equal(data, np.asarray(codes))
        np.testing.assert_array_equal(scale, np.asarray(scales))
    assert scale[3] == 1.0 and not data[3].any()
    if reader == "callable":
        # every row asked for once, in ranges of at most a slice, none past
        # the catalogue
        assert sorted(asked) == asked and asked[0][0] == 0
        assert all(0 < hi - lo <= slice_rows for lo, hi in asked)
        assert sum(hi - lo for lo, hi in asked) == 1000
    want = _engine(uf, mf, lists).topk(rows, 7)
    for a, b in zip(want, eng.topk(rows, 7)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shards", [None, 2])
def test_sliced_upload_of_a_float_table_is_the_whole_cast(
        table_dtype, shards, monkeypatch):
    uf, mf, lists, rows = _problem(3)
    whole = _engine(uf, mf, lists, shards=shards, table_dtype=table_dtype)
    _slices(monkeypatch, 100)
    sliced = _engine(uf, lambda lo, hi: mf[lo:hi], lists, shards=shards,
                     table_dtype=table_dtype, num_movies=mf.shape[0])
    assert sliced._table[1] is None
    np.testing.assert_array_equal(np.asarray(sliced._table[0]),
                                  np.asarray(whole._table[0]))
    for a, b in zip(whole.topk(rows, 7), sliced.topk(rows, 7)):
        np.testing.assert_array_equal(a, b)


def test_one_pass_bfloat16_control_fails_the_stated_limits(monkeypatch):
    """The nearest arithmetic under the stated one: the dequantized tile
    through a one-pass bfloat16 matmul.  No knob of the program does that;
    the test patches the fold's compute dtype.  It must fail both limits
    while every id it serves stays valid."""
    uf, mf, lists, _ = _problem(4, m=20_001, users=256, rank=128)
    rows, k = np.arange(256), 10
    sound = _engine(uf, mf, lists, tile_m=512).topk(rows, k)
    real = topk_kernel.serve_compute_dtype
    monkeypatch.setattr(
        topk_kernel, "serve_compute_dtype",
        lambda dtype: (jnp.bfloat16, None) if dtype == jnp.int8
        else real(dtype))
    # the control has no second pass to defer: every tile is completed
    assert topk_kernel.deferred_passes(jnp.dtype("int8")) == ()
    assert topk_kernel.score_passes(jnp.dtype("int8")) == 1
    # jax keeps traces by function and shapes: another tile height (the
    # scores do not depend on it) makes the patched fold a trace of its own
    tracer = telemetry.configure()
    try:
        control = _engine(uf, mf, lists, tile_m=256).topk(rows, k)
        (compute,) = [e["args"] for e in tracer.events()
                      if e.get("ph") == "X"
                      and e["name"] == "serve/batch/compute"]
    finally:
        telemetry.shutdown(write=False)
    assert compute["completed_tiles"] == compute["tiles"]
    seen = [lists[r] for r in rows]
    best, _, at = quantized_topk(uf[rows], mf, seen, k, sound[1])
    assert topk_gaps(sound[0], best, at) <= (RANK_GAP, SCORE_ERR)
    best, _, at = quantized_topk(uf[rows], mf, seen, k, control[1])
    rank_gap, score_err = topk_gaps(control[0], best, at)
    assert rank_gap > RANK_GAP and score_err > SCORE_ERR
    for row, got in zip(rows, control[1]):
        assert len(set(got.tolist())) == k
        assert not set(got.tolist()) & set(np.asarray(lists[row]).tolist())


def test_a_table_past_the_device_budget_goes_up_in_slices_or_is_refused(
        monkeypatch):
    """32 KB of float32 against a device that holds 20 KB: whole, it is
    refused in words before anything is allocated; as int8 it fits, and goes
    up in 100-row slices of codes: no float32 slice of the table reaches the
    device, no range over a slice is asked of the reader."""
    uf, mf, lists, rows = _problem(5)
    monkeypatch.setattr(engine_mod, "_device_bytes_limit", lambda d: 20_000)
    _slices(monkeypatch, 100)
    with pytest.raises(ValueError, match="does not fit its device.*32,256 B"
                                         ".*20,000 B.*shards=.*table_dtype="):
        _engine(uf, mf, lists, table_dtype="float32")
    with pytest.raises(ValueError, match="does not fit its device"):
        _engine(uf, lambda lo, hi: mf[lo:hi], lists, table_dtype="bfloat16",
                num_movies=1000)  # 16 KB held + two 3.2 KB slices in flight
    put, asked = [], []
    real_put = jax.device_put
    monkeypatch.setattr(
        jax, "device_put",
        lambda x, *a, **kw: put.append((np.shape(x), np.asarray(x).dtype))
        or real_put(x, *a, **kw))

    def read(lo, hi):
        asked.append(hi - lo)
        return mf[lo:hi]

    before = {id(a) for a in jax.live_arrays()}
    tracer = telemetry.configure()
    try:
        eng = _engine(uf, read, lists, num_movies=1000)
        vals, ids = eng.topk(rows, 7)
        events = {e["name"]: e["args"] for e in tracer.events()
                  if e.get("ph") == "X"}
    finally:
        telemetry.shutdown(write=False)
    assert max(asked) == 100 and sum(asked) == 1000
    tables = [(shape, dt) for shape, dt in put if len(shape) == 2]
    assert tables == [((100, RANK), np.int8)] * 10 + [((8, RANK), np.int8)]
    for a in jax.live_arrays():
        if id(a) not in before and a.ndim == 2 and a.shape[0] >= 1000:
            assert a.dtype == jnp.int8, (a.shape, a.dtype)
    held = 1008 * RANK + 1008 * 4
    assert events["serve/engine/table_upload"] == {
        "shards": 1, "rows_per_shard": 1008, "rows_per_slice": 100,
        "slices": 11, "bytes": held, "table_dtype": "int8",
        "quantized_on": "host"}
    compute = events["serve/batch/compute"]
    assert compute["table_dtype"] == "int8" and compute["scan_bytes"] == held
    best, best_ids, at = quantized_topk(
        uf[rows], mf, [lists[r] for r in rows], 7, ids)
    np.testing.assert_array_equal(ids, best_ids)
    assert topk_gaps(vals, best, at) <= (0.0, 2e-6)


@pytest.mark.parametrize("shards", [None, 2])
def test_deltas_and_a_swap_keep_the_rule_and_the_placement(shards,
                                                          monkeypatch):
    """A delta row's codes and scale are what quantizing the whole updated
    table gives, and ``load_state`` takes a row reader as the constructor
    does."""
    uf, mf, lists, rows = _problem(6)
    _slices(monkeypatch, 300)
    eng = _engine(uf, mf, lists, shards=shards)
    rng = np.random.default_rng(7)
    delta_rows = np.array([0, 3, 299, 300, 999])
    delta = ((rng.random((5, RANK), dtype=np.float32) - 0.5) * 0.35)
    assert eng.apply_movie_deltas(delta_rows, delta) == 5
    mf2 = mf.copy()
    mf2[delta_rows] = delta
    padded = np.zeros((eng.table_rows, RANK), np.float32)
    padded[:1000] = mf2
    for got, want in zip(_table(eng), quantize_rows(padded)):
        np.testing.assert_array_equal(got, want)
    mf3 = ((rng.random(mf.shape, dtype=np.float32) - 0.5) * 0.35)
    placed = eng._table[0].sharding
    eng.load_state(uf, lambda lo, hi: mf3[lo:hi], epoch=2)
    assert eng._table[0].sharding == placed and eng.table_swaps == 1
    padded[:1000] = mf3
    for got, want in zip(_table(eng), quantize_rows(padded)):
        np.testing.assert_array_equal(got, want)


def test_host_quantizer_is_the_rule_on_awkward_rows():
    """Ties at .5 round to even, the largest magnitude maps to ±127, a
    zero row keeps scale 1, and the pieces of the thread pool join up."""
    rng = np.random.default_rng(9)
    f = ((rng.random((40_000, 16), dtype=np.float32) - 0.5) * 0.35)
    f[0] = 0.0
    f[1] = 0.0
    f[1, :7] = [127.0, 0.5, 1.5, 2.5, 3.5, -0.5, -1.5]  # scale exactly 1
    f[2, 5] = -3.0
    for threads in (1, 4):
        codes, scales = quantize_rows_host(f, threads=threads)
        want_c, want_s = quantize_rows(f)
        np.testing.assert_array_equal(codes, want_c)
        np.testing.assert_array_equal(scales, want_s)
    assert scales[0] == 1.0 and not codes[0].any()
    assert codes[2, 5] == -127 and np.abs(codes).max() == 127
    # halves round to the even neighbour
    assert scales[1] == 1.0
    assert codes[1, :7].tolist() == [127, 0, 2, 2, 4, 0, -2]


# -- the int8 tile's score block: three exact bfloat16 passes (ISSUE 35) -----

def _split_cases(name):
    rng = np.random.default_rng(35)
    if name == "weights":  # what the benchmark's user factors look like
        return (rng.random((256, 128), dtype=np.float32) - 0.5) * 0.35
    if name == "normal":
        return rng.standard_normal((256, 128)).astype(np.float32)
    if name == "wide":  # every binade from 1e-30 to 1e30, both signs
        mag = np.exp(rng.uniform(np.log(1e-30), np.log(1e30), (256, 128)))
        return (mag * rng.choice([-1.0, 1.0], mag.shape)).astype(np.float32)
    # zeros, negatives, powers of two, their neighbours a float32 ulp off,
    # values that round up into the next binade, a very small one
    pows = 2.0 ** np.arange(-40, 41, dtype=np.float32)
    return np.concatenate([
        np.array([0.0, -0.0, 1.0, -1.0, 1e-30, -1e-30, 3.0, -0.1,
                  1.9999999, 255.5, 65535.99, np.pi, -np.e], np.float32),
        pows, -pows, np.nextafter(pows, np.float32(0)),
        np.nextafter(pows, np.float32(np.inf)),
    ]).astype(np.float32)[None]


@pytest.mark.parametrize("jitted", [False, True])
@pytest.mark.parametrize("values", ["weights", "normal", "wide", "awkward"])
def test_three_bfloat16_pieces_sum_to_u_bit_for_bit(values, jitted):
    u = _split_cases(values)
    split = topk_kernel.split_bf16x3
    pieces = (jax.jit(split) if jitted else split)(jnp.asarray(u))
    assert pieces.dtype == jnp.bfloat16 and pieces.shape == (3,) + u.shape
    hi, mid, lo = (np.asarray(p.astype(jnp.float32)) for p in pieces)
    for total in ((hi + mid) + lo, hi + (mid + lo)):
        assert total.dtype == np.float32
        np.testing.assert_array_equal(total, u)
        # to the bit, but for the sign of a zero (-0.0 comes back 0.0)
        np.testing.assert_array_equal(total.view(np.uint32)[u != 0],
                                      u.view(np.uint32)[u != 0])
    # the first piece is u rounded to bfloat16: the controls' operand
    np.testing.assert_array_equal(
        hi, np.asarray(jnp.asarray(u).astype(jnp.bfloat16)
                       .astype(jnp.float32)))
    # each piece has what is left over: 2^-8, 2^-16 of the one before
    assert (np.abs(mid) <= np.abs(hi) * 2.0 ** -8).all()
    assert (np.abs(lo) <= np.abs(hi) * 2.0 ** -16).all()


@pytest.mark.parametrize("jitted", [False, True])
def test_every_code_is_exact_in_bfloat16(jitted):
    codes = np.arange(-127, 128, dtype=np.int8)
    assert codes.size == 255
    through = lambda c: c.astype(jnp.bfloat16).astype(jnp.float32)
    got = (jax.jit(through) if jitted else through)(jnp.asarray(codes))
    np.testing.assert_array_equal(np.asarray(got),
                                  codes.astype(np.float32))


def _block_error(route, seed, monkeypatch=None, drop=()):
    """Worst distance of the scorer's int8 scores at rank 128, tile 512
    from the float64 value of s_j sum_i u_i c_ij, over s_j sum_i |u_i
    c_ij|, at the ids it served (every row of four tiles enters a top-K
    somewhere: K = 64 of 2,048 rows for 32 users)."""
    from cfk_tpu.compat import emulate_topk_scores

    rng = np.random.default_rng(seed)
    m, k, b, k_top, tile = 2_048, 128, 32, 64, 512
    codes = rng.integers(-127, 128, (m, k), dtype=np.int8)
    scales = rng.uniform(1e-3, 2e-3, m).astype(np.float32)
    u = ((rng.random((b, k), dtype=np.float32) - 0.5) * 0.35)
    if drop:
        real = topk_kernel.split_bf16x3
        monkeypatch.setattr(
            topk_kernel, "split_bf16x3",
            lambda x: real(x).at[np.asarray(drop)].set(0))
    fn = (topk_kernel.topk_scores_pallas if route == "kernel"
          else emulate_topk_scores)
    vals, ids = fn(jnp.asarray(u), jnp.asarray(codes), jnp.asarray(scales),
                   None, k_top=k_top, num_movies=m, tile_m=tile)
    vals, ids = np.asarray(vals), np.asarray(ids)
    assert vals.dtype == np.float32 and (ids >= 0).all()
    terms = u.astype(np.float64)[:, None, :] * codes[ids].astype(np.float64)
    s = scales[ids].astype(np.float64)
    return (np.abs(vals - s * terms.sum(-1))
            / (s * np.abs(terms).sum(-1))).max()


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("route", ["kernel", "twin"])
def test_three_passes_are_a_float32_sum_and_each_piece_is_needed(
        route, seed, monkeypatch):
    sound = _block_error(route, seed)
    assert sound <= 2.0 ** -20
    # u_lo dropped: 16 bits of u instead of 24
    two = _block_error(route, seed, monkeypatch, drop=(2,))
    assert two >= 10 * sound and two <= 2.0 ** -15
    # and u_mid with it: the one-pass arithmetic of the controls
    one = _block_error(route, seed, monkeypatch, drop=(1, 2))
    assert one >= 10 * two


@pytest.mark.parametrize("shards", [None, 2])
@pytest.mark.parametrize("table_dtype,passes", [
    ("int8", 3), ("float32", 7), ("bfloat16", 1)])
def test_compute_span_says_how_many_passes_a_tile_takes(table_dtype, passes,
                                                        shards):
    uf, mf, lists, rows = _problem(10)
    assert topk_kernel.score_passes(jnp.dtype(table_dtype)) == passes
    tracer = telemetry.configure()
    try:
        _engine(uf, mf, lists, shards=shards,
                table_dtype=table_dtype).topk(rows, 7)
        (compute,) = [e["args"] for e in tracer.events()
                      if e.get("ph") == "X"
                      and e["name"] == "serve/batch/compute"]
    finally:
        telemetry.shutdown(write=False)
    assert compute["table_dtype"] == table_dtype
    assert compute["score_passes"] == passes
    assert compute.get("shards") == shards
    # the tiles every one of those passes ran on: all of a bfloat16
    # table's, of an int8 or a float32 table's those whose first gate
    # opened (all shards')
    assert compute["select_tiles"] <= compute["completed_tiles"]
    assert compute["seen_hit_tiles"] <= compute["completed_tiles"]
    if table_dtype == "bfloat16":
        assert compute["completed_tiles"] == compute["tiles"]
    else:
        assert compute["completed_tiles"] <= compute["tiles"]


# -- passes 1 and 2 behind a gate that pass 0 decides (ISSUE 48) -------------

_HALF = 1.0 + 2.0 ** -8  # the midpoint between two bfloat16 neighbours


def _bound_case(name, t=64, k=128, b=16):
    """(u [b, k], codes [t, k] int8, scales [t]) built against the bound:
    what passes 1 and 2 add to row 0 is as large as the operands allow."""
    rng = np.random.default_rng(sum(map(ord, name)))
    u = ((rng.random((b, k), dtype=np.float32) - 0.5) * 0.35)
    codes = rng.integers(-127, 128, (t, k), dtype=np.int8)
    scales = rng.uniform(1e-3, 2e-3, t).astype(np.float32)
    if name in ("residual", "residual_neg", "both_extremes"):
        # every entry a hair under a bfloat16 midpoint: piece 0 rounds
        # down and the residual is the largest a float32 can leave
        mag = np.nextafter(np.float32(_HALF), np.float32(0)) * (
            2.0 ** rng.integers(-6, 3, (b, k))).astype(np.float32)
        u = (mag * rng.choice([-1.0, 1.0], (b, k))).astype(np.float32)
    if name == "code_min":
        codes[:] = -128  # a code the quantizer never writes
        u = -np.abs(u)
    hi = np.asarray(jnp.asarray(u).astype(jnp.bfloat16).astype(jnp.float32))
    # row r < b: codes of the largest magnitude, each with the sign of
    # user r's residual, so passes 1 and 2 add all they can for that user
    sign = np.where(u - hi >= 0, 1, -1).astype(np.int8)
    if name != "code_min":
        codes[:b] = 127 * sign
    if name == "residual_neg":
        codes[:b] = -127 * sign  # and take away all they can
    if name in ("scale_extremes", "both_extremes"):
        scales[:] = np.float32(2.0 ** -60)
        scales[:b:2] = np.float32(2.0 ** 60)  # 2^120 apart in one tile
    if name == "scale_top_row_elsewhere":
        # the largest scale sits on a row that scores low
        scales[:] = np.float32(1e-3)
        scales[-1] = np.float32(0.5)
        codes[-1] = 0
    if name == "negative_scale":
        scales[::3] *= -1
    return u, codes, scales


BOUND_CASES = ("weights", "residual", "residual_neg", "code_min",
               "scale_extremes", "both_extremes", "scale_top_row_elsewhere",
               "negative_scale")


@pytest.mark.parametrize("jitted", [False, True])
@pytest.mark.parametrize("case", BOUND_CASES)
def test_the_first_gate_never_hides_an_entrant(case, jitted):
    """``_bound_max`` from pass 0 alone is no smaller than the maximum of
    the completed block, for every user, on inputs built against it; so a
    K-th score a hair under the exact maximum leaves the gate open."""
    u, codes, scales = _bound_case(case)

    def both(u, codes, scales):
        pieces, slack = topk_kernel.resident_operand(u, jnp.int8)
        scale = scales[:, None]
        p0 = topk_kernel._code_pass(pieces, codes.astype(jnp.bfloat16), 0)
        factor = jnp.max(jnp.abs(scale), axis=0, keepdims=True)
        bound = topk_kernel._bound_max(p0, scale, factor, slack)
        exact = topk_kernel._complete_scores(p0, pieces, codes, scale)
        first = topk_kernel._tile_max(
            topk_kernel._times_row_scale(p0, scale))
        return bound, exact, first

    bound, exact, first = map(np.asarray, (jax.jit(both) if jitted else both)(
        jnp.asarray(u), jnp.asarray(codes), jnp.asarray(scales)))
    top = exact.max(axis=0)
    assert np.isfinite(bound).all() and np.isfinite(exact).all()
    assert (bound[0] >= top).all()
    hair_under = np.nextafter(top, np.float32(-np.inf))[None]
    assert bool(topk_kernel._entrant(jnp.asarray(bound),
                                     jnp.asarray(hair_under)))
    assert (bound[0] > hair_under[0]).all()  # user by user, not just one
    if case.startswith("residual") or case == "both_extremes":
        # the case is worth its name: pass 0 alone is off by a good part
        # of the slack the bound allows for
        off = np.abs(top - first[0]).max()
        assert off >= 0.2 * (bound[0] - first[0]).min() > 0
    # in float64 the three passes are the dequantized dot: the bound
    # holds against that too, up to the block's own float32 rounding
    deq = codes.astype(np.float64) * scales.astype(np.float64)[:, None]
    real = (deq @ u.astype(np.float64).T).max(axis=0)
    assert (bound[0] >= real - 1e-5 * np.abs(real)).all()


def _defer_problem(data_case, b, exclusion, nt=35, t=16, k=16, k_top=10):
    """(u, codes, scales, seen rectangle or None, num_movies): 2 G + 3
    tiles, so the last grid step is a ragged slab of three, the last tile
    reaching past ``num_movies``."""
    rng = np.random.default_rng(b + len(data_case) + len(exclusion))
    m = nt * t - 5
    u = ((rng.random((b, k), dtype=np.float32) - 0.5) * 0.35)
    mf = ((rng.random((nt * t, k), dtype=np.float32) - 0.5) * 0.35)
    if data_case == "ties":
        mf[:] = mf[:7][rng.integers(0, 7, nt * t)]  # seven distinct rows
    if data_case == "residual":
        u, _, _ = _bound_case("residual", t=b, k=k, b=b)
        u = u * np.float32(0.01)
    # later tiles score lower, so that their gates shut for a full batch
    mf *= (0.85 ** (np.arange(nt * t) // t)).astype(np.float32)[:, None]
    mf[m:] = 10.0  # padding rows would win if the mask let them
    codes, scales = quantize_rows(mf)
    if exclusion == "none" and data_case != "few":
        return u, codes, scales, None, m
    if data_case == "few":
        # user i keeps i % (K + 1) candidates: from none to exactly K
        lists = [np.sort(rng.permutation(m)[i % (k_top + 1):])
                 for i in range(b)]
    elif exclusion == "sparse":
        lists = [np.sort(rng.choice(m, int(rng.integers(0, 4)), False))
                 for _ in range(b)]
    else:  # every tile holds a cell of some user: the tile's best row
        lists = [np.zeros(0, np.int64) for _ in range(b)]
        best = (mf[:m] @ u[0]).reshape(-1)
        lists[0] = np.sort(np.asarray(
            [j * t + int(np.argmax(best[j * t:min((j + 1) * t, m)]))
             for j in range(nt)]))
        lists[1 % b] = np.union1d(lists[1 % b], rng.choice(m, 5, False))
    movies, indptr = _csr([x.astype(np.int32) for x in lists])
    st = topk_kernel.build_seen_tiles(
        movies, indptr, np.arange(b), num_movies=m, tile_m=t)
    return u, codes, scales, jnp.asarray(st), m


@pytest.mark.parametrize("data_case", ["ragged", "ties", "few", "residual"])
@pytest.mark.parametrize("exclusion", ["none", "sparse", "every_tile"])
@pytest.mark.parametrize("b", [8, 128, 256])
def test_deferred_fold_is_the_completed_fold_bit_for_bit(
        b, exclusion, data_case, monkeypatch):
    """Kernel (interpret path) and twin with passes 1 and 2 behind the
    first gate, against the same fold with that gate held open on every
    tile — three passes, the masks and the exact gate everywhere: the
    parent's arithmetic.  Scores, ids and the selection's counts to the
    bit; the exclusion counts and the completed tiles no larger."""
    from cfk_tpu.compat import emulate_topk_counted

    k_top, t = 10, 16
    u, codes, scales, st, m = _defer_problem(data_case, b, exclusion)
    args = (jnp.asarray(u), jnp.asarray(codes), jnp.asarray(scales), st)
    kw = dict(k_top=k_top, num_movies=m, tile_m=t)
    routes = (topk_kernel.topk_scores_counted, emulate_topk_counted)
    deferred = [tuple(map(np.asarray, fn(*args, **kw))) for fn in routes]
    monkeypatch.setattr(
        topk_kernel, "_bound_max",
        lambda p0, scale, factor, slack: jnp.full(
            (1, p0.shape[1]), jnp.inf, jnp.float32))
    completed = [tuple(map(np.asarray, fn(*args, **kw))) for fn in routes]
    nt = codes.shape[0] // t
    for got, want in zip(deferred, completed):
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
        assert got[0].tobytes() == want[0].tobytes()
        assert got[2][:2].tolist() == want[2][:2].tolist()
        assert (got[2][2:] <= want[2][2:]).all()
        assert want[2][4] == nt
        assert got[2][1] <= got[2][4]  # a tile that ran a round was completed
        assert got[2][3] <= got[2][4]  # and so was one that ran a mask
    # kernel == twin with all five counts, either way
    for kernel, twin in (deferred, completed):
        for x, y in zip(kernel, twin):
            np.testing.assert_array_equal(x, y)
    if data_case in ("ragged", "residual"):
        assert deferred[0][2][4] < nt  # the gate did shut somewhere
    # and the answer is the dequantized table's, by the plain reference
    if data_case == "ragged" and exclusion == "none":
        mf = codes[:m].astype(np.float32) * scales[:m, None]
        sc = mf.astype(np.float64) @ u.astype(np.float64).T
        want_ids = np.argsort(-sc, axis=0, kind="stable")[:k_top].T
        assert (deferred[0][1] == want_ids).mean() > 0.99


def _dots(jaxpr):
    """``dot_general`` equations in a jaxpr, sub-jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _dots(sub)
    return n


@pytest.mark.parametrize("route", ["kernel", "twin"])
@pytest.mark.parametrize("table_dtype,dots", [
    ("float32", 2), ("bfloat16", 1), ("int8", 3)])
def test_which_tables_defer_what(table_dtype, dots, route):
    """A bfloat16 tile is one ``dot_general``, in the fold's masked and
    unmasked branch each, and every tile is completed; an int8 tile is
    pass 0 and, behind the gate, passes 2 and 1; a float32 tile (since
    ISSUE 50) its one bfloat16 pass and, behind the gate, the block."""
    from cfk_tpu.compat import emulate_topk_counted

    uf, mf, lists, _ = _problem(48, m=35 * TILE - 5, users=8)
    data, scale = quantize_table(jnp.asarray(engine_mod.pad_table(mf, TILE)),
                                 table_dtype)
    movies, indptr = _csr(lists[:8])
    st = jnp.asarray(topk_kernel.build_seen_tiles(
        movies, indptr, np.arange(8), num_movies=mf.shape[0], tile_m=TILE))
    fn = (topk_kernel.topk_scores_counted if route == "kernel"
          else emulate_topk_counted)
    call = lambda u, st: fn(u, data, scale, st, k_top=5,
                            num_movies=mf.shape[0], tile_m=TILE)
    assert topk_kernel.deferred_passes(data.dtype) == {
        "int8": (1, 2), "float32": (1, 2, 3, 4, 5, 6), "bfloat16": ()}[
            table_dtype]
    counts = np.asarray(call(jnp.asarray(uf[:8]), st)[2])
    if table_dtype == "bfloat16":
        assert counts[4] == 35
    else:
        assert counts[1] <= counts[4] < 35
    for seen, branches in ((None, 1), (st, 2)):
        n = _dots(jax.make_jaxpr(call)(jnp.asarray(uf[:8]), seen).jaxpr)
        # the kernel masks a float tile in place, the twin's fold has a
        # branch with the masks and one without; the int8 completion is
        # traced once on either route
        want = dots * (branches if route == "twin" and dots == 1 else 1)
        assert n == want, (n, want)
