"""Ask the chip's compiler, without the chip: every Pallas route the default
settings pick on a TPU, compiled for a described v5e at the real widths.

Interpret mode cannot see what Mosaic refuses (slices off the tiling, VMEM
over the scoped limit, primitives with no lowering), so these compile the
kernels — and the half-steps that call them — with ``interpret`` resolved the
way a TPU backend resolves it, for ``v5e:2x2`` described through
``jax.experimental.topologies`` (no device attached; nothing runs).  Shapes
are the Netflix-Prize deployment's: the statics ``Dataset.from_coo(
layout="tiled", dense_stream=True, chunk_elems=65_536,
accum_chunk_elems=262_144)`` produces at 480,189 × 17,770 × 100,480,507
(measured once, PR 21), and the ML-25M serve table (59,392 × 128, B=64, K=10).

A compile that passes is not a chip run; ``chip_smoke.py`` is.  Each test
asserts ``tpu_custom_call`` in the compiled module so an XLA twin cannot pass
for the kernel, and each gate is checked from both sides: what it admits
compiles, what the compiler refuses it does not admit.

One file, on purpose: only one process at a time may load libtpu, so the
topology is described inside a module-scoped fixture — never at import — and
every compile runs in the test's own process.
"""

import jax
import jax.numpy as jnp
import pytest

f32, bf16, i8, i32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32

USERS, MOVIES = 480_189, 17_770
# user half, dense stream: (NC, C, Ec, T, NT, NG, BG)
U_NC, U_C, U_EC, T, U_NT, U_NG, U_BG = 1589, 65_536, 333, 128, 704, 11, 32_768
U_SEG, U_META = U_EC + 1, U_NG + 4 * U_NT
# movie half, accum: (NC, C, T, H, Ec)
M_NC, M_C, M_H, M_EC = 403, 262_144, 131_072, 430
M_NT, M_SEG = M_C // T, M_EC + 1
LAM = 0.05


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the plug-in raises: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` → a ShapeDtypeStruct placed on one described
    v5e chip.  The persistent compile cache is off around the module: an
    entry written for a described device cannot be read back without one,
    and the next compile would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the code that asks ``jax.default_backend()`` (``interpret=
    None``, ``solver="auto"``) onto its TPU branch — this process's default
    backend is the CPU, the compile target is not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *args):
    """Lower + compile for the described chip; the module must hold a
    Mosaic kernel."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _carry(chip, k):
    return chip((k, k), f32), chip((k,), f32), chip((), f32)


# -- solve kernels -----------------------------------------------------------

def test_solve_rank64(chip):
    from cfk_tpu.ops.pallas import solve_kernel as sk

    e, k = 2560, 64
    _compile(lambda a, b: sk.gauss_solve_pallas(a, b, interpret=False),
             chip((k, k, e), f32), chip((k, e), f32))
    for algo in ("lu", "gj"):
        _compile(
            lambda a, b, r: sk.gauss_solve_reg_pallas(
                a, b, r, reg_mode="diag", lam=LAM, interpret=False,
                algo=algo),
            chip((e + 1, k, k), f32), chip((e + 1, k), f32),
            chip((e + 1,), f32))


@pytest.mark.slow  # the unrolled LU-128 elimination compiles in ~2 min
def test_solve_rank128_lu(chip):
    from cfk_tpu.ops.pallas import solve_kernel as sk

    e, k = 2561, 128
    _compile(
        lambda a, b, r: sk.gauss_solve_reg_pallas(
            a, b, r, reg_mode="diag", lam=LAM, interpret=False, algo="lu"),
        chip((e, k, k), f32), chip((e, k), f32), chip((e,), f32))


# -- Gram kernels, rank 64 bf16 (the headline configuration) -----------------

def test_gram_tiles_rank64_bf16(chip):
    from cfk_tpu.ops.pallas import gram_kernel as gk

    k = 64
    _compile(
        lambda g, rt, seg: gk.gram_tiles_pallas(
            g, rt, seg, num_segments=M_SEG, tile_rows=T, interpret=False),
        chip((M_C, k), bf16), chip((M_C,), f32), chip((M_NT,), i32))


def test_gram_tiles_dense_rank64_bf16(chip):
    from cfk_tpu.ops.pallas import gram_kernel as gk

    k = 64
    _compile(
        lambda g, rt, meta, ca, cb, ci: gk.gram_tiles_dense_pallas(
            g, rt, meta, num_segments=U_SEG, tile_rows=T, num_tiles=U_NT,
            num_groups=U_NG, block_rows=U_BG, interpret=False,
            carry=(ca, cb, ci)),
        chip((U_C, k), bf16), chip((U_NT * T,), f32), chip((U_META,), i32),
        *_carry(chip, k))


# -- the gates, from both sides ----------------------------------------------

def _dense_gather(chip, k, dtype):
    """Compile the dense gather kernel at the user half's statics for a
    table of ``dtype``."""
    from cfk_tpu.ops.pallas import gram_kernel as gk

    # int8 rows need the scale-carrying wt stream
    wt = [chip((U_C,), f32)] if dtype == i8 else []

    def fn(tbl, nb, rt, meta, ca, cb, ci, *wt):
        return gk.gram_tiles_dense_gather_pallas(
            tbl, nb, wt[0] if wt else None, rt, meta, num_segments=U_SEG,
            tile_rows=T, num_tiles=U_NT, num_groups=U_NG, block_rows=U_BG,
            interpret=False, carry=(ca, cb, ci))

    return _compile(fn, chip((MOVIES, k), dtype), chip((U_C,), i32),
                    chip((U_NT * T,), f32), chip((U_META,), i32),
                    *_carry(chip, k), *wt)


def test_gather_gate_admits_what_compiles(chip):
    from cfk_tpu.ops.pallas.gram_kernel import in_kernel_gather_supported

    gate = lambda k, dt: in_kernel_gather_supported(
        U_C, U_META + 1, T, U_BG, k=k, table_dtype=dt)
    assert gate(128, f32)
    _dense_gather(chip, 128, f32)
    # what Mosaic refuses (one-row DMA slices off the tiling) is not admitted
    for k, dt in ((64, f32), (64, bf16), (128, bf16), (64, i8), (128, i8)):
        assert not gate(k, dt), (k, dt)
    # the accum half's 256k-entry chunks overflow the SMEM prefetch budget
    assert not in_kernel_gather_supported(
        M_C, M_NT, T, k=128, table_dtype=f32)


@pytest.mark.parametrize("k,dtype", [(64, f32), (128, bf16)])
def test_gather_gate_refusals_are_the_compilers(chip, k, dtype):
    """The refused shapes still fail to compile — when one starts to pass,
    the gate can admit it."""
    with pytest.raises(Exception, match="aligned to tiling"):
        _dense_gather(chip, k, dtype)


def test_fused_epilogue_gate_admits_what_compiles(chip):
    from cfk_tpu.ops.pallas import gram_kernel as gk

    k = 64
    assert gk.fused_gram_solve_supported(U_SEG, k)
    _compile(
        lambda g, rt, meta, reg, lseg, ca, cb, ci:
        gk.gram_solve_tiles_dense_pallas(
            g, rt, meta, reg, lseg, num_segments=U_SEG, tile_rows=T,
            num_tiles=U_NT, num_groups=U_NG, block_rows=U_BG,
            reg_mode="diag", lam=LAM, interpret=False, carry=(ca, cb, ci)),
        chip((U_C, k), bf16), chip((U_NT * T,), f32), chip((U_META,), i32),
        chip((U_SEG,), f32), chip((), i32), *_carry(chip, k))
    # rank 128: the v5e compiler ran out of VMEM on the elimination's stack
    # (104 / 121 MiB with bf16 / f32 windows) — never admitted
    for segs in (1, U_SEG, 2561):
        assert not gk.fused_gram_solve_supported(segs, 128)


def test_mode_resolvers_route_the_defaults(as_tpu):
    """What default knobs resolve to at the deployment's statics — the
    routes the half-step compiles below must contain."""
    from cfk_tpu.ops.solve import _resolve_solver
    from cfk_tpu.ops.tiled import resolve_tiled_route

    assert _resolve_solver("auto") == "pallas"
    user = ("dstream", (U_NC, U_C, U_EC, T, U_NT, U_NG, U_BG))
    movie = ("accum", (M_NC, M_C, T, M_H, M_EC))
    route = lambda half, k, dt: resolve_tiled_route(
        *half, k, LAM, table_dtype=dt, solver="auto")
    assert route(user, 64, bf16) == ("xla", LAM)
    assert route(user, 64, i8) == ("xla", LAM)
    assert route(user, 128, f32) == ("fused", None)
    for k, dt in ((64, bf16), (128, f32)):  # 256k chunks overflow SMEM
        assert route(movie, k, dt) == ("xla", None)


# -- whole half-steps through the default routes -----------------------------

def _user_blocks(chip):
    return dict(
        neighbor_idx=chip((U_NC * U_C,), i32),
        rating=chip((U_NC * U_NT * T,), f32),
        tile_meta=chip((U_NC * U_META,), i32),
        chunk_entity=chip((U_NC * U_EC,), i32),
        chunk_count=chip((U_NC * U_EC,), i32),
        carry_in=chip((U_NC,), f32), last_seg=chip((U_NC,), i32),
        count=chip((USERS,), i32),
    ), ("tiled", "dstream", U_NC, U_C, U_EC, T, U_NT, U_NG, U_BG)


def _movie_blocks(chip):
    n = M_NC * M_C
    return dict(
        neighbor_idx=chip((n,), i32), rating=chip((n,), f32),
        weight=chip((n,), f32), tile_seg=chip((M_NC * M_NT,), i32),
        chunk_base=chip((M_NC,), i32),
        chunk_entity=chip((M_NC * M_EC,), i32),
        chunk_count=chip((M_NC * M_EC,), i32),
        carry_in=chip((M_NC,), f32), last_seg=chip((M_NC,), i32),
        slice_starts=chip((5,), i32), count=chip((MOVIES,), i32),
    ), ("tiled", "accum", M_NC, M_C, T, M_H, M_EC)


def _half_step(chip, side, k, dtype, table_dtype):
    from cfk_tpu.ops.tiled import tiled_half_step

    if side == "user":
        (blk, chunks), fixed_rows, ents = _user_blocks(chip), MOVIES, USERS
    else:
        (blk, chunks), fixed_rows, ents = _movie_blocks(chip), USERS, MOVIES
    return _compile(
        lambda fixed, b: tiled_half_step(
            fixed, b, chunks, ents, LAM, solver="auto",
            table_dtype=table_dtype),
        chip((fixed_rows, k), dtype), blk)


@pytest.mark.parametrize("table_dtype", ["float32", "int8"])
@pytest.mark.parametrize("side", ["user", "movie"])
def test_half_step_rank64_bf16(chip, as_tpu, side, table_dtype):
    """The headline route (XLA gather + fused epilogue on the user half,
    Gram kernel + one LU-64 solve on the movie half), plain and with the
    int8 gather table."""
    compiled = _half_step(chip, side, 64, bf16, table_dtype)
    # fits one v5e chip (16 GiB) with the other half's blocks resident too
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < 12 << 30


def test_half_step_rank64_f32_user(chip, as_tpu):
    """float32 storage at rank 64: inside the half-step program the stream
    block reaches the fused kernel lane-padded (twice the window bytes a
    standalone compile of the kernel sees), and the chip's compiler refused
    it by 13 MiB until ``_dense_window_bytes`` counted the padding — found
    on the chip by ``chip_smoke.py --multichip``'s one-device reference."""
    _half_step(chip, "user", 64, f32, "float32")


@pytest.mark.slow  # LU-128 compiles in ~2 min per program
@pytest.mark.parametrize("side", ["user", "movie"])
def test_half_step_rank128_f32(chip, as_tpu, side):
    """In-kernel gather (user half) + split epilogue with the standalone
    LU-128 solve."""
    _half_step(chip, side, 128, f32, "float32")


# -- the bucketed port (iALS++ / bucketed ALS) -------------------------------

@pytest.mark.parametrize("rows,width", [(4096, 16), (1024, 128)])
def test_bucket_port_piece_rank64_bf16(chip, as_tpu, rows, width):
    """One width class through ``ops.bucketed`` as the defaults route it at
    rank 64 bf16: the tile kernels with ``tile_rows = width`` and up to 256
    tiles per group, ``lax.map``'d over several pieces.  Two refusals were
    repaired here: XLA fused the kernel into the loop's output update and
    held it to the default 16 MiB VMEM limit (``optimization_barrier``),
    and 256 unrolled tile Grams overran the kernel's own budget
    (``_walk_stack_bytes``)."""
    from cfk_tpu.ops import bucketed as bp

    k, f_rows = 64, 59_047
    fused, gather = bp.resolve_bucket_modes(
        None, None, "auto", rows, width, k, LAM, None, table_dtype=bf16)
    assert (fused, gather) == (True, "xla")
    assert bp._sub_rows(rows, width, k, fused, None) < rows  # several pieces
    _compile(
        lambda table, nb, wt, rt, cnt: bp.bucket_gram_solve(
            table, None, nb, wt, rt, cnt, lam=LAM, reg_mode="diag",
            solver="auto", fused=fused, gather=gather, algo=None),
        chip((f_rows, k), bf16), chip((rows, width), i32),
        chip((rows, width), f32), chip((rows, width), f32),
        chip((rows,), f32))


# -- serving -----------------------------------------------------------------

def _compile_scorer(chip, dtype, b, k_top, w, m=59_047):
    """The one-device scorer, by default over the ML-25M table (59,047 ×
    128)."""
    from cfk_tpu.serving.topk_kernel import SeenTiles, topk_scores_counted

    k, tile_m = 128, 512
    m_pad = -(-m // tile_m) * tile_m
    nt = m_pad // tile_m
    scale = [chip((m_pad,), f32)] if dtype == i8 else []

    def fn(u, tbl, seen, *sc):
        return topk_scores_counted(
            u, tbl, sc[0] if sc else None, seen, k_top=k_top,
            num_movies=m, tile_m=tile_m, interpret=False)

    return _compile(fn, chip((b, k), f32), chip((m_pad, k), dtype),
                    SeenTiles(chip((nt, b, w), i32), chip((nt,), i32)),
                    *scale)


@pytest.mark.parametrize("dtype", [f32, bf16, i8])
def test_serve_scorer_ml25m_width(chip, dtype):
    _compile_scorer(chip, dtype, b=64, k_top=10, w=64)


@pytest.mark.parametrize("b,k_top", [
    (256, 16),  # a full batch of the serve cells (K = 10 padded to 16)
    (64, 128),  # the engine's default K = 100, padded
    (8, 1),  # one slot: the shift is the identity
])
def test_serve_scorer_gated_selection_shapes(chip, b, k_top):
    """The gated fold's loop — a ``while`` on a scalar reduced from a
    vector compare, the one-sublane roll of the [K, B] carry, the SMEM
    counts — at the carry heights and batch widths the server asks for."""
    _compile_scorer(chip, f32, b=b, k_top=k_top, w=16)


@pytest.mark.parametrize("dtype,m,g_want,ragged", [
    (f32, 9_350_000, 16, 6),  # the one-chip cell: 18,262 = 16 x 1,141 + 6
    (f32, 12_047_500, 16, 11),  # a shard of the four-chip cell: 23,531
    (bf16, 9_350_000, 16, 6),  # a bfloat16 table of the same catalogue
    (i8, 48_190_000, 16, 10),  # the int8 cell: 94,122 = 16 x 5,882 + 10
])
def test_serve_scorer_slab_of_the_cells(chip, dtype, m, g_want, ragged):
    """A grid step streams G tiles ([G·512, 128] of table, [G, 1, 512] of
    scales, [G, 16, 256] of the rectangle) and folds them in a loop whose
    trip count on the last step is what is left of the table: at the three
    cells' sizes no rung of the ladder divides NT, so every one of them
    compiles the clipped edge blocks and the dynamic trip count."""
    from cfk_tpu.serving.topk_kernel import slab_tiles

    nt = -(-m // 512)
    g = slab_tiles(nt, 256, 16, 128, dtype, tile_m=512, k_top=16)
    assert (g, nt % g) == (g_want, ragged)
    _compile_scorer(chip, dtype, b=256, k_top=16, w=16, m=m)


@pytest.mark.parametrize("b", [128, 256])
def test_serve_scorer_int8_cells_deferred_passes(chip, b):
    """The int8 cells' call at the two batch buckets a saturated window
    fills (48.19 M x 128 codes, 94,122 tiles, 16 slots): pass 0 and the
    first gate's bound in the straight-line block (a lane reduction of the
    tile's [1, 512] row of scales, a [1, 1] factor times the [1, B] slack
    row), passes 2 and 1 from the slab still in VMEM inside the tile's
    turn, the masks nested behind that gate: one Mosaic call, the slack
    row made outside it, and nothing the size of the table beside it."""
    compiled = _compile_scorer(chip, i8, b=b, k_top=16, w=16, m=48_190_000)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "s32[94122]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("b", [128, 256])
def test_serve_scorer_float32_cells_first_gate(chip, b):
    """The float32 cells' call at the two batch buckets a saturated window
    fills (9.35 M x 128 rows, 18,262 tiles, 16 slots): one bfloat16 pass
    and the first gate's bound in the straight-line block (the tile turned
    to bfloat16, its largest |entry| down the sublanes and across the
    lanes, that entry's exponent bits, two [1, 1] factors times the two
    [1, B] slack rows), the block at ``Precision.HIGHEST`` from the slab
    still in VMEM inside the tile's turn, the masks nested behind that
    gate: one Mosaic call, ``u``'s bfloat16 piece and the slack rows made
    outside it, and nothing the size of the table beside it."""
    compiled = _compile_scorer(chip, f32, b=b, k_top=16, w=16, m=9_350_000)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "s32[18262]" in text
    assert f"bf16[{b},128]" in text and f"f32[2,1,{b}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("dtype, grid_tiles, b", [
    (f32, 512, 256),  # Movies: 407 tiles in 26 slabs, the rung of 32
    (f32, 8192, 256),  # Books: 4,629 tiles in 290 slabs, the rung of 512
    (f32, 512, 8),  # the smallest batch bucket
    (i8, 512, 256), (bf16, 512, 256),  # the bodies share the fold
])
def test_serve_scorer_dept_ranged(chip, dtype, grid_tiles, b):
    """A department's scan of the one-chip catalogue (9.35 M x 128 rows):
    the range's two rows ride in as a third scalar-prefetch operand beside
    the row offset and the hits, every block's index map adds the range's
    first slab to the grid step (and holds at its last), the grid runs the
    rung's steps, the rectangle and the hits cover the rung's tiles only:
    one Mosaic call, and nothing the size of the table beside it."""
    from cfk_tpu.serving.topk_kernel import SeenTiles, topk_scores_counted

    k, tile_m, m = 128, 512, 9_350_000
    m_pad = -(-m // tile_m) * tile_m
    scale = [chip((m_pad,), f32)] if dtype == i8 else []

    def fn(u, tbl, seen, rows, *sc):
        return topk_scores_counted(
            u, tbl, sc[0] if sc else None, seen, k_top=16, num_movies=m,
            tile_m=tile_m, interpret=False, rows=rows,
            grid_tiles=grid_tiles)

    compiled = _compile(
        fn, chip((b, k), f32), chip((m_pad, k), dtype),
        SeenTiles(chip((grid_tiles, b, 16), i32), chip((grid_tiles,), i32)),
        chip((2,), i32), *scale)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"s32[{grid_tiles}]" in text and "s32[18262]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("g", [1, 2, 4, 8, 16])
@pytest.mark.parametrize("dtype", [f32, i8])
def test_serve_scorer_every_rung_of_the_slab_ladder(chip, monkeypatch, dtype,
                                                    g):
    """Each step height the ladder holds, over 117 tiles (ragged at every
    G but 1) and a rectangle 64 slots wide: what a call takes when the
    budget or the table's length brings G down."""
    from cfk_tpu.serving import topk_kernel

    assert g in topk_kernel._SLAB_LADDER
    monkeypatch.setattr(topk_kernel, "slab_tiles", lambda *a, **k: g)
    _compile_scorer(chip, dtype, b=64, k_top=10, w=64, m=59_600)


def test_serve_scorer_amazon14_cell_shape(chip):
    """The one-chip serve cell's own call: 18,262 tiles × 256 rows × 16
    slots, so the tiles' hits ride in as a second scalar-prefetch operand
    of 18,262 words of SMEM (the four-chip cell's 23,531 a shard:
    ``test_serve_sharded_four_devices``), and the fold's two branches —
    the whole fold with the masks and without — compile side by side."""
    text = _compile_scorer(chip, f32, b=256, k_top=16, w=16,
                           m=9_350_000).as_text()
    assert "s32[18262]" in text


@pytest.mark.parametrize("rung, add", [(1, False), (8, False), (16, True)],
                         ids=["one_piece", "eight_pieces", "on_top_at_the_top"])
def test_serve_seen_rectangle_amazon14_cell_shape(chip, rung, add):
    """The exclusion rectangle's one program a batch at the one-chip cells'
    shape ([18,262, 256, 16] int32, 299 MB), for a cell list padded to
    ``rung`` pieces: it needs one temporary the rectangle's size beside its
    result (the scatter works on a flat copy that is laid out for the scorer
    at the end) and no more, whatever the rung; the program that adds to a
    rectangle writes its result in the donated one's place."""
    from cfk_tpu.serving.engine import _seen_tiles_jit_fn
    from cfk_tpu.serving.topk_kernel import SeenTiles, seen_cell_capacity

    shape = (18_262, 256, 16)
    rect = 4 * shape[0] * shape[1] * shape[2]
    seen = (SeenTiles(chip(shape, i32), chip(shape[:1], i32)) if add
            else None)
    mem = _seen_tiles_jit_fn().lower(
        chip((4, rung * seen_cell_capacity(shape[1])), i32), seen,
        shape=shape, tile_m=512).compile().memory_analysis()
    assert rect <= mem.output_size_in_bytes < 1.01 * rect
    assert mem.temp_size_in_bytes < 1.01 * rect
    assert (mem.alias_size_in_bytes >= rect) if add else (
        mem.alias_size_in_bytes == 0)


@pytest.mark.parametrize("touched,width", [(256, 128), (8, 8)])
def test_foldin_amazon14_stream_cell_shape(chip, as_tpu, touched, width):
    """The fold-in of ``amazon14-stream-r128.serve-foldin`` at the two ends
    of its pow2 grid, against the 9,350,144-row float32 table the engine
    serves: gather, Gram, the lane-batched Cholesky (the cell's solver is
    ``cholesky``; on a TPU that is ``cholesky_solve_lanes``, a Mosaic call
    INSIDE the program, not a second one) and the sentinel's word in one
    program under the name the benchmark's reader looks for.  The grid has
    30 shapes and a warm start lowers them all again: a kernel that unrolls
    itself (the fused LU-128 takes ~3 min a shape) fails the clock here and
    not in a cell's set-up."""
    import time

    from cfk_tpu.streaming import foldin

    rect = lambda dt: chip((touched, width), dt)
    t0 = time.perf_counter()
    compiled = foldin._padded_fold.lower(
        chip((9_350_144, 128), f32), rect(jnp.int32), rect(f32), rect(f32),
        chip((touched,), f32), chip((), jnp.int32), chip((), f32),
        lam=LAM, solver="cholesky", reg_solve_algo=None).compile()
    assert time.perf_counter() - t0 < 20.0
    text = compiled.as_text()
    assert "jit__padded_fold" in text and "tpu_custom_call" in text
    # XLA's own factorisation is off the path
    assert "Cholesky" not in text and "triangular-solve" not in text.lower()
    # the table is an argument, never a copy: nothing the size of it is made
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28


@pytest.mark.parametrize("slab", [4096, 1024, 256, 64])
def test_foldin_cells_route_amazon14_skew_cell_shape(chip, as_tpu, slab):
    """The cells route of ``amazon14-stream-r128-skew.serve-foldin-skew``:
    each slab's Gram program (gather, one batched ``HIGHEST`` GEMM over the
    chunk rows, summed by owner onto 256 systems) against the 9,350,144-row
    float32 table, and the route's one solve (the lane-batched Cholesky
    inside it), under the names the benchmark's readers look for.  The
    largest slab gathers 268 MB and makes as much of chunk Grams: nothing
    the size of the table."""
    import time

    from cfk_tpu.streaming import foldin

    assert slab in foldin.SLABS and len(foldin.SLABS) == 4
    k, e = 128, 256
    t0 = time.perf_counter()
    gram = foldin._cells_fold_gram.lower(
        chip((9_350_144, k), f32), chip((slab, 2 * foldin.CHUNK + 2), i32),
        chip((e, k, k), f32), chip((e, k), f32)).compile()
    assert time.perf_counter() - t0 < 20.0
    assert "jit__cells_fold_gram" in gram.as_text()
    assert gram.memory_analysis().temp_size_in_bytes < 1 << 30
    solve = foldin._cells_fold_solve.lower(
        chip((e, k, k), f32), chip((e, k), f32), chip((e,), f32),
        chip((), i32), chip((), f32),
        lam=LAM, solver="cholesky", reg_solve_algo=None).compile()
    text = solve.as_text()
    assert "jit__cells_fold_solve" in text and "tpu_custom_call" in text
    assert "Cholesky" not in text and "triangular-solve" not in text.lower()


@pytest.mark.parametrize("program", ["rectangle-256x128", "rectangle-8x8",
                                     "slab-4096", "slab-64", "probe"])
def test_foldin_amazon23_int8_stream_cell_shape(chip, as_tpu, program):
    """The fold-in of ``amazon23-stream-r128-int8.serve-foldin-skew-int8``
    against the table as the engine holds it, 48,190,464 int8 codes and a
    float32 scale a row (ISSUE 47): both ends of the rectangle's grid, the
    largest and the smallest slab of the cells route, and the sentinel's
    probe of the fixed side.  The codes and the scales are gathered where
    they lie, by the same indices, and dequantized among the gathered rows:
    nothing the size of the table, or of a float32 block of it, is made (a
    relayout of the codes would be a 6.17 GB temporary).  Prints the gather
    ops the chip's compiler makes (``-s`` shows them)."""
    import re
    import time

    from cfk_tpu.streaming import foldin, session

    m, k, e = 48_190_464, 128, 256
    fixed = (chip((m, k), i8), chip((m,), f32))
    t0 = time.perf_counter()
    if program == "probe":
        compiled = session._side_word_fn().lower(
            fixed, chip((), f32)).compile()
        # one float32 a row, the rows' squared norms: 193 MB, once a table
        name, temp = "side_word", 4 * m + (1 << 20)
    elif program.startswith("rectangle"):
        touched, width = map(int, program.split("-")[1].split("x"))
        rect = lambda dt: chip((touched, width), dt)
        compiled = foldin._padded_fold.lower(
            fixed, rect(jnp.int32), rect(f32), rect(f32),
            chip((touched,), f32), chip((), jnp.int32), chip((), f32),
            lam=LAM, solver="cholesky", reg_solve_algo=None).compile()
        name, temp = "jit__padded_fold", 1 << 28
        assert "tpu_custom_call" in compiled.as_text()
    else:
        slab = int(program.split("-")[1])
        compiled = foldin._cells_fold_gram.lower(
            fixed, chip((slab, 2 * foldin.CHUNK + 2), i32),
            chip((e, k, k), f32), chip((e, k), f32)).compile()
        # the slab's codes (67 MB), their float32 rows and the chunk Grams
        name, temp = "jit__cells_fold_gram", 1 << 30
    assert time.perf_counter() - t0 < 20.0
    text = compiled.as_text()
    assert name in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp
    # the table goes in as it is held and comes out nowhere
    assert m * (k + 4) <= mem.argument_size_in_bytes < m * (k + 4) + (1 << 28)
    if program != "probe":  # (its convert is fused into the reduction)
        assert f"f32[{m},{k}]" not in text
    gathers = [ln.strip() for ln in text.splitlines()
               if re.search(r"= \S+ gather\(", ln)]
    print(f"\n{program}: temp {mem.temp_size_in_bytes:,} B; gathers:")
    for ln in gathers:
        print("   ", ln[:160])
    if program != "probe":
        assert any(ln.split("= ")[1].startswith("s8[") for ln in gathers)
        assert any(ln.split("= ")[1].startswith("f32[") for ln in gathers)


@pytest.mark.parametrize("k,e", [(128, 2561), (64, 333), (8, 8)])
def test_cholesky_lanes(chip, k, e):
    """The lane-batched Cholesky alone, at the widths the half-steps name
    and a batch that leaves a ragged last tile: a loop over the columns, so
    seconds at any rank."""
    import time

    from cfk_tpu.ops.pallas import solve_kernel as sk

    t0 = time.perf_counter()
    _compile(lambda a, b: sk.cholesky_solve_lanes(a, b, interpret=False),
             chip((e, k, k), f32), chip((e, k), f32))
    assert time.perf_counter() - t0 < 20.0


def test_serve_scorer_amazon23_int8_cell_shape(chip):
    """The int8 cell's own call: 94,122 tiles of 48,190,464 codes on one
    chip, the scales as a lane-dense [NT, 1, T] view.  As a [M_pad, 1]
    operand they were copied out to one scale a 128-lane row on every
    call, 24.7 GB here (ISSUE 32): the view is a bitcast, and the call
    needs no temporary at all.  The body is the three-pass one (ISSUE 35):
    the codes to bfloat16 in register, three bfloat16 matmuls against the
    pieces of ``u``, which reach the kernel as one resident [3, B, k]
    operand split outside it, and the scale on each 128-lane half of the
    block (under ``shard_map``: ``test_serve_sharded_four_devices``)."""
    compiled = _compile_scorer(chip, i8, b=256, k_top=16, w=16, m=48_190_000)
    text = compiled.as_text()
    assert "f32[94122,1,512]" in text and "s32[94122]" in text
    assert "f32[48190464,1]" not in text
    assert "bf16[3,256,128]" in text and "reduce-precision" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20
    # codes + scales + the [NT, 256, 16] rectangle + the small operands
    held = 48_190_464 * (128 + 4) + 94_122 * 256 * 16 * 4
    assert held < mem.argument_size_in_bytes < held + (8 << 20)


@pytest.mark.parametrize("m,k_top,b,dtype", [
    (59_047, 10, 64, bf16),  # the ML-25M table
    (48_190_000, 16, 256, f32),  # Amazon-2023: 24.7 GB, 6.17 GB a chip
    (48_190_000, 16, 256, i8),  # its codes over four chips: the int8 body
])
def test_serve_sharded_four_devices(topo, chip, as_tpu, m, k_top, b, dtype):
    """Item-axis sharded serving as one program over the described 2×2
    mesh, shard_map's vma check ON: the kernel's outputs carry the table's
    vma, and the merged selections come back stacked over the mesh axis.
    The scorer's custom call carries the shard entry's name, the rectangle
    is built slice by slice, and no chip is handed more than its shard."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cfk_tpu.parallel import spmd
    from cfk_tpu.parallel.mesh import AXIS
    from cfk_tpu.serving.topk_kernel import SeenTiles

    mesh = Mesh(np.array(topo.devices[:4]), (AXIS,))
    k, tile_m, w = 128, 512, 16
    per = -(-m // (4 * tile_m)) * tile_m
    m_pad, nt = 4 * per, 4 * per // tile_m
    on = lambda shape, dt, spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, spec))
    fn = spmd._serve_topk_sharded_fn(mesh, per, dtype == i8, True, k_top, m,
                                     tile_m)
    seen = SeenTiles(on((nt, b, w), i32, P(AXIS)), on((nt,), i32, P(AXIS)))
    scale = [on((m_pad,), f32, P(AXIS))] if dtype == i8 else []
    scorer = fn.lower(
        on((b, k), f32, P()), on((m_pad, k), dtype, P(AXIS)), *scale, seen,
    ).compile()
    text = scorer.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    assert "%_topk_shard_call." in text
    # each shard's call streams slabs of its own tiles, the last one ragged
    # at the Amazon-2023 size (23,531 tiles a shard)
    from cfk_tpu.serving.topk_kernel import slab_tiles
    g = slab_tiles(nt // 4, b, w, k, dtype, tile_m=tile_m, k_top=k_top)
    assert g == 16 and (nt // 4) % g == (11 if m > 59_047 else 13)
    shard_bytes = (per * (k * jnp.dtype(dtype).itemsize + 4 * len(scale))
                   + nt // 4 * b * w * 4)
    # (a narrow batch's rectangle is padded to whole 128-lane registers)
    assert scorer.memory_analysis().argument_size_in_bytes < 1.5 * shard_bytes
    # a list of up to eight pieces in one run, and the top rung's program
    # run again on its own result (``SEEN_PIECE_RUNGS``)
    for fresh, rung in ((True, 8), (False, 16)):
        build = spmd._serve_seen_tiles_sharded_fn(
            mesh, (nt, b, w), tile_m, fresh)
        ops = [on((4, rung * 16 * b), i32, P())]
        if not fresh:
            ops.append(seen)
        mem = build.lower(*ops).compile().memory_analysis()
        assert mem.output_size_in_bytes < 1.5 * nt // 4 * max(b, 128) * w * 4
