"""A fold-in reads the item table in whatever form one device holds it
(ISSUE 47): ``fold_in_rows`` over an engine's ``(data, scale)`` pair against
the float64 solve on the DEQUANTIZED rows, on both routes; a float32 pair is
the plain array's program and bits; ``ServeEngine.fold_table`` hands over its
own buffers and refuses a mesh in words; a ``StreamSession`` on an int8
engine folds, commits and publishes, and the engine's next answer is the
exact top-K of the solved row against the dequantized table; ``prewarm``
closes the program set against the table as held; a resume onto a table of
another dtype is refused."""

import types

import numpy as np
import pytest

from benchmarks.harness import reference_foldin
from cfk_tpu import telemetry
from cfk_tpu.config import ALSConfig
from cfk_tpu.serving import ServeEngine
from cfk_tpu.streaming import (
    StreamConfig, StreamProducer, StreamSession, StreamState, foldin)
from cfk_tpu.transport import InMemoryBroker
from cfk_tpu.transport.checkpoint import CheckpointManager
from tests.serve_reference import dequantize_rows, quantize_rows

LIMIT = 1e-4  # foldin_row_err's, in every stream cell
ITEMS, RANK = 30000, 128
LENGTHS = {"padded": (1, 7, 64, 127, 128), "cells": (1, 127, 129, 1000, 5000)}


def _table(items=ITEMS, rank=RANK, seed=0):
    rng = np.random.default_rng(seed)
    return ((rng.random((items, rank)) - 0.5) * 0.35).astype(np.float32)


def _lists(lengths, items=ITEMS, seed=1):
    rng = np.random.default_rng(seed)
    return [(np.sort(rng.choice(items, n, replace=False)).astype(np.int32),
             rng.integers(1, 6, n).astype(np.float32)) for n in lengths]


def _held(table, dtype):
    """(the pair an engine of ``dtype`` holds ``table`` as, the float32 view
    its answers and fold-ins are exact against)."""
    import jax.numpy as jnp

    if dtype == "int8":
        codes, scales = quantize_rows(table)
        return ((jnp.asarray(codes), jnp.asarray(scales)),
                dequantize_rows(codes, scales))
    data = jnp.asarray(table, jnp.dtype(dtype))
    return (data, None), np.asarray(data.astype(jnp.float32))


def _errs(view, lists, rows, lam=0.05):
    return [reference_foldin.row_err(
        row, reference_foldin.solve_row(view, mv, rt, lam))
        for (mv, rt), row in zip(lists, rows)]


@pytest.fixture(scope="module", params=[
    (dtype, route) for dtype in ("int8", "bfloat16")
    for route in ("padded", "cells")], ids="-".join)
def folded(request):
    dtype, route = request.param
    table, lists = _table(), _lists(LENGTHS[route])
    assert foldin.fold_route(lists) == route
    fixed, view = _held(table, dtype)
    rows = foldin.fold_in_rows(fixed, lists, lam=0.05, solver="cholesky")
    return table, view, lists, rows


@pytest.mark.parametrize("which", range(5))
def test_pair_against_the_float64_solve_on_the_dequantized_rows(folded, which):
    _, view, lists, rows = folded
    err = _errs(view, lists, rows)[which]
    assert 0 <= err < LIMIT / 10, err


def test_the_float32_factors_the_codes_were_made_from_fail_the_limit(folded):
    """The control the cell's ``correct`` must catch: a row solved against
    the dequantized view is not the solution over the factors the view was
    rounded from (a code's step is max|row| / 127)."""
    table, view, lists, rows = folded
    if np.array_equal(table, view):
        pytest.skip("nothing was rounded")
    assert max(_errs(table, lists, rows)) > LIMIT
    assert max(_errs(view, lists, rows)) < LIMIT / 10


@pytest.mark.parametrize("route", ["padded", "cells"])
def test_float32_pair_is_the_plain_arrays_program_and_bits(route):
    """``(data, None)`` over a float32 table: the same solved bits as the
    array alone, from the same lowered programs (what the three float32
    stream cells run must not move)."""
    import jax.numpy as jnp

    table, lists = jnp.asarray(_table(3000)), _lists(
        LENGTHS[route][:4], items=3000)
    plain = foldin.fold_in_rows(table, lists, lam=0.05, solver="cholesky")
    pair = foldin.fold_in_rows((table, None), lists, lam=0.05,
                               solver="cholesky")
    np.testing.assert_array_equal(plain, pair)

    def lowered(fixed):
        if route == "padded":
            rect = lambda dt: jnp.zeros((8, 16), dt)
            return foldin._padded_fold.lower(
                fixed, rect(jnp.int32), rect(jnp.float32), rect(jnp.float32),
                jnp.zeros((8,), jnp.float32), np.int32(8), np.float32(1e6),
                lam=0.05, solver="cholesky", reg_solve_algo=None).as_text()
        return foldin._cells_fold_gram.lower(
            fixed, jnp.zeros((64, 2 * foldin.CHUNK + 2), jnp.int32),
            jnp.zeros((8, RANK, RANK), jnp.float32),
            jnp.zeros((8, RANK), jnp.float32)).as_text()

    assert lowered(table) == lowered((table, None))


def test_gather_rows_is_code_times_scale_in_float32():
    import jax.numpy as jnp

    from cfk_tpu.ops.solve import gather_rows, table_parts

    table = _table(500, 16)
    idx = np.random.default_rng(3).integers(0, 500, (7, 9)).astype(np.int32)
    for dtype in ("int8", "bfloat16", "float32"):
        fixed, view = _held(table, dtype)
        rows = gather_rows(fixed, jnp.asarray(idx))
        assert rows.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(rows), view[idx])
        assert table_parts(fixed) == fixed
    # a trainer's plain array is gathered as it is stored
    plain = jnp.asarray(table, jnp.bfloat16)
    assert gather_rows(plain, jnp.asarray(idx)).dtype == jnp.bfloat16
    assert table_parts(plain) == (plain, None)


# -- the engine hands over what it holds --------------------------------------

def _engine(table_dtype, **kw):
    rng = np.random.default_rng(5)
    u_tab = ((rng.random((40, 8)) - 0.5) * 0.35).astype(np.float32)
    return ServeEngine(u_tab, _table(200, 8), num_users=40, num_movies=200,
                       tile_m=64, table_dtype=table_dtype, **kw)


@pytest.mark.parametrize("table_dtype", ["int8", "bfloat16", "float32"])
def test_fold_table_hands_over_the_engines_own_buffers(table_dtype):
    import jax

    # arrays of the table's extent (another test's may linger in the process)
    tables = lambda: sum(a.shape == (256, 8) for a in jax.live_arrays())
    before = tables()
    engine = _engine(table_dtype)
    data, scale = held = engine.fold_table()
    assert held is engine._table and engine.fold_table() is held
    assert data is engine._table[0] and str(data.dtype) == table_dtype
    assert (scale is None) == (table_dtype != "int8")
    # nothing the table's shape beside the table
    assert data.shape == (256, 8) and tables() == before + 1


@pytest.mark.parametrize("table_dtype", ["int8", "float32"])
def test_fold_table_refuses_a_mesh_in_words(tmp_path, table_dtype):
    engine = _engine(table_dtype, shards=2)
    with pytest.raises(ValueError, match=r"row-sharded over 2 devices.*R8"):
        engine.fold_table()
    with pytest.raises(ValueError, match="row-sharded over 2 devices"):
        StreamSession(
            StreamState.from_csr(np.zeros(41, np.int64),
                                 np.zeros(0, np.int32),
                                 np.zeros(0, np.float32), num_movies=200),
            ALSConfig(rank=8), InMemoryBroker(),
            CheckpointManager(str(tmp_path)), engine=engine,
            base_model=types.SimpleNamespace(user_factors=engine.user_base()))


# -- a session on an int8 engine, end to end ----------------------------------

def _stack(tmp_path, table_dtype):
    """``tests/test_serving.py``'s seeded stack (300 users, 200 items, rank
    8; engine, request server, session on the engine's table) over a table
    of ``table_dtype``, with the view its answers are exact against."""
    from tests.test_serving import _stream_stack

    s = _stream_stack(tmp_path, table_dtype=table_dtype)
    s.view, s.store, s.items_n = _held(s.m_tab, table_dtype)[1], tmp_path, 200
    return s


def _session(s):
    """A second session over ``s``'s store and engine: it resumes."""
    return StreamSession(
        StreamState.from_csr(s.indptr, s.items, s.values,
                             num_movies=s.items_n),
        ALSConfig(rank=s.u_tab.shape[1], lam=s.lam, health_check_every=1),
        s.broker, CheckpointManager(str(s.store)),
        stream=StreamConfig(batch_records=8),
        base_model=types.SimpleNamespace(user_factors=s.u_tab),
        engine=s.engine)


@pytest.mark.parametrize("table_dtype", ["int8", "bfloat16"])
def test_a_session_on_a_quantized_engine_folds_commits_and_is_served(
        tmp_path, table_dtype):
    import jax

    s = _stack(tmp_path, table_dtype)
    held = s.engine.fold_table()
    assert s.session._m is None and s.session._fixed() is held
    # arrays of the table's extent, in any dtype (another test's may linger)
    tables = lambda: sum(a.shape == held[0].shape for a in jax.live_arrays())
    before = tables()
    user, item = 3, int(np.setdiff1d(np.arange(s.items_n), s.items[
        s.indptr[3]:s.indptr[4]])[0])
    s.producer.send(user, item, 5.0)
    s.server.step()
    while s.session.in_flight:
        s.server.step()
    assert s.session.stream_step == 1 and s.engine.commit_ordinal == 1
    # the engine still holds the one table, and nothing its shape was made
    assert s.engine.fold_table() is held
    assert tables() == before
    # the committed row solves the user's equations over the dequantized
    # view (and not over the factors the view was rounded from)
    mv, rt = s.session.state.neighbors(user)
    assert item in mv
    row = s.session.user_rows([user])[0]
    exact = reference_foldin.solve_row(s.view, mv, rt, s.lam)
    assert reference_foldin.row_err(row, exact) < LIMIT / 10
    # and the next answer is the exact top-K of that row against the view
    rid = s.client.request(user, s.k)
    for _ in range(4):
        s.server.step()
    resp = {r.req_id: r for r in s.client.poll_responses()}[rid]
    assert resp.ordinal == 1 and not resp.error
    best, scores = reference_foldin.exact_topk(row[None], s.view, [mv], s.k)
    rank_gap, score_err = reference_foldin.topk_gaps(
        np.asarray(resp.movie_rows)[None], np.asarray(resp.scores)[None],
        best, scores)
    # an int8 table is scored in exact float32 (three bfloat16 passes); a
    # bfloat16 one in one pass, against a user vector rounded to bfloat16
    gap, err = (1e-5, 2e-5) if table_dtype == "int8" else (1e-2, 1e-2)
    assert rank_gap <= gap and score_err <= err
    assert item not in resp.movie_rows


def test_no_float32_table_is_handed_out_of_a_quantized_engine(tmp_path):
    s = _stack(tmp_path, "int8")
    with pytest.raises(ValueError, match="no float32 item table"):
        s.session.movie_factors
    with pytest.raises(ValueError, match="no float32 item table"):
        s.session.model()
    with pytest.raises(ValueError, match="float32 normal equations"):
        StreamSession(
            StreamState.from_csr(s.indptr, s.items, s.values,
                                 num_movies=s.items_n),
            ALSConfig(rank=8, dtype="bfloat16"), s.broker,
            CheckpointManager(str(tmp_path / "other")), engine=s.engine,
            base_model=types.SimpleNamespace(user_factors=s.u_tab))


def test_the_spans_say_what_was_gathered_from_which_table(tmp_path):
    s = _stack(tmp_path, "int8")
    tracer = telemetry.configure(None)
    try:
        s.producer.send(3, 150, 4.0)
        s.session.step()
        events = [e for e in tracer.events() if e.get("ph") == "X"]
    finally:
        telemetry.shutdown(write=False)
    solve = [e["args"] for e in events if e["name"] == "stream/batch/solve"
             and "table_dtype" in e.get("args", {})]
    batch = [e["args"] for e in events if e["name"] == "stream/batch"]
    assert len(solve) == 1 and len(batch) == 1
    for args in (solve[0], batch[0]):
        assert args["table_dtype"] == "int8" and args["route"] == "padded"
        # a row of 8 codes and its float32 scale a padded cell
        assert args["gather_bytes"] == args["padded_cells"] * (8 + 4)
    assert batch[0]["rank"] == 8


def test_gather_bytes_follow_the_table_on_both_routes():
    import jax.numpy as jnp

    table = _table(3000)
    for route, lengths in LENGTHS.items():
        lists = _lists(lengths[:4], items=3000)
        for dtype, row in (("float32", 512), ("bfloat16", 256),
                           ("int8", 132)):
            fold = foldin.fold_in_dispatch(
                _held(table, dtype)[0], lists, lam=0.05, solver="cholesky")
            assert fold.route == route and fold.table_dtype == dtype
            assert fold.gather_bytes == fold.padded_cells * row > 0
            fold.fetch()
        # the plain array a session of its own holds
        fold = foldin.fold_in_dispatch(jnp.asarray(table), lists, lam=0.05,
                                       solver="cholesky")
        assert fold.gather_bytes == fold.padded_cells * 512
        fold.fetch()


def test_prewarm_closes_the_set_against_the_table_as_held(tmp_path):
    """The same fixed set of programs, against codes and scales: after
    ``prewarm`` a window whose lists lie on both sides of ``CHUNK`` traces
    nothing."""
    rng = np.random.default_rng(2)
    users_n, items_n, rank = 4, 6000, 8
    lens = np.array([100, 3, 10, 1])
    indptr = np.concatenate([[0], np.cumsum(lens)])
    items = np.concatenate([np.arange(n) for n in lens]).astype(np.int32)
    values = rng.integers(1, 6, items.size).astype(np.float32)
    u_tab = ((rng.random((users_n, rank)) - 0.5) * 0.35).astype(np.float32)
    m_tab = _table(items_n, rank)
    engine = ServeEngine(u_tab, m_tab, num_users=users_n, num_movies=items_n,
                         tile_m=64, table_dtype="int8")
    broker = InMemoryBroker()
    producer = StreamProducer(broker)
    sess = StreamSession(
        StreamState.from_csr(indptr, items, values, num_movies=items_n),
        ALSConfig(rank=rank, lam=0.05, solver="cholesky",
                  health_check_every=1), broker,
        CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=64),
        base_model=types.SimpleNamespace(user_factors=u_tab), engine=engine)
    warm = sess.prewarm()
    assert warm["programs"] == 4 * 5 + len(foldin.SLABS) + 1
    assert sess.prewarm()["new_traces"] == 0
    before = foldin.trace_count()
    grown, routes = 100, set()
    for batch in range(12):
        n = 64 if batch else 20  # 100 -> 120 (the rectangle), then past 128
        producer.send_many(np.zeros(n, np.int64), grown + np.arange(n),
                           np.full(n, 4.0, np.float32))
        producer.send_many(np.array([1, 2]), np.array([500 + batch] * 2),
                           np.full(2, 3.0, np.float32))
        grown += n
        sess.step()
        routes.add(foldin.fold_route([sess.state.neighbors(0)]))
    assert routes == {"padded", "cells"}
    assert foldin.trace_count() == before
    codes, scales = (np.asarray(x) for x in engine.fold_table())
    exact = reference_foldin.solve_row(
        dequantize_rows(codes, scales), *sess.state.neighbors(0), 0.05)
    assert reference_foldin.row_err(sess.user_rows([0])[0], exact) < LIMIT / 10


def test_a_resume_onto_a_table_of_another_dtype_is_refused(tmp_path):
    s = _stack(tmp_path, "int8")
    s.producer.send(3, 150, 4.0)
    s.session.step()
    assert s.session.stream_step == 1
    st = s.session.manager.restore(1)
    assert st.meta["table_dtype"] == "int8"
    # the same table: resumes, and holds the unit
    again = _session(s)
    assert again.stream_step == 1
    np.testing.assert_array_equal(again.user_rows([3]),
                                  s.session.user_rows([3]))
    # a float32 table over the same store: refused, both tables named
    with pytest.raises(ValueError, match=r"solved against a int8 item "
                       r"table; the engine serves a float32 one"):
        _stack(tmp_path, "float32")
