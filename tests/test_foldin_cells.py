"""The fold-in whose work follows the cells (ISSUE 41): the cells route
against the float64 normal equations and against the rectangle, its fixed
set of programs, ``StreamState``'s array reads against the dict semantics,
and event order end to end through producer, log, consumer, stage, commit,
store and resume."""

import types

import numpy as np
import pytest

from benchmarks.harness import reference_events, reference_foldin
from cfk_tpu.config import ALSConfig
from cfk_tpu.streaming import (
    StreamConfig, StreamProducer, StreamSession, StreamState, foldin)
from cfk_tpu.transport import InMemoryBroker
from cfk_tpu.transport.checkpoint import CheckpointManager
from cfk_tpu.transport.serdes import RatingUpdate

LIMIT = 1e-4  # foldin_row_err's, in every stream cell
LENGTHS = (1, 127, 128, 129, 1000, 10000)
ITEMS = 30000


def _table(rank, seed=0):
    rng = np.random.default_rng(seed)
    return ((rng.random((ITEMS, rank)) - 0.5) * 0.35).astype(np.float32)


def _lists(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [(np.sort(rng.choice(ITEMS, n, replace=False)).astype(np.int32),
             rng.integers(1, 6, n).astype(np.float32)) for n in lengths]


def _errs(table, lists, rows, lam=0.05):
    return [reference_foldin.row_err(
        row, reference_foldin.solve_row(table, mv, rt, lam))
        for (mv, rt), row in zip(lists, rows)]


@pytest.fixture(scope="module", params=(8, 16, 128))
def folded(request):
    """One micro-batch holding every length, at one rank: (table, lists,
    float32 rows, rows through a bfloat16 table)."""
    import jax.numpy as jnp

    table, lists = _table(request.param), _lists(LENGTHS)
    rows = foldin.fold_in_rows(jnp.asarray(table), lists, lam=0.05,
                               solver="cholesky")
    narrow = foldin.fold_in_rows(jnp.asarray(table, jnp.bfloat16), lists,
                                 lam=0.05, solver="cholesky")
    return table, lists, rows, narrow


@pytest.mark.parametrize("which", range(len(LENGTHS)), ids=map(str, LENGTHS))
def test_cells_route_against_the_float64_normal_equations(folded, which):
    table, lists, rows, _ = folded
    assert foldin.fold_route(lists) == "cells"
    err = _errs(table, lists, rows)[which]
    assert 0 < err < LIMIT / 10, err  # 4.6e-6 on one cell at rank 128


def test_a_bfloat16_gram_fails_the_limit(folded):
    table, lists, rows, narrow = folded
    assert max(_errs(table, lists, narrow)) > LIMIT
    assert max(_errs(table, lists, rows)) < LIMIT


@pytest.mark.parametrize("rank", (8, 128))
def test_cells_route_agrees_with_the_rectangle_where_both_apply(rank):
    """Lists the rectangle takes, solved beside one list that forces the
    cells route: the same normal equations, to rounding."""
    import jax.numpy as jnp

    table = jnp.asarray(_table(rank))
    light = _lists((1, 5, 64, 127, 128), seed=3)
    assert foldin.fold_route(light) == "padded"
    rect = foldin.fold_in_rows(table, light, lam=0.05, solver="cholesky")
    mixed = light + _lists((129,), seed=4)
    assert foldin.fold_route(mixed) == "cells"
    cells = foldin.fold_in_rows(table, mixed, lam=0.05, solver="cholesky")
    np.testing.assert_allclose(cells[:len(light)], rect, atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("cells,want", [
    (0, []), (1, [64]), (64, [64]), (65, [64, 64]), (255, [64] * 4),
    (256, [256]), (1950, [1024, 256, 256, 256, 64, 64, 64]),
    (4096 * 3 + 1, [4096, 4096, 4096, 64])])
def test_slab_plan_covers_with_under_a_slab_to_spare(cells, want):
    plan = foldin.slab_plan(cells)
    assert plan == want
    assert 0 <= sum(plan) - cells < foldin.SLABS[-1]


@pytest.mark.parametrize("touched", (16, 64, 200, 256))
@pytest.mark.parametrize("seed", range(2))
def test_padded_cells_stay_under_twice_the_cells(seed, touched):
    """Requirement (i): what the layout gathers is the cells plus under a
    chunk a user and under a slab a batch, so under twice the cells for
    every batch past ``CHUNK * (touched + SLABS[-1])`` cells (41 k at 256
    users), and past 16 k for users drawn by activity from the power-law
    tail."""
    rng = np.random.default_rng(seed)
    # a rating's user is activity weighted: list lengths drawn by n x the
    # power law's n^-1.963, cut at 10,000
    n = np.arange(1, 10001)
    p = n ** (1.0 - 1.963)
    lens = rng.choice(n, touched, p=p / p.sum())
    lists = [(np.arange(n, dtype=np.int32), np.ones(n, np.float32))
             for n in lens]
    slabs, count, cells = foldin._chunk_rows(lists, 256)
    padded = sum(s.shape[0] for s in slabs) * foldin.CHUNK
    assert cells == lens.sum() == count.sum()
    assert padded <= cells + foldin.CHUNK * (len(lists) + foldin.SLABS[-1])
    assert cells > 16384 and padded < 2 * cells
    # every cell is in exactly one chunk row of its owner, in list order
    got = [[] for _ in lists]
    for slab in slabs:
        for row in slab:
            fill, owner = row[2 * foldin.CHUNK], row[2 * foldin.CHUNK + 1]
            got[owner] += row[:fill].tolist()
    assert all(g == mv.tolist() for g, (mv, _) in zip(got, lists))


# -- StreamState: arrays against the dict semantics ---------------------------

def _dict_cells(indptr, items, values, row, applied):
    """What ``StreamState._cells`` built before PR 41: the row's base cells
    (seq -1), then every applied write in order."""
    cells = {}
    if row < indptr.shape[0] - 1:
        for mv, rt in zip(items[indptr[row]:indptr[row + 1]].tolist(),
                          values[indptr[row]:indptr[row + 1]].tolist()):
            cells[mv] = (rt, -1)
    cells.update(applied.get(row, {}))
    return cells


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("sorted_base", (True, False))
def test_array_reads_equal_the_dict_semantics(seed, sorted_base):
    """Random upsert sequences (re-rates, equal and lower seqs, new users,
    batches staged over uncommitted ones): ``stage``'s counts and writes
    and ``neighbors``' lists are those of the dict the state used to
    rebuild, to the bit."""
    rng = np.random.default_rng(seed)
    users_n, items_n = 12, 40
    lens = rng.integers(0, 9, users_n)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    items = np.concatenate(
        [rng.choice(items_n, n, replace=sorted_base is False)
         for n in lens] + [np.zeros(0, np.int64)]).astype(np.int32)
    if sorted_base:
        items = np.concatenate(
            [np.sort(items[lo:hi]) for lo, hi in zip(indptr[:-1], indptr[1:])]
            + [np.zeros(0, np.int32)]).astype(np.int32)
    values = rng.integers(1, 6, items.size).astype(np.float32)
    state = StreamState.from_csr(indptr, items, values, num_movies=items_n)
    applied: dict = {}
    rows_of: dict = {}

    def expect(batch, view):
        """(writes, fresh, stale, rerated) by the dict semantics over
        ``view`` (row -> cells as applied + staged)."""
        writes, fresh, stale, rerated = {}, 0, 0, 0
        for u in batch:
            row = rows_of.setdefault(
                u.user, u.user if u.user < users_n else
                users_n + sum(r >= users_n for r in rows_of.values()))
            cur = writes.get(row, {}).get(u.movie)
            if cur is None:
                cur = _dict_cells(indptr, items, values, row, view).get(
                    u.movie)
            if cur is not None and u.seq <= cur[1]:
                stale += 1
                continue
            rerated += cur is not None
            writes.setdefault(row, {})[u.movie] = (float(u.rating), u.seq)
            fresh += 1
        return writes, fresh, stale, rerated

    seq = 0
    for _ in range(12):
        batches = []
        for _ in range(int(rng.integers(1, 3))):
            n = int(rng.integers(1, 10))
            batch = []
            for _ in range(n):
                seq += 1
                batch.append(RatingUpdate(
                    seq=int(seq - rng.integers(0, 6)),  # late, or a repeat
                    user=int(rng.integers(0, users_n + 3)),
                    movie=int(rng.integers(0, items_n)),
                    rating=float(rng.integers(1, 6))))
            batches.append(batch)
        over, view = [], {r: dict(c) for r, c in applied.items()}
        for batch in batches:
            want, fresh, stale, rerated = expect(batch, view)
            pending = state.stage(batch, over)
            assert pending.cell_writes == want
            assert (pending.stats.fresh, pending.stats.stale,
                    pending.stats.rerated) == (fresh, stale, rerated)
            for row, cells in want.items():
                view.setdefault(row, {}).update(cells)
            over.append(pending)
            overlays = [{k: v for p in over
                         for k, v in p.cell_writes.get(row, {}).items()}
                        for row in pending.touched_rows]
            for (mv, rt), row, overlay in zip(
                    state.neighbors_many(pending.touched_rows, overlays),
                    pending.touched_rows, overlays):
                one = state.neighbors(row, overlay)
                assert (mv.dtype, rt.dtype) == (one[0].dtype, one[1].dtype)
                assert mv.tolist() == one[0].tolist()
                assert rt.tolist() == one[1].tolist()
            for row in pending.touched_rows:
                mv, rt = state.neighbors(
                    row, {k: v for p in over
                          for k, v in p.cell_writes.get(row, {}).items()})
                cells = _dict_cells(indptr, items, values, row, view)
                assert mv.dtype == np.int32 and rt.dtype == np.float32
                assert mv.tolist() == sorted(cells)
                assert rt.tolist() == [
                    np.float32(cells[m][0]) for m in sorted(cells)]
        for pending in over:
            state.commit(pending)
        applied = view
        assert state.applied_seq_high == max(
            [s for c in applied.values() for _, s in c.values()] + [-1])


# -- event order, end to end --------------------------------------------------

def _session(tmp_path, broker, state, tables, batch_records=8):
    return StreamSession(
        state, ALSConfig(rank=tables[0].shape[1], lam=0.05,
                         health_check_every=1), broker,
        CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=batch_records),
        base_model=types.SimpleNamespace(user_factors=tables[0],
                                         movie_factors=tables[1]))


def test_event_order_through_log_commit_and_resume(tmp_path):
    """Events sent out of event order, with a repeated seq: the cell's
    highest seq wins whatever arrived last, every record is consumed and
    committed once, the outranked ones are counted and change nothing; a
    producer on the same log resumes past the highest seq, not the last;
    the reopened store holds the same cells."""
    rng = np.random.default_rng(5)
    users_n, items_n, rank = 20, 30, 4
    lens = rng.integers(1, 6, users_n)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    items = np.concatenate([np.sort(rng.choice(items_n, n, replace=False))
                            for n in lens]).astype(np.int32)
    values = rng.integers(1, 6, items.size).astype(np.float32)
    tables = (((rng.random((users_n, rank)) - 0.5) * 0.35).astype(np.float32),
              ((rng.random((items_n, rank)) - 0.5) * 0.35).astype(np.float32))
    n = 64
    ev_users = rng.choice([0, 1, 2, 3, users_n, users_n + 1], n)
    ev_items = rng.integers(0, 2, n)  # few cells: they collide
    ev_values = rng.integers(1, 6, n).astype(np.float32)
    ev_seqs = 100 + np.arange(n)
    ev_seqs[10] = ev_seqs[9]  # a retried append
    arrival = np.argsort(np.arange(n) + rng.integers(0, 40, n) *
                         (rng.random(n) < 0.5), kind="stable")
    users, its, vals, seqs = (ev_users[arrival], ev_items[arrival],
                              ev_values[arrival], ev_seqs[arrival])
    assert np.any(np.diff(seqs) < 0)
    broker = InMemoryBroker()
    producer = StreamProducer(broker)
    assert producer.send_many(users[:40], its[:40], vals[:40],
                              seqs=seqs[:40]) == seqs[0]
    for j in range(40, n):
        assert producer.send(int(users[j]), int(its[j]), float(vals[j]),
                             seq=int(seqs[j])) == seqs[j]
    assert producer.next_seq == ev_seqs.max() + 1
    assert StreamProducer(broker).next_seq == ev_seqs.max() + 1
    assert int(seqs[-1]) != ev_seqs.max()

    state = StreamState.from_csr(indptr, items, values, num_movies=items_n)
    sess = _session(tmp_path, broker, state, tables)
    events = []
    sess.add_commit_listener(events.append)
    sess.run()
    assert sess.consumer.cursors == {0: n} and len(events) == 8
    lost = reference_events.outranked(users, its, seqs)
    assert lost.sum() > 3
    assert sess.metrics.counters["updates_stale"] == lost.sum()
    assert sess.metrics.counters["updates_fresh"] == n - lost.sum()
    win = reference_events.winners(users, its, seqs)

    def check(st):
        for j in win:
            row = st.user_row(int(users[j]))
            mv, rt = st.neighbors(row)
            assert rt[np.searchsorted(mv, its[j])] == vals[j]
        assert st.applied_seq_high == ev_seqs.max()

    check(sess.state)
    # a user's list as of each commit: the reference's, by event order
    committed = np.repeat([e["stream_step"] for e in events], 8)
    for e in events:
        for row in e["touched_rows"]:
            raw = int(sess.state.user_raw_ids()[row])
            mine = [(its[j], vals[j], seqs[j], committed[j])
                    for j in np.nonzero(users == raw)[0]]
            base = ((items[indptr[row]:indptr[row + 1]],
                     values[indptr[row]:indptr[row + 1]])
                    if row < users_n else ((), ()))
            want = reference_events.list_as_of(*base, mine, e["stream_step"])
            if e["stream_step"] == events[-1]["stream_step"]:
                mv, rt = sess.state.neighbors(row)
                assert mv.tolist() == want[0].tolist()
                assert rt.tolist() == want[1].tolist()
    from cfk_tpu.resilience.loop import drain_checkpoints

    drain_checkpoints(sess.manager)
    again = _session(tmp_path, broker, state.fresh(), tables)
    assert again.consumer.cursors == {0: n}
    check(again.state)
    np.testing.assert_array_equal(again.user_factors, sess.user_factors)


def test_rows_are_the_same_bits_whatever_the_arrival_order(tmp_path):
    """One micro-batch's records in two arrival orders (each with its
    event's seq): the same cells win, and the solved rows are equal to the
    bit, on the cells route."""
    rng = np.random.default_rng(9)
    users_n, items_n, rank = 6, 4000, 8
    lens = np.array([300, 2, 150, 40, 1, 129])
    indptr = np.concatenate([[0], np.cumsum(lens)])
    items = np.concatenate([np.sort(rng.choice(items_n, n, replace=False))
                            for n in lens]).astype(np.int32)
    values = rng.integers(1, 6, items.size).astype(np.float32)
    tables = (((rng.random((users_n, rank)) - 0.5) * 0.35).astype(np.float32),
              ((rng.random((items_n, rank)) - 0.5) * 0.35).astype(np.float32))
    n = 32
    users = rng.integers(0, users_n, n)
    its = rng.integers(0, items_n, n)
    vals = rng.integers(1, 6, n).astype(np.float32)
    seqs = np.arange(n)
    rows = []
    for k, order in enumerate((np.arange(n), rng.permutation(n))):
        broker = InMemoryBroker()
        StreamProducer(broker).send_many(users[order], its[order],
                                         vals[order], seqs=seqs[order])
        sess = _session(
            tmp_path / str(k), broker,
            StreamState.from_csr(indptr, items, values, num_movies=items_n),
            tables, batch_records=n)
        got = sess.step()
        assert got["records"] == n
        rows.append(sess.user_factors.copy())
    np.testing.assert_array_equal(rows[0], rows[1])
    assert not np.array_equal(rows[0], tables[0])


# -- the programs are a fixed set ---------------------------------------------

def test_prewarm_is_a_fixed_set_no_list_outgrows(tmp_path):
    """``prewarm`` runs 6 x 5 rectangles, the four slab programs and one
    solve, whatever the longest list; afterwards a list that grows from 64
    cells to past 20,000 traces nothing, on either route."""
    rng = np.random.default_rng(2)
    users_n, items_n, rank = 4, 24000, 8
    lens = np.array([64, 3, 10, 1])
    indptr = np.concatenate([[0], np.cumsum(lens)])
    items = np.concatenate([np.arange(n) for n in lens]).astype(np.int32)
    values = rng.integers(1, 6, items.size).astype(np.float32)
    tables = (((rng.random((users_n, rank)) - 0.5) * 0.35).astype(np.float32),
              ((rng.random((items_n, rank)) - 0.5) * 0.35).astype(np.float32))
    broker = InMemoryBroker()
    producer = StreamProducer(broker)
    sess = _session(
        tmp_path, broker,
        StreamState.from_csr(indptr, items, values, num_movies=items_n),
        tables, batch_records=256)
    warm = sess.prewarm()
    assert warm["programs"] == 6 * 5 + len(foldin.SLABS) + 1 == 35
    assert warm["new_traces"] == warm["programs"]
    assert sess.prewarm()["new_traces"] == 0
    before = foldin.trace_count()
    grown, routes = 64, set()
    for batch in range(79):
        n = 256 if batch else 60  # first 64 -> 124 (the rectangle), then up
        producer.send_many(np.zeros(n, np.int64), grown + np.arange(n),
                           np.full(n, 4.0, np.float32))
        grown += n
        sess.step()
        routes.add(foldin.fold_route([sess.state.neighbors(0)]))
    assert grown > 20000 and sess.state.neighbors(0)[0].shape[0] == grown
    assert routes == {"padded", "cells"}
    assert foldin.trace_count() == before
    exact = reference_foldin.solve_row(tables[1], *sess.state.neighbors(0),
                                       0.05)
    assert reference_foldin.row_err(sess.user_rows([0])[0], exact) < LIMIT / 30


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_csr_find_against_a_search_a_cell(seed):
    """``utils.search.csr_find``: one bisection over a batch of (row, item)
    cells gives what ``np.searchsorted`` gives in each row's slice, for
    empty lists, lists of one, lists of thousands, rows past the CSR and
    items below, between, among and above a list's."""
    from cfk_tpu.utils.search import csr_find

    rng = np.random.default_rng(seed)
    lens = rng.choice([0, 1, 2, 7, 128, 3000], 40)
    indptr = np.zeros(41, np.int64)
    np.cumsum(lens, out=indptr[1:])
    values = np.concatenate([
        np.sort(rng.choice(5000, n, replace=False)) for n in lens] + [
        np.zeros(0, np.int64)]).astype(np.int32)
    rows = rng.integers(0, 44, 600)
    held = rng.random(600) < 0.5
    needles = rng.integers(-1, 5001, 600)
    for i in np.flatnonzero(held & (rows < 40)):
        mine = values[indptr[rows[i]]:indptr[rows[i] + 1]]
        if mine.shape[0]:
            needles[i] = rng.choice(mine)
    want = np.full(600, -1, np.int64)
    for i, (row, needle) in enumerate(zip(rows.tolist(), needles.tolist())):
        if row < 40:
            lo, hi = indptr[row], indptr[row + 1]
            at = lo + np.searchsorted(values[lo:hi], needle)
            if at < hi and values[at] == needle:
                want[i] = at
    assert (want >= 0).sum() > 100
    np.testing.assert_array_equal(
        csr_find(indptr, values, rows, needles), want)
    for i in range(0, 600, 7):  # a few cells: searched one by one
        np.testing.assert_array_equal(
            csr_find(indptr, values, rows[i:i + 7], needles[i:i + 7]),
            want[i:i + 7])
    empty = np.zeros(0, np.int64)
    assert csr_find(indptr, values, empty, empty).shape == (0,)
    assert (csr_find(np.zeros(1, np.int64), values[:0], rows, needles)
            == -1).all()
