"""The skewed serve-while-folding-in runner end to end at the toy cell beside
this file (a CPU rehearsal): the cell is ``correct`` as built, a program
without the entry points is refused before any data, the new readers report
nothing on a trace without the counts, and each planted fault makes
``correct`` false by the check that is its own."""

import json
import os
import types

import numpy as np
import pytest

from benchmarks import run

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "toy_stream_skew")
MANIFEST = os.path.join(TOY, "BENCHMARK.json")
CELL = "toy-stream-skew.foldin-skew"
CHECKS = ["compiles_in_window", "failed_requests", "invalid_id_sets",
          "rank_gap", "score_err", "lost_ratings", "stale_reads",
          "foldin_row_err", "reopened_store", "misordered_cells"]
NEW_METRICS = {"foldin_pad_ratio.foldin", "foldin_cells.foldin",
               "stream_stale_share.skew", "stream_rerated_share.skew"}


def drive(capsys, *, trace=0, seed=3_000_000_017, seconds=2):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--manifest",
                   MANIFEST], require_tpu=False)
    out, err = capsys.readouterr()
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert list(res)[-1] == "checks" and list(res["checks"]) == CHECKS
    assert err.strip().splitlines()[-1] == f"correct: {res['correct']}"
    return res, out


def failed_checks(res):
    return {n for n, c in res["checks"].items()
            if not (isinstance(c["value"], float)
                    and c["value"] <= c["limit"])}


def test_the_cell_is_correct_as_built(capsys):
    res, out = drive(capsys)
    assert res["correct"] is True and res["failed"] == 0, out
    assert not failed_checks(res)
    assert set(res["metrics"]) == {"serve_req_per_s", "setup_s"}
    assert res["attempted"] == pytest.approx(2 * (200 + 150), rel=0.02)
    # events arrived after a newer one of their cell, and lost
    assert " 0 outranked" not in out and "0 differ" in out
    assert "0 late, 0 never committed" in out


def test_a_traced_run_reads_the_new_metrics(capsys):
    res, out = drive(capsys, trace=1)
    assert res["correct"] is True, out[-3000:]
    got = set(res["metrics"])
    assert NEW_METRICS <= got
    # the device's numbers need a device: nothing on the CPU, and no error
    assert not {"foldin_cells_roofline.foldin",
                "foldin_cells_device_ms.foldin", "foldin_device_ms.foldin",
                "foldin_roofline.foldin"} & got
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["foldin_pad_ratio.foldin"] >= 1
    assert m["foldin_cells.foldin"] >= m["stream_touched_users.foldin"]
    assert 0 < m["stream_stale_share.skew"] < m["stream_rerated_share.skew"]
    assert m["stream_rerated_share.skew"] == pytest.approx(0.3, abs=0.08)


def test_a_program_that_cannot_take_event_seqs_is_refused_before_any_data(
        capsys, monkeypatch):
    from cfk_tpu.streaming import StreamProducer

    monkeypatch.setattr(StreamProducer, "send_many",
                        lambda self, users, movies, ratings: None)
    with pytest.raises(SystemExit) as stop:
        drive(capsys)
    assert "lacks StreamProducer.send_many(seqs=)" in str(stop.value)
    assert "seen lists" not in capsys.readouterr().out


def test_the_new_readers_report_nothing_without_the_counts():
    """A program before PR 41: ``stream/batch`` and ``stream/batch/stage``
    spans without ``cells``, ``stale`` or ``rerated``, and a trace in which
    the cells route's modules never ran."""
    from benchmarks.harness import roofline

    spans = [
        {"name": "stream/batch", "ts": 0, "dur": 9, "args": {
            "ordinal": 1, "touched": 3, "entities": 8, "width": 16,
            "rank": 128, "gather_bytes": 1, "operand_bytes": 1}},
        {"name": "stream/batch/stage", "ts": 1, "dur": 1, "args": {
            "records": 5, "fresh": 5, "new_users": 0}}]
    trace = types.SimpleNamespace(
        modules=[[(0.0, 1e-3, "jit__padded_fold(123)")]])
    ctx = types.SimpleNamespace(program_spans=spans, trace_data=trace,
                                peaks=roofline.PEAKS["TPU v5 lite"])
    for family in ("foldin_cells_roofline", "foldin_cells_device_ms",
                   "foldin_pad_ratio", "foldin_cells", "stream_stale_share",
                   "stream_rerated_share"):
        reader = run.load_module(os.path.join(
            run.HERE, "layer_metrics", family + ".py"), "t_" + family)
        assert reader.read(ctx, family + ".x") is None, family
    # with the counts they read, and the share of the useful floor is the
    # floor over the device's time in every fold-in module
    spans[0]["args"].update(cells=2000, padded_cells=3000, chunks=0,
                            route="padded")
    spans[1]["args"].update(stale=1, rerated=2)
    trace.modules[0] += [(2e-3, 2.5e-3, "jit__cells_fold_gram(7)"),
                         (3e-3, 3.5e-3, "jit__cells_fold_solve(8)")]
    want = {"foldin_cells_device_ms": 2.0, "foldin_pad_ratio": 1.5,
            "foldin_cells": 2000, "stream_stale_share": 0.2,
            "stream_rerated_share": 0.4}
    for family, value in want.items():
        reader = run.load_module(os.path.join(
            run.HERE, "layer_metrics", family + ".py"), "t_" + family)
        assert reader.read(ctx, family + ".x") == pytest.approx(value)
    from benchmarks.harness import roofline_foldin_cells
    from benchmarks.layer_metrics import foldin_cells_roofline

    floor = roofline_foldin_cells.cells_cost(2000, 3, 128).floor_s(ctx.peaks)
    assert floor == pytest.approx((2000 * 520 + 3 * 512) / 819e9)
    assert foldin_cells_roofline.read(ctx, "x") == pytest.approx(
        100 * floor / 2e-3)


# -- planted faults: each makes ``correct`` false by its own check -----------

def test_the_later_arrival_wins(capsys, monkeypatch):
    """A state that applies an outranked record all the same (it counts it,
    and then lets it overwrite the newer event's value): the store's cells
    are not those of their highest seq.  The corrupted rating is in its
    user's normal equations from then on, so a sampled row of that user may
    fail its own check too."""
    from cfk_tpu.streaming import StreamState

    real = StreamState.stage

    def arrival_order(self, updates, over=()):
        pending = real(self, updates, over)
        for upd in updates:
            row, mv = self.user_row(upd.user), self.movie_row(upd.movie)
            held = None if row is None or mv is None else (
                pending.cell_writes.get(row, {}).get(mv)
                or self._held(row, mv, over))
            if held is not None and upd.seq < held[1]:
                pending.cell_writes.setdefault(row, {})[mv] = (
                    float(upd.rating), int(upd.seq))
        return pending

    monkeypatch.setattr(StreamState, "stage", arrival_order)
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert "misordered_cells" in failed_checks(res)
    assert failed_checks(res) <= {"misordered_cells", "foldin_row_err"}
    assert res["checks"]["lost_ratings"]["value"] == 0


def test_an_outranked_event_not_accounted(capsys, monkeypatch):
    """The outranked records leave no trace in the commit's counts: fresh +
    outranked is short of the events sent."""
    from cfk_tpu.streaming import StreamState

    real = StreamState.stage

    def forgetful(self, updates, over=()):
        pending = real(self, updates, over)
        pending.stats.stale = 0
        return pending

    monkeypatch.setattr(StreamState, "stage", forgetful)
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert failed_checks(res) == {"lost_ratings"}


def test_a_heavy_users_remainder_dropped_from_the_gram(capsys, monkeypatch):
    """Only the first chunk row of every list reaches the Gram (the ridge
    still counts the whole list)."""
    from cfk_tpu.streaming import foldin

    real = foldin._chunk_rows

    def first_rows_only(neighbor_data, entities, index=None):
        slabs, count, cells = real(
            [(mv[:foldin.CHUNK], rt[:foldin.CHUNK])
             for mv, rt in neighbor_data], entities, index)
        count[:len(neighbor_data)] = [mv.shape[0] for mv, _ in neighbor_data]
        return slabs, count, cells

    monkeypatch.setattr(foldin, "_chunk_rows", first_rows_only)
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert failed_checks(res) == {"foldin_row_err"}
    assert res["checks"]["foldin_row_err"]["value"] > 1e-3


def test_a_heavy_hot_users_exclusion_cut_short(capsys, monkeypatch):
    """The merge of base slice and overlay goes wrong where it is long: a
    user holding more than 128 cells who rated something is excluded by the
    first 128 cells of the merged list alone, so the rest of the list, the
    newly rated items among it, can be served."""
    from cfk_tpu.serving.engine import ServeEngine

    real = ServeEngine._batch_seen

    def cut_short(self, user_rows):
        movies, indptr = real(self, user_rows)
        keep = np.ones(movies.shape[0], bool)
        for i, row in enumerate(np.asarray(user_rows).tolist()):
            if row in self._seen_hot and indptr[i + 1] - indptr[i] > 128:
                keep[indptr[i] + 128:indptr[i + 1]] = False
        sizes = np.add.reduceat(keep, indptr[:-1]) * (np.diff(indptr) > 0)
        return movies[keep], np.concatenate([[0], np.cumsum(sizes)])

    monkeypatch.setattr(ServeEngine, "_batch_seen", cut_short)
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert "invalid_id_sets" in failed_checks(res)
    # a served item that should not be there outranks the reference's
    assert failed_checks(res) <= {"invalid_id_sets", "rank_gap", "score_err"}


def test_a_cells_program_traced_inside_the_window(capsys, monkeypatch):
    from cfk_tpu.streaming import StreamSession, foldin

    monkeypatch.setattr(
        StreamSession, "prewarm",
        lambda self, **kw: {"programs": 0, "new_traces": 0, "prewarm_s": 0.0})
    for program in (foldin._padded_fold, foldin._cells_fold_gram,
                    foldin._cells_fold_solve):
        program.clear_cache()
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert failed_checks(res) == {"compiles_in_window"}


# -- the generators and the reference, alone ----------------------------------

def test_reference_events_orders_by_seq_not_by_arrival():
    from benchmarks.harness import reference_events as ref

    users = np.array([1, 1, 1, 2, 2, 1])
    items = np.array([5, 5, 5, 5, 5, 6])
    seqs = np.array([10, 9, 11, 3, 3, 1])
    assert ref.outranked(users, items, seqs).tolist() == [
        False, True, False, False, True, False]
    assert ref.winners(users, items, seqs).tolist() == [2, 5, 3]
    mv, rt = ref.list_as_of([4, 5], [2.0, 3.0], [
        (5, 1.0, 10, 1), (5, 5.0, 9, 2), (5, 4.0, 11, 9), (7, 2.0, 0, 2)], 2)
    # the late seq 9 lost to seq 10; seq 11 is not committed yet at 2
    assert mv.tolist() == [4, 5, 7] and rt.tolist() == [2.0, 1.0, 2.0]


def test_the_tail_holds_the_lengths_it_drew():
    from benchmarks.harness import seen_tail

    s = seen_tail.solve_exponent(6.857, 10000)
    assert s == pytest.approx(1.9634961, abs=1e-6)
    items, indptr, facts = seen_tail.seen_lists(
        20000, 9000, exponent=1.5, max_len=3000, seed=3, tile_m=64)
    lens = np.diff(indptr)
    assert facts["cells"] == items.size == lens.sum()
    assert facts["longest"] == lens.max() > 1000 and facts["topped_up"] > 0
    rows = np.repeat(np.arange(20000), lens)
    keys = rows.astype(np.int64) * 9000 + items
    assert np.all(np.diff(keys) > 0)  # ascending in every list, none twice
    _, per_tile = np.unique(rows * 141 + items // 64, return_counts=True)
    assert facts["most_cells_a_user_a_tile"] == per_tile.max()


def test_the_events_carry_the_mix():
    from benchmarks.harness import seen_tail, stream_gen_skew
    from benchmarks.harness import reference_events as ref

    items, indptr, _ = seen_tail.seen_lists(
        5000, 8000, exponent=1.9675, max_len=600, seed=11, tile_m=64)
    n = 20000
    ev = stream_gen_skew.stream_events(
        indptr, items, n, seed=5, rating_rate=1000.0, new_user_share=0.02,
        hot_share=0.2, hot_users=64, hot_period_s=5, rerate_share=0.10,
        rerate_pair_share=0.2, rerate_pair_gap_s=0.5, late_share=0.05,
        late_by_s=[0.1, 1.5], seq0=7)
    assert sorted(ev.seqs.tolist()) == list(range(7, 7 + n))
    assert ev.new.mean() == pytest.approx(0.02, abs=0.005)
    assert ev.rerate.mean() == pytest.approx(0.10, abs=0.01)
    assert ev.late.mean() == pytest.approx(0.05, abs=0.005)
    # an event on time is never sent before an earlier one on time
    on_time = ev.seqs[~ev.late]
    assert np.all(np.diff(on_time) > 0)
    # a late one arrives 0.1-1.5 s x 1000 events/s after its place
    place = np.flatnonzero(ev.late) - (ev.seqs[ev.late] - 7)
    assert place.min() >= 0 and place.max() <= 1500
    lost = ref.outranked(ev.users, ev.items, ev.seqs)
    assert 0 < lost.sum() < 0.003 * n and lost[ev.late | ev.rerate].sum() == lost.sum()
    # a cell that is no re-rate is new to its user: not in the base list
    for j in np.flatnonzero(~ev.rerate & ~ev.new)[:500]:
        lo, hi = indptr[ev.users[j]], indptr[ev.users[j] + 1]
        assert ev.items[j] not in items[lo:hi]
