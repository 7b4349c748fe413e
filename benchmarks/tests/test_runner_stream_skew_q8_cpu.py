"""The skewed serve-while-folding-in runner over an int8 table, end to end at
the toy cell beside this file (a CPU rehearsal): the cell is ``correct`` as
built; a program whose fold-in cannot read a quantized table is refused
before any data; the new readers report nothing on spans that do not name
their table; and the two controls of the cell, planted as faults (the
gathered rows through bfloat16 after dequantization; the fold-in solved
against the float32 factors the codes were made from), each make ``correct``
false by ``foldin_row_err`` and nothing else.

What no test can see is a float32 copy of an item block kept by the
program: the runner prints the device's ``bytes_in_use`` after prewarm
beside the bytes of codes and scales for that.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmarks import run

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "toy_stream_skew_q8")
MANIFEST = os.path.join(TOY, "BENCHMARK.json")
CELL = "toy-stream-skew-q8.foldin-skew-q8"
CHECKS = ["compiles_in_window", "failed_requests", "invalid_id_sets",
          "rank_gap", "score_err", "lost_ratings", "stale_reads",
          "foldin_row_err", "reopened_store", "misordered_cells"]
SEED = 3_000_000_017


def drive(capsys, *, trace=0, seed=SEED, seconds=2):
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


@pytest.fixture
def fresh_fold_programs():
    """A planted arithmetic needs traces of its own: jax keeps them by
    function and shapes, and a sound run of this process left some."""
    from cfk_tpu.streaming import foldin

    programs = (foldin._padded_fold, foldin._cells_fold_gram,
                foldin._cells_fold_solve)
    for p in programs:
        p.clear_cache()
    yield
    for p in programs:
        p.clear_cache()


def test_the_cell_is_correct_as_built(capsys):
    res, out = drive(capsys)
    assert res["correct"] is True and res["failed"] == 0, out
    assert not failed_checks(res)
    assert set(res["metrics"]) == {"serve_req_per_s", "setup_s"}
    assert "table_dtype=int8" in out and "read in row blocks" in out
    # two blocks of item rows, so the blockwise reference crosses an edge
    assert "over 300,000 rows" in out and "0 differ" in out
    assert "the device holds" in out and "fold-in programs against the " \
        "table as held" in out
    assert "the machine's used memory" in out
    # sound rows sit where the float32 cells' do
    assert res["checks"]["foldin_row_err"]["value"] < 3e-6


def test_a_traced_run_reads_the_new_metrics(capsys):
    res, out = drive(capsys, trace=1)
    assert res["correct"] is True, out[-3000:]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["setup_table_upload_s"] > 0
    # a padded cell gathers 16 codes and one float32 scale
    assert m["foldin_gather_mb.foldin"] > 0
    assert (m["foldin_gather_mb.foldin"] * 1e6) % (16 + 4) == 0
    # the device's numbers need a device: nothing on the CPU, and no error
    assert not {"foldin_q8_roofline.foldin", "foldin_cells_device_ms.foldin",
                "foldin_device_ms.foldin", "foldin_roofline.foldin"} & set(m)


def test_a_program_whose_fold_in_reads_float32_only_is_refused_at_once(
        capsys, monkeypatch):
    from cfk_tpu.ops import solve

    monkeypatch.delattr(solve, "gather_rows")
    with pytest.raises(SystemExit) as stop:
        drive(capsys)
    assert "lacks ops.solve.gather_rows" in str(stop.value)
    assert "seen lists" not in capsys.readouterr().out


def test_the_new_readers_report_nothing_without_the_tables_name():
    """A program before PR 47: spans whose ``gather_bytes`` is float32's on
    the rectangle and 0 on the cells route, and no ``table_dtype``."""
    from benchmarks.harness import roofline, roofline_foldin_q8

    spans = [
        {"name": "stream/batch", "ts": 0, "dur": 9, "args": {
            "ordinal": 1, "touched": 3, "rank": 128, "cells": 2000,
            "padded_cells": 3000, "gather_bytes": 0, "route": "cells"}},
        {"name": "stream/batch/solve", "ts": 1, "dur": 1, "args": {
            "touched": 3, "gather_bytes": 0, "cells": 2000}},
        {"name": "stream/batch/solve", "ts": 3, "dur": 1}]
    trace = types.SimpleNamespace(
        modules=[[(0.0, 1e-3, "jit__cells_fold_gram(7)"),
                  (2e-3, 3e-3, "jit__cells_fold_solve(8)")]])
    ctx = types.SimpleNamespace(program_spans=spans, trace_data=trace,
                                peaks=roofline.PEAKS["TPU v5 lite"])
    readers = {family: run.load_module(os.path.join(
        run.HERE, "layer_metrics", family + ".py"), "t_" + family)
        for family in ("foldin_q8_roofline", "foldin_gather_mb")}
    for family, reader in readers.items():
        assert reader.read(ctx, family + ".foldin") is None, family
    # a float32 table named: the gather's megabytes read, the int8 share not
    for e in spans[:2]:
        e["args"].update(table_dtype="float32", gather_bytes=3000 * 512)
    assert readers["foldin_gather_mb"].read(ctx, "x") == pytest.approx(1.536)
    assert readers["foldin_q8_roofline"].read(ctx, "x") is None
    # the int8 table: bytes true to it (140 a cell, 512 a solved row)
    for e in spans[:2]:
        e["args"].update(table_dtype="int8", gather_bytes=3000 * 132)
    assert readers["foldin_gather_mb"].read(ctx, "x") == pytest.approx(0.396)
    cost = roofline_foldin_q8.cells_cost(2000, 3, 128)
    assert cost.bytes == 2000 * 140 + 3 * 512
    # at 140 B a cell the operations bound the floor, not the bytes
    floor = cost.floor_s(ctx.peaks)
    assert floor == pytest.approx(cost.flops / 197e12)
    assert floor > cost.bytes / 819e9
    assert readers["foldin_q8_roofline"].read(ctx, "x") == pytest.approx(
        100 * floor / 2e-3)
    # no fold-in module in the trace: nothing
    trace.modules = [[(0.0, 1e-3, "jit__topk_call(1)")]]
    assert readers["foldin_q8_roofline"].read(ctx, "x") is None


# -- the cell's two controls, planted: each fails ``foldin_row_err`` alone ----

def test_gathered_rows_through_bfloat16_after_dequantization(
        capsys, monkeypatch, fresh_fold_programs):
    import jax.numpy as jnp

    from cfk_tpu.ops import solve

    monkeypatch.setattr(solve, "_gram_compute_dtype",
                        lambda gathered: (jnp.bfloat16, None))
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert failed_checks(res) == {"foldin_row_err"}
    assert res["checks"]["foldin_row_err"]["value"] > 3e-4


def test_the_fold_in_solved_against_the_float32_factors(
        capsys, monkeypatch, fresh_fold_programs):
    """The rows a fold-in gathers are those of the float32 factors the codes
    were made from (a copy of the table kept beside the codes, or a
    dequantization that is not the written rule's): the solved row is not
    the solution over the table its user is scored against."""
    import jax.numpy as jnp

    from benchmarks.harness import reference_q8
    from cfk_tpu.ops import solve

    with open(os.path.join(TOY, "configs", "toy-stream-skew-q8.json")) as f:
        config = json.load(f)
    factors = reference_q8.FactorBlocks(
        config["items"], config["rank"], seed=SEED + 1,
        scale=config["factor_scale"])[0:config["items"]]

    def from_the_factors(fixed, neighbor_idx):
        rows = fixed[0].shape[0]  # the engine's, padded to whole tiles
        table = jnp.zeros((rows, factors.shape[1]), jnp.float32).at[
            :factors.shape[0]].set(factors)
        return table[neighbor_idx]

    monkeypatch.setattr(solve, "gather_rows", from_the_factors)
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert failed_checks(res) == {"foldin_row_err"}
    assert res["checks"]["foldin_row_err"]["value"] > 3e-4


# -- the generator and the reference's rows, alone ----------------------------

def test_the_tail_in_blocks_holds_the_lengths_it_drew():
    from benchmarks.harness import seen_tail_blocks

    kw = dict(exponent=1.5, max_len=3000, seed=3, tile_m=64,
              users_per_block=4096)
    items, indptr, facts = seen_tail_blocks.seen_lists_blocks(
        20000, 9000, threads=2, **kw)
    lens = np.diff(indptr)
    assert facts["cells"] == items.size == lens.sum()
    assert facts["longest"] == lens.max() > 1000 and facts["topped_up"] > 0
    rows = np.repeat(np.arange(20000), lens)
    keys = rows.astype(np.int64) * 9000 + items
    assert np.all(np.diff(keys) > 0)  # ascending in every list, none twice
    _, per_tile = np.unique(rows * 141 + items // 64, return_counts=True)
    assert facts["most_cells_a_user_a_tile"] == per_tile.max()
    assert facts["activity_weighted_mean_list"] == pytest.approx(
        (lens.astype(float) ** 2).sum() / lens.sum())
    heavy = lens > 1024
    assert facts["users_over_1024"] == heavy.sum() > 0
    assert facts["cell_share_over_1024"] == pytest.approx(
        lens[heavy].sum() / lens.sum())
    # a block is drawn from its own child seed: the thread count is nothing
    again, indptr2, facts2 = seen_tail_blocks.seen_lists_blocks(
        20000, 9000, threads=1, **kw)
    assert np.array_equal(items, again) and np.array_equal(indptr, indptr2)
    assert facts == facts2
    # the facts alone, for writing a configuration down
    none, indptr3, facts3 = seen_tail_blocks.seen_lists_blocks(
        20000, 9000, threads=2, keep_items=False, **kw)
    assert none is None and facts3 == facts


def test_a_catalogue_past_24_bits_of_item_rows():
    from benchmarks.harness import seen_tail, seen_tail_blocks

    s = seen_tail.solve_exponent(10.485, 10000)
    assert s == pytest.approx(1.8656207, abs=1e-6)
    items, indptr, facts = seen_tail_blocks.seen_lists_blocks(
        3000, 48_190_000, exponent=s, max_len=10000, seed=5,
        users_per_block=1024, threads=2)
    assert items.dtype == np.int32 and items.max() > 1 << 24
    assert 0 <= items.min() and items.max() < 48_190_000
    assert facts["most_cells_a_user_a_tile"] <= 16


def test_row_table_is_the_rows_of_the_whole_view():
    from benchmarks.harness import reference_foldin_q8, reference_q8

    kw = dict(seed=3, scale=0.35)
    whole = reference_q8.DequantizedBlocks(700_000, 16, **kw)[0:700_000]
    ids = np.random.default_rng(0).integers(0, 700_000, 5000)
    table = reference_foldin_q8.RowTable(
        reference_q8.DequantizedBlocks(700_000, 16, reuse=True, threads=2,
                                       **kw), ids)
    assert table.reads == 2  # three blocks, two a read
    np.testing.assert_array_equal(table[ids], whole[ids])
    np.testing.assert_array_equal(table[ids[:7]], whole[ids[:7]])
    with pytest.raises(KeyError):
        table[np.setdiff1d(np.arange(10), ids)[:1]]
    with pytest.raises(IndexError):
        reference_foldin_q8.RowTable(
            reference_q8.FactorBlocks(100, 4, **kw), [100])
    # the float32 factors the codes were made from are another table
    factors = reference_foldin_q8.RowTable(
        reference_q8.FactorBlocks(700_000, 16, **kw), ids, blocks_a_read=1)
    assert factors.reads == 3
    gap = np.abs(factors[ids] - table[ids]).max()
    assert 0 < gap <= 0.175 / 127 / 2 * 1.001
