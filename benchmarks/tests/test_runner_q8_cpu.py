"""The row-block runner end to end at a toy cell on the CPU (``toy_q8/``: an
int8 table handed to the engine as a row reader): it runs, a traced run reads
the upload span and the scan counter, the controls each make ``correct``
false, a program whose engine takes its table whole is refused before any data
is made; the lazy tables against ``datagen.factor_table`` and the written
rule; the new reader on made-up spans."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import datagen, reference_blocks, reference_q8

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_q8")
MANIFEST = os.path.join(TOY, "BENCHMARK.json")
CELL = "toy-serve-q8.serve-q8"


def drive(capsys, *, trace=0, seed=3_000_000_019, manifest=MANIFEST):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace), "--manifest", manifest],
                  require_tpu=False)
    out = capsys.readouterr().out
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), out


def _with_config(tmp_path, edit):
    root = tmp_path / "toy_q8"
    shutil.copytree(TOY, root)
    path = root / "configs" / "toy-serve-q8.json"
    cfg = json.loads(path.read_text())
    edit(cfg)
    path.write_text(json.dumps(cfg))
    return str(root / "BENCHMARK.json")


def test_q8_toy_cell_runs_and_is_correct(capsys):
    res, out = drive(capsys)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_req_per_s", "setup_s"}
    assert res["metrics"]["serve_req_per_s"]["value"] == pytest.approx(150, rel=0.1)
    assert res["device"]["count"] == 1
    # 3,000 rows pad to 47 x 64: codes and a float32 scale a row
    assert ("items read in row blocks (table_dtype=int8, tile_m=64, 3008 table "
            "rows = 48,128 B of codes + 12,032 B of scales)") in out
    assert "over the dequantized table" in out
    assert out.count("against limit") >= 5
    assert res["checks"]["score_err"]["value"] <= 2e-6


def test_traced_run_reads_the_upload_span_and_the_scan_counter(capsys):
    res, _ = drive(capsys, trace=1)
    # no TPU plane in a CPU trace: the roofline's reader says nothing
    assert set(res["metrics"]) == {"setup_table_upload_s",
                                   "serve_batch_size.toy", "serve_scan_gb.toy"}
    assert 0 < res["metrics"]["setup_table_upload_s"]["value"] < 30
    assert res["metrics"]["serve_scan_gb.toy"]["value"] == pytest.approx(
        (48_128 + 12_032) / 1e9)


def test_control_one_pass_bfloat16_fails_the_topk_check(capsys, tmp_path,
                                                        monkeypatch):
    """The arithmetic under the stated one, switched on from outside the
    program: the dequantized tile through a one-pass bfloat16 matmul."""
    import jax.numpy as jnp

    from cfk_tpu.serving import topk_kernel

    real = topk_kernel.serve_compute_dtype
    monkeypatch.setattr(
        topk_kernel, "serve_compute_dtype",
        lambda dtype: (jnp.bfloat16, None) if dtype == jnp.int8
        else real(dtype))
    # another tile height: a trace of its own, not the sound run's
    manifest = _with_config(tmp_path, lambda c: c["engine"].update(tile_m=32))
    res, out = drive(capsys, manifest=manifest)
    assert res["correct"] is False
    assert any("FAILED" in l for l in out.splitlines() if "check score_err" in l)
    assert any("-> ok" in l for l in out.splitlines()
               if "check invalid_id_sets" in l)


def test_float32_answers_fail_against_the_dequantized_reference(capsys,
                                                                tmp_path):
    """The guarantee is on the dequantized view: a program that served the
    float32 table's top-K is as wrong as one that rounded too much."""
    manifest = _with_config(tmp_path, lambda c: c.update(table_dtype="float32"))
    res, out = drive(capsys, manifest=manifest)
    assert res["correct"] is False
    assert any("FAILED" in l for l in out.splitlines() if "check score_err" in l)


def test_served_answer_altered_where_it_is_produced(capsys, monkeypatch):
    from cfk_tpu.serving.engine import ServeEngine

    real = ServeEngine.topk

    def second_best(self, rows, k, **kw):
        vals, ids = real(self, rows, k, **kw)
        ids = ids.copy()
        ids[:, 0] = ids[:, 1]
        return vals, ids

    monkeypatch.setattr(ServeEngine, "topk", second_best)
    res, out = drive(capsys)
    assert res["correct"] is False
    assert "check invalid_id_sets" in out


def test_a_program_that_takes_its_table_whole_is_refused_before_any_data(
        capsys, monkeypatch):
    """The parent commit's engine has no row reader: the runner exits at
    once, with no result line, before a seen list or a factor is made."""
    from cfk_tpu.serving import engine as engine_mod

    from benchmarks.harness import seen_blocks

    monkeypatch.delattr(engine_mod, "row_reader")
    monkeypatch.setattr(datagen, "factor_table", lambda *a, **kw: 1 / 0)
    monkeypatch.setattr(seen_blocks, "seen_lists_blocks",
                        lambda *a, **kw: 1 / 0)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "1",
                  "--manifest", MANIFEST], require_tpu=False)
    assert "takes its item table whole" in str(e.value)
    assert not any(l.startswith("{") for l in capsys.readouterr().out.splitlines())


def test_the_sweep_tool_loads_the_row_block_runner(capsys):
    sweep = run.load_module(os.path.join(run.HERE, "tools", "sweep.py"),
                            "sweep_under_test")
    assert sweep.main(["--cpu", "--manifest", MANIFEST, "--workload", CELL,
                       "--seconds", "1", "--rates", "100"]) == 0
    out = capsys.readouterr().out
    assert "items read in row blocks" in out
    rows = [json.loads(l.split(" ", 1)[1]) for l in out.splitlines()
            if l.startswith("SWEEP_ROW ")]
    assert [r["offered_req_per_s"] for r in rows] == [100.0]
    assert rows[0]["new_traces"] == 0


# -- the lazy tables against the whole-array generator and the rule -----------

@pytest.mark.parametrize("threads", [1, 3])
def test_factor_blocks_are_datagens_table_a_row_range_at_a_time(threads):
    rows, rank = 2 * reference_q8.BLOCK + 1000, 4  # three blocks, the last short
    whole = datagen.factor_table(rows, rank, seed=11, scale=0.35, threads=2)
    lazy = reference_q8.FactorBlocks(rows, rank, seed=11, scale=0.35,
                                     threads=threads)
    assert lazy.shape == whole.shape
    b = reference_q8.BLOCK
    for lo, hi in ((0, rows), (0, 10), (b - 3, b + 5), (b, 2 * b),
                   (2 * b - 1, rows), (rows - 7, rows), (5, 5)):
        got = lazy[lo:hi]
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, whole[lo:hi])
    np.testing.assert_array_equal(lazy[rows - 5:rows + 50], whole[rows - 5:])
    other = reference_q8.FactorBlocks(rows, rank, seed=12, scale=0.35)
    assert not np.array_equal(other[0:10], whole[0:10])
    with pytest.raises(IndexError):
        lazy[0:10:2]


def test_dequantized_blocks_are_the_rule_and_the_programs_codes():
    from cfk_tpu.ops.quant import quantize_rows_host

    rows, rank = reference_q8.BLOCK + 777, 8
    whole = datagen.factor_table(rows, rank, seed=21, scale=0.35, threads=2)
    codes, scales = reference_q8.quantize_rows(whole)
    # the reference imports nothing of the program; the program's host
    # quantizer computes the same rule to the bit
    got_codes, got_scales = quantize_rows_host(whole, threads=2)
    np.testing.assert_array_equal(got_codes, codes)
    np.testing.assert_array_equal(got_scales, scales)
    assert codes.dtype == np.int8 and np.abs(codes).max() == 127
    view = reference_q8.dequantize_rows(codes, scales)
    assert 1e-4 < np.abs(view - whole).max() < 0.175 / 127  # half a step
    in_place = whole.copy()
    assert reference_q8.round_to_the_view(in_place) is None
    np.testing.assert_array_equal(in_place, view)
    lazy = reference_q8.DequantizedBlocks(rows, rank, seed=21, scale=0.35,
                                          threads=2)
    np.testing.assert_array_equal(lazy[0:rows], view)
    b = reference_q8.BLOCK
    np.testing.assert_array_equal(lazy[b - 9:b + 9], view[b - 9:b + 9])
    zero = np.zeros((2, rank), np.float32)
    assert reference_q8.quantize_rows(zero)[1].tolist() == [1.0, 1.0]
    # the blockwise reference runs over the lazy view unedited
    u = datagen.factor_table(5, rank, seed=3, scale=0.35, threads=1)
    seen = [np.array([1, 2, 3])] * 5
    want = reference_blocks.exact_topk_blocks(u, view, seen, 10, block=b)
    got = reference_blocks.exact_topk_blocks(u, lazy, seen, 10, block=3 * b)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[0], want[0])


# -- the new reader, on spans made up for it ----------------------------------

def test_scan_reader_takes_the_median_and_says_nothing_without_the_count():
    reader = run.load_module(
        os.path.join(run.HERE, "layer_metrics", "serve_scan_gb.py"), "r_scan")

    class Ctx:
        program_spans = [
            {"name": "serve/batch/compute", "args": {"scan_bytes": s}}
            for s in (6_361_141_248, 6_361_141_248, 4_787_273_728)
        ] + [{"name": "serve/batch/upload", "args": {"bytes": 7}}]

    assert reader.read(Ctx, "serve_scan_gb.saturate") == pytest.approx(6.361141248)
    Ctx.program_spans = [{"name": "serve/batch/compute", "args": {"n": 3}}]
    assert reader.read(Ctx, "serve_scan_gb.saturate") is None


def test_the_cells_files_state_what_the_issue_fixed():
    with open(os.path.join(run.ROOT, "benchmarks", "configs",
                           "amazon23-serve-r128-int8.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(run.ROOT, "benchmarks", "configs",
                           "amazon23-serve-r128.json")) as f:
        x4 = json.load(f)
    # the same data set and law; only the host's user rows are cut (PR 32:
    # the one-chip machine's 40 GiB host), and the file says so
    for key in ("items", "ratings", "corpus_seed", "seen_lists", "rank",
                "factor_scale"):
        assert cfg[key] == x4[key], key
    assert cfg["published"] == {k: x4[k] for k in ("users", "items", "ratings")}
    assert cfg["reduced"] == ["users"] and "users" in cfg["reduced_why"]
    assert cfg["users"] == 40_000_000 < x4["users"]
    assert cfg["table_dtype"] == "int8"
    assert cfg["engine"] == {"tile_m": 512, "batch_quantum": 8}
    assert any("dequantized table" in g and "no arithmetic narrower than "
               "float32 after dequantization" in g for g in cfg["guarantees"])
    with open(os.path.join(run.ROOT, "benchmarks", "traffic",
                           "serve-saturate-int8.json")) as f:
        mix = json.load(f)
    assert mix["runner"] == "serve_blocks" and mix["max_batch"] == 256
    assert (mix["k"], mix["drain_seconds"], mix["trace_seconds"]) == (10, 40, 8)


def test_seen_lists_share_the_four_chip_cells_cache(tmp_path):
    """Same key, same bytes as ``serve_x4._seen`` whichever runner built
    them: the lists do not depend on the thread count."""
    from benchmarks.runners import serve_blocks, serve_x4

    class Ctx:
        cache_dir = str(tmp_path)
        said = []

        def say(self, msg):
            self.said.append(msg)

    with open(os.path.join(TOY, "configs", "toy-serve-q8.json")) as f:
        config = json.load(f)
    built = serve_blocks._seen(Ctx(), config)
    loaded = serve_x4._seen(Ctx(), config)  # finds the other runner's cache
    assert "cache hit" in Ctx.said[-1] and "built" in Ctx.said[0]
    for a, b in zip(built, loaded):
        np.testing.assert_array_equal(a, b)
    os.remove(os.path.join(tmp_path, os.listdir(tmp_path)[0]))
    for name in os.listdir(tmp_path):
        os.remove(os.path.join(tmp_path, name))
    again = serve_x4._seen(Ctx(), config)  # built by the x4 runner itself
    for a, b in zip(built, again):
        np.testing.assert_array_equal(a, b)
