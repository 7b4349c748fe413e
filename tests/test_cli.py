"""CLI tests: reference-compatible run form, flag form, and the evaluator."""

import json

import numpy as np
import pytest

from cfk_tpu.cli import main

TINY = "/root/reference/data/data_sample_tiny.txt"


@pytest.mark.reference_data
def test_run_reference_form(capsys, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)  # predictions/ lands under tmp
    rc = main(["run", "4", "5", "0.05", "7", TINY, "426", "302"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "MSE:" in out and "RMSE:" in out
    mse = float(out.split("MSE:")[1].split()[0])
    assert mse <= 0.30


@pytest.mark.reference_data
def test_run_warns_on_wrong_counts(capsys, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    rc = main(["run", "4", "3", "0.05", "1", TINY, "9999", "1"])
    assert rc == 0
    err = capsys.readouterr().err
    assert "warning: NUM_MOVIES=9999" in err
    assert "warning: NUM_USERS=1" in err


@pytest.mark.reference_data
def test_train_and_evaluate_roundtrip(capsys, tmp_path):
    pred = str(tmp_path / "pred.csv")
    rc = main([
        "train", "--data", TINY, "--rank", "5", "--lam", "0.05",
        "--iterations", "7", "--seed", "0", "--output", pred,
        "--metrics", "json",
    ])
    assert rc == 0
    captured = capsys.readouterr()
    metrics = json.loads(captured.out.strip().splitlines()[-1])
    assert metrics["gauges"]["mse"] <= 0.27
    assert metrics["counters"]["iterations"] == 7
    assert metrics["phase_seconds"]["train"] > 0

    rc = main(["evaluate", TINY, pred])
    assert rc == 0
    out = capsys.readouterr().out
    mse = float(out.split("MSE:")[1].split()[0])
    assert mse <= 0.27


@pytest.mark.reference_data
def test_auto_layout_resolution(capsys, monkeypatch):
    """--layout auto (the default): padded below the threshold, tiled
    above, and ring/auto exchanges force tiled up front."""
    import cfk_tpu.cli as cli

    class _Coo:
        num_ratings = 100

    assert cli._resolve_auto_layout(_Coo()) == "padded"
    _Coo.num_ratings = cli.AUTO_LAYOUT_TILED_NNZ
    assert cli._resolve_auto_layout(_Coo()) == "tiled"
    # End-to-end: tiny data under auto trains on the padded path and the
    # resolved layout reaches the config (no 'auto' leaks into ALSConfig).
    rc = main(["train", "--data", TINY, "--rank", "3", "--iterations", "2",
               "--seed", "0", "--output", "none"])
    assert rc == 0
    # Forcing the threshold to 0 makes the same data resolve to tiled.
    monkeypatch.setattr(cli, "AUTO_LAYOUT_TILED_NNZ", 0)
    rc = main(["train", "--data", TINY, "--rank", "3", "--iterations", "2",
               "--seed", "0", "--output", "none", "--chunk-elems", "4096"])
    assert rc == 0


@pytest.mark.reference_data
def test_train_survives_unmaterializable_dense_preds(capsys, tmp_path, monkeypatch):
    """At Netflix-Prize scales the dense U·Mᵀ cannot exist; training must still
    finish, report factored train MSE, and only skip the CSV dump."""
    from cfk_tpu.models.als import ALSModel

    def boom(self, *, allow_huge=False):
        raise ValueError("dense prediction matrix would be huge")

    monkeypatch.setattr(ALSModel, "predict_dense", boom)
    rc = main([
        "train", "--data", TINY, "--rank", "3", "--lam", "0.05",
        "--iterations", "2", "--seed", "0",
        "--output", str(tmp_path / "pred.csv"), "--metrics", "json",
    ])
    assert rc == 0
    captured = capsys.readouterr()
    assert "skipping the prediction CSV dump" in captured.err
    assert "RMSE=" in captured.err  # factored MSE eval still ran
    metrics = json.loads(captured.out.strip().splitlines()[-1])
    assert "mse" in metrics["gauges"]


@pytest.mark.reference_data
def test_checkpoint_journal_bad_tcp_url(capsys, tmp_path):
    """A malformed tcp journal target must be a clean flag error, not a
    traceback deep in training."""
    rc = main([
        "train", "--data", TINY, "--rank", "3", "--iterations", "1",
        "--checkpoint-journal", "tcp://nohost", "--output", "none",
    ])
    assert rc == 2
    assert "bad broker url" in capsys.readouterr().err


@pytest.mark.reference_data
def test_checkpoint_journal_conflicts_with_dir(capsys, tmp_path):
    rc = main([
        "train", "--data", TINY, "--rank", "3", "--iterations", "1",
        "--checkpoint-dir", str(tmp_path / "a"),
        "--checkpoint-journal", str(tmp_path / "b"), "--output", "none",
    ])
    assert rc == 2
    assert "mutually exclusive" in capsys.readouterr().err


@pytest.mark.reference_data
def test_evaluate_shape_mismatch(capsys, tmp_path):
    bad = tmp_path / "bad.csv"
    bad.write_text("2 3 real\n1 2 3\n4 5 6\n")
    rc = main(["evaluate", TINY, str(bad)])
    assert rc == 2
    assert "prediction matrix is" in capsys.readouterr().err


@pytest.mark.reference_data
def test_predict_from_checkpoint(capsys, tmp_path):
    """train --checkpoint-dir, then predict + evaluate without retraining:
    the standalone dump must score identically to the train-time metrics."""
    import re

    from cfk_tpu.cli import main

    data = "/root/reference/data/data_sample_tiny.txt"
    ck = str(tmp_path / "ck")
    assert main([
        "train", "--data", data, "--rank", "4", "--iterations", "2",
        "--seed", "0", "--checkpoint-dir", ck, "--output", "none",
    ]) == 0
    rmse_train = re.search(r"RMSE=([0-9.]+)", capsys.readouterr().err).group(1)
    pred = str(tmp_path / "pred.csv")
    assert main(["predict", "--checkpoint-dir", ck, "--data", data,
                 "--output", pred]) == 0
    assert "iteration-2 checkpoint" in capsys.readouterr().err
    assert main(["evaluate", data, pred]) == 0
    rmse_eval = re.search(r"RMSE: ([0-9.]+)", capsys.readouterr().out).group(1)
    assert abs(float(rmse_train) - float(rmse_eval)) < 1e-3
    # wrong data for the checkpoint fails loudly
    assert main(["predict", "--checkpoint-dir", ck, "--data",
                 "/root/reference/data/data_sample_medium.txt",
                 "--output", str(tmp_path / "x.csv")]) == 1
    assert "smaller than the data implies" in capsys.readouterr().err


@pytest.mark.reference_data
def test_train_implicit_eval_ranking(capsys, tmp_path):
    from cfk_tpu.cli import main

    rc = main([
        "train", "--data", "/root/reference/data/data_sample_tiny.txt",
        "--implicit", "--rank", "8", "--alpha", "2", "--iterations", "4",
        "--seed", "0", "--eval-ranking", "10", "--output", "none",
        "--metrics", "json",
    ])
    assert rc == 0
    out = capsys.readouterr()
    assert "recall_at_10" in out.out and "mpr" in out.out
    assert "leave-one-out Recall@10=" in out.err
    # explicit model refuses the flag
    assert main([
        "train", "--data", "/root/reference/data/data_sample_tiny.txt",
        "--rank", "8", "--iterations", "1", "--eval-ranking", "5",
        "--output", "none",
    ]) == 1
    assert "requires --implicit" in capsys.readouterr().err


@pytest.mark.reference_data
def test_train_implicit(capsys, tmp_path):
    rc = main([
        "train", "--data", TINY, "--implicit", "--rank", "4",
        "--lam", "0.1", "--alpha", "5", "--iterations", "2",
        "--output", "none",
    ])
    assert rc == 0


@pytest.mark.reference_data
def test_train_with_checkpointing(capsys, tmp_path):
    ck = str(tmp_path / "ck")
    args = [
        "train", "--data", TINY, "--rank", "3", "--iterations", "3",
        "--seed", "1", "--checkpoint-dir", ck, "--output", "none",
        "--metrics", "json",
    ]
    assert main(args) == 0
    metrics = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert metrics["counters"]["checkpoints"] == 3
    # Re-run: resumes at 3, no new iterations.
    assert main(args) == 0
    metrics = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert metrics["counters"].get("iterations", 0) == 0
