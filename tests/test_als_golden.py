"""Golden end-to-end test: tiny dataset at the reference's published config
(k=5, 7 iterations, λ=0.05) must reach MSE ≤ 0.27 — the reference reports
0.265 / RMSE 0.515 (the reference's README.md:207-211)."""

import numpy as np

from cfk_tpu.config import ALSConfig
from cfk_tpu.eval.metrics import mse_rmse_from_blocks
from cfk_tpu.eval.predict import load_prediction_csv, save_prediction_csv
from cfk_tpu.models.als import train_als


def test_tiny_golden_mse(tiny_dataset):
    config = ALSConfig(rank=5, lam=0.05, num_iterations=7, seed=0)
    model = train_als(tiny_dataset, config)
    preds = model.predict_dense()
    assert preds.shape == (302, 426)
    mse, rmse = mse_rmse_from_blocks(preds, tiny_dataset)
    # Reference: MSE 0.265. Allow slack for init-RNG differences.
    assert mse <= 0.27, f"tiny MSE {mse} above reference threshold"
    assert rmse <= 0.52


def test_factored_mse_matches_dense(tiny_dataset):
    """The chunked factor-space evaluator must agree with the dense-matrix
    path (it replaces it at scales where U·Mᵀ cannot be materialized)."""
    from cfk_tpu.eval.metrics import mse_rmse_from_model

    config = ALSConfig(rank=4, lam=0.05, num_iterations=3, seed=1)
    model = train_als(tiny_dataset, config)
    mse_d, rmse_d = mse_rmse_from_blocks(model.predict_dense(), tiny_dataset)
    mse_f, rmse_f = mse_rmse_from_model(model, tiny_dataset, chunk=1000)
    # f32 matmul vs f64-accumulated dot products round differently at ~1e-9
    assert abs(mse_d - mse_f) < 1e-7
    assert abs(rmse_d - rmse_f) < 1e-7


def test_prediction_csv_roundtrip(tiny_dataset, tmp_path):
    config = ALSConfig(rank=3, lam=0.05, num_iterations=2, seed=0)
    model = train_als(tiny_dataset, config)
    preds = model.predict_dense()
    path = save_prediction_csv(preds, str(tmp_path / "pred"))
    loaded = load_prediction_csv(path)
    assert loaded.shape == preds.shape
    np.testing.assert_allclose(loaded, preds, rtol=1e-6, atol=1e-6)
    # Header matches EJML dense-CSV so the reference's calculate_mse.py can read it.
    first = open(path).readline().split()
    assert first == ["302", "426", "real"]


def test_seed_determinism(tiny_dataset):
    config = ALSConfig(rank=4, lam=0.05, num_iterations=2, seed=7)
    p1 = train_als(tiny_dataset, config).predict_dense()
    p2 = train_als(tiny_dataset, config).predict_dense()
    np.testing.assert_array_equal(p1, p2)
