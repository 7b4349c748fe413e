"""Planted-factor quality validation (VERDICT r1 item #6).

The reference's RMSE bars need the real Netflix corpus, which this environment
cannot fetch (no egress).  Proxy: generate ratings from KNOWN low-rank
factors + Gaussian noise and assert the production at-scale pipeline
(tiled layout, bf16 factor storage, per-entity solves) recovers them —
held-out RMSE must approach the noise floor σ.  Held-out cells exclude
every (user, movie) pair seen in training (Zipf-hot pairs collide), which
skews them cold — the conservative direction.  Calibration at this shape:
converged recovery reaches ≈1.50σ (finite-data estimation error over the
cold held-out pairs); an undertrained/broken pipeline sits at the
zero-predictor level ≈5.5σ, so the 1.7σ bound discriminates sharply.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from cfk_tpu.config import ALSConfig
from cfk_tpu.data.blocks import Dataset
from cfk_tpu.data.synthetic import planted_factor_coo
from cfk_tpu.eval.metrics import mse_rmse_heldout
from cfk_tpu.models.als import train_als

NOISE = 0.2


@pytest.fixture(scope="module")
def planted():
    train, held = planted_factor_coo(
        2000, 300, 150_000, rank=16, noise=NOISE, heldout=10_000, seed=0
    )
    return train, held


def test_planted_recovery_production_config(planted):
    train, held = planted
    ds = Dataset.from_coo(train, layout="tiled")
    cfg = ALSConfig(rank=16, lam=0.005, num_iterations=10, seed=0,
                    layout="tiled", dtype="bfloat16")
    model = train_als(ds, cfg)
    _, rmse, n = mse_rmse_heldout(model, ds, held)
    assert n > 3000  # enough fresh (collision-free) cells survive
    assert rmse < 1.7 * NOISE, (
        f"held-out RMSE {rmse:.4f} vs noise floor {NOISE} — the at-scale "
        "pipeline failed to recover the planted factors"
    )


def test_planted_recovery_sharded_ring(planted):
    """The same recovery bound through 4-way ring SPMD — quality of the
    full distributed at-scale path."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import train_als_sharded

    train, held = planted
    ds = Dataset.from_coo(train, layout="tiled", num_shards=4, ring=True,
                          ring_warn=False)
    cfg = ALSConfig(rank=16, lam=0.005, num_iterations=10, seed=0,
                    layout="tiled", dtype="bfloat16", num_shards=4,
                    exchange="ring")
    model = train_als_sharded(ds, cfg, make_mesh(4))
    _, rmse, _ = mse_rmse_heldout(model, ds, held)
    assert rmse < 1.7 * NOISE


def test_undertrained_fails_the_bound(planted):
    """One iteration must NOT pass — the bound actually measures recovery."""
    train, held = planted
    ds = Dataset.from_coo(train, layout="tiled")
    cfg = ALSConfig(rank=16, lam=0.005, num_iterations=1, seed=0,
                    layout="tiled", dtype="bfloat16")
    _, rmse, _ = mse_rmse_heldout(train_als(ds, cfg), ds, held)
    assert rmse > 1.7 * NOISE
