"""MSE / RMSE evaluation.

In-process equivalent of the reference's offline evaluator
(``scripts/calculate_mse.py:78-91``): mean squared error over the observed
(nonzero) rating cells only, against the dense prediction matrix whose rows
are users ascending by id and columns movies ascending by id.
"""

from __future__ import annotations

import math

import numpy as np

from cfk_tpu.data.blocks import Dataset


def mse_rmse(
    predictions: np.ndarray,  # [num_users, num_movies]
    user_dense: np.ndarray,  # [nnz] dense user indices
    movie_dense: np.ndarray,  # [nnz] dense movie indices
    rating: np.ndarray,  # [nnz]
) -> tuple[float, float]:
    """MSE/RMSE over observed ratings (vectorized; no dense ratings matrix)."""
    pred = predictions[user_dense, movie_dense]
    se = float(np.sum((rating.astype(np.float64) - pred.astype(np.float64)) ** 2))
    mse = se / rating.shape[0]
    return mse, math.sqrt(mse)


def mse_rmse_from_blocks(predictions: np.ndarray, dataset: Dataset) -> tuple[float, float]:
    return mse_rmse(
        predictions,
        dataset.coo_dense.user_raw,
        dataset.coo_dense.movie_raw,
        dataset.coo_dense.rating,
    )


def mse_rmse_heldout(
    model, dataset, held, chunk: int = 1 << 22
) -> tuple[float, float, int]:
    """(MSE, RMSE, cells evaluated) on held-out raw-id cells.

    ``held`` is a RatingsCOO with RAW external ids; cells whose user or
    movie never appeared in training (no dense index) are dropped — their
    factors don't exist.  Streams factor-space dot products like
    ``mse_rmse_from_model``.  Used by the planted-factor quality
    validation (tests/test_planted.py).
    """
    u, m = model.host_factors()
    um, mm = dataset.user_map, dataset.movie_map
    u_idx = np.searchsorted(um.raw_ids, held.user_raw)
    m_idx = np.searchsorted(mm.raw_ids, held.movie_raw)
    u_idx = np.minimum(u_idx, um.num_entities - 1)
    m_idx = np.minimum(m_idx, mm.num_entities - 1)
    known = (um.raw_ids[u_idx] == held.user_raw) & (
        mm.raw_ids[m_idx] == held.movie_raw
    )
    ud, md, r = u_idx[known], m_idx[known], held.rating[known]
    se = 0.0
    for lo in range(0, r.shape[0], chunk):
        sl = slice(lo, lo + chunk)
        pred = np.einsum("nk,nk->n", u[ud[sl]], m[md[sl]], dtype=np.float64)
        se += float(np.sum((r[sl].astype(np.float64) - pred) ** 2))
    n = int(r.shape[0])
    mse = se / max(n, 1)
    return mse, math.sqrt(mse), n


def mse_rmse_from_model(model, dataset: Dataset, chunk: int = 1 << 22) -> tuple[float, float]:
    """MSE/RMSE straight from the factor matrices, never materializing P.

    Predictions at the observed cells are per-row dot products
    ``Σ_k U[u,k]·M[m,k]`` streamed in nnz chunks — O(chunk·k) memory, so it
    works at full-Netflix scale where the dense U·Mᵀ matrix
    (``ALSModel.predict_dense``) would be hundreds of GB.
    """
    u, m = model.host_factors()
    ud = dataset.coo_dense.user_raw
    md = dataset.coo_dense.movie_raw
    r = dataset.coo_dense.rating
    se = 0.0
    for lo in range(0, r.shape[0], chunk):
        sl = slice(lo, lo + chunk)
        pred = np.einsum(
            "nk,nk->n", u[ud[sl]], m[md[sl]], dtype=np.float64
        )
        se += float(np.sum((r[sl].astype(np.float64) - pred) ** 2))
    mse = se / max(r.shape[0], 1)
    return mse, math.sqrt(mse)
