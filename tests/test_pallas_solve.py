"""Pallas Gauss-Jordan solve kernel: parity vs the Cholesky path (interpret
mode on CPU; the same kernel compiles for TPU VMEM tiles)."""

import numpy as np
import pytest

import jax.numpy as jnp

from cfk_tpu.config import ALSConfig
from cfk_tpu.models.als import train_als
from cfk_tpu.ops.pallas import gauss_solve_pallas
from cfk_tpu.ops.solve import batched_spd_solve, dispatch_spd_solve


def spd_batch(rng, e, k, ridge=0.5):
    m = rng.standard_normal((e, k, k)).astype(np.float32)
    a = np.einsum("eij,ekj->eik", m, m) + ridge * np.eye(k, dtype=np.float32)
    x = rng.standard_normal((e, k)).astype(np.float32)
    b = np.einsum("eij,ej->ei", a, x)
    return a, b, x


@pytest.mark.parametrize("k,e", [(5, 37), (8, 128), (16, 300), (64, 40)])
def test_gauss_matches_cholesky(rng, k, e):
    a, b, x_true = spd_batch(rng, e, k)
    chol = batched_spd_solve(jnp.asarray(a), jnp.asarray(b))
    gauss = gauss_solve_pallas(jnp.asarray(a.transpose(1, 2, 0)), jnp.asarray(b.T)).T
    np.testing.assert_allclose(gauss, chol, rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(gauss, x_true, rtol=5e-3, atol=5e-3)


def test_dispatch_solver(rng):
    a, b, _ = spd_batch(rng, 6, 50)
    c = dispatch_spd_solve(jnp.asarray(a), jnp.asarray(b), "cholesky")
    p = dispatch_spd_solve(jnp.asarray(a), jnp.asarray(b), "pallas")
    np.testing.assert_allclose(c, p, rtol=5e-3, atol=5e-3)
    with pytest.raises(ValueError, match="unknown solver"):
        dispatch_spd_solve(jnp.asarray(a), jnp.asarray(b), "qr")


def test_train_with_pallas_solver_matches(tiny_dataset):
    base = dict(rank=5, lam=0.05, num_iterations=3, seed=0)
    chol = train_als(tiny_dataset, ALSConfig(**base)).predict_dense()
    pall = train_als(tiny_dataset, ALSConfig(**base, solver="pallas")).predict_dense()
    np.testing.assert_allclose(pall, chol, rtol=1e-2, atol=1e-2)


def test_config_rejects_unknown_solver():
    with pytest.raises(ValueError, match="solver"):
        ALSConfig(solver="lu")


def test_rank_above_blocked_cap_falls_back_to_cholesky(rng):
    from cfk_tpu.ops.pallas import PALLAS_MAX_RANK, gauss_solve_pallas

    # Above 2·PALLAS_MAX_RANK even the blocked Schur path bows out; the
    # dispatcher must hand off to cholesky (bitwise-identical here, since
    # the fallback IS batched_spd_solve).
    k = 2 * PALLAS_MAX_RANK + 8
    a, b, _ = spd_batch(rng, 4, k)
    out = dispatch_spd_solve(jnp.asarray(a), jnp.asarray(b), "pallas")
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(batched_spd_solve(jnp.asarray(a), jnp.asarray(b))),
    )
    # ...while the kernels themselves refuse loudly.
    with pytest.raises(ValueError, match="rank"):
        gauss_solve_pallas(jnp.asarray(a.transpose(1, 2, 0)), jnp.asarray(b.T))


@pytest.mark.parametrize("k", [96, 128])
def test_blocked_schur_solve_matches_cholesky(k):
    """Ranks above PALLAS_MAX_RANK route through one level of blocked Schur
    elimination on the same kernels (interpret mode here; compiled coverage
    in tests/test_pallas_tpu.py)."""
    import jax.numpy as jnp

    from cfk_tpu.ops.solve import batched_spd_solve, dispatch_spd_solve

    rng = np.random.default_rng(k)
    e = 60
    x = rng.standard_normal((e, k, 12)).astype(np.float32)
    a = np.einsum("ekr,elr->ekl", x, x) + 8.0 * np.eye(k, dtype=np.float32)
    b = rng.standard_normal((e, k)).astype(np.float32)
    want = np.asarray(batched_spd_solve(jnp.asarray(a), jnp.asarray(b)))
    got = np.asarray(dispatch_spd_solve(jnp.asarray(a), jnp.asarray(b), "pallas"))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_multi_rhs_kernel_matches_loop():
    """gauss_solve_multi_pallas solves every RHS column like the single-RHS
    kernel does."""
    import jax.numpy as jnp

    from cfk_tpu.ops.pallas import gauss_solve_multi_pallas, gauss_solve_pallas

    rng = np.random.default_rng(1)
    k, m, e = 16, 5, 40
    x = rng.standard_normal((e, k, 8)).astype(np.float32)
    a = np.einsum("ekr,elr->ekl", x, x) + 4.0 * np.eye(k, dtype=np.float32)
    bs = rng.standard_normal((e, k, m)).astype(np.float32)
    al = jnp.asarray(np.transpose(a, (1, 2, 0)))
    got = np.asarray(
        gauss_solve_multi_pallas(al, jnp.asarray(np.transpose(bs, (1, 2, 0))))
    )
    for j in range(m):
        want = np.asarray(gauss_solve_pallas(al, jnp.asarray(bs[:, :, j].T)))
        np.testing.assert_allclose(got[:, j, :], want, rtol=1e-4, atol=1e-4)


def test_sharded_pallas_matches_single_device(tiny_coo):
    """The pallas solver under shard_map (both exchanges) must match the
    single-device cholesky reference — covers the vma-tagging branch."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.models.als import train_als
    from cfk_tpu.parallel.mesh import make_mesh
    from cfk_tpu.parallel.spmd import train_als_sharded

    ds1 = Dataset.from_coo(tiny_coo, num_shards=1)
    base = dict(rank=4, lam=0.05, num_iterations=2, seed=3)
    ref = train_als(ds1, ALSConfig(**base)).predict_dense()
    ds4 = Dataset.from_coo(tiny_coo, num_shards=4)
    mesh = make_mesh(4)
    for exchange in ("all_gather", "ring"):
        got = train_als_sharded(
            ds4,
            ALSConfig(**base, num_shards=4, exchange=exchange, solver="pallas"),
            mesh,
        ).predict_dense()
        np.testing.assert_allclose(got, ref, rtol=1e-2, atol=1e-2, err_msg=exchange)


# -- the lane-batched Cholesky (what solver="cholesky" runs on a TPU) --------

LAM = 0.05


def _normal_equations(rng, e, k, width=24):
    """ALS-WR systems as the fold-in makes them: A = sum f f^T + lam max(n,1) I
    over n rows of +-0.175, b = sum r f.  System 0 is all padding (n = 0:
    lam I against 0), system 1 sits at the ridge's floor (one row repeated:
    rank one + lam n I)."""
    f = rng.uniform(-0.175, 0.175, (e, width, k)).astype(np.float32)
    n = rng.integers(1, width + 1, size=e)
    n[0] = 0
    f[1] = f[1, :1]
    f *= (np.arange(width)[None, :, None] < n[:, None, None])
    r = rng.integers(1, 6, size=(e, width)).astype(np.float32)
    a = np.einsum("epk,epl->ekl", f, f)
    a += (LAM * np.maximum(n, 1))[:, None, None] * np.eye(k, dtype=np.float32)
    b = np.einsum("epk,ep->ek", f, r)
    return a.astype(np.float32), b.astype(np.float32)


@pytest.mark.parametrize("e", [8, 200, 256])
@pytest.mark.parametrize("k", [8, 64, 128])
def test_cholesky_lanes_matches_float64(k, e):
    """The kernel body under the Pallas interpreter against numpy's float64
    solve of the same systems: whole tiles, a ragged last tile (200) and
    fewer systems than lanes (8); and a system's bits are its own, whatever
    shares its tile."""
    from cfk_tpu.ops.pallas.solve_kernel import cholesky_solve_lanes

    rng = np.random.default_rng(1000 * k + e)
    a, b = _normal_equations(rng, e, k)
    got = np.asarray(cholesky_solve_lanes(jnp.asarray(a), jnp.asarray(b)))
    want = np.linalg.solve(a.astype(np.float64),
                           b.astype(np.float64)[..., None])[..., 0]
    assert got.shape == (e, k) and got.dtype == np.float32
    np.testing.assert_array_equal(got[0], 0.0)  # lam I x = 0
    err = np.abs(got - want) / np.maximum(np.abs(want), 1.0)
    assert err.max() < 1e-5, err.max()
    # other lanes, other neighbours, another tile count: the same bits
    order = rng.permutation(e)
    others, _ = _normal_equations(rng, 131, k)
    a2 = np.concatenate([others, a[order]])
    b2 = np.concatenate([np.ones((131, k), np.float32), b[order]])
    again = np.asarray(cholesky_solve_lanes(jnp.asarray(a2), jnp.asarray(b2)))
    np.testing.assert_array_equal(again[131:], got[order])


def _xla_cholesky_solve(a, b):
    import jax

    chol = jnp.linalg.cholesky(a)
    y = jax.lax.linalg.triangular_solve(
        chol, b[..., None], left_side=True, lower=True, transpose_a=False)
    return jax.lax.linalg.triangular_solve(
        chol, y, left_side=True, lower=True, transpose_a=True)[..., 0]


@pytest.mark.parametrize("case,backend,k,dtype,route", [
    ("cpu", "cpu", 128, "float32", "xla"),
    ("k136", "tpu", 136, "float32", "xla"),
    ("k12", "tpu", 12, "float32", "xla"),
    ("float64", "tpu", 8, "float64", "xla"),
    ("the_fold_in", "tpu", 128, "float32", "lanes"),
    ("rank8", "tpu", 8, "float32", "lanes"),
])
def test_batched_spd_solve_gate(monkeypatch, case, backend, k, dtype, route):
    """``batched_spd_solve`` adapts on what it sees of its input: only a TPU
    backend with float32 systems of k <= 128, k % 8 == 0 takes the lane
    kernel; everything else runs XLA's three calls, to the bit."""
    import jax

    from cfk_tpu.ops import solve
    from cfk_tpu.ops.pallas import solve_kernel

    calls = []

    def lanes(a, b):
        calls.append(a.shape)
        return _xla_cholesky_solve(a, b)

    monkeypatch.setattr(solve_kernel, "cholesky_solve_lanes", lanes)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with jax.enable_x64(dtype == "float64"):
        rng = np.random.default_rng(k)
        a, b = _normal_equations(rng, 6, k)
        a, b = jnp.asarray(a, dtype), jnp.asarray(b, dtype)
        assert a.dtype == dtype
        assert solve.spd_solve_route(a, b) == route
        # the span's name for it asks about float32 systems of this rank
        assert solve.solve_route("cholesky", k) == (
            route if dtype == "float32" else "lanes")
        got = np.asarray(solve.batched_spd_solve(a, b))
        want = np.asarray(_xla_cholesky_solve(a, b))
    assert calls == ([(6, k, k)] if route == "lanes" else [])
    np.testing.assert_array_equal(got, want)
    assert solve.solve_route("pallas", k) == "pallas"
