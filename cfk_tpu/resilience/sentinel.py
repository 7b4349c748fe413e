"""Numerical-health sentinel: cheap on-device probes over the factor state.

A diverged ALS run is cheap to detect and expensive to miss: one NaN in a
factor row poisons every Gram that row touches on the next half-iteration,
so by the time the final RMSE is computed the whole model is garbage.  The
probes here are O(E·k) reductions — two ``isfinite`` all-reduces and two
max-row-norm watchdogs over U/M — against the iteration's O(nnz·k + E·k²)
solve work (what they cost on the chip is not measured).

Two consumption modes, one probe:

- **in-carry** (fused ``fori_loop`` paths, ``fold_probe``): the probe word
  rides the loop carry as an int32 pair ``[first_bad_iter, reasons]``;
  the host inspects it once after the loop.
- **stepped** (checkpointed / SPMD loops, ``probe_word``): the jitted word
  is fetched on the ``health_check_every`` cadence; the reductions run on
  sharded arrays unchanged (XLA inserts the collectives).

Reason bits compose, so one word carries every tripped condition.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

# Reason bits of the probe word (compose with |).
NONFINITE_U = 1  # NaN/Inf in the user factors
NONFINITE_M = 2  # NaN/Inf in the movie factors
NORM_U = 4  # a user factor row's 2-norm exceeded the watchdog limit
NORM_M = 8  # a movie factor row's 2-norm exceeded the watchdog limit
RING_EXCHANGE = 16  # a ring-rotated factor block went non-finite in flight

_REASONS = {
    NONFINITE_U: "nonfinite_user_factors",
    NONFINITE_M: "nonfinite_movie_factors",
    NORM_U: "user_norm_watchdog",
    NORM_M: "movie_norm_watchdog",
    RING_EXCHANGE: "ring_exchange_corruption",
}


def describe_word(word: int) -> list[str]:
    """Human-readable reasons for a tripped probe word."""
    return [name for bit, name in _REASONS.items() if word & bit]


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """Sentinel knobs resolved from ``ALSConfig`` (``health_from_config``)."""

    every: int = 1  # evaluate the probe every N completed iterations
    norm_limit: float = 1e6  # max factor-row 2-norm before the watchdog trips


@dataclasses.dataclass(frozen=True)
class HealthReport:
    """Host-side diagnostic for one sentinel trip (or a clean run)."""

    iteration: int  # first iteration whose probe tripped; -1 = healthy
    word: int  # reason bitmask (0 = healthy)
    stats: dict  # max row norms etc. at detection time (may be empty)

    @property
    def healthy(self) -> bool:
        return self.word == 0

    @property
    def reasons(self) -> list[str]:
        return describe_word(self.word)

    def summary(self) -> str:
        if self.healthy:
            return "healthy"
        parts = ",".join(self.reasons)
        return f"iteration {self.iteration}: {parts}"


def health_from_config(config) -> HealthConfig | None:
    """The sentinel config an ``ALSConfig`` selects, or None when off."""
    every = getattr(config, "health_check_every", None)
    if every is None:
        return None
    return HealthConfig(
        every=every, norm_limit=config.health_norm_limit
    )


def probe_word(u: jax.Array, m: jax.Array, norm_limit: float) -> jax.Array:
    """int32 reason bitmask over the factor pair; 0 = healthy.

    Pure jnp reductions — jit/shard-map/fori-loop safe, and correct on
    row-sharded global arrays (the all-reduce is XLA's problem).  The norm
    watchdog compares squared row norms so no sqrt is paid; an Inf row
    trips both its non-finite bit and its norm bit, which is fine — bits
    compose.
    """
    return (side_word(u, norm_limit, NONFINITE_U, NORM_U)
            | side_word(m, norm_limit, NONFINITE_M, NORM_M))


def side_word(x, norm_limit, nonfinite_bit: int, norm_bit: int,
              rows=None) -> jax.Array:
    """One side of ``probe_word``: the two bits of one factor array, over
    all of its rows or over its first ``rows`` (a traced count: a padded
    solve's trailing rows are nobody's factors).  ``x`` may be the pair a
    serving engine holds its table as, ``(data, scale or None)``: the rows
    probed are ``data x scale``, their norms taken from the codes' own
    sums (one pass over the table as stored, no float32 copy of it)."""
    x, scale = x if isinstance(x, tuple) else (x, None)
    xf = x.astype(jnp.float32)
    if rows is not None:
        xf = jnp.where(jnp.arange(xf.shape[0])[:, None] < rows, xf, 0.0)
    limit_sq = jnp.asarray(norm_limit, jnp.float32) ** 2
    finite = jnp.all(jnp.isfinite(xf))
    row_sq = jnp.sum(jnp.square(xf), axis=-1)
    if scale is not None:
        finite &= jnp.all(jnp.isfinite(scale))
        row_sq = row_sq * jnp.square(scale)
    norm_sq = jnp.max(row_sq)
    w = jnp.where(finite, jnp.int32(0), jnp.int32(nonfinite_bit))
    return w | jnp.where(norm_sq > limit_sq, jnp.int32(norm_bit), jnp.int32(0))


@jax.jit
def health_stats(u: jax.Array, m: jax.Array) -> jax.Array:
    """[max_row_norm_u, max_row_norm_m] float32 — the diagnostic detail a
    tripped probe's report carries (one fetch, two scalars)."""
    row_norm = lambda x: jnp.sqrt(
        jnp.max(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1))
    )
    return jnp.stack([row_norm(u), row_norm(m)])


def carry_init() -> jax.Array:
    """Fresh in-carry health word: ``[first_bad_iter=-1, reasons=0]``."""
    return jnp.array([-1, 0], jnp.int32)


def fold_probe(
    hw: jax.Array,
    i,
    u: jax.Array,
    m: jax.Array,
    *,
    every: int,
    norm_limit: float,
    total: int | None = None,
) -> jax.Array:
    """Fold one iteration's probe into the carried health word.

    Evaluates the probe only on the ``every`` cadence and only while the
    word is still clean (``lax.cond`` skips the reductions entirely on
    off-cadence iterations — the near-zero-overhead contract).  ``i`` is
    the zero-based iteration index; cadence counts completed iterations,
    matching the stepped loops.  Pass the loop's ``total`` iteration
    count so the FINAL iteration is always probed even when ``total`` is
    not a multiple of ``every`` — the returned state must never dodge
    the sentinel (the stepped loops force the same final probe).
    """
    due = ((i + 1) % every == 0) & (hw[0] < 0)
    if total is not None:
        due = due | ((i + 1 == total) & (hw[0] < 0))

    def check(hw):
        w = probe_word(u, m, norm_limit)
        tripped = w > 0
        return jnp.where(
            tripped,
            jnp.stack([jnp.int32(i), w]),
            hw,
        )

    return lax.cond(due, check, lambda hw: hw, hw)


def report_from_carry(hw, u=None, m=None) -> HealthReport:
    """Host-side report from a fetched in-carry word (and optional factor
    stats when the caller still holds the device arrays)."""
    import numpy as np

    hw = np.asarray(hw)
    it, word = int(hw[0]), int(hw[1])
    stats = {}
    if word and u is not None and m is not None:
        nu, nm = (float(x) for x in np.asarray(health_stats(u, m)))
        stats = {"max_row_norm_u": nu, "max_row_norm_m": nm}
    return HealthReport(iteration=it if word else -1, word=word, stats=stats)
