"""Autotune: measure the plans nearest the model's optimum, cache the winner.

The cost model ranks; hardware decides.  ``autotune`` takes the model's
top ``top_n`` candidates (always including the legacy-default "pinned"
plan, so the tuned winner can never be worse than the pre-planner
behavior on the measured workload), times each with the injected
``measure(plan) -> seconds`` callable on a TRIMMED workload, and persists
the winner in a JSON store keyed by

    (shape-class, device fingerprint, cfk_tpu version)

— a stale key on any axis (new problem scale, different chip/count, code
upgrade) is a MISS, never a silently-wrong hit.  Plan provenance records
model-estimated and measured cost plus hit/miss so a regression is
attributable to the decision.

Measurement is always opt-in: trainers consult the cache but never
measure (warm it offline with ``cfk_tpu plan --autotune``).
"""

from __future__ import annotations

import json
import os
import tempfile
import time
import zlib

from cfk_tpu.plan.cost import plan_cost
from cfk_tpu.plan.spec import (
    PLAN_FIELDS,
    PLAN_FIELDSET_VERSION,
    DeviceSpec,
    ExecutionPlan,
    PlanConstraints,
    PlanProvenance,
    ProblemShape,
)

_SCHEMA = 1
DEFAULT_CACHE_PATH = os.path.join(
    os.path.expanduser("~"), ".cache", "cfk_tpu", "plan_cache.json"
)


def cache_key(shape: ProblemShape, device: DeviceSpec,
              constraints: PlanConstraints | None = None) -> str:
    from cfk_tpu import __version__

    # The PLAN-FIELD SET is part of the key (ISSUE 11): a winner tuned
    # before a new plan field existed (e.g. offload_tier) carries no
    # decision for it, so it must read as a MISS — not silently resolve
    # the new knob to whatever from_dict would default.  crc of the
    # sorted field names: stable per schema, changes with any field add.
    # PLAN_FIELDSET_VERSION folds in semantic changes to EXISTING fields
    # (ISSUE 19: bucketed × host_window became resolvable) so winners
    # tuned under the old feasible set also miss.
    fields_tag = zlib.crc32(
        (f"v{PLAN_FIELDSET_VERSION}|"
         + "|".join(sorted(PLAN_FIELDS))).encode()
    )
    key = (f"{shape.shape_class()}|{device.fingerprint()}|v{__version__}"
           f"|p{fields_tag:08x}")
    pins = (constraints or PlanConstraints()).pinned()
    if pins:
        # The pins are part of the tuning PROBLEM: a winner measured with
        # table_dtype free must never answer a query that pinned it (the
        # cached plan would override an explicit config knob — including
        # combinations the config layer refuses outright).
        key += "|" + ",".join(f"{f}={pins[f]}" for f in sorted(pins))
    return key


class PlanCache:
    """The JSON winner store.  Load-on-read, atomic rewrite-on-put; a
    corrupt or wrong-schema file reads as empty (autotune re-measures —
    the cache is an optimization, never a correctness dependency)."""

    def __init__(self, path: str | None = None) -> None:
        self.path = path or DEFAULT_CACHE_PATH

    def _load(self) -> dict:
        try:
            with open(self.path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return {}
        if not isinstance(doc, dict) or doc.get("schema") != _SCHEMA:
            return {}
        entries = doc.get("entries")
        return entries if isinstance(entries, dict) else {}

    def get(self, key: str) -> dict | None:
        entry = self._load().get(key)
        if not isinstance(entry, dict) or "plan" not in entry:
            return None
        return entry

    def put(self, key: str, plan: ExecutionPlan, *, measured_s: float,
            model_s: float) -> None:
        entries = self._load()
        entries[key] = {
            "plan": plan.as_dict(),
            "measured_s": measured_s,
            "model_s": model_s,
            "saved_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(self.path) or ".", suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"schema": _SCHEMA, "entries": entries}, f,
                          indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise


def autotune(shape: ProblemShape, device: DeviceSpec | None = None,
             constraints: PlanConstraints | None = None, *,
             cache_path: str | None = None, measure=None, top_n: int = 3,
             ) -> tuple[ExecutionPlan, PlanProvenance]:
    """Resolve via the measured-winner cache (see module docstring).

    ``measure(plan) -> seconds`` runs the trimmed workload; None means
    cache-consult only — a miss falls back to the model's choice with
    ``cache="miss"`` recorded (the trainer-entry mode)."""
    from cfk_tpu.plan.resolver import rank_plans
    from cfk_tpu.plan.resolver import plan as _plan

    device = device or DeviceSpec.detect()
    constraints = constraints or PlanConstraints()
    cache = PlanCache(cache_path)
    key = cache_key(shape, device, constraints)
    hit = cache.get(key)
    if hit is not None:
        try:
            ep = ExecutionPlan.from_dict(hit["plan"])
        except (ValueError, TypeError):
            ep = None  # stale/corrupt entry: treat as miss
        # Belt over the keyed braces: a hit must still AGREE with every
        # current pin (hand-edited/legacy cache files), or it is stale.
        # Pins absent from the stored plan's own ``pinned`` set were
        # soft-released at tune time (e.g. fused pinned on past the rank
        # cap) — those legitimately differ.
        if ep is not None and any(
            f in ep.pinned and getattr(ep, f) != v
            for f, v in constraints.pinned().items()
        ):
            ep = None
        if ep is not None:
            return ep, PlanProvenance(
                plan=ep, source="autotune-cache",
                est_cost_s=hit.get("model_s"),
                measured_s=hit.get("measured_s"), cache="hit",
            )
    if measure is None:
        ep, prov = _plan(shape, device, constraints, mode="model")
        prov.source = "model"
        prov.cache = "miss"
        return ep, prov
    ranked = rank_plans(shape, device, constraints)
    # The candidates: the model's top-N, plus the legacy-default plan so
    # the tuned winner is never worse than pre-planner behavior.
    pinned_ep, _ = _plan(shape, device, constraints, mode="pinned")
    cands = [ep for _, ep in ranked[:top_n]]
    if pinned_ep not in cands:
        cands.append(pinned_ep)
    results = []
    for ep in cands:
        s = float(measure(ep))
        results.append((s, ep))
    results.sort(key=lambda t: t[0])
    measured_s, winner = results[0]
    model_s = plan_cost(shape, device, winner).seconds
    cache.put(key, winner, measured_s=measured_s, model_s=model_s)
    return winner, PlanProvenance(
        plan=winner, source="autotune", est_cost_s=model_s,
        measured_s=measured_s, cache="miss",
        explain=tuple(
            ("candidate", round(s, 6), ep.summary()) for s, ep in results
        ),
    )


def trimmed_shape(shape: ProblemShape, *, max_nnz: int = 200_000,
                  ) -> ProblemShape:
    """Scale a shape down for measurement: entity counts and nnz shrink
    proportionally (rank/shards/algorithm are exact — they change kernel
    shapes, which is what is being measured)."""
    import dataclasses

    if shape.nnz <= max_nnz:
        return shape
    f = max_nnz / shape.nnz
    return dataclasses.replace(
        shape,
        num_users=max(int(shape.num_users * f), 64),
        num_movies=max(int(shape.num_movies * f), 16),
        nnz=max_nnz, gather_rows=None,
    )


def measure_with_training(shape: ProblemShape, base_config=None, *,
                          iters: int = 2, seed: int = 0):
    """The default offline measure: a trimmed synthetic workload through
    the REAL trainer with the candidate plan pinned as config knobs.
    Returns ``measure(plan) -> s/iter`` (min over ``iters`` timed after a
    warmup iteration).  Used by ``cfk_tpu plan --autotune``."""
    import dataclasses as dc

    import numpy as np

    from cfk_tpu.config import ALSConfig

    tshape = trimmed_shape(shape)

    def measure(ep: ExecutionPlan) -> float:
        from cfk_tpu.data.cache import cached_scale_dataset

        base = base_config or ALSConfig()
        cfg = dc.replace(
            base,
            rank=tshape.rank,
            num_iterations=1,
            num_shards=1,
            layout=ep.layout,
            exchange="all_gather",
            overlap=ep.overlap,
            fused_epilogue=ep.fused_epilogue,
            in_kernel_gather=ep.in_kernel_gather,
            reg_solve_algo=ep.reg_solve_algo,
            table_dtype=ep.table_dtype,
            solver=ep.solver,
            # Thread the staging engine too (ISSUE 13): on a host_window
            # resolve the enumerated pool/serial candidates must EXECUTE
            # their own mode, or both arms would measure the config
            # default and the cached winner's staging value would not be
            # backed by any measurement.
            staging=ep.staging,
            plan="pinned",
        )
        ds = cached_scale_dataset(
            users=tshape.num_users, movies=tshape.num_movies,
            nnz=tshape.nnz, seed=seed, layout=ep.layout,
            chunk_elems=ep.chunk_elems, tile_rows=tshape.tile_rows,
            log=lambda *a, **k: None,
        )
        from cfk_tpu.models.als import train_als

        times = []
        train_als(ds, cfg)  # warmup/compile
        for _ in range(max(iters, 1)):
            t0 = time.time()
            model = train_als(ds, cfg)
            np.asarray(model.user_factors[:1])
            times.append(time.time() - t0)
        return min(times)

    return measure
