"""Plan resolution: enumerate feasible candidates, cost them, pick cheapest.

``plan(shape, device, constraints)`` is the one entry point: constraints
pin fields (an ``ALSConfig``'s explicit knobs arrive as pins via
``spec.constraints_from_config``), the resolver enumerates the free
fields' candidates in legacy-preference order, drops candidates any
feasibility gate refuses — the SAME gates the half-steps execute under
(``quant.validate_table_dtype_layout``, the config layout/exchange/
algorithm rules, the kernel registry's ``supported`` predicates, the
device's VMEM/SMEM budgets) — and returns the cost-model minimum.  Ties
resolve to the first-enumerated candidate, i.e. the pre-planner default.

Pinned-but-impossible combinations split two ways, mirroring today's
behavior exactly:

- HARD conflicts (the ones ``ALSConfig.__post_init__`` itself refuses:
  int8 × padded/segment, ring × bucketed/segment, als++ × tiled/segment…)
  raise ``PlanConstraintError`` with both pins named.
- SOFT fallbacks (fused epilogue pinned on past the rank cap, in-kernel
  gather pinned on for an unsupported tile shape…) resolve to the
  effective execution — the pin is RELEASED (recorded in ``explain``) so
  the trainers thread the same deferred sentinel as before and the
  downstream gates do what they always did.
"""

from __future__ import annotations

import itertools

from cfk_tpu.plan import registry as _registry
from cfk_tpu.plan.cost import plan_cost
from cfk_tpu.plan.spec import (
    PLAN_FIELDS,
    DeviceSpec,
    ExecutionPlan,
    PlanConstraintError,
    PlanConstraints,
    PlanProvenance,
    ProblemShape,
    constraints_from_config,
)

_TRAIN_FIELDS = ("layout", "exchange", "chunk_elems", "fused_epilogue",
                 "in_kernel_gather", "overlap", "reg_solve_algo",
                 "table_dtype", "solver", "gram_backend", "offload_tier",
                 "ici_group", "staging", "hot_rows")
_SERVE_FIELDS = ("table_dtype", "serve_batch_quantum", "serve_tile_m")


def hard_conflict(shape: ProblemShape, pins: dict) -> str | None:
    """A pinned combination today's config layer REFUSES (vs silently
    falls back from).  Returns the conflict message, or None."""
    layout = pins.get("layout")
    if pins.get("table_dtype") == "int8" and layout not in (
        None, "tiled", "bucketed"
    ):
        return (f"table_dtype='int8' needs layout 'tiled'/'bucketed' (the "
                f"per-row scale rides their weight streams); pinned "
                f"layout={layout!r}")
    if pins.get("exchange") == "ring" and layout in ("bucketed", "segment"):
        return (f"exchange='ring' supports the padded/tiled layouts; "
                f"pinned layout={layout!r}")
    if pins.get("exchange") == "hier_ring" and layout not in (None, "tiled"):
        return (f"exchange='hier_ring' is implemented for the tiled "
                f"layout; pinned layout={layout!r}")
    if pins.get("offload_tier") == "host_window":
        if shape.kind != "train":
            return ("offload_tier='host_window' is a TRAINING tier; "
                    "serve shapes keep the item table device-resident "
                    "by construction — unpin it for a serve resolve")
        if shape.implicit:
            # Implicit out-of-core (ISSUE 19): the bucketed windowed
            # driver runs iALS and iALS++ via the streamed global-Gram
            # reduction + width-class windows.
            if layout not in (None, "bucketed"):
                return ("offload_tier='host_window' for the implicit "
                        "family streams the bucketed width-class layout; "
                        f"pinned layout={layout!r}")
        else:
            if layout not in (None, "tiled"):
                return (f"offload_tier='host_window' streams the tiled "
                        f"stream-mode layout; pinned layout={layout!r}")
            if shape.algorithm != "als":
                return ("offload_tier='host_window' supports explicit ALS "
                        f"at layout='tiled'; algorithm="
                        f"{shape.algorithm!r} (the explicit subspace "
                        "windowed walk is the documented follow-up)")
        # Sharded host_window is a real executor now (ISSUE 12): the
        # windowed driver runs per-shard staged windows under the
        # all_gather scan or the ring/hier_ring visit schedules.
    if shape.algorithm != "als":
        if layout in ("segment", "tiled"):
            return (f"algorithm={shape.algorithm!r} supports padded/"
                    f"bucketed layouts; pinned layout={layout!r}")
        if pins.get("exchange") in ("ring", "hier_ring"):
            return (f"algorithm={shape.algorithm!r} supports "
                    "exchange='all_gather' only; pinned "
                    f"exchange={pins['exchange']!r}")
    ici = pins.get("ici_group")
    if ici and shape.num_shards % ici != 0:
        # The same divisibility rule ALSConfig enforces (the outer ring
        # walks whole inner rings) — a plan must never promise a
        # hierarchy hier_visit_order/half_step_tiled_ring_hier refuse.
        return (f"ici_group={ici} must divide "
                f"num_shards={shape.num_shards} (the outer ring walks "
                "whole inner rings)")
    if pins.get("hot_rows") and pins.get("offload_tier") == "device":
        # The hot cache is the host_window tier's staged-byte lever; the
        # device tier has no staging to cut.
        return (f"hot_rows={pins['hot_rows']} is a host_window-tier "
                "knob (it cuts staged PCIe bytes); pinned "
                "offload_tier='device' has no staging — unpin one side")
    return None


def _feasible(shape: ProblemShape, device: DeviceSpec, cand: dict,
              ) -> str | None:
    """Reason this fully-assigned candidate cannot execute, or None.
    These mirror the execution-time gates one-for-one."""
    layout = cand["layout"]
    if cand["table_dtype"] == "int8" and layout not in ("tiled", "bucketed"):
        return "int8 table needs a weight stream (tiled/bucketed)"
    if cand["exchange"] == "ring" and layout not in ("padded", "tiled"):
        return "ring exchange needs the padded/tiled layouts"
    if cand["exchange"] == "hier_ring" and layout != "tiled":
        return "hier_ring exchange is implemented for the tiled layout"
    if shape.num_shards == 1 and cand["exchange"] != "all_gather":
        return "ring exchanges are multi-shard schedules"
    if shape.algorithm != "als" and layout in ("segment", "tiled"):
        return "subspace optimizers need padded/bucketed"
    if shape.algorithm != "als" and cand["exchange"] != "all_gather":
        return "subspace optimizers are all_gather only"
    if cand["offload_tier"] == "host_window" and shape.kind == "train":
        if shape.implicit:
            # ISSUE 19: the implicit windowed driver streams the
            # bucketed width-class layout (both iALS and iALS++ — the
            # global-Gram reduction serves either solve).  iALS is
            # all_gather only, and the generic exchange rules above
            # already refuse ring exchanges at bucketed layouts.
            if layout != "bucketed":
                return ("implicit host-window offload streams the "
                        "bucketed width-class layout")
        else:
            if layout != "tiled":
                return ("host-window offload streams the tiled stream "
                        "layout")
            if shape.algorithm != "als":
                return ("explicit host-window offload supports the full "
                        "ALS solve (the explicit subspace windowed walk "
                        "is the ROADMAP follow-up)")
        # Sharded host_window executes (ISSUE 12): the windowed driver
        # pairs per-shard staged windows with the all_gather scan or the
        # ring/hier_ring visit schedules; the generic exchange rules
        # above already refuse ring exchanges at one shard and non-tiled
        # ring layouts.
    if cand["hot_rows"] and cand["offload_tier"] != "host_window":
        return ("the hot-row cache is the host_window tier's staged-byte "
                "lever (the resident tier has no staging)")
    mosaic = _registry.backend_available("mosaic_tpu")
    if cand["gram_backend"] == "pallas" and not mosaic:
        return "mosaic_tpu backend unavailable"
    if cand["fused_epilogue"]:
        if cand["gram_backend"] != "pallas" or cand["solver"] != "pallas":
            return "fused epilogue needs the pallas gram backend + solver"
        gate = _registry.REGISTRY.get("gram_solve", "mosaic_tpu").supported
        if not gate(num_segments=1, k=shape.rank,
                    algo=cand["reg_solve_algo"]):
            return (f"rank {shape.rank} exceeds the fused "
                    f"{cand['reg_solve_algo']} elimination cap")
    if cand["in_kernel_gather"]:
        if cand["gram_backend"] != "pallas":
            return "in-kernel gather lives inside the pallas gram kernel"
        tr = shape.tile_rows
        entries = min(cand["chunk_elems"], 2 * shape.nnz)
        gate = _registry.REGISTRY.get("gram_gather", "mosaic_tpu").supported
        # table_dtype "float32" is the identity: the kernel would DMA
        # from the table at the factors' storage dtype.  Mosaic lowers
        # that DMA only on a TPU; elsewhere the plan runs the XLA twin.
        dma_dtype = (shape.dtype if cand["table_dtype"] == "float32"
                     else cand["table_dtype"])
        if not gate(entries=entries, meta_words=entries // max(tr, 1) + 2,
                    tile_rows=tr, block_rows=None, k=shape.rank,
                    table_dtype=dma_dtype, lowered=device.kind == "tpu"):
            return ("chunk shape refused by the gather "
                    "rank/dtype/SMEM/alignment gate")
    if cand["solver"] == "pallas":
        from cfk_tpu.ops.pallas import PALLAS_MAX_RANK

        if shape.rank > 2 * PALLAS_MAX_RANK:
            return (f"rank {shape.rank} exceeds the pallas solver's "
                    f"blocked cap {2 * PALLAS_MAX_RANK}")
    return None


# (knob, pinned value that may be infeasible, minimal-dependency probe
# overrides).  Each is a pin today's EXECUTION silently falls back from,
# so the resolver must release it (recording why) rather than raise —
# `ops.solve.dispatch_spd_solve` quietly takes cholesky past the pallas
# rank cap, the chunk resolvers quietly split/XLA-gather, and a
# single-device trainer never consults the exchange knob.  The probe
# overrides disable DEPENDENT knobs so the trial's refusal reason is
# about this pin, not a knock-on (fused needs the pallas solver, so a
# solver probe must not fail on the fused gate).
_SOFT_PINS = (
    ("gram_backend", "pallas",
     dict(fused_epilogue=False, in_kernel_gather=False)),
    ("solver", "pallas",
     dict(fused_epilogue=False, in_kernel_gather=False)),
    ("fused_epilogue", True, {}),
    ("in_kernel_gather", True, dict(fused_epilogue=False)),
    ("exchange", "ring", dict(fused_epilogue=False,
                              in_kernel_gather=False)),
    ("exchange", "hier_ring", dict(fused_epilogue=False,
                                   in_kernel_gather=False)),
)


def _soft_release(shape, device, pins, explain):
    """Release pins whose execution would silently fall back today
    (``_SOFT_PINS``), so the resolved plan reports the EFFECTIVE
    execution instead of raising on a config that has always trained.
    The released knob goes back to the resolver (which re-derives the
    fallback the gates would take) and the release is recorded in
    ``explain``."""
    pins = dict(pins)
    for knob, value, overrides in _SOFT_PINS:
        if pins.get(knob) != value:
            continue
        trial = dict(pins)
        for f in PLAN_FIELDS:
            trial.setdefault(f, PLAN_FIELDS[f][0])
        trial.update(overrides)
        trial[knob] = value
        reason = _feasible(shape, device, trial)
        if reason is not None:
            explain.append((knob, None,
                            f"pinned {value!r} but infeasible ({reason}); "
                            "released to the execution-time fallback"))
            pins.pop(knob)
    return pins


def candidates(shape: ProblemShape, constraints: PlanConstraints,
               device: DeviceSpec | None = None) -> "itertools.product":
    """(field order, value tuples) for the free-field product."""
    fields = _SERVE_FIELDS if shape.kind == "serve" else _TRAIN_FIELDS
    pins = constraints.pinned()
    axes = []
    tier_vals: tuple = ("device",)
    for f in fields:
        if f in pins:
            axes.append((f, (pins[f],)))
            if f == "offload_tier":
                tier_vals = (pins[f],)
        else:
            vals = PLAN_FIELDS[f]
            if f == "exchange" and shape.num_shards == 1:
                vals = ("all_gather",)
            if f == "staging" and "host_window" not in tier_vals:
                # The staging engine exists only on the host_window tier
                # — enumerating it for resident candidates would mint
                # cost-identical duplicates that crowd real candidates
                # out of autotune's measured top-N.
                vals = (PLAN_FIELDS[f][0],)
            if f == "offload_tier":
                # The axis IS the memory-budget predicate (ISSUE 11): a
                # fitting problem enumerates only the resident tier (the
                # legacy default, zero extra candidates), an oversized one
                # only host_window — so the resolver can never promise a
                # resident table the executor's own predicate refuses.
                # Workloads no windowed driver serves (serve kind, the
                # explicit subspace optimizer) keep the legacy resident
                # tier regardless — the budget cannot re-route them (and
                # a pinned 'device' there is never refused: _rank_plans'
                # budget raise shares THIS eligibility).  Implicit
                # shapes route to the bucketed windowed driver (ISSUE
                # 19); explicit ALS to the tiled one.
                vals = (("host_window",)
                        if (_host_window_eligible(shape, pins)
                            and device is not None
                            and not _fits_device(
                                shape, device,
                                table_dtype=pins.get("table_dtype")))
                        else ("device",))
                tier_vals = vals
            if f == "hot_rows":
                # Like the tier axis, this one IS a budget predicate
                # (ISSUE 15): a free hot_rows on the host_window tier
                # resolves to the ~10% power-law target when the hot
                # reservation fits the planner-side headroom, 0
                # otherwise — so the plan carries a nonzero hot fraction
                # ONLY when the budget admits it.  The executor clamps
                # the target to the real coverage-curve knee (and its
                # exact headroom) at window-plan build time.
                vals = ((_planner_hot_rows(shape, device, pins),)
                        if ("host_window" in tier_vals
                            and device is not None)
                        else (0,))
            axes.append((f, vals))
    names = [f for f, _ in axes]
    return names, itertools.product(*[v for _, v in axes])


def _fits_device(shape: ProblemShape, device: DeviceSpec,
                 table_dtype: str | None = None) -> bool:
    from cfk_tpu.offload.budget import shape_fits_device

    return shape_fits_device(shape, device, table_dtype=table_dtype)


def _stage_dtype_of(shape: ProblemShape, pins: dict) -> str:
    """The staging dtype the hot reservation is charged at: the pinned
    table dtype when it shrinks staging (bf16/int8), else the storage
    dtype — with an UNPINNED table dtype charged at the storage dtype
    (the largest candidate: the conservative reservation)."""
    td = pins.get("table_dtype")
    if td in ("bfloat16", "int8"):
        return td
    return shape.dtype


def _planner_hot_rows(shape: ProblemShape, device: DeviceSpec,
                      pins: dict) -> int:
    from cfk_tpu.offload.budget import planner_hot_rows

    return planner_hot_rows(
        shape.num_users, shape.num_movies, shape.rank,
        _stage_dtype_of(shape, pins), device.hbm_bytes,
    )


def _host_window_eligible(shape: ProblemShape, pins: dict) -> bool:
    """Whether the host_window tier is an ALTERNATIVE for this resolve —
    the one eligibility both the offload_tier axis and the pinned-device
    budget raise consult, so an explicit ``offload_tier='device'`` pin is
    refused exactly when unpinning it would have re-routed (and never
    with a dead-end remedy on shapes the windowed driver cannot serve).
    Sharded shapes qualify (ISSUE 12) — every exchange the sharded
    trainers run (all_gather / ring / hier_ring) has a windowed twin."""
    exchange_ok = (pins.get("exchange")
                   in (None, "all_gather", "ring", "hier_ring"))
    if shape.num_shards == 1:
        exchange_ok = pins.get("exchange") in (None, "all_gather")
    if shape.implicit:
        # ISSUE 19: the implicit family's out-of-core twin is the
        # bucketed windowed driver — iALS and iALS++ both qualify
        # (all_gather only; IALSConfig refuses other exchanges anyway).
        return (shape.kind == "train"
                and shape.algorithm in ("als", "ials++")
                and pins.get("layout") in (None, "bucketed")
                and pins.get("exchange") in (None, "all_gather"))
    return (shape.kind == "train"
            and shape.algorithm == "als"
            and pins.get("layout") in (None, "tiled")
            and exchange_ok)


def _assemble(shape: ProblemShape, cand: dict, pinned: frozenset,
              pins: dict | None = None) -> ExecutionPlan:
    """Fill non-enumerated fields with pins, then defaults, and name the
    kernel backend per slot from the resolved knobs (a serve-kind resolve
    enumerates only the serve fields, but pinned train fields must still
    appear in the plan verbatim)."""
    full = {f: PLAN_FIELDS[f][0] for f in PLAN_FIELDS}
    full.update(pins or {})
    full.update(cand)
    mosaic = (_registry.backend_available("mosaic_tpu")
              and full["gram_backend"] == "pallas")
    emu = "xla_emulation"
    moz = "mosaic_tpu"
    fused = full["fused_epilogue"] and full["solver"] == "pallas" and mosaic
    gather = full["in_kernel_gather"] and mosaic
    kernels = (
        ("gram", moz if mosaic else emu),
        ("gram_gather", moz if gather else emu),
        ("gram_solve", moz if fused else emu),
        ("gram_solve_gather", moz if (fused and gather) else emu),
        ("reg_solve",
         moz if (full["solver"] == "pallas"
                 and _registry.backend_available(moz)) else emu),
        ("topk", moz if _registry.backend_available(moz) else emu),
    )
    return ExecutionPlan(**full, kernels=kernels, pinned=pinned)


def _rank_plans(shape: ProblemShape, device: DeviceSpec,
                constraints: PlanConstraints | None = None,
                ) -> tuple[list[tuple[float, "ExecutionPlan"]], tuple]:
    """(ranked candidates cheapest-first, soft-release explain rows).
    Stable: enumeration order — legacy defaults first — breaks ties."""
    constraints = constraints or PlanConstraints()
    explain: list = []
    pins = constraints.pinned()
    conflict = hard_conflict(shape, pins)
    if conflict is not None:
        raise PlanConstraintError(conflict)
    if (pins.get("offload_tier") == "device"
            and _host_window_eligible(shape, pins)
            and not _fits_device(shape, device,
                                 table_dtype=pins.get("table_dtype"))):
        # The core ISSUE 11 guarantee: no plan may promise a resident
        # table the memory-budget predicate (offload.budget — the SAME
        # predicate the executor uses) says cannot exist.
        from cfk_tpu.offload.budget import train_resident_bytes

        need = train_resident_bytes(
            shape.num_users, shape.num_movies, shape.nnz, shape.rank,
            dtype=shape.dtype, table_dtype=pins.get("table_dtype"),
            num_shards=shape.num_shards,
        )["total"]
        raise PlanConstraintError(
            f"offload_tier='device' pinned but the PER-SHARD resident "
            f"working set (~{need / 1e9:.2f} GB at "
            f"num_shards={shape.num_shards}) exceeds the device budget "
            f"({device.hbm_bytes / 1e9:.2f} GB × budget fraction) — "
            "unpin offload_tier (the resolver will pick 'host_window') "
            "or shrink the problem"
        )
    # Hot-row cache resolution (ISSUE 15) — the hot-fraction decision
    # the plan CLI's --explain prints: which tier this resolve takes,
    # whether the reservation fits, and the target the axis will carry.
    will_host_window = (
        pins.get("offload_tier") == "host_window"
        or (_host_window_eligible(shape, pins)
            and "offload_tier" not in pins
            and not _fits_device(shape, device,
                                 table_dtype=pins.get("table_dtype")))
    )
    hot_pin = pins.get("hot_rows")
    if hot_pin:
        if not will_host_window:
            # Execution ignores the knob on the resident tier (the
            # windowed driver is the only consumer) — release, don't
            # raise, per the _SOFT_PINS convention.
            explain.append(("hot_rows", None,
                            f"pinned {hot_pin} but this resolve stays on "
                            "the resident tier (no staging to cut); "
                            "released to the execution-time no-op"))
            pins.pop("hot_rows")
        else:
            from cfk_tpu.offload.budget import (
                hot_reservation_bytes,
                hot_reservation_fits,
                max_hot_rows,
            )

            stage = _stage_dtype_of(shape, pins)
            if not hot_reservation_fits(hot_pin, shape.rank, stage,
                                        device.hbm_bytes):
                need = hot_reservation_bytes(hot_pin, shape.rank, stage)
                admit = max_hot_rows(device.hbm_bytes, shape.rank, stage)
                # Mirror the pinned-impossible offload_tier convention:
                # a reservation the budget predicate refuses raises AT
                # RESOLUTION, naming the bytes.
                raise PlanConstraintError(
                    f"hot_rows={hot_pin} pinned but its device "
                    f"reservation ({need / 1e6:.2f} MB at the {stage!r} "
                    f"staging dtype) exceeds the hot-cache budget share "
                    f"({admit} rows on this device) — lower hot_rows, "
                    "unpin it (the resolver clamps to the headroom), or "
                    "pin 0 for the full-staging engine"
                )
    elif hot_pin is None and will_host_window and device is not None:
        target = _planner_hot_rows(shape, device, pins)
        stage = _stage_dtype_of(shape, pins)
        if target > 0:
            from cfk_tpu.offload.budget import hot_reservation_bytes

            explain.append((
                "hot_rows", target,
                f"budget headroom admits the hot reservation "
                f"({hot_reservation_bytes(target, shape.rank, stage) / 1e6:.2f}"
                f" MB at {stage}) — target min(~10% of rows, headroom); "
                "the executor clamps to the coverage-curve knee"
            ))
        else:
            explain.append((
                "hot_rows", 0,
                "hot reservation refused by the budget headroom — "
                "windows stage their full row sets"
            ))
    pins = _soft_release(shape, device, pins, explain)
    constraints = PlanConstraints(**pins)
    names, prod = candidates(shape, constraints, device)
    pinned = frozenset(pins)
    ranked = []
    for idx, values in enumerate(prod):
        cand = dict(zip(names, values))
        # a serve-kind candidate (table dtype, batch quantum, tile rows)
        # has no gate of its own
        reason = (None if shape.kind == "serve"
                  else _feasible(shape, device, _with_defaults(cand)))
        if reason is not None:
            continue
        ep = _assemble(shape, cand, pinned, pins)
        cost = plan_cost(shape, device, ep)
        ranked.append((cost.seconds, idx, ep, cost))
    if not ranked:
        raise PlanConstraintError(
            f"no feasible plan for {shape.shape_class()} under pins "
            f"{sorted(pins.items())} — every candidate was refused"
        )
    ranked.sort(key=lambda t: (t[0], t[1]))
    return [(s, ep) for s, _, ep, _ in ranked], tuple(explain)


def rank_plans(shape: ProblemShape, device: DeviceSpec,
               constraints: PlanConstraints | None = None,
               ) -> list[tuple[float, ExecutionPlan]]:
    """All feasible candidates, cheapest first."""
    return _rank_plans(shape, device, constraints)[0]


def _with_defaults(cand: dict) -> dict:
    full = {f: PLAN_FIELDS[f][0] for f in PLAN_FIELDS}
    full.update(cand)
    return full


def plan(shape: ProblemShape, device: DeviceSpec | None = None,
         constraints: PlanConstraints | None = None, *,
         mode: str = "model", cache_path: str | None = None,
         measure=None) -> tuple[ExecutionPlan, PlanProvenance]:
    """Resolve an execution plan.

    ``mode="model"``    — cost-model minimum over the feasible set.
    ``mode="pinned"``   — no optimization: pins + legacy defaults (the
                          pre-planner behavior, as a plan object).
    ``mode="autotune"`` — consult the JSON cache; on a miss, measure the
                          top candidates when a ``measure`` callable is
                          given (``autotune.autotune``), else fall back
                          to the model choice with cache="miss".
    """
    device = device or DeviceSpec.detect()
    constraints = constraints or PlanConstraints()
    if mode == "autotune":
        from cfk_tpu.plan.autotune import autotune

        return autotune(shape, device, constraints,
                        cache_path=cache_path, measure=measure)
    if mode not in ("model", "pinned"):
        raise ValueError(f"unknown plan mode {mode!r}")
    ranked, explain = _rank_plans(shape, device, constraints)
    if mode == "pinned":
        # First-enumerated feasible candidate == pins + preference-order
        # defaults; rank_plans sorts by cost, so re-derive by index order.
        best = min(
            ((s, ep) for s, ep in ranked),
            key=lambda t: _preference_index(t[1], device),
        )[1]
        cost = plan_cost(shape, device, best)
        prov = PlanProvenance(plan=best, source="pinned",
                              est_cost_s=cost.seconds, explain=explain)
        return best, prov
    est, best = ranked[0]
    cost = plan_cost(shape, device, best)
    explain = explain + tuple(
        (name, round(val, 6), "cost term (s)")
        for name, val in sorted(cost.terms.items(), key=lambda t: -t[1])
    )
    source = "model" if len(ranked) > 1 else "pinned"
    prov = PlanProvenance(plan=best, source=source, est_cost_s=est,
                          explain=explain)
    return best, prov


def _preference_index(ep: ExecutionPlan, device: DeviceSpec) -> tuple:
    """Lexicographic position of a plan in legacy-preference order.

    The solver's legacy default is device-dependent (``"auto"`` resolves
    pallas on TPU, cholesky elsewhere — ``ops.solve._resolve_solver``),
    so the preference order flips with the device kind; every other
    field's preference is the candidate-tuple order."""
    idx = []
    for f, vals in PLAN_FIELDS.items():
        if f == "solver" and device.kind != "tpu":
            vals = tuple(reversed(vals))
        v = getattr(ep, f)
        idx.append(vals.index(v) if v in vals else len(vals))
    return tuple(idx)


def shape_for_config(config, *, num_users: int, num_movies: int, nnz: int,
                     implicit: bool = False,
                     gather_rows: float | None = None) -> ProblemShape:
    """The ``ProblemShape`` a trainer resolves its plan for."""
    return ProblemShape(
        num_users=max(num_users, 1), num_movies=max(num_movies, 1),
        nnz=max(nnz, 1), rank=config.rank, num_shards=config.num_shards,
        implicit=implicit, algorithm=config.algorithm,
        sweeps=config.sweeps if config.algorithm != "als" else 1,
        dtype=config.dtype, gather_rows=gather_rows,
    )


def plan_for_config(config, *, num_users: int, num_movies: int, nnz: int,
                    implicit: bool = False,
                    gather_rows: float | None = None,
                    device: DeviceSpec | None = None,
                    cache_path: str | None = None,
                    ) -> tuple[ExecutionPlan, PlanProvenance]:
    """The trainer entry: shape from the dataset's counts, pins from the
    config's explicit knobs, mode from ``config.plan``.  Trainer-side
    autotune NEVER measures (that belongs offline — ``cfk_tpu plan
    --autotune``); it consults the cache and falls back to the model on a
    miss, recording hit/miss."""
    shape = shape_for_config(
        config, num_users=num_users, num_movies=num_movies, nnz=nnz,
        implicit=implicit, gather_rows=gather_rows,
    )
    constraints = constraints_from_config(config)
    mode = getattr(config, "plan", "model")
    return plan(shape, device, constraints, mode=mode,
                cache_path=cache_path)


def fleet_host_window_plan(shape: ProblemShape, *, host_ram_bytes: float,
                           processes: int, armed: bool = True) -> dict:
    """Provenance for the FLEET out-of-core tier: prove that a shape whose
    factor tables exceed one host's RAM budget fits once the
    ``HostFactorStore`` is range-sharded over ``processes`` hosts.

    Returns a breakdown dict recording both verdicts — the single-host
    refusal (``single_host_fits``) and the per-process fit
    (``fleet_fits``) — alongside the byte terms they were judged on, so a
    bench row or a fleet launcher can show WHY the fleet was required.
    Raises ``PlanConstraintError`` when even the fleet does not fit (the
    message names the two levers: more processes, or more host RAM)."""
    from cfk_tpu.offload.budget import (
        RESIDENT_FRACTION,
        fleet_host_ram_bytes,
        fits_fleet_host,
    )

    if processes < 1:
        raise PlanConstraintError(f"processes must be >= 1, got {processes}")
    if shape.num_shards % processes != 0:
        raise PlanConstraintError(
            f"num_shards={shape.num_shards} must be divisible by "
            f"processes={processes}: the window exchange assigns each "
            f"process a contiguous run of shards")
    kw = dict(dtype=shape.dtype, armed=armed)
    single = fleet_host_ram_bytes(shape.num_users, shape.num_movies,
                                  shape.nnz, shape.rank, processes=1, **kw)
    fleet = fleet_host_ram_bytes(shape.num_users, shape.num_movies,
                                 shape.nnz, shape.rank,
                                 processes=processes, **kw)
    single_fits = fits_fleet_host(
        shape.num_users, shape.num_movies, shape.nnz, shape.rank,
        host_ram_bytes=host_ram_bytes, processes=1, **kw)
    fleet_fits = fits_fleet_host(
        shape.num_users, shape.num_movies, shape.nnz, shape.rank,
        host_ram_bytes=host_ram_bytes, processes=processes, **kw)
    if not fleet_fits:
        raise PlanConstraintError(
            f"per-process host window footprint "
            f"{fleet['total'] / 2**20:.1f} MiB exceeds the "
            f"{host_ram_bytes * RESIDENT_FRACTION / 2**20:.1f} MiB resident "
            f"budget even at processes={processes}; raise processes (shards "
            f"permitting) or host_ram_bytes")
    return {
        "tier": "fleet_host_window",
        "processes": processes,
        "host_ram_bytes": float(host_ram_bytes),
        "resident_fraction": RESIDENT_FRACTION,
        "single_host_bytes": single["total"],
        "single_host_fits": single_fits,
        "per_process_bytes": fleet["total"],
        "per_process_breakdown": fleet,
        "fleet_fits": fleet_fits,
    }
