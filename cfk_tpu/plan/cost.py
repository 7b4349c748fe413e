"""Per-plan cost estimation — ``utils/roofline``'s byte/flop accounting
extended to price CANDIDATE plans, not just the plan that ran.

``utils.roofline`` answers "how far is this measured iteration from the
hardware floor?".  The planner needs the prospective version: "what would
this iteration cost under THAT knob setting?" — so each term the roofline
charges (gather bytes per table dtype, per-width-class padded cells, the
fused epilogue's removed A-batch round trip, the materialized gather
stream, ring payload bytes, the serve table scan) appears here as a
per-plan delta.  The total is an ESTIMATE for ranking plans (and for the
autotune mode's "measure the 2–3 nearest the optimum" trim); absolute
accuracy is neither promised nor needed — monotonicity in each knob is
(the matrix test in tests/test_plan.py pins the orderings that matter).

All terms are seconds on the given ``DeviceSpec``.  The breakdown dict is
what ``cfk_tpu plan --explain`` prints.
"""

from __future__ import annotations

import dataclasses
import math

from cfk_tpu.plan.spec import DeviceSpec, ExecutionPlan, ProblemShape

# Gather-slot inflation per layout when no measured ``gather_rows`` is
# available: padding slots fetch rows like real slots (the engine charges
# the slot).  tiled ≈ 1.26 (the measured tile-padding share at the full
# Netflix build), bucketed ≈ 1.57 (measured at the ML-25M build, ROADMAP
# item 4), segment = exact O(nnz), padded = the rectangle pads every
# entity to the max degree — unknowable without the data, call it 3×
# (power-law data routinely exceeds it; the pin exists so the model
# PENALIZES padded at scale, which is the decision that matters).
_GATHER_PAD_FACTOR = {
    "tiled": 1.26,
    "bucketed": 1.57,
    "segment": 1.0,
    "padded": 3.0,
}

# Interpret-mode pallas off-TPU is a test-only path, orders of magnitude
# slow — the model must never pick it on a cpu/gpu device.
_OFFCHIP_PALLAS_SOLVER_PENALTY = 50.0
# What ``solver="cholesky"`` costs a training iteration on a TPU over the
# fused pallas solve: ~1.7× end to end when it was XLA's batched-Cholesky
# custom calls (pre-ledger round 2; PERF.md §8).  Since PR 40 those systems
# (float32, k <= 128, k % 8 == 0) take the lane-batched Cholesky kernel, so
# the figure now prices the split schedule (the [E, k, k] Gram batch written
# to HBM, turned and read back) plus that kernel, and over-prices it; no
# train cell has measured the new ratio, so it stays until one does.
_TPU_CHOLESKY_PENALTY = 1.7


@dataclasses.dataclass(frozen=True)
class PlanCost:
    """Estimated seconds for one unit of work (a full train iteration, or
    one serve batch at the plan's quantum) plus the term breakdown."""

    seconds: float
    unit: str  # "s/iter" | "s/batch"
    terms: dict

    def explain_lines(self) -> list[str]:
        out = []
        for name, val in sorted(self.terms.items(), key=lambda t: -t[1]):
            out.append(f"{name:28s} {val:.6f} s")
        out.append(f"{'TOTAL (' + self.unit + ')':28s} {self.seconds:.6f} s")
        return out


def gather_rows_for(shape: ProblemShape, plan: ExecutionPlan) -> float:
    """Layout-aware gather-slot count per iteration (both sides), before
    the sweeps multiplier — the measured count when the shape carries one
    (real blocks exist), the per-layout heuristic otherwise."""
    if shape.gather_rows is not None:
        return float(shape.gather_rows)
    return 2.0 * shape.nnz * _GATHER_PAD_FACTOR[plan.layout]


def train_iteration_cost(shape: ProblemShape, device: DeviceSpec,
                         plan: ExecutionPlan) -> PlanCost:
    """One full ALS iteration (both half-steps) under ``plan``."""
    from cfk_tpu.utils.roofline import (
        als_iteration_cost,
        table_gather_bytes_per_row,
    )

    k = shape.rank
    factor_bytes = 2 if shape.dtype == "bfloat16" else 4
    rows = gather_rows_for(shape, plan) * max(shape.sweeps, 1)
    base = als_iteration_cost(
        shape.nnz, shape.num_users, shape.num_movies, k,
        factor_bytes=factor_bytes, implicit=shape.implicit,
        table_dtype=plan.table_dtype,
        gather_rows=gather_rows_for(shape, plan), sweeps=shape.sweeps,
    )
    shards = max(shape.num_shards, 1)
    bw = device.hbm_bytes_per_s
    terms: dict[str, float] = {}

    # The three floors, per shard (work divides; the roofline model's
    # min-bytes already include the gather bytes).
    compute_s = base.model_flops / shards / device.peak_flops
    if plan.solver == "cholesky":
        # the solve share of the flops pays the split schedule's solve
        solve_flops = (shape.num_users + shape.num_movies) * (
            k**3 / 3.0 + 2.0 * k**2
        )
        penalty = (_TPU_CHOLESKY_PENALTY if device.kind == "tpu" else 1.0)
        compute_s += solve_flops * (penalty - 1.0) / shards / device.peak_flops
    if plan.solver == "pallas" and device.kind != "tpu":
        compute_s *= _OFFCHIP_PALLAS_SOLVER_PENALTY
    if plan.reg_solve_algo == "gj":
        # GJ's k³ elimination vs LU's k³/3 — only the solve term triples.
        solve_flops = (shape.num_users + shape.num_movies) * (k**3 / 3.0)
        compute_s += 2.0 * solve_flops / shards / device.peak_flops
    terms["compute"] = compute_s
    terms["hbm_min_bytes"] = base.min_hbm_bytes / shards / bw
    terms["gather_floor"] = base.gather_bound_s(
        rows_per_s=device.gather_rows_per_s, bandwidth=bw,
    ) / shards

    floor = max(terms["compute"], terms["hbm_min_bytes"],
                terms["gather_floor"])
    total = floor

    extra = 0.0
    if not plan.in_kernel_gather or plan.gram_backend != "pallas":
        # The materialized [C, k] stream: every gathered row is written to
        # HBM and read back once per side.
        stream_bytes = 2.0 * rows * k * factor_bytes
        extra += stream_bytes / shards / bw
        terms["xla_gather_stream"] = stream_bytes / shards / bw
    if not plan.fused_epilogue or plan.gram_backend != "pallas":
        # The per-chunk [Ec, k, k] A-batch round trip the fusion deletes.
        ents = shape.num_users + shape.num_movies
        abatch_bytes = ents * (k * k + k) * 4.0 * 2
        extra += abatch_bytes / shards / bw
        terms["split_epilogue_abatch"] = abatch_bytes / shards / bw

    # Exchange: bytes every half-iteration moves between shards.  The
    # ring rotates (S-1)/S of the fixed table through each device; the
    # all_gather replicates (S-1)/S of it inbound.  Payload cells follow
    # the TABLE dtype (quantized ring payloads, PR 7).
    if shards > 1:
        row_bytes = table_gather_bytes_per_row(
            k, plan.table_dtype, factor_bytes
        )
        table_rows = shape.num_users + shape.num_movies  # both halves
        wire = table_rows * row_bytes * (shards - 1) / shards
        # Intra-domain legs of EVERY exchange are modeled at HBM-bandwidth
        # order (the pre-planner convention — `ici_bytes_per_s` is kept on
        # the DeviceSpec for the on-TPU recalibration, ROADMAP backlog
        # item (f)); only DOMAIN-CROSSING transfers pay `dcn_bytes_per_s`,
        # so the fabric model is consistent across the three exchanges and
        # the hierarchy's advantage is exactly its fewer slow-fabric hops.
        multi_host = bool(device.ici_domain
                          and shards > device.ici_domain)
        if plan.exchange == "hier_ring":
            # Of the S-1 transfers, O·(I-1) rotate inside the domain and
            # O-1 hop the DCN.  ici_domain=0 means one domain (all inner)
            # — the schedule and the cost degenerate to the flat ring's.
            # ``ici_group`` is a real plan field now (ISSUE 12): an
            # explicit ALSConfig.ici_group pin reaches the model here, so
            # it prices the hierarchy that actually runs; 0 (auto) falls
            # back to the DEVICE topology (ici_domain), the same physical
            # quantity execution's resolve_ici_group defaults to.
            inner = plan.ici_group or device.ici_domain or shards
            inner = inner if shards % inner == 0 else shards
            outer = shards // inner
            inner_frac = (outer * (inner - 1)) / max(shards - 1, 1)
            exch = (wire * inner_frac / bw
                    + wire * (1.0 - inner_frac) / device.dcn_bytes_per_s)
        elif plan.exchange == "ring" and multi_host:
            # Bulk-synchronous shift-by-1: EVERY ring step is gated by
            # its domain-boundary edge, so the whole rotation runs at DCN
            # speed — the inversion hier_ring exists to fix.
            exch = wire / device.dcn_bytes_per_s
        else:
            exch = wire / bw
            if multi_host:
                # all_gather's inbound share crossing domains.
                exch += (wire / device.ici_domain
                         / device.dcn_bytes_per_s)
        # Overlap hides the exchange behind compute up to the floor,
        # serial schedules expose it.
        if plan.overlap:
            exposed = max(0.0, exch - floor * 0.5)
        else:
            exposed = exch
        terms["exchange_exposed"] = exposed
        extra += exposed

    # Out-of-core tier (ISSUE 11/12): every half-iteration stages the
    # fixed side's windows over PCIe — the full table once per half-step,
    # plus the duplication of rows shared between adjacent windows (~15%
    # on power-law data) — DIVIDED across shards: each shard stages only
    # the window residual its own chunks reference, concurrently on its
    # own host's PCIe (the DCN share of remote-shard rows is priced by
    # the exchange term above, unchanged).  Staged cells follow the
    # STAGING dtype (ISSUE 12): bf16 halves, int8 ships the (1-byte
    # codes + one f32 scale per row) pair — a quarter, the honest bytes
    # the executor's ``offload_staged_mb`` now records.
    #
    # Hiding (ISSUE 13): the POOLED staging engine overlaps the whole
    # host pipeline (gather, quantize, checksum, device_put issue)
    # across shards and windows on worker threads, so staging hides
    # under compute up to the FULL floor; the serial double buffer only
    # overlaps one window at a time on the consuming thread and — like
    # the exchange term — is credited half the floor, and only when the
    # chunk pipelines overlap at all.  (The donation reclaim is a
    # MEMORY credit, not a time term: it lands in offload.budget —
    # larger admitted windows, the ×1 accumulator reservation, and the
    # resident-tier solve-output credit the tier predicate consumes.)
    if plan.offload_tier == "host_window":
        stage_itemsize = {"bfloat16": 2.0, "int8": 1.0}.get(
            plan.table_dtype, float(factor_bytes)
        )
        row_overhead = 4.0 if plan.table_dtype == "int8" else 0.0
        stage_bytes_per_row = k * stage_itemsize + row_overhead
        window_dup = 1.15
        pcie = ((shape.num_users + shape.num_movies) * stage_bytes_per_row
                * window_dup / shards / device.pcie_bytes_per_s)
        # Hot-row cache (ISSUE 15): the term scales by the COLD
        # reference fraction.  The resolver cannot see the real skew, so
        # the coverage of a top-f head is estimated with the Zipf(1)
        # harmonic mass H_f/H_n ≈ ln(1+f)/ln(1+n) — the curve the
        # counter-based synth generator (and Netflix-like data) follows
        # closely enough to RANK hot against cold staging; the executor
        # meters the real per-window coverage (offload_hot_coverage) and
        # the bench hot-A/B row records the measured cut.  Floored so a
        # hot plan never looks free: the cold tail and the chunk arrays
        # still cross PCIe every window.
        if plan.hot_rows > 0:
            import math

            n = shape.num_users + shape.num_movies
            f = min(plan.hot_rows, n)
            coverage = math.log1p(f) / max(math.log1p(n), 1e-9)
            pcie *= max(1.0 - coverage, 0.05)
        if plan.staging == "pool":
            exposed_pcie = max(0.0, pcie - floor)
        elif plan.overlap:
            exposed_pcie = max(0.0, pcie - floor * 0.5)
        else:
            exposed_pcie = pcie
        terms["host_window_pcie"] = exposed_pcie
        extra += exposed_pcie
        # Implicit out-of-core (ISSUE 19): each half-iteration also
        # streams the fixed side's FULL table once more for the
        # global-Gram reduction (the [k,k] accumulator's block feed) —
        # a second pass at the staging dtype, never hidden by the hot
        # cache (the Gram must see every row) and serial with compute
        # today (the accumulator is a device-side dependency of every
        # window's solve, so only the double buffer overlaps it).
        if shape.implicit:
            gram_pcie = ((shape.num_users + shape.num_movies)
                         * stage_bytes_per_row / shards
                         / device.pcie_bytes_per_s)
            terms["host_window_gram_pcie"] = gram_pcie
            extra += gram_pcie

    # Chunking overhead: each chunk pays a fixed dispatch cost (scan step
    # + DMA setup), so tiny chunks are overhead-bound; oversized chunks
    # pay transient-gather HBM pressure (the measured r4 knee — gather
    # rate falls as the per-chunk working set grows past ~256 MB).
    chunks = max(1.0, rows / max(plan.chunk_elems, 1))
    dispatch = chunks * 20e-6
    terms["chunk_dispatch"] = dispatch
    extra += dispatch
    chunk_bytes = plan.chunk_elems * k * factor_bytes
    if chunk_bytes > 256 << 20:
        pressure = terms["gather_floor"] * 0.25
        terms["chunk_gather_pressure"] = pressure
        extra += pressure

    return PlanCost(seconds=total + extra, unit="s/iter", terms=terms)


def serve_batch_cost_for(shape: ProblemShape, device: DeviceSpec,
                         plan: ExecutionPlan) -> PlanCost:
    """One coalesced serve batch at the plan's quantum — reported per
    REQUEST-slot second so quanta are comparable: the table scan amortizes
    over the batch, which is exactly the lever the quantum moves."""
    from cfk_tpu.utils.roofline import serve_batch_cost

    b = plan.serve_batch_quantum
    cost = serve_batch_cost(
        shape.num_movies, shape.rank, b, shape.serve_k,
        table_dtype=plan.table_dtype,
    )
    shards = max(shape.num_shards, 1)
    flops_s = cost.model_flops / shards / device.peak_flops
    bytes_s = cost.hbm_bytes / shards / device.hbm_bytes_per_s
    batch_s = max(flops_s, bytes_s)
    # Coalescing wait: a batch cannot dispatch before it fills (or the
    # server's poll quantum passes); model half a batch service time of
    # queueing so unbounded quanta do not look free.
    wait_s = batch_s * 0.5
    per_request = (batch_s + wait_s) / b
    terms = {
        "score_flops": flops_s,
        "table_scan_bytes": bytes_s,
        "coalesce_wait": wait_s,
    }
    # Ranked PER REQUEST-SLOT: quanta are only comparable on what one
    # request costs — per batch, a bigger quantum always looks worse even
    # though it amortizes the table scan, which is the whole lever.
    return PlanCost(seconds=per_request, unit="s/request", terms=terms)


def plan_cost(shape: ProblemShape, device: DeviceSpec,
              plan: ExecutionPlan) -> PlanCost:
    if shape.kind == "serve":
        return serve_batch_cost_for(shape, device, plan)
    return train_iteration_cost(shape, device, plan)
