"""Execution-plan vocabulary: shapes, devices, plans, constraints, provenance.

Everything that decides HOW a half-iteration or a serve batch executes —
layout, chunk size, fused epilogue, in-kernel gather, overlap, elimination
algorithm, gather-table dtype, exchange strategy, serve batch quantum, and
the kernel backend per slot — is captured by one frozen ``ExecutionPlan``.
Before this subsystem those knobs were resolved ad-hoc across ``config.py``,
``ops/tiled.py``, ``ops/bucketed.py``, ``ops/solve.py``, ``parallel/spmd.py``,
``serving/engine.py`` and the four trainers, each with its own fallback
logic (ROADMAP item 5).  ALX (arXiv 2112.02194) is the argument for making
these placement/tiering decisions from a byte/flop model; JAXMg
(arXiv 2601.14466) for putting kernel selection behind one seam so a second
backend is a registry entry, not a rewrite.

This module is deliberately importable WITHOUT jax (like ``config.py``):
the resolver and registry import the heavy gates lazily.

Bit-exactness contract: an ``ALSConfig``'s concrete knobs become PINNED
constraints (``constraints_from_config``), and ``ExecutionPlan.
half_step_kwargs`` threads the config's own sentinel (``None``/``"auto"``)
for every knob the config left deferred — so the default-config path routes
through exactly the same downstream resolution (process defaults, jit
cache keys) as before the planner existed, and is
bit-identical by construction.  The plan's *resolved* concrete choices are
what provenance records and what the cost model priced.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Literal


class PlanConstraintError(ValueError):
    """Raised when pinned constraints conflict with each other or with a
    feasibility gate (e.g. ``table_dtype='int8'`` pinned against
    ``layout='padded'``).  The message names the conflicting pins."""


# Every execution-affecting knob, with the candidate values the resolver
# may enumerate when the field is unpinned.  Order encodes the tie-break
# preference (the legacy default first), so a cost tie resolves to the
# pre-planner behavior.
PLAN_FIELDS: dict[str, tuple] = {
    "layout": ("tiled", "bucketed", "padded", "segment"),
    # "hier_ring" (ISSUE 11): the ICI-ring-within-DCN-ring schedule —
    # inner rings rotate device-resident slices, outer hops cross the
    # slower fabric once per phase (parallel.spmd.half_step_tiled_ring_hier).
    "exchange": ("all_gather", "ring", "hier_ring"),
    # 64k is the measured-best full-scale chunk (BENCH r4) AND the largest
    # class that fits the in-kernel gather's scalar-prefetch SMEM gate.
    "chunk_elems": (1 << 20, 1 << 16, 1 << 18, 1 << 22),
    "fused_epilogue": (True, False),
    "in_kernel_gather": (True, False),
    "overlap": (True, False),
    "reg_solve_algo": ("lu", "gj"),
    "table_dtype": ("float32", "bfloat16", "int8"),
    "solver": ("pallas", "cholesky"),
    "gram_backend": ("pallas", "xla"),
    "serve_batch_quantum": (8, 16, 32, 64, 128, 256),
    "serve_tile_m": (512,),
    # Out-of-core tier (ISSUE 11): "device" keeps both factor tables
    # HBM-resident (feasible ONLY while cfk_tpu.offload.budget's predicate
    # passes — the same PER-SHARD predicate the executor sizes windows
    # with); "host_window" keeps them in host RAM and streams device_put
    # windows (cfk_tpu.offload.windowed — sharded too, ISSUE 12).  The
    # resolver's enumeration axis is the predicate itself, so oversized
    # problems resolve to host_window instead of promising a resident
    # table that cannot exist.
    "offload_tier": ("device", "host_window"),
    # Inner-ring size of the hierarchical exchange (ISSUE 12 — promoted
    # from an ALSConfig-only knob so the cost model can SEE the hierarchy
    # it prices).  0 = auto: the device's ici_domain (execution resolves
    # devices-per-process via spmd.resolve_ici_group — the same physical
    # quantity).  An explicit ALSConfig.ici_group pins it, so the model
    # prices the hierarchy that actually runs; adding this field also
    # rotates the autotune cache's plan-field-set digest, invalidating
    # every pre-ici_group winner (they carry no decision for it).
    "ici_group": (0,),
    # Host staging engine mode of the host_window tier (ISSUE 13):
    # "pool" overlaps the per-(shard, window) host staging work across
    # shards and windows on a bounded thread pool (the default execution
    # mode — the ALX per-shard transfer pipeline's host half), "serial"
    # is the PR 10/11 one-thread double buffer.  crc-identical across
    # the knob; the cost model prices only how much of the
    # host_window_pcie term stays exposed.  ALSConfig.staging always
    # pins it (a concrete dataclass default, like overlap), and its
    # existence rotates the autotune field-set digest — pre-staging
    # winners carry no decision for it and must miss.
    "staging": ("pool", "serial"),
    # Skew-aware hot-row device cache of the host_window tier (ISSUE
    # 15): the TOTAL top-referenced fixed-table rows (both sides) kept
    # device-resident at the staging dtype, so windows stage only their
    # cold delta.  0 = off (the PR 12 full-staging engine).  A free
    # field resolves through the resolver's budget-predicate axis: the
    # ~10% power-law target when the reservation fits the headroom
    # (offload.budget.planner_hot_rows), 0 otherwise — "nonzero only
    # when the budget admits".  The executor re-resolves the exact count
    # against the real coverage-curve knee at window-plan build time;
    # the plan's value is the budget-admitted TARGET the cost model
    # priced.  crc-identical across the knob; adding the field rotates
    # the autotune digest (pre-hot winners carry no decision for it).
    "hot_rows": (0,),
}

# Semantic version of the plan field SET (ISSUE 19).  The autotune cache
# digests the sorted field NAMES, which rotates on any field add — but a
# feasibility change that adds no field (bucketed × host_window becoming
# resolvable for the implicit family) would leave stale winners readable
# under the old semantics.  Bump this whenever the feasible set of an
# EXISTING field changes; autotune folds it into the field-set digest so
# every pre-change winner reads as a miss.
PLAN_FIELDSET_VERSION = 2

# Fields whose pins are free-form positive ints (the candidate tuples
# above are only the resolver's enumeration grid for UNPINNED fields).
_NUMERIC_FIELDS = ("chunk_elems", "serve_batch_quantum", "serve_tile_m",
                   "ici_group", "hot_rows")
# Numeric fields where 0 is a legal pin (an explicit OFF, not "unset"):
# hot_rows=0 pins the full-staging engine.
_ZERO_OK_FIELDS = ("hot_rows",)


@dataclasses.dataclass(frozen=True)
class ProblemShape:
    """The workload the plan is resolved for.

    ``kind="train"`` describes one ALS(-WR/iALS) half-iteration pair;
    ``kind="serve"`` one top-K scoring stream.  ``gather_rows`` optionally
    carries the MEASURED layout-aware gather-slot count (padded cells per
    width class) when real blocks exist — the cost model falls back to
    per-layout padding heuristics otherwise."""

    num_users: int
    num_movies: int
    nnz: int
    rank: int
    num_shards: int = 1
    implicit: bool = False
    algorithm: str = "als"
    sweeps: int = 1
    dtype: str = "float32"  # factor storage dtype (not a plan knob)
    tile_rows: int = 16
    kind: Literal["train", "serve"] = "train"
    serve_k: int = 100
    gather_rows: float | None = None

    def __post_init__(self) -> None:
        for f in ("num_users", "num_movies", "nnz", "rank", "num_shards"):
            if getattr(self, f) < 1:
                raise ValueError(f"{f} must be >= 1, got {getattr(self, f)}")
        if self.kind not in ("train", "serve"):
            raise ValueError(f"unknown shape kind {self.kind!r}")

    def shape_class(self) -> str:
        """The autotune cache's shape key: sizes bucketed to powers of two
        (a 162k-user and a 180k-user problem share a tuned plan; rank and
        shard count are exact — they change kernel shapes)."""
        b = lambda n: 1 << max(int(n) - 1, 0).bit_length()
        tag = (f"{self.kind}:u{b(self.num_users)}:m{b(self.num_movies)}:"
               f"n{b(self.nnz)}:k{self.rank}:s{self.num_shards}:"
               f"{self.algorithm}")
        if self.implicit:
            tag += ":implicit"
        if self.kind == "serve":
            tag += f":top{self.serve_k}"
        return tag


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """What the cost model knows about the hardware.

    Build one with ``detect()`` (the running backend) or ``nominal()``; both
    take a TPU's numbers from the ONE peaks table
    (``utils.roofline.DEVICE_PEAKS``, keyed by ``device_kind``) and refuse a
    TPU that is not in it.  ``kind="cpu"`` carries NOMINAL numbers: off-TPU
    the model is used only to RANK candidate plans (CI, the plan CLI), never
    as an absolute latency claim — the ratios (gather is row-slot-bound,
    fusion saves the A-batch round trip, quantization shrinks the scan) are
    what transfer.
    """

    kind: str  # "tpu" | "cpu" | "gpu"
    name: str = ""
    num_devices: int = 1
    hbm_bytes: float = dataclasses.field(kw_only=True)
    hbm_bytes_per_s: float = dataclasses.field(kw_only=True)
    peak_flops: float = dataclasses.field(kw_only=True)
    gather_rows_per_s: float = dataclasses.field(kw_only=True)
    vmem_bytes: int = 96 << 20  # the gram kernels' resident-output cap
    smem_bytes: int = 512 << 10  # _GATHER_SMEM_BYTES_CAP
    # Fabric tiers the offload/hier-exchange terms price (ISSUE 11).
    # ALL THREE ARE OFF-TPU GUESSES pending the on-TPU validation backlog
    # (ROADMAP): PCIe ≈ gen4 ×16 effective, ICI ≈ one v5e link pair,
    # DCN ≈ per-host data-center NIC share.  Off-TPU the model only RANKS,
    # so the ratios (PCIe ≪ HBM, DCN ≪ ICI) are what matter.
    pcie_bytes_per_s: float = 32e9
    ici_bytes_per_s: float = 90e9
    dcn_bytes_per_s: float = 25e9
    # Devices per ICI domain (host): the hier-ring cost term's inner-ring
    # size.  0 = all devices share one ICI domain (single host).
    ici_domain: int = 0

    # Nominal host-CPU numbers: a memory-bandwidth-bound machine with no
    # dedicated gather engine (rows/s set high enough never to bind —
    # every fetch is just bytes), so the bytes floors dominate the
    # ranking off-TPU.  That matches what this container MEASURES
    # (bf16/int8 tables measurably cheaper per PR 7/8 rows); the flops
    # number is deliberately generous so compute never masks the byte
    # terms the host ranking exists to compare.
    _CPU = dict(hbm_bytes=32 * 1024**3, hbm_bytes_per_s=50e9,
                peak_flops=2e13, gather_rows_per_s=2e9)

    @classmethod
    def nominal(cls, kind: str, name: str = "", num_devices: int = 1,
                ) -> "DeviceSpec":
        """A spec for ``kind`` with reference numbers: for ``"tpu"`` the
        peaks-table row of ``name`` (a ``device_kind`` or an alias such as
        ``"v5e"``; a TPU with no row raises), for anything else the nominal
        byte-bound host numbers."""
        if kind != "tpu":
            return cls(kind=kind, name=name or kind,
                       num_devices=num_devices, **cls._CPU)
        from cfk_tpu.utils.roofline import device_peaks

        pk = device_peaks(name)
        return cls(
            kind=kind, name=name, num_devices=num_devices,
            hbm_bytes=pk.hbm_bytes, hbm_bytes_per_s=pk.hbm_bytes_per_s,
            peak_flops=pk.peak_bf16_flops,
            gather_rows_per_s=pk.gather_rows_per_s,
        )

    @classmethod
    def detect(cls) -> "DeviceSpec":
        """The current jax backend, as a spec (see ``nominal``)."""
        import jax

        devices = jax.devices()
        return cls.nominal(
            jax.default_backend(), name=devices[0].device_kind,
            num_devices=len(devices),
        )

    def fingerprint(self) -> str:
        """The autotune cache's device key: a measured winner is only
        trusted on the hardware (and device count) it was measured on."""
        name = self.name.replace(" ", "_") or self.kind
        return f"{self.kind}:{name}:x{self.num_devices}"


@dataclasses.dataclass(frozen=True)
class PlanConstraints:
    """Optional pins, one per plan field.  ``None`` = the resolver is free
    to choose; a concrete value fixes that plan field (and is validated
    against the feasibility gates — an impossible pin raises
    ``PlanConstraintError`` instead of silently un-pinning)."""

    layout: str | None = None
    exchange: str | None = None
    chunk_elems: int | None = None
    fused_epilogue: bool | None = None
    in_kernel_gather: bool | None = None
    overlap: bool | None = None
    reg_solve_algo: str | None = None
    table_dtype: str | None = None
    solver: str | None = None
    gram_backend: str | None = None
    serve_batch_quantum: int | None = None
    serve_tile_m: int | None = None
    offload_tier: str | None = None
    ici_group: int | None = None
    staging: str | None = None
    hot_rows: int | None = None

    def __post_init__(self) -> None:
        for f, candidates in PLAN_FIELDS.items():
            v = getattr(self, f)
            if v is None:
                continue
            if f in _NUMERIC_FIELDS:
                # Numeric pins accept any positive value (the candidate
                # tuple is only the resolver's enumeration grid); the
                # _ZERO_OK_FIELDS additionally accept an explicit 0.
                floor = 0 if f in _ZERO_OK_FIELDS else 1
                if not isinstance(v, int) or v < floor:
                    raise PlanConstraintError(
                        f"constraint {f}={v!r} must be a positive int"
                        + (" (or 0 = off)" if floor == 0 else "")
                    )
            elif v not in candidates:
                raise PlanConstraintError(
                    f"constraint {f}={v!r} is not a known value; "
                    f"candidates: {candidates}"
                )

    def pinned(self) -> dict:
        return {f: getattr(self, f) for f in PLAN_FIELDS
                if getattr(self, f) is not None}

    def merge(self, other: "PlanConstraints") -> "PlanConstraints":
        """Combine two pin sets; the same field pinned to two different
        values is a CONFLICT (loud error naming both), not a silent win."""
        out = {}
        for f in PLAN_FIELDS:
            a, b = getattr(self, f), getattr(other, f)
            if a is not None and b is not None and a != b:
                raise PlanConstraintError(
                    f"conflicting constraints: {f}={a!r} vs {f}={b!r} — "
                    "unpin one side (an ALSConfig knob and an explicit "
                    "constraint must agree)"
                )
            out[f] = a if a is not None else b
        return PlanConstraints(**out)


def constraints_from_config(config) -> PlanConstraints:
    """An ``ALSConfig``'s explicit knobs, as pinned plan constraints.

    Concrete config fields pin (``layout``, ``table_dtype``, ``overlap``,
    ``exchange`` — their dataclass defaults are real values, so the
    default config pins them to today's behavior); tri-state knobs
    (``fused_epilogue``/``in_kernel_gather`` ``None``, ``reg_solve_algo``/
    ``solver`` ``"auto"``) stay free — those are exactly the knobs whose
    downstream resolution is bit-exact across choices, which is what keeps
    the default path bit-identical while the resolver prices them."""
    return PlanConstraints(
        layout=config.layout,
        exchange=config.exchange if config.exchange != "auto" else None,
        chunk_elems=(config.chunk_cells()
                     if config.hbm_chunk_elems is not None else None),
        fused_epilogue=config.fused_epilogue,
        in_kernel_gather=config.in_kernel_gather,
        overlap=bool(config.overlap),
        reg_solve_algo=(None if config.reg_solve_algo == "auto"
                        else config.reg_solve_algo),
        table_dtype=config.table_dtype,
        solver=None if config.solver == "auto" else config.solver,
        offload_tier=(None
                      if getattr(config, "offload_tier", "auto") == "auto"
                      else config.offload_tier),
        ici_group=getattr(config, "ici_group", None),
        # staging always pins (ISSUE 13): 'auto' resolves to the pool
        # deterministically (offload.staging.resolve_staging), so the
        # plan records the engine that actually runs.
        staging=("pool"
                 if getattr(config, "staging", "auto") == "auto"
                 else config.staging),
        # hot_rows: None (auto) stays FREE — the resolver's budget-
        # predicate axis decides; an explicit 0 (off) or count pins.
        hot_rows=getattr(config, "hot_rows", None),
    )


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """One fully-resolved execution: every knob concrete, plus the kernel
    backend per slot and the set of fields that were pinned (vs chosen by
    the cost model).  Frozen + hashable — safe as a jit-static and as a
    cache value."""

    layout: str
    exchange: str
    chunk_elems: int
    fused_epilogue: bool
    in_kernel_gather: bool
    overlap: bool
    reg_solve_algo: str
    table_dtype: str
    solver: str
    gram_backend: str
    serve_batch_quantum: int = 8
    serve_tile_m: int = 512
    # Out-of-core tier (ISSUE 11): "device" = HBM-resident factor tables,
    # "host_window" = host-RAM stores + device_put-pipelined windows
    # (cfk_tpu.offload) — gated by offload.budget's per-shard fit
    # predicate.
    offload_tier: str = "device"
    # Hierarchical-exchange inner-ring size (ISSUE 12); 0 = the device's
    # ICI domain (spmd.resolve_ici_group's physical default).
    ici_group: int = 0
    # Host staging engine of the host_window tier (ISSUE 13): "pool"
    # (concurrent per-(shard, window) staging, the default) | "serial".
    staging: str = "pool"
    # Hot-row device cache target of the host_window tier (ISSUE 15):
    # total resident rows across both sides (0 = off — the device tier's
    # only value, and the budget-refused resolution).
    hot_rows: int = 0
    # (slot, backend) pairs — "mosaic_tpu" | "xla_emulation" per kernel
    # slot (cfk_tpu.plan.registry.KERNEL_SLOTS).
    kernels: tuple = ()
    pinned: frozenset = frozenset()

    def knob_dict(self) -> dict:
        return {f: getattr(self, f) for f in PLAN_FIELDS}

    def kernel_backends(self) -> dict:
        return dict(self.kernels)

    def half_step_kwargs(self, config=None) -> dict:
        """The trainer-facing knob dict — the ONE seam the trainers read
        instead of poking ``ALSConfig`` fields directly.

        For a knob the caller's config left deferred (not pinned), this
        returns the config's own sentinel (``None``/``"auto"``) rather
        than the resolved concrete value: the downstream half-steps then
        resolve through the same process defaults as before the planner,
        so jit cache keys and bit-exactness are untouched (ROADMAP D13:
        nothing patches those defaults any more).  The resolved value is still visible in ``knob_dict``
        and in the provenance record.  A PINNED knob threads concrete.
        """
        pin = self.pinned
        return dict(
            overlap=self.overlap if "overlap" in pin else None,
            fused_epilogue=(self.fused_epilogue
                            if "fused_epilogue" in pin else None),
            in_kernel_gather=(self.in_kernel_gather
                              if "in_kernel_gather" in pin else None),
            reg_solve_algo=(self.reg_solve_algo
                            if "reg_solve_algo" in pin else "auto"),
            table_dtype=self.table_dtype,
            solver=self.solver if "solver" in pin else "auto",
        )

    def summary(self) -> str:
        """Compact one-line description (bench rows, metrics notes)."""
        kb = ",".join(f"{s}={b.split('_')[0]}" for s, b in self.kernels)
        tier = ("" if self.offload_tier == "device"
                else f"tier={self.offload_tier} ")
        if self.ici_group:
            tier += f"ici={self.ici_group} "
        if self.offload_tier == "host_window" and self.staging != "pool":
            tier += f"stage={self.staging} "
        if self.offload_tier == "host_window" and self.hot_rows:
            tier += f"hot={self.hot_rows} "
        return (f"{tier}{self.layout}/{self.exchange} "
                f"chunk={self.chunk_elems} "
                f"fused={'on' if self.fused_epilogue else 'off'} "
                f"gather={'fused' if self.in_kernel_gather else 'xla'} "
                f"overlap={'on' if self.overlap else 'off'} "
                f"algo={self.reg_solve_algo} table={self.table_dtype} "
                f"solver={self.solver} "
                f"serve_q={self.serve_batch_quantum} [{kb}]")

    def as_dict(self) -> dict:
        d = self.knob_dict()
        d["kernels"] = list(map(list, self.kernels))
        d["pinned"] = sorted(self.pinned)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ExecutionPlan":
        d = dict(d)
        kernels = tuple((s, b) for s, b in d.pop("kernels", ()))
        pinned = frozenset(d.pop("pinned", ()))
        known = {f: d[f] for f in PLAN_FIELDS if f in d}
        missing = set(PLAN_FIELDS) - set(known)
        if missing:
            raise ValueError(f"plan dict missing fields: {sorted(missing)}")
        return cls(**known, kernels=kernels, pinned=pinned)


@dataclasses.dataclass
class PlanProvenance:
    """Where a plan came from and what it was believed/measured to cost.

    Recorded in every bench row and checkpoint manifest that executes
    under a plan, so a regression is attributable to the DECISION that
    caused it (model mis-ranking, stale cache, forced fallback), not just
    the symptom.  ``transitions`` accumulates mid-run plan changes — a
    recovery-ladder rung or a kernel-backend outage is a plan transition
    now, recorded with the same vocabulary."""

    plan: ExecutionPlan
    source: str  # "model" | "pinned" | "autotune" | "autotune-cache"
    est_cost_s: float | None = None
    measured_s: float | None = None
    cache: str | None = None  # "hit" | "miss" | None (no cache consulted)
    explain: tuple = ()  # (field, value, reason) rows from the resolver
    transitions: list = dataclasses.field(default_factory=list)

    def record_transition(self, reason: str, detail: str) -> dict:
        t = {"reason": reason, "detail": detail,
             "index": len(self.transitions)}
        self.transitions.append(t)
        return t

    def summary(self) -> str:
        bits = [f"source={self.source}"]
        if self.est_cost_s is not None:
            bits.append(f"est={self.est_cost_s:.4g}s")
        if self.measured_s is not None:
            bits.append(f"measured={self.measured_s:.4g}s")
        if self.cache is not None:
            bits.append(f"cache={self.cache}")
        return f"{self.plan.summary()} ({' '.join(bits)})"

    def as_row(self) -> dict:
        """The bench-row provenance column(s) — flat, JSON-friendly."""
        row = {
            "plan": self.plan.summary(),
            "plan_source": self.source,
        }
        if self.est_cost_s is not None:
            row["plan_est_s"] = round(self.est_cost_s, 6)
        if self.measured_s is not None:
            row["plan_measured_s"] = round(self.measured_s, 6)
        if self.cache is not None:
            row["plan_cache"] = self.cache
        if self.transitions:
            row["plan_transitions"] = json.dumps(self.transitions)
        return row

    def as_meta(self) -> dict:
        """The checkpoint-manifest provenance record."""
        return {
            "plan": self.plan.as_dict(),
            "plan_source": self.source,
            "plan_est_s": self.est_cost_s,
            "plan_measured_s": self.measured_s,
            "plan_cache": self.cache,
            "plan_transitions": list(self.transitions),
        }
