"""Typed configuration for the framework.

The reference scatters its configuration across seven positional CLI args
copied into global mutable statics (``apps/ALSApp.java:17-22,41-48``) that are
read from processors and even the wire deserializer
(``serdes/FeatureMessage/FeatureMessageDeserializer.java:33``), plus a separate
shell script with its own copy of the partition count (``setup.sh``).  Here the
whole configuration is one frozen dataclass threaded explicitly.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Literal


_ASYNC_PERMUTE_FLAG = "xla_tpu_enable_async_collective_permute"


def set_async_collective_permute(mode: str) -> None:
    """Force XLA's async collective-permute pass on/off.

    The double-buffered ring schedule only hides its ICI transfers when the
    compiler splits each collective-permute into start/done pairs and lets
    independent compute run in between; this is the escape hatch when that
    pass itself is the suspect (e.g. an A/B against the serial schedule
    that wants the transfer synchronous at the compiler level too).

    The flag travels via ``LIBTPU_INIT_ARGS`` — parsed only when libtpu
    actually initializes a TPU backend, and silently unused everywhere
    else.  It must NOT go through ``XLA_FLAGS``: CPU/GPU-only XLA builds
    treat the TPU-only flag as unknown and ABORT the whole process at
    backend init (``parse_flags_from_env.cc: F Unknown flags`` — measured
    in this container, where libtpu is importable but the CPU backend
    parses the env).  libtpu reads the env at TPU init, so this must run
    BEFORE the first TPU computation — the CLI applies it at trainer
    entry, before the dataset load touches jax; the sharded trainers
    re-apply best-effort.  An existing occurrence of the flag is
    REWRITTEN to the requested value (an explicit on/off must win over
    leftovers from a previous experiment).  Idempotent; "auto" is a no-op
    (the compiler default already schedules collective permutes async on
    current TPU toolchains).
    """
    if mode == "auto":
        return
    if mode not in ("on", "off"):
        raise ValueError(f"unknown async_collective_permute {mode!r}")
    want = f"--{_ASYNC_PERMUTE_FLAG}={'true' if mode == 'on' else 'false'}"
    flags = os.environ.get("LIBTPU_INIT_ARGS", "")
    parts = [p for p in flags.split() if _ASYNC_PERMUTE_FLAG not in p]
    os.environ["LIBTPU_INIT_ARGS"] = " ".join(parts + [want])


def _jax_backend_initialized() -> bool:
    """Best-effort: has any XLA backend already been created?  Uses a
    private jax registry (the only signal there is); unknowable → False."""
    import sys

    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge

        return bool(getattr(xla_bridge, "_backends", None))
    except Exception:  # pragma: no cover - jax internals moved
        return False


def apply_overlap_xla_flags(config: "ALSConfig") -> None:
    """``set_async_collective_permute`` from a config (trainer entry).

    The sharded trainers run after a Mesh exists — i.e. after the backend
    initialized and libtpu already parsed LIBTPU_INIT_ARGS — so from there
    an explicit on/off can no longer take effect this process.  The env is
    still written (idempotent; helps forked workers), but a loud warning
    says to apply it earlier (the CLI does, before the dataset load; a
    library user should call ``set_async_collective_permute`` before the
    first jax computation)."""
    if config.async_collective_permute == "auto":
        return
    if _jax_backend_initialized():
        import warnings

        warnings.warn(
            f"async_collective_permute="
            f"{config.async_collective_permute!r} set after the jax "
            "backend initialized: libtpu has already parsed "
            "LIBTPU_INIT_ARGS, so this run keeps the compiler default — "
            "call cfk_tpu.config.set_async_collective_permute(...) before "
            "the first jax computation (the CLI does this) for it to "
            "take effect"
        )
    set_async_collective_permute(config.async_collective_permute)


# The persistent compilation cache when nothing else names one: ONE fixed,
# git-ignored directory at the root of the checkout.  Fixed because the path
# is part of jax's cache key discipline in practice — a directory that moves
# (a temp name, a pid, a timestamp) never hits — and named without asking a
# backend anything, so it exists before the first device is touched.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache",
)


def enable_compile_cache(cache_dir: str | None = None) -> str:
    """Turn on jax's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax itself uses it and
    nothing here names another directory — ``cache_dir`` (the
    ``ALSConfig.compile_cache_dir`` / ``--compile-cache-dir`` seam)
    included: the environment owns the location, because only a directory
    the machine was started with survives from one run to the next.
    Otherwise the cache lives at ``cache_dir``, else at
    ``DEFAULT_COMPILE_CACHE_DIR``.  jax's own keying (program, compile
    options, backend and device kind) keeps executables for different
    hardware apart inside one directory.

    The thresholds are lowered to cache every program — the fold-in/serve
    bucket programs this exists for compile in milliseconds each but number
    dozens per cold process.  Must run BEFORE the first compile to cover it
    (trainer/session/engine entries call this; jax ignores dir changes for
    programs already compiled).  Idempotent.  A directory that cannot be
    created or written raises: a silently cold cache costs every later run
    its compile time."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env_dir or cache_dir or DEFAULT_COMPILE_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    if not os.access(path, os.W_OK | os.X_OK):
        raise PermissionError(
            f"compile cache directory {path!r} is not writable"
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not env_dir and jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # jax latches "no cache" on the first compile that ran without a
        # dir; reset so the next compile initializes against this one.
        cc.reset_cache()
    return path


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    """Hyper-parameters + execution layout for a block-partitioned ALS run.

    Mirrors the reference CLI surface (``apps/ALSAppRunner.java:16-28``):
    NUM_PARTITIONS → ``num_shards``, NUM_FEATURES → ``rank``, LAMBDA → ``lam``,
    NUM_ITERATIONS → ``num_iterations``; NUM_MOVIES/NUM_USERS are derived from
    the data (the reference made users pass them by hand).
    """

    rank: int = 5
    lam: float = 0.05
    num_iterations: int = 7
    num_shards: int = 1
    seed: int = 42

    # Execution knobs (no analog in the reference — TPU-specific).
    # Storage/exchange dtype of the factor matrices: bfloat16 halves HBM and
    # ICI bytes; Gram accumulation and solves always run float32 internally.
    dtype: Literal["float32", "bfloat16"] = "float32"
    # How fixed-side factors travel between shards each half-iteration:
    #   "all_gather" — one all_gather over ICI, every shard sees full factors
    #                  (the all-to-all-join analog; OutBlock dedup comes free).
    #   "ring"       — ppermute ring, shards accumulate partial Gram matrices
    #                  block by block (the block-to-block-join analog; never
    #                  materializes the full fixed-side matrix per device).
    #                  Available for the padded and tiled layouts; tiled ring
    #                  datasets must be built with Dataset.from_coo(...,
    #                  ring=True).  BOTH halves ring — refused when a half's
    #                  per-entity ring accumulator could not fit (many solve
    #                  entities), which is exactly when all_gather is
    #                  strictly better there.
    #   "hier_ring"  — hierarchical ICI-ring-within-DCN-ring (tiled ring
    #                  datasets only, ISSUE 11): shards group into inner
    #                  rings of ``ici_group`` devices that rotate blocks
    #                  over the fast fabric, with ONE outer hop across the
    #                  slow fabric per phase — O·(I−1) ICI transfers and
    #                  O−1 DCN hops instead of a flat ring whose boundary
    #                  edges pay DCN every step.  Same blocks, same
    #                  accumulator structure as "ring"; with one inner
    #                  ring (ici_group == num_shards) the schedule — and
    #                  the factors — are bit-identical to "ring".
    #   "auto"       — per-HALF memory optimum (tiled layout only): ring on
    #                  the half whose fixed table is big and solve entities
    #                  few (movies at Netflix shape: rotate 480k-user blocks
    #                  instead of all_gathering them), all_gather on the
    #                  other (its ring accumulator would dwarf the table it
    #                  saves).  Build the dataset with Dataset.from_coo(...,
    #                  ring="auto").
    exchange: Literal["all_gather", "ring", "hier_ring", "auto"] = (
        "all_gather"
    )
    # Inner-ring size of the hierarchical exchange: devices per ICI
    # domain.  None = auto (jax.local_device_count() when it divides
    # num_shards, else one flat ring).  Must divide num_shards.
    ici_group: int | None = None
    # Communication/compute overlap — the default execution mode for every
    # ring-layout half-iteration and chunk-streaming body: ring steps are
    # double-buffered (the next block's ppermute is issued before the
    # current block's Gram consumes it) and chunk scans prefetch chunk c+1's
    # neighbor-factor gather while chunk c solves (cfk_tpu.ops.pipeline).
    # False pins the serial reference schedule (each phase drains before
    # the next starts) — the A/B baseline.
    # Factors are bit-identical either way (tests/test_overlap.py).
    overlap: bool = True
    # Fused Gram+solve epilogue: solve each chunk's normal equations INSIDE
    # the pallas Gram kernel's VMEM residency (ridge + lane-vectorized
    # elimination on the resident [Ec, k, k] batch), writing back only the
    # solved [Ec, k] factor rows — the split path's per-chunk A-batch HBM
    # write + readback disappears (cfk_tpu/ops/pallas/gram_kernel.py;
    # ARCHITECTURE.md "Fused Gram+solve epilogue").  None = the process
    # default (on wherever legal: pallas gram backend + pallas solver +
    # rank within the fused elimination's cap — LU 128 / GJ 64 — with
    # automatic fallback to the split schedule otherwise).  False pins the
    # split Gram→HBM→solve schedule in the tiled chunk scans (factors
    # bit-exact either way — the split chunk solve keeps the one-pass
    # reg+solve kernel, so only the round-trip toggles; the A/B
    # baseline) and gates the accum/ring paths' final fused
    # reg+solve pass.  The knob does not reach the segment/bucketed/
    # padded half-steps, whose solves follow the process default
    # (ops.solve.default_fused_epilogue) only.
    fused_epilogue: bool | None = None
    # In-kernel neighbor gather: fuse the per-chunk neighbor-factor gather
    # into the pallas Gram kernels — the fixed factor table stays in
    # HBM/ANY memory and the kernel DMAs each tile's indexed rows straight
    # into its VMEM double buffer, with the weighted (√aw) premultiply and
    # the padding zero row applied in-register, so the materialized [C, k]
    # gathered stream (HBM write + readback) disappears from the tiled
    # stream/dense/accum/ring chunk bodies (cfk_tpu/ops/pallas/gram_kernel
    # ``*_gather_pallas``; ARCHITECTURE.md "In-kernel neighbor gather").
    # None = the process default (on wherever supported: pallas Gram
    # backend + the kernels' SMEM/alignment gates, with automatic fallback
    # to the XLA-gather path otherwise — interpret/old-jax runs use the
    # emulation twin either way).  False pins the XLA-gather schedule (the
    # A/B baseline).  Factors are bit-identical across the knob
    # (tests/test_in_kernel_gather.py).
    in_kernel_gather: bool | None = None
    # HBM gather-table dtype (cfk_tpu.ops.quant; approximate-computing MF,
    # arXiv 1808.03843): the RAW fixed-side table each half-iteration
    # gathers from is stored "float32" (identity — bit-identical to
    # pre-quantization behavior), "bfloat16" (half the gather bytes), or
    # "int8" (a quarter, plus one f32 scale per row — symmetric per-row
    # quantization, the scale folded into the kernels' premultiply weight
    # so the dequantize rides the existing √aw/mask pass).  Gram/solve
    # accumulation stays float32 in-register for every choice, and the
    # SOLVED (master) factors keep ``dtype`` — this knob only shrinks the
    # gather operand, which is what the bytes-bound gather roofline
    # charges.  int8 needs the per-row scale threaded through a weight
    # stream, which the tiled and bucketed layouts have; padded/segment
    # support float32/bfloat16 only.  Ring exchanges rotate the quantized
    # payload (bf16 on both rings, int8+scale on the tiled ring).
    table_dtype: Literal["float32", "bfloat16", "int8"] = "float32"
    # Elimination algorithm of the fused reg+solve kernels: "lu" (reverse
    # no-pivot LU, rank cap 128) or "gj" (Gauss-Jordan, cap 64); "auto"
    # defers to the process default (ops.pallas.solve_kernel.
    # default_reg_solve_algo — the CFK_REG_SOLVE_ALGO env var's patch
    # point).  This is a real threaded parameter
    # (a jit-static on every half-step), which is how the recovery
    # ladder's GJ rung flips it now (cfk_tpu.resilience.policy) — it used
    # to ride the env var.
    reg_solve_algo: Literal["auto", "lu", "gj"] = "auto"
    # Escape hatch for XLA's async collective-permute scheduling on TPU —
    # the compiler pass that actually hides the ring's ppermute behind the
    # double-buffered Gram compute.  "auto" leaves the compiler default
    # (async on current XLA); "on"/"off" force the flag via
    # LIBTPU_INIT_ARGS (``apply_overlap_xla_flags`` — must run before TPU
    # backend init to take effect, which the sharded trainers attempt
    # best-effort; harmless off-TPU, where libtpu never parses it).
    async_collective_permute: Literal["auto", "on", "off"] = "auto"
    # --- HBM bounding: ONE knob ------------------------------------------
    # Every layout bounds the same quantity — the transient neighbor-factor
    # gather feeding the MXU — by streaming solves through HBM in chunks.
    # ``hbm_chunk_elems`` is that budget in gather *cells* (rows × width ≈
    # ratings per chunk) for every layout:
    #   - padded: consumed at solve time — entities per chunk are derived
    #     as ``hbm_chunk_elems // rectangle_width`` (see
    #     ``padded_solve_chunk``);
    #   - bucketed/segment/tiled: consumed at dataset build time — pass it
    #     as ``Dataset.from_coo(..., chunk_elems=cfg.chunk_cells())`` (the
    #     CLI's --chunk-elems does); the chunk hints then live statically
    #     on the blocks.
    # None = layout defaults (padded: whole shard at once; build-time
    # layouts: the 1M-cell default).
    hbm_chunk_elems: int | None = None
    # DEPRECATED alias: entities per padded-layout solve chunk, overriding
    # the derived value.  Use hbm_chunk_elems.
    solve_chunk: int | None = None
    # Batched k×k SPD solve backend: "cholesky" = ops.solve.batched_spd_solve
    # (XLA custom calls; on a TPU the lane-batched Cholesky kernel for
    # float32 systems of k <= 128, k % 8 == 0);
    # "pallas" = lane-vectorized Gauss-Jordan TPU kernel (cfk_tpu.ops.pallas);
    # "auto" = pallas on TPU for ranks within the kernel's VMEM budget
    # (~1.7× faster end-to-end at full-Netflix scale than XLA's batched
    # cholesky/triangular custom calls, latency-bound at small k),
    # cholesky everywhere else (CPU interpret-mode pallas is test-only slow).
    solver: Literal["auto", "cholesky", "pallas"] = "auto"
    # Pad ragged neighbor lists up to a multiple of this (MXU-friendly tiling).
    # Consumed wherever blocks are built from this config (ring-block builds,
    # CLI/bench dataset construction); pass it to Dataset.from_coo when
    # building datasets by hand.
    pad_multiple: int = 8
    # InBlock memory layout:
    #   "padded"   — one [E, max_nnz] rectangle per side. Simple and fastest
    #                up to medium scale, but pads every entity to the global
    #                max degree — quadratic waste on power-law data.
    #   "bucketed" — power-of-two width classes (the ALX layout); total
    #                padded cells stay within ~2× nnz, required at full
    #                Netflix-Prize scale. all_gather exchange only.
    #   "segment"  — flat CSR-style runs scanned in fixed-size nnz chunks;
    #                Gram matrices accumulate by grouped ragged matmul on the
    #                MXU, and entities hotter than one chunk straddle chunks
    #                via a carried partial Gram. Exactly O(nnz) memory for
    #                arbitrarily skewed degree distributions. all_gather only.
    #   "tiled"    — segment layout with entity runs padded to [T]-row tiles:
    #                Grams become one batched tile GEMM + a tiny segment-sum,
    #                and the few-entity side gathers from dynamic table
    #                slices (the big-table gather cliff). ~2× faster than
    #                "segment" at full-Netflix scale — the at-scale default.
    #                all_gather exchange only.
    layout: Literal["padded", "bucketed", "segment", "tiled"] = "padded"
    # DEPRECATED alias for hbm_chunk_elems (the build-time consumption is
    # described there); retained so round-2 configs keep working.
    bucket_chunk_elems: int = 1 << 20
    # Per-entity optimizer.  "als" = the reference's exact full k×k normal-
    # equation solve every half-iteration.  "als++" = warm-started subspace
    # block coordinate descent (the explicit-feedback analog of iALS++,
    # cfk_tpu/ops/subspace.py): per coordinate block B solve
    # A[B,B]δ = −g[B] with ALS-WR's λ·n·I regularization; with
    # block_size == rank one sweep equals the full solve exactly.  Cheaper
    # per epoch at large rank, but a different per-epoch trajectory — the
    # reference-parity path stays "als".  padded/bucketed layouts only.
    algorithm: str = "als"
    block_size: int = 32
    sweeps: int = 1
    # --- self-healing (cfk_tpu.resilience) -------------------------------
    # Numerical-health sentinel cadence: probe the factor state (isfinite
    # reductions + max-row-norm watchdogs, O(E·k) — measured < 2% s/iter
    # at health_check_every=1 on the bench dense-stream config) every N
    # completed iterations.  None disables the sentinel entirely; the
    # fused single-device loop then stays a pure fori_loop and the stepped
    # loops skip the probe fetch.  Must be >= 1 when set.
    health_check_every: int | None = None
    # Factor-row 2-norm above which the watchdog trips even though every
    # value is still finite — catches the slow blow-up that precedes
    # overflow by several iterations (divergence is cheapest to fix early).
    health_norm_limit: float = 1e6
    # Recovery ladder bounds (cfk_tpu.resilience.policy): total sentinel
    # trips tolerated before the run stops retrying; each trip rolls back
    # to the last good checkpoint and climbs one escalation rung
    # (retry → λ×lam_escalation → split epilogue → GJ elimination — the
    # default of 4 makes the full ladder reachable before degrading).
    max_recoveries: int = 4
    lam_escalation: float = 10.0
    # When retries are exhausted: "degrade" returns the last-good factors
    # with a diagnostic report in the metrics (production default — a
    # stale model beats no model), "raise" raises TrainingDivergedError.
    on_unrecoverable: Literal["degrade", "raise"] = "degrade"
    # --- execution planner (cfk_tpu.plan, ISSUE 9) -----------------------
    # How the trainers resolve their ExecutionPlan.  Every CONCRETE knob
    # above becomes a pinned constraint (plan.constraints_from_config), so
    # the CLI surface is unchanged and the default config's execution is
    # bit-identical across modes; the planner prices the knobs the config
    # left deferred (None/"auto") and records provenance either way.
    #   "model"    — cost-model resolution of the free knobs (default;
    #                today's free knobs are bit-exact across choices).
    #   "pinned"   — no optimization: pins + legacy process defaults (the
    #                pre-planner behavior, still recorded as a plan).
    #   "autotune" — consult the measured-winner cache (warmed offline by
    #                `cfk_tpu plan --autotune`); model fallback
    #                with cache=miss provenance when cold.  Trainers never
    #                measure inline.
    plan: Literal["model", "pinned", "autotune"] = "model"
    # --- out-of-core factor tables (cfk_tpu.offload, ISSUE 11) ----------
    # Where the factor tables live during training:
    #   "auto"        — the planner decides via the memory-budget predicate
    #                   (cfk_tpu.offload.budget): resident while both
    #                   tables + blocks fit the device budget (today's
    #                   behavior, bit-identical), host_window past it.
    #   "device"      — pin HBM-resident tables; the planner REFUSES
    #                   (PlanConstraintError) when the budget predicate
    #                   says they cannot fit, instead of promising an OOM.
    #   "host_window" — pin the out-of-core path: host-RAM factor stores
    #                   with device_put-pipelined windows
    #                   (offload.windowed.train_als_host_window — explicit
    #                   ALS, tiled layout; sharded too, per-shard windows
    #                   under the all_gather scan or the ring/hier_ring
    #                   visit schedules with int8 (codes, scales) PCIe
    #                   staging; bit-exact vs the resident paths).
    offload_tier: Literal["auto", "device", "host_window"] = "auto"
    # --- host staging engine (cfk_tpu.offload.staging, ISSUE 13) --------
    # How the host_window tier's windows are staged (gather + quantize +
    # checksum + device_put):
    #   "auto"/"pool" — ONE bounded thread pool per half-iteration stages
    #                   every shard's windows ahead of consumption, so
    #                   shard d+1's host-side window work overlaps shard
    #                   d's compute (the ALX per-shard transfer pipeline's
    #                   host half; the default, like PR 1's overlap).
    #   "serial"      — the PR 10/11 single-thread double buffer (the
    #                   A/B baseline).
    # Factors are crc-identical across the knob (the staging order never
    # changes the consumption order — tests/test_offload_sharded.py).
    staging: Literal["auto", "pool", "serial"] = "auto"
    # Staged-ahead windows beyond the one being consumed (pool mode).
    # None = offload.staging.DEFAULT_POOL_DEPTH; always clamped so
    # depth+1 worst-case windows fit the per-shard window budget next to
    # the ring accumulator reservation (offload.budget.max_pool_depth).
    staging_pool_depth: int | None = None
    # --- skew-aware hot-row device cache (cfk_tpu.offload.hot, ISSUE 15)
    # The host_window tier keeps the top-f fixed-table rows (by cross-
    # window reference count — the power-law head) device-resident at
    # the staging dtype, and windows stage only their COLD DELTA vs the
    # schedule predecessor:
    #   None  — AUTO: f from the coverage-curve knee of the window
    #           plans' own reference counts, clamped by the budget
    #           headroom left after the accumulator/window/delta-arena
    #           reservations (resolves to 0 — off — when headroom or
    #           skew refuses).
    #   0     — OFF: byte-for-byte the PR 12 full-staging engine.
    #   >= 1  — pin the TOTAL resident rows across both sides; an
    #           impossible reservation raises loudly (planner AND
    #           executor, offload.budget.hot_reservation_fits).
    # Factors are crc-identical across the knob (assembled windows are
    # bitwise the fully-staged ones); only staged PCIe bytes change.
    hot_rows: int | None = None
    # --- warm-start compile caching (ISSUE 13) --------------------------
    # Directory for jax's persistent compilation cache; trainers/serving/
    # streaming apply it at entry via enable_compile_cache(), BEFORE their
    # first compile.  None = the fixed in-checkout default
    # (DEFAULT_COMPILE_CACHE_DIR).  Where JAX_COMPILATION_CACHE_DIR is set
    # the environment wins and this field is not consulted.  Cold-process
    # time-to-first-step/batch is what the cache buys; trace counts are
    # unchanged (tracing is jax-side — the cache removes the XLA compile
    # behind each trace).
    compile_cache_dir: str | None = None
    # --- elastic fleet membership (ISSUE 20) ----------------------------
    # Multi-process host_window training survives a dead peer live: a
    # collective failure triggers the shrink protocol (min-agree the
    # covered step, repartition ownership, reload the orphan slice,
    # continue) instead of the bounded exit, and a restarted host can
    # rejoin at an iteration boundary.  None = AUTO: elastic when a
    # fleet-manifests directory is available (the protocol needs the
    # per-host manifests to agree and reload), off otherwise.
    fleet_elastic: bool | None = None
    # Transient-vs-fatal peer classification: a fleet collective that
    # fails with a retryable error (slow GC pause, dropped packet) is
    # retried with backoff+jitter up to fleet_retry_attempts times
    # before the peer is declared dead and the shrink fires.
    fleet_retry_attempts: int = 2
    fleet_retry_base_s: float = 0.05
    fleet_retry_max_delay_s: float = 1.0
    # A collective that HANGS (no error) is declared dead after this
    # many seconds — SIGKILL'd Gloo peers sometimes hang the survivor
    # rather than erroring.  None disables the timeout (the
    # StallWatchdog remains the outer backstop).
    fleet_collective_timeout_s: float | None = None

    def _valid_algorithms(self) -> tuple[str, ...]:
        return ("als", "als++")

    def _check_host_window(self) -> None:
        """The per-family ``offload_tier='host_window'`` gate.  The
        explicit base family streams the tiled stream-mode layout under
        explicit ALS; ``IALSConfig`` overrides for the bucketed
        width-class windows (ISSUE 19)."""
        if self.layout != "tiled":
            raise ValueError(
                f"offload_tier='host_window' streams the tiled "
                f"stream-mode layout; layout={self.layout!r}"
            )
        if self.algorithm != "als":
            raise ValueError(
                "offload_tier='host_window' supports the explicit ALS "
                f"optimizer at layout='tiled'; algorithm="
                f"{self.algorithm!r} (the subspace als++ windowed walk "
                "is the documented follow-up — the implicit family's "
                "iALS/iALS++ run out-of-core via IALSConfig)"
            )

    def chunk_cells(self) -> int:
        """The gather-cell budget for build-time layouts: the one knob
        (``hbm_chunk_elems``) when set, else the deprecated
        ``bucket_chunk_elems`` (whose default is the historical 1M)."""
        if self.hbm_chunk_elems is not None:
            return self.hbm_chunk_elems
        return self.bucket_chunk_elems

    def padded_solve_chunk(self, width: int) -> int | None:
        """Entities per padded-layout solve chunk under the cell budget.

        The deprecated explicit ``solve_chunk`` (entity units) wins when
        set; otherwise ``hbm_chunk_elems // width`` — the same budget the
        build-time layouts consume, derived for a rectangle ``width``
        columns wide.  None = solve the whole shard at once."""
        if self.solve_chunk is not None:
            return self.solve_chunk
        if self.hbm_chunk_elems is None:
            return None
        return max(1, self.hbm_chunk_elems // max(width, 1))

    def __post_init__(self) -> None:
        if self.async_collective_permute not in ("auto", "on", "off"):
            raise ValueError(
                "unknown async_collective_permute "
                f"{self.async_collective_permute!r}"
            )
        if self.fused_epilogue not in (None, True, False):
            raise ValueError(
                f"fused_epilogue must be None/True/False, got "
                f"{self.fused_epilogue!r}"
            )
        if self.in_kernel_gather not in (None, True, False):
            raise ValueError(
                f"in_kernel_gather must be None/True/False, got "
                f"{self.in_kernel_gather!r}"
            )
        if self.table_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                f"table_dtype must be 'float32', 'bfloat16' or 'int8', "
                f"got {self.table_dtype!r}"
            )
        if self.table_dtype == "int8" and self.layout not in (
            "tiled", "bucketed"
        ):
            # Mirrors ops.quant.validate_table_dtype_layout (kept inline so
            # config stays importable without jax): int8 needs the per-row
            # dequant scale folded into a weight stream, which only the
            # tiled/bucketed formulations carry.
            raise ValueError(
                f"table_dtype='int8' supports layout='tiled'/'bucketed' "
                f"(the per-row scale rides their weight streams); "
                f"layout={self.layout!r} should use 'bfloat16' or 'float32'"
            )
        if self.reg_solve_algo not in ("auto", "lu", "gj"):
            raise ValueError(
                f"reg_solve_algo must be 'auto', 'lu' or 'gj', got "
                f"{self.reg_solve_algo!r}"
            )
        if self.rank < 1:
            raise ValueError(f"rank must be >= 1, got {self.rank}")
        if self.num_iterations < 1:
            raise ValueError(f"num_iterations must be >= 1, got {self.num_iterations}")
        if self.num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {self.num_shards}")
        if self.lam < 0:
            raise ValueError(f"lam must be >= 0, got {self.lam}")
        if self.exchange not in ("all_gather", "ring", "hier_ring", "auto"):
            raise ValueError(f"unknown exchange {self.exchange!r}")
        if self.exchange == "hier_ring" and self.layout != "tiled":
            raise ValueError(
                f"exchange='hier_ring' is implemented for layout='tiled' "
                f"(the ring-built tiled blocks); layout={self.layout!r}"
            )
        if self.ici_group is not None:
            if self.ici_group < 1:
                raise ValueError(
                    f"ici_group must be >= 1 (devices per inner ring), "
                    f"got {self.ici_group}"
                )
            if self.num_shards % self.ici_group != 0:
                raise ValueError(
                    f"ici_group={self.ici_group} must divide "
                    f"num_shards={self.num_shards} (the outer ring walks "
                    "whole inner rings)"
                )
        if self.offload_tier not in ("auto", "device", "host_window"):
            raise ValueError(
                f"offload_tier must be 'auto', 'device' or 'host_window', "
                f"got {self.offload_tier!r}"
            )
        if self.staging not in ("auto", "pool", "serial"):
            raise ValueError(
                f"staging must be 'auto', 'pool' or 'serial', got "
                f"{self.staging!r}"
            )
        if self.staging_pool_depth is not None and self.staging_pool_depth < 1:
            raise ValueError(
                f"staging_pool_depth must be >= 1 (windows staged ahead "
                f"of consumption), got {self.staging_pool_depth}; use "
                "staging='serial' for the unpooled baseline"
            )
        if self.hot_rows is not None and self.hot_rows < 0:
            raise ValueError(
                f"hot_rows must be None (auto), 0 (off) or a positive "
                f"total resident row count, got {self.hot_rows}"
            )
        if self.offload_tier == "host_window":
            # Family hook: explicit ALS streams the tiled stream-mode
            # layout; the implicit family (IALSConfig) overrides with the
            # bucketed width-class gate (ISSUE 19).  Sharded host_window
            # is supported (ISSUE 12): the windowed driver runs per-shard
            # staged windows under the all_gather scan or the
            # ring/hier_ring visit schedules — no shard-count restriction
            # here; exchange/layout rules below still apply.
            self._check_host_window()
        if self.solver not in ("auto", "cholesky", "pallas"):
            raise ValueError(f"unknown solver {self.solver!r}")
        if self.layout not in ("padded", "bucketed", "segment", "tiled"):
            raise ValueError(f"unknown layout {self.layout!r}")
        if self.layout not in ("padded", "tiled") and self.exchange == "ring":
            raise ValueError(
                f"layout={self.layout!r} supports exchange='all_gather' only"
            )
        if self.exchange == "auto" and self.layout != "tiled":
            raise ValueError(
                "exchange='auto' (per-half ring/all_gather selection) "
                f"applies to layout='tiled'; layout={self.layout!r} should "
                "pick 'all_gather' or 'ring' explicitly"
            )
        if self.health_check_every is not None and self.health_check_every < 1:
            raise ValueError(
                f"health_check_every must be >= 1 (iterations between "
                f"sentinel probes), got {self.health_check_every}; use "
                "health_check_every=None to disable the health sentinel"
            )
        if self.health_norm_limit <= 0:
            raise ValueError(
                f"health_norm_limit must be > 0 (a factor-row 2-norm "
                f"bound), got {self.health_norm_limit}"
            )
        if self.max_recoveries < 0:
            raise ValueError(
                f"max_recoveries must be >= 0, got {self.max_recoveries}"
            )
        if self.fleet_retry_attempts < 0:
            raise ValueError(
                f"fleet_retry_attempts must be >= 0 (retries before a "
                f"peer is declared dead), got {self.fleet_retry_attempts}"
            )
        if self.fleet_retry_base_s <= 0:
            raise ValueError(
                f"fleet_retry_base_s must be > 0, got "
                f"{self.fleet_retry_base_s}"
            )
        if self.fleet_retry_max_delay_s < self.fleet_retry_base_s:
            raise ValueError(
                f"fleet_retry_max_delay_s must be >= fleet_retry_base_s, "
                f"got {self.fleet_retry_max_delay_s} < "
                f"{self.fleet_retry_base_s}"
            )
        if (self.fleet_collective_timeout_s is not None
                and self.fleet_collective_timeout_s <= 0):
            raise ValueError(
                f"fleet_collective_timeout_s must be > 0 (or None to "
                f"disable), got {self.fleet_collective_timeout_s}"
            )
        if self.lam_escalation <= 1:
            raise ValueError(
                f"lam_escalation must be > 1 (it multiplies λ on "
                f"escalation), got {self.lam_escalation}"
            )
        if self.on_unrecoverable not in ("degrade", "raise"):
            raise ValueError(
                f"on_unrecoverable must be 'degrade' or 'raise', got "
                f"{self.on_unrecoverable!r}"
            )
        if self.plan not in ("model", "pinned", "autotune"):
            raise ValueError(
                f"plan must be 'model', 'pinned' or 'autotune', got "
                f"{self.plan!r}"
            )
        if self.hbm_chunk_elems is not None and self.hbm_chunk_elems < 1:
            raise ValueError(
                f"hbm_chunk_elems must be >= 1, got {self.hbm_chunk_elems}"
            )
        if self.layout != "padded" and self.solve_chunk is not None:
            raise ValueError(
                f"solve_chunk (deprecated) applies to layout='padded' "
                f"only; use hbm_chunk_elems — one budget for every layout "
                f"(build-time layouts consume it via Dataset.from_coo(..., "
                "chunk_elems=cfg.chunk_cells()), which the CLI's "
                "--chunk-elems does)"
            )
        if self.algorithm not in self._valid_algorithms():
            raise ValueError(
                f"unknown algorithm {self.algorithm!r} for "
                f"{type(self).__name__}; valid: {self._valid_algorithms()}"
            )
        if self.algorithm != "als":
            if self.layout in ("segment", "tiled"):
                raise ValueError(
                    f"{self.algorithm} supports the padded and bucketed "
                    f"layouts (bucketed is the at-scale one); the "
                    f"{self.layout} layout's chunk-straddling entities "
                    "would need cross-chunk score updates — use "
                    "layout='bucketed'"
                )
            if self.rank % self.block_size != 0:
                raise ValueError(
                    f"rank {self.rank} not divisible by block_size "
                    f"{self.block_size}"
                )
            if self.sweeps < 1:
                raise ValueError(f"sweeps must be >= 1, got {self.sweeps}")
            if self.exchange != "all_gather":
                raise ValueError(
                    f"{self.algorithm} supports exchange='all_gather' only"
                )
            if self.solve_chunk is not None:
                raise ValueError(
                    f"solve_chunk is not honored by {self.algorithm} (the "
                    "subspace sweep has no entity-chunked padded path); use "
                    "layout='bucketed' with chunk_elems to bound HBM"
                )
