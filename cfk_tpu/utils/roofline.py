"""Model-FLOP / HBM-byte accounting and MFU for the ALS iteration.

The reference has no notion of compute efficiency — its hot loop is a
per-entity EJML solve (``processors/MFeatureCalculator.java:85-99``) and its
only telemetry is wall-clock milliseconds.  On TPU the honest yardstick is
the hardware: model FLOPs per iteration over the chip's peak (MFU), and the
minimum HBM traffic over measured bandwidth (roofline).  The planner's cost
model prices from these counts (``plan/cost.py``, ``plan/spec.py``).

Conventions
-----------
- *Model FLOPs* count the algorithmic minimum, independent of backend: the
  Gram/RHS contractions (2 FLOPs per MAC) plus one Cholesky-cost solve per
  entity.  Implementation overhead (the pallas Gauss-Jordan's 2k³ vs
  Cholesky's k³/3, padding waste, masked lanes) deliberately does NOT count —
  MFU measures useful work extracted from the chip.
- *Min HBM bytes* count each operand's unavoidable traffic once: the random
  neighbor-factor gathers, one read of the block arrays, one write+read of
  the per-entity Gram/RHS intermediates (they cross an op boundary into the
  solve), and the factor write-back.  Fusion can only approach this from
  above; the gap between the measured iteration and ``min_bytes / bandwidth``
  is the tractable inefficiency.
"""

from __future__ import annotations

import dataclasses

@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """One chip's peaks, as the roofline and the cost model charge them."""

    peak_bf16_flops: float  # per second
    hbm_bytes_per_s: float
    hbm_bytes: int
    gather_rows_per_s: float  # XLA row-gather engine, rows per second
    source: str


# The ONE peaks table, keyed by ``jax.devices()[0].device_kind``.  A device
# that is not here is an error (``device_peaks``), never a default: an MFU or
# a roofline share over another chip's peak is not a number.
DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        peak_bf16_flops=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16 * 1024**3,
        # XLA's row-gather engine sustained ~600M rows/s on ≤34 MB tables
        # REGARDLESS of row width (64-col bf16 and 128-col rows timed
        # identically) — row-slot-bound, not byte-bound.  ALS is two
        # gathers per rating per iteration, which makes THIS the binding
        # resource at full Netflix scale: the row-gather floor (~0.36
        # s/iter) sits 6.7× above the naive HBM roofline (54 ms).
        gather_rows_per_s=600e6,
        source=(
            "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
            "16 GB HBM at 819 GB/s per chip; gather rate measured on one "
            "chip (pre-ledger round 3, 2026-08-01, earlier toolchain, "
            "not re-measured: PERF.md section 8)"
        ),
    ),
}
# Names people type for a table row (``cfk_tpu plan --device v5e``).
DEVICE_KIND_ALIASES = {"v5e": "TPU v5 lite"}


def _peaks_or_none(device_kind: str) -> DevicePeaks | None:
    return DEVICE_PEAKS.get(DEVICE_KIND_ALIASES.get(device_kind, device_kind))


def device_peaks(device_kind: str) -> DevicePeaks:
    """The table row for ``device_kind`` (or an alias of it); raises for a
    device without published peaks."""
    pk = _peaks_or_none(device_kind)
    if pk is None:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)} — add a row to "
            "cfk_tpu.utils.roofline.DEVICE_PEAKS with its source before "
            "reporting an efficiency against it"
        )
    return pk


@dataclasses.dataclass(frozen=True)
class IterationCost:
    """Per-full-iteration (both half-steps) model cost of one ALS sweep."""

    model_flops: float
    min_hbm_bytes: float
    gather_rows: float  # factor rows fetched by index per iteration
    gather_bytes: float = 0.0  # bytes those row fetches move (table dtype)

    def achieved_tflops(self, seconds: float) -> float:
        return self.model_flops / seconds / 1e12

    def mfu(self, seconds: float, peak_flops: float) -> float:
        return self.model_flops / seconds / peak_flops

    def hbm_bound_s(self, bandwidth: float) -> float:
        """Naive roofline floor: minimum HBM traffic over peak bandwidth."""
        return self.min_hbm_bytes / bandwidth

    def gather_bound_s(self, rows_per_s: float, bandwidth: float) -> float:
        """Gather floor: the binding resource for ALS on this chip.

        Every rating needs its neighbor's factor row on each side every
        iteration.  Two sub-floors, the floor is their max:

        - row-slot: the measured engine rate is per ROW, independent of
          row bytes (XLA's gather engine; the in-kernel DMA gather issues
          one descriptor per row, so rows/s bounds it the same way), and
        - bytes: the rows must still physically cross HBM —
          ``gather_bytes / bandwidth``.  This is the sub-floor the table
          dtype moves (bf16 halves it, int8+scale quarters it); the
          row-slot sub-floor is dtype-independent, which is exactly why
          ``vs_gather_roofline`` must model both or quantized runs would
          be compared against a floor they can no longer touch.
        """
        return max(self.gather_rows / rows_per_s,
                   self.gather_bytes / bandwidth)


FULL_NETFLIX_NNZ = 100_480_507


_NOT_MEASURED = "not measured: no published peaks for device_kind {!r}"


def this_device_kind() -> str:
    """``device_kind`` of the device this process computes on."""
    import jax

    return jax.devices()[0].device_kind


def roofline_row(cost: IterationCost, s_per_iter: float,
                 table_dtype: str | None = None, *,
                 device_kind: str) -> dict:
    """The model-cost and efficiency fields every recorded benchmark row
    carries.  The counts (model FLOPs, minimum bytes) hold on any device;
    the efficiencies are taken against the peaks of ``device_kind`` and, on
    a device without published peaks (the CPU backend the tests run on),
    left out with a ``"roofline": "not measured…"`` note — a CPU timing
    over a TPU's peak is not a slower version of that number.

    Its callers were the deleted measurement scripts (ROADMAP D11).
    ``table_dtype``
    records the gather-table quantization the run used (None → float32
    pre-quantization semantics are NOT implied — pass what the run ran)."""
    row = {
        "device_kind": device_kind,
        "model_tflops_per_iter": round(cost.model_flops / 1e12, 4),
        "min_hbm_gb_per_iter": round(cost.min_hbm_bytes / 1e9, 3),
        "gather_gb_per_iter": round(cost.gather_bytes / 1e9, 3),
    }
    pk = _peaks_or_none(device_kind)
    if pk is None:
        row["roofline"] = _NOT_MEASURED.format(device_kind)
    else:
        hbm_s = cost.hbm_bound_s(pk.hbm_bytes_per_s)
        gather_s = cost.gather_bound_s(pk.gather_rows_per_s,
                                       pk.hbm_bytes_per_s)
        row.update({
            "achieved_tflops": round(cost.achieved_tflops(s_per_iter), 4),
            "mfu": round(cost.mfu(s_per_iter, pk.peak_bf16_flops), 5),
            "hbm_roofline_s": round(hbm_s, 4),
            "vs_hbm_roofline": round(s_per_iter / hbm_s, 2),
            "gather_roofline_s": round(gather_s, 4),
            "vs_gather_roofline": round(s_per_iter / gather_s, 2),
        })
    if table_dtype is not None:
        row["table_dtype"] = table_dtype
    return row


def table_gather_bytes_per_row(rank: int, table_dtype: str | None,
                               factor_bytes: int = 4) -> float:
    """Bytes one gathered factor row moves under the given table dtype —
    k cells at the table itemsize, plus the int8 scheme's one f32 scale
    per row (``ops.quant``).  ``table_dtype="float32"`` is the quant
    IDENTITY — the table stays at the storage dtype — so the effective
    cell size is min(table, storage): a bf16-stored f32-table run still
    gathers 2-byte cells."""
    from cfk_tpu.ops.quant import resolve_table_dtype, table_itemsize

    per_row = rank * min(table_itemsize(table_dtype), factor_bytes)
    if resolve_table_dtype(table_dtype) == "int8":
        per_row += 4  # the per-row f32 dequant scale rides along
    return float(per_row)


def bucketed_gather_rows(movie_blocks, user_blocks) -> float:
    """Honest gather-row count for the bucketed layout: every PADDED cell
    of every width class fetches a row (padding slots gather the clamped /
    zero row like any other — the engine charges the slot), so the floor
    is Σ rows·width per class per side, not 2·nnz, which understates the
    floor by the padding ratio (~1.3–2× on power-law data)."""
    return float(movie_blocks.padded_cells + user_blocks.padded_cells)


@dataclasses.dataclass(frozen=True)
class ServeBatchCost:
    """Per-scoring-batch model cost of the top-K serve path (ISSUE 8).

    The serve kernel's traffic model is simple and strict: every batch
    scans the ENTIRE item factor table exactly once (movie-axis tiles
    streamed through VMEM — there is no reuse across batches to model,
    and no dense [B, M] score matrix to charge because none exists), plus
    the [B, k] batch in and the [B, K] selection out.  The table scan is
    what the quantized-table dtypes shrink — bf16 halves it, int8+scale
    quarters it — which is why ``vs_roofline`` must be computed against
    the dtype-aware floor or quantized rows would be compared against a
    floor they can no longer touch (the same honesty rule as the gather
    roofline)."""

    model_flops: float  # 2·B·M_pad·k score MACs (the merge is negligible)
    hbm_bytes: float  # table scan + batch in + [B, K] out

    def flops_bound_s(self, peak: float) -> float:
        return self.model_flops / peak

    def bytes_bound_s(self, bandwidth: float) -> float:
        return self.hbm_bytes / bandwidth

    def batch_bound_s(self, peak: float, bandwidth: float) -> float:
        """The floor is max(compute, bytes): at serving batch sizes the
        table scan dominates (B ≪ M), so the roofline QPS is essentially
        batch · bandwidth / table_bytes — bigger batches and smaller
        table dtypes are THE two levers."""
        return max(self.flops_bound_s(peak), self.bytes_bound_s(bandwidth))


def serve_batch_cost(num_movies: int, rank: int, batch: int, k_top: int,
                     *, table_dtype: str | None = None,
                     m_pad: int | None = None) -> ServeBatchCost:
    """Model cost of one [batch, k_top] top-K scoring batch.

    ``m_pad`` is the padded table row count actually scanned (tile/shard
    padding scans too — charge what the kernel reads); the per-row bytes
    follow the table dtype exactly like the gather floor
    (``table_gather_bytes_per_row`` — int8 is charged codes PLUS the
    per-row f32 scale, never a flat 1 B/row)."""
    row_bytes = table_gather_bytes_per_row(rank, table_dtype)
    io_bytes = batch * rank * 4.0 + batch * k_top * 8.0
    rows = float(m_pad if m_pad is not None else num_movies)
    flops = 2.0 * batch * rows * rank
    table_bytes = rows * row_bytes
    return ServeBatchCost(
        model_flops=flops, hbm_bytes=table_bytes + io_bytes
    )


def serve_roofline_row(cost: ServeBatchCost, s_per_batch: float,
                       table_dtype: str | None = None, *,
                       device_kind: str) -> dict:
    """The model-cost and efficiency fields of a serve batch (the same
    no-peaks rule as ``roofline_row``; its callers were the deleted
    measurement scripts: ROADMAP D11)."""
    row = {
        "device_kind": device_kind,
        "serve_batch_tflops": round(cost.model_flops / 1e12, 6),
        "serve_batch_mb": round(cost.hbm_bytes / 1e6, 3),
        # the batch's HBM traffic: the table scan + io
        "bytes_scanned_per_batch": round(cost.hbm_bytes),
    }
    pk = _peaks_or_none(device_kind)
    if pk is None:
        row["roofline"] = _NOT_MEASURED.format(device_kind)
    else:
        floor = cost.batch_bound_s(pk.peak_bf16_flops, pk.hbm_bytes_per_s)
        row["serve_roofline_s"] = round(floor, 6)
        row["vs_roofline"] = round(s_per_batch / floor, 2)
    if table_dtype is not None:
        row["table_dtype"] = table_dtype
    return row


def als_iteration_cost(
    nnz: int,
    num_users: int,
    num_movies: int,
    rank: int,
    *,
    factor_bytes: int = 2,  # bf16 storage
    implicit: bool = False,
    table_dtype: str | None = None,  # gather-table quantization (ops.quant)
    gather_rows: float | None = None,  # layout-aware row count override
    sweeps: int = 1,  # subspace sweeps per half-iteration (iALS++/ALS++)
) -> IterationCost:
    """Model FLOPs + minimum HBM bytes for one full ALS(-WR / iALS) iteration.

    FLOPs:
      - Gram + RHS: every rating contributes one rank-k outer product and one
        scaled vector add on each side → 2 · nnz · k · (k+1) FLOPs per side
        (the RHS rides as column k+1 of the grouped matmul).
      - Solves: one SPD solve per entity per iteration, counted at Cholesky
        cost k³/3 + 2k² (factorization + two triangular solves).
      - iALS adds the global Gram YᵀY: 2 · (U+M) · k² per iteration.

    Bytes (minimum):
      - neighbor-factor gathers: gather_rows · bytes/row — the table dtype
        sets the bytes (``table_gather_bytes_per_row``; bf16 halves the
        f32 rows, int8+scale quarters them), and ``gather_rows`` defaults
        to 2·nnz (one row per rating per side) with layout-aware
        overrides (``bucketed_gather_rows`` counts padded cells per width
        class; ``sweeps`` > 1 multiplies — each subspace sweep re-gathers),
      - block arrays read once: neighbor idx (4 B) + rating (4 B) per rating
        per side (the mask is derivable and the segment metadata is O(E)),
      - Gram/RHS intermediates cross the matmul→solve op boundary:
        (U + M) · (k² + k) · 4 bytes written + read,
      - factor write-back: (U + M) · k · factor_bytes.
    """
    k = rank
    entities = num_users + num_movies
    gram = 2.0 * nnz * k * (k + 1) * 2  # both sides
    solve = entities * (k**3 / 3.0 + 2.0 * k**2)
    flops = gram + solve
    if implicit:
        flops += 2.0 * entities * k * k  # global YᵀY

    if gather_rows is None:
        gather_rows = 2.0 * nnz
    gather_rows = gather_rows * max(sweeps, 1)
    if table_dtype is None:
        row_bytes = float(k * factor_bytes)
    else:
        row_bytes = table_gather_bytes_per_row(k, table_dtype, factor_bytes)
    gather = gather_rows * row_bytes
    blocks = 2.0 * nnz * 8
    gram_io = entities * (k * k + k) * 4.0 * 2
    factors_out = entities * k * factor_bytes
    return IterationCost(
        model_flops=flops,
        min_hbm_bytes=gather + blocks + gram_io + factors_out,
        gather_rows=gather_rows,
        gather_bytes=gather,
    )
