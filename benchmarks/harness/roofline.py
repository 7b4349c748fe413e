"""Published peaks and the operations and bytes a kernel needs, from shapes.

A copy, kept with the benchmark, of the arithmetic in
``cfk_tpu/utils/roofline.py`` (``DEVICE_PEAKS``, ``serve_batch_cost``): a
PR that claims a gain may edit the program's copy but not this one.  A roofline share is the least time the chip could take —
the larger of operations over peak FLOP/s and bytes over peak bytes/s — over
the kernel's measured device time; it cannot pass 100 %.
"""

from __future__ import annotations

import dataclasses

from benchmarks.harness import stats


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # per second
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


# Keyed by ``jax.devices()[0].device_kind``.  A device that is not here is an
# error, never a default.
PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16 * 1024**3,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def floor_s(self, pk: Peaks) -> float:
        return max(self.flops / pk.bf16_flops, self.bytes / pk.hbm_bytes_per_s)


def topk_cost(table_rows: int, rank: int, batch: int, k_top: int,
              table_bytes_per_cell: int) -> Cost:
    """One scoring batch: the whole (padded) item table is scanned once, the
    [B, k] batch goes in and the [B, K] selection comes out."""
    return Cost(
        flops=2.0 * batch * table_rows * rank,
        bytes=(float(table_rows) * rank * table_bytes_per_cell
               + batch * rank * 4.0 + batch * k_top * 8.0),
    )


def serve_batch_floor_s(window: dict, config: dict, pk: Peaks):
    """The least seconds one chip could take over one batch of a serve
    window: ``topk_cost`` of the rows the device holds (the whole padded
    table, or ``table_rows // shards`` of it over a mesh, where the chips
    scan their slices at once) at the bucket the window's median batch was
    padded to.  Nothing where the window made no batch."""
    sizes = window.get("batch_sizes")
    if not sizes:
        return None
    batch = max(8, 1 << (int(stats.median(sizes)) - 1).bit_length())
    return topk_cost(
        window["table_rows"] // window.get("shards", 1), config["rank"],
        batch, window["k_pad"],
        {"float32": 4, "bfloat16": 2, "int8": 1}[config["table_dtype"]]
    ).floor_s(pk)
