"""Top-K recommendation serving at traffic (ISSUE 8 / ROADMAP item 1).

The serving half the reference never had: instead of materializing U·Mᵀ
(``processors/FeatureCollector.java``), a Pallas score+top-K kernel streams
movie-axis tiles of the (optionally quantized) item table through VMEM and
only [B, K] ids+scores ever reach HBM (``topk_kernel``); the table shards
over the item axis with an O(B·shards·K) merge (``parallel.spmd.
serve_topk_sharded``); a request server coalesces queries from the
transport log into pow2-bucketed batches (``server``) over a live-updating
``ServeEngine`` whose hot-user factor cache re-serves streaming fold-in
commits (``engine``); and an open-loop generator measures QPS/p50/p99
honestly (``loadgen``; PERF.md for what the chip measured).

The replicated fleet (ISSUE 18 / ROADMAP item 3, ``fleet``) puts N
replicas behind the request log: user-keyed routing, admission control
with explicit retriable rejections, versioned factor-delta shipping with
seq-gap detection + epoch-snapshot resync (bit-exact, ``table_crc``),
zero-downtime epoch rollover (background prewarm + single pointer flip),
and kill/failover at the committed cursor (at-least-once re-serve).
"""

from cfk_tpu.serving.engine import (
    ServeEngine,
    engine_from_model,
    pad_table,
    row_reader,
)
from cfk_tpu.serving.fleet import (
    DELTAS_TOPIC,
    AdmissionController,
    DeltaPublisher,
    FleetReplica,
    ServeFleet,
    SnapshotStore,
    ensure_deltas_topic,
    table_crc,
)
from cfk_tpu.serving.loadgen import (
    LoadReport,
    run_open_loop,
    warm_serve_programs,
    zipf_user_rows,
)
from cfk_tpu.serving.server import (
    REQUESTS_TOPIC,
    RESPONSES_TOPIC,
    RecommendServer,
    ServeClient,
    ensure_serve_topics,
)
from cfk_tpu.serving.topk_kernel import (
    build_seen_tiles,
    group_seen_cells,
    scatter_seen_cells,
    topk_scores_pallas,
)

__all__ = [
    "ServeEngine",
    "engine_from_model",
    "pad_table",
    "row_reader",
    "LoadReport",
    "run_open_loop",
    "warm_serve_programs",
    "zipf_user_rows",
    "REQUESTS_TOPIC",
    "RESPONSES_TOPIC",
    "DELTAS_TOPIC",
    "RecommendServer",
    "ServeClient",
    "ensure_serve_topics",
    "ensure_deltas_topic",
    "AdmissionController",
    "DeltaPublisher",
    "FleetReplica",
    "ServeFleet",
    "SnapshotStore",
    "table_crc",
    "build_seen_tiles",
    "group_seen_cells",
    "scatter_seen_cells",
    "topk_scores_pallas",
]
