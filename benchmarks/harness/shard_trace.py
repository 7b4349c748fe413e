"""What the readers of the serve programs look for in a device trace, by the
names the program gives them today (``serving/topk_kernel.py`` on one device,
``parallel/spmd.py`` over a mesh): renamed, they are no longer found, the
metrics are no longer reported and a traced run of their cell is refused,
which is the point."""

# the scorer's Mosaic custom call, one scan under either name:
# ``_topk_call.<n>`` on one device, ``_topk_shard_call.<n>`` in the shard program
SCORER = r"^_topk(_shard)?_call"
SCORE_PROGRAM = "_topk_shard_call"  # scorer + all_gathers + merge
BUILD_PROGRAM = "_seen_tiles_shard_call"  # a chip's slice of the rectangle


def program_seconds(trace, fragment: str):
    """(seconds, runs) of device 0's programs whose name holds ``fragment``."""
    runs = [b - a for a, b, name in (trace.modules[0] if trace.modules else ())
            if fragment in name]
    return sum(runs), len(runs)
