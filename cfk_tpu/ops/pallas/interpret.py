"""How a Pallas wrapper's ``interpret`` argument is resolved — the one place
in the kernels that asks jax which backend it runs on."""

from __future__ import annotations

import jax


def resolve_interpret(interpret):
    """``False``: compile the kernel with Mosaic.  ``True``: the CPU route —
    each wrapper's own: its XLA twin (the gather, dense and fused-epilogue
    Gram kernels, always; ``gram_tiles_pallas`` and the top-K scorer only
    under shard_map, where the Pallas interpreter fails the vma check),
    else the kernel body under the Pallas interpreter.  ``"kernel"``: the
    kernel body under the Pallas interpreter, twin or not — how the CPU
    tests execute the bodies whose ``True`` route is a twin.  ``None``
    resolves to ``False`` on a TPU backend and ``True`` anywhere else; a
    caller that must not run off the chip (``chip_smoke.py``) checks the
    backend itself and fails."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    if interpret not in (True, False, "kernel"):
        raise ValueError(
            f"interpret must be None, True, False or 'kernel', "
            f"got {interpret!r}"
        )
    return interpret
