"""KernelRegistry: every compute kernel behind one backend-pluggable seam.

The Pallas kernels and their XLA-emulation twins used to be dispatched by
``compat``/gate checks scattered across ``ops/tiled.py``, ``ops/bucketed.py``
and both SPMD ring half-steps.  Here each kernel SLOT (gram, gram+solve,
their gather-fused twins, the fused reg+solve, the serve top-K) registers
its implementations per BACKEND:

- ``mosaic_tpu``     — the Pallas kernels (Mosaic lowering on TPU; the
                       bit-exact interpret/emulation route off-TPU, which
                       is why forcing this backend off is a *plan change*,
                       not a numeric change),
- ``xla_emulation``  — the plain-XLA formulations (materialized gather
                       stream, einsum Gram, batched Cholesky, scan top-K).

A Mosaic-GPU or JAXMg-style multi-GPU backend (arXiv 2601.14466) becomes a
third registry entry, not a rewrite: register loaders for the slots it
implements and the resolver's feasibility gates pick it up.

The central mode resolvers (``resolve_gather_mode``/``resolve_fused_chunk_
lam`` — previously duplicated logic in ``ops.tiled``, mirrored by
``ops.bucketed.resolve_bucket_modes``) live HERE now; ``ops.tiled`` keeps
thin aliases so existing call sites and tests are untouched.  Both consult
``backend_available``: forcing ``mosaic_tpu`` unavailable (an outage, a
chaos drill, a not-yet-ported platform) reroutes every next trace to the
emulation backend — and bumps ``generation()`` so the resilient loop knows
a rebuilt step would resolve differently (a recovery rung is a plan
transition).

Importable without jax; kernel loaders and gates import lazily.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

KERNEL_BACKENDS = ("mosaic_tpu", "xla_emulation")

# slot → what executes there.  One name per dispatch seam in the half-steps
# and the serve path.
KERNEL_SLOTS = (
    "gram",               # per-chunk tile Gram (split epilogue)
    "gram_solve",         # fused in-VMEM Gram+ridge+solve
    "gram_gather",        # Gram with in-kernel DMA row gather
    "gram_solve_gather",  # both fusions
    "reg_solve",          # batched ridge+solve (the fused reg kernels)
    "topk",               # streaming score+top-K serve kernel
)


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One (slot, backend) registration.  ``loader`` returns the callable
    lazily (kernels import jax); ``supported`` is the static feasibility
    gate the resolver consults (None = always feasible)."""

    slot: str
    backend: str
    loader: object  # () -> callable
    supported: object = None  # (**shape_kwargs) -> bool


class KernelRegistry:
    """slot × backend → KernelSpec, with a forced-unavailability switch.

    ``generation`` increments on every availability change so long-lived
    consumers (the resilient training loop) can detect that a step rebuilt
    NOW would resolve to different kernels than the step they hold.
    """

    def __init__(self) -> None:
        self._specs: dict[tuple[str, str], KernelSpec] = {}
        self._unavailable: set[str] = set()
        self._generation = 0
        self._lock = threading.Lock()

    # -- registration -----------------------------------------------------

    def register(self, slot: str, backend: str, loader, supported=None,
                 ) -> KernelSpec:
        if slot not in KERNEL_SLOTS:
            raise ValueError(
                f"unknown kernel slot {slot!r}; slots: {KERNEL_SLOTS}"
            )
        spec = KernelSpec(slot=slot, backend=backend, loader=loader,
                          supported=supported)
        with self._lock:
            self._specs[(slot, backend)] = spec
        return spec

    def get(self, slot: str, backend: str) -> KernelSpec:
        try:
            return self._specs[(slot, backend)]
        except KeyError:
            raise KeyError(
                f"no kernel registered for slot={slot!r} "
                f"backend={backend!r}; registered: "
                f"{sorted(self._specs)}"
            ) from None

    def backends_for(self, slot: str) -> tuple[str, ...]:
        return tuple(b for (s, b) in self._specs if s == slot)

    # -- availability -----------------------------------------------------

    def backend_available(self, backend: str) -> bool:
        """Is the backend currently usable?  ``xla_emulation`` always is
        (it is the degradation floor); ``mosaic_tpu`` unless forced off.
        Off-TPU the mosaic entries still count as available — they run
        through the bit-exact interpret/emulation route, and refusing them
        here would change CPU CI's coverage of the kernel code paths."""
        return backend not in self._unavailable

    def force_unavailable(self, backend: str, unavailable: bool = True,
                          ) -> None:
        """Flip a backend's availability (chaos drills, real outages).
        Every mode resolver consults this at trace time, so the next step
        REBUILD lands on a still-available backend; already-compiled
        programs keep running their traced kernels."""
        if backend == "xla_emulation" and unavailable:
            raise ValueError(
                "xla_emulation is the degradation floor and cannot be "
                "forced unavailable"
            )
        with self._lock:
            before = backend in self._unavailable
            if unavailable:
                self._unavailable.add(backend)
            else:
                self._unavailable.discard(backend)
            if before != unavailable:
                self._generation += 1

    @contextlib.contextmanager
    def unavailable(self, backend: str):
        """Scoped ``force_unavailable`` for tests/drills."""
        self.force_unavailable(backend, True)
        try:
            yield self
        finally:
            self.force_unavailable(backend, False)

    def generation(self) -> int:
        return self._generation

    def availability_summary(self) -> str:
        down = sorted(self._unavailable)
        if not down:
            return "all kernel backends available"
        return (f"backend(s) {','.join(down)} unavailable "
                f"(generation {self._generation}); "
                "falling back to xla_emulation")


REGISTRY = KernelRegistry()


def backend_available(backend: str) -> bool:
    return REGISTRY.backend_available(backend)


def generation() -> int:
    return REGISTRY.generation()


def _register_builtins() -> None:
    """The in-tree kernels.  Loaders are lazy (jax imports); the
    ``supported`` gates are the SAME functions the half-steps gate on, so
    registry feasibility and executed behavior cannot drift."""

    def _gk(name):
        def load():
            from cfk_tpu.ops.pallas import gram_kernel

            return getattr(gram_kernel, name)

        return load

    def _gather_gate(entries=None, meta_words=None, tile_rows=None,
                     block_rows=None, k=None, table_dtype=None,
                     lowered=True, **_):
        from cfk_tpu.ops.pallas.gram_kernel import in_kernel_gather_supported

        if entries is None:
            return True
        return in_kernel_gather_supported(
            entries, meta_words, tile_rows, block_rows, k=k,
            table_dtype=table_dtype, lowered=lowered)

    def _fused_gate(num_segments=None, k=None, algo=None, **_):
        from cfk_tpu.ops.pallas.gram_kernel import fused_gram_solve_supported

        if k is None:
            return True
        return fused_gram_solve_supported(num_segments, k, algo)

    R = REGISTRY
    R.register("gram", "mosaic_tpu", _gk("gram_tiles_pallas"))
    R.register("gram_solve", "mosaic_tpu", _gk("gram_solve_tiles_pallas"),
               supported=_fused_gate)
    R.register("gram_gather", "mosaic_tpu", _gk("gram_tiles_gather_pallas"),
               supported=_gather_gate)
    R.register("gram_solve_gather", "mosaic_tpu",
               _gk("gram_solve_tiles_gather_pallas"),
               supported=lambda **kw: _gather_gate(**kw) and _fused_gate(**kw))

    def _load_reg_solve():
        from cfk_tpu.ops.pallas import gauss_solve_reg_pallas

        return gauss_solve_reg_pallas

    def _reg_solve_gate(k=None, algo=None, **_):
        from cfk_tpu.ops.pallas.solve_kernel import _fused_reg_rank_cap

        return True if k is None else k <= _fused_reg_rank_cap(algo)

    R.register("reg_solve", "mosaic_tpu", _load_reg_solve,
               supported=_reg_solve_gate)

    def _load_topk():
        from cfk_tpu.serving.topk_kernel import topk_scores_pallas

        return topk_scores_pallas

    R.register("topk", "mosaic_tpu", _load_topk)

    # XLA-emulation twins — the same math through plain XLA ops (the
    # compat twins where one exists, the split/einsum formulations
    # otherwise).  Always feasible: this backend is the degradation floor.
    def _load_emulate(name):
        def load():
            from cfk_tpu import compat

            return getattr(compat, name)

        return load

    def _load_solve(name):
        def load():
            from cfk_tpu.ops import solve

            return getattr(solve, name)

        return load

    def _load_tiled_xla():
        # The einsum+segment-sum formulation lives in the tiled chunk
        # dispatcher (backend="xla"); the dispatcher IS the entry point.
        from cfk_tpu.ops.tiled import _entity_gram_chunk

        return _entity_gram_chunk

    R.register("gram", "xla_emulation", _load_tiled_xla)
    R.register("gram_solve", "xla_emulation",
               _load_emulate("emulate_fused_gram_solve"))
    R.register("gram_gather", "xla_emulation",
               _load_emulate("emulate_in_kernel_gather"))
    R.register("gram_solve_gather", "xla_emulation",
               _load_emulate("emulate_fused_gram_solve"))
    R.register("reg_solve", "xla_emulation",
               _load_solve("dispatch_spd_solve"))
    R.register("topk", "xla_emulation", _load_emulate("emulate_topk_scores"))


_register_builtins()


# -- central mode resolution (the logic ops.tiled/ops.bucketed/both spmd
# -- ring half-steps used to carry copies of) ------------------------------

def resolve_gather_mode(in_kernel_gather, backend, entries, meta_words,
                        tile_rows, num_segments, k, block_rows=None, *,
                        table_dtype) -> str:
    """Static gating of the in-kernel gather: ``"fused"`` (the kernel DMAs
    the indexed rows itself) or ``"xla"`` (the materialized-stream
    schedule).  ``table_dtype`` is the dtype of the table the kernel would
    DMA from (after quantization).  Gates: the knob, the pallas Gram
    backend (the XLA A/B backend has no kernel to gather inside),
    ``mosaic_tpu`` registry availability (a forced-unavailable backend
    reroutes the next trace to the emulation schedule), the
    kernels' rank/dtype/SMEM/alignment support gate
    (``in_kernel_gather_supported`` — what the chip's compiler accepts),
    and the same resident-output VMEM cap the split kernels fall back on.
    The rank/dtype term is Mosaic's row-DMA limit, so it binds where
    Mosaic lowers the kernel — where ``interpret=None`` resolves to False,
    a TPU backend.  Anywhere else "fused" is the gather wrappers' XLA
    twin, which has no such limit: the CPU tests go on covering the
    gather-fused schedule at toy ranks, and the windowed and sharded
    drivers keep taking the same route as each other there.  A refused
    shape keeps the XLA-gather path (same math; kernel vs twin agree to
    float32 round-off — tests/test_in_kernel_gather.py)."""
    if backend != "pallas":
        return "xla"
    if not REGISTRY.backend_available("mosaic_tpu"):
        return "xla"
    from cfk_tpu.ops.tiled import resolve_in_kernel_gather

    if not resolve_in_kernel_gather(in_kernel_gather):
        return "xla"
    if 2 * num_segments * k * (k + 1) * 4 > (96 << 20):
        return "xla"  # mirrors _entity_gram_chunk's resident-output cap
    from cfk_tpu.ops.pallas.interpret import resolve_interpret

    gate = REGISTRY.get("gram_gather", "mosaic_tpu").supported
    if not gate(entries=entries, meta_words=meta_words, tile_rows=tile_rows,
                block_rows=block_rows, k=k, table_dtype=table_dtype,
                lowered=resolve_interpret(None) is False):
        return "xla"
    return "fused"


def resolve_fused_chunk_lam(fused_epilogue, solver, k, num_segments,
                            backend, lam, implicit, algo=None):
    """Static gating of the fused Gram+solve chunk path.

    Returns the concretized λ (0.0 for the implicit/matrix mode, whose λ
    rides inside the shared reg matrix) when the fused path is legal, or
    None → the caller keeps the split Gram→HBM→solve schedule.  Gates:
    the per-call/config/process fused knob, the pallas Gram backend (the
    XLA A/B backend has no VMEM residency to exploit), ``mosaic_tpu``
    registry availability, the pallas solver (cholesky callers asked for
    ``batched_spd_solve`` — a program of its own after the Gram, XLA's
    calls or on a TPU the lane-batched Cholesky kernel: honoring that
    means splitting), the fused elimination's
    rank/VMEM caps (for the elimination ``algo`` the caller threads — GJ
    caps at 64 where LU reaches 128), and a concretizable λ (the kernel
    bakes it in as a compile-time constant; a traced per-step λ falls
    back to the split path's unfused solve, same math).
    """
    import jax

    from cfk_tpu.ops.solve import _resolve_solver, resolve_fused_epilogue

    if not resolve_fused_epilogue(fused_epilogue):
        return None
    if backend != "pallas" or _resolve_solver(solver) != "pallas":
        return None
    if not REGISTRY.backend_available("mosaic_tpu"):
        return None
    gate = REGISTRY.get("gram_solve", "mosaic_tpu").supported
    if not gate(num_segments=num_segments, k=k, algo=algo):
        return None
    if implicit:
        return 0.0
    try:
        return float(lam)
    except (jax.errors.ConcretizationTypeError, TypeError):
        return None
