"""Collective implementation selector.

Rebuild of the reference's ``mpi.collectiveSelector`` (SURVEY.md §3 C9,
reconstructed — reference mount empty): a runtime-switchable table that picked
an implementation per (cpu|gpu) x (singlenode|multinode) among
{mpi, nccl, gloo, p2p/custom}.  On TPU the discriminators become the mesh
topology and tensor size, and the implementations become:

- ``"xla"``          stock XLA collectives over the whole mesh (the mpi/nccl
                     analog; XLA's allreduce is the tuned vendor path).
- ``"hierarchical"`` explicit two-level staging: reduce_scatter over ICI ->
                     allreduce over DCN -> all_gather over ICI (the analog of
                     the reference's custom hierarchical intra-node reduce ->
                     inter-node allreduce -> intra-node broadcast).
- ``"pallas"``       hand-written chunked ring kernels over ICI remote DMA
                     (the analog of the reference's custom chunked/pipelined
                     MPI_Isend/Irecv rings).

Backends self-register; lookup is by name with size-cutover logic mirroring the
reference's "small tensors stay on the stock path" constants.

``nbytes`` is the real transfer size: the fused pytree collectives
(torchmpi_tpu/fusion.py) coalesce a tree's leaves into dtype-grouped
buckets BEFORE routing, so the cutover and the tuning-plan provider see
the fused bucket's bytes — not per-leaf crumbs that would always fall
below ``custom_min_bytes`` and key plan entries at sizes nobody measured.
"""

from __future__ import annotations

import warnings
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from . import runtime

# op name -> backend name -> implementation fn.  Implementation signature is
# op-specific; see collectives.py _IN_AXIS_OPS.
_REGISTRY: Dict[str, Dict[str, Callable]] = {}

# Bumped by every register(): part of each CollectivePlan key
# (torchmpi_tpu/planner.py), so re-registering an implementation at
# runtime strands the plans that resolved the old one — the planner's
# analog of a cache keyed on the resolved impl object.
_generation = 0


def generation() -> int:
    return _generation


def register(op: str, backend: str, fn: Callable) -> None:
    global _generation
    _REGISTRY.setdefault(op, {})[backend] = fn
    _generation += 1


def available(op: Optional[str] = None) -> Dict:
    """Introspection (reference: ``mpi.collectiveAvailability``)."""
    if op is not None:
        return dict(_REGISTRY.get(op, {}))
    return {k: sorted(v.keys()) for k, v in _REGISTRY.items()}


# Plan provider hook (torchmpi_tpu.tuning): fn(op, nbytes, dtype, axes)
# -> Optional[backend name].  Registered by tuning.configure() when the
# config opts into backend="auto"; consulted by select() BEFORE the
# static cutover so measured per-(op, size, mesh) decisions take
# precedence over the hand-tuned constants.
_plan_provider: Optional[Callable] = None


def set_plan_provider(fn: Callable) -> None:
    global _plan_provider
    _plan_provider = fn


def clear_plan_provider() -> None:
    global _plan_provider
    _plan_provider = None


def plan_provider() -> Optional[Callable]:
    return _plan_provider


def select(
    op: str,
    backend: str,
    *,
    nbytes: Optional[int] = None,
    custom_min_bytes: int = 0,
    n_dcn: int = 1,
    explicit: bool = False,
    dtype=None,
    axes=None,
) -> Callable:
    """Pick the implementation for ``op``.

    ``backend="auto"`` consults the registered tuning-plan provider (a
    measured, persisted per-topology decision — see
    ``torchmpi_tpu/tuning/``) BEFORE the static cutover; a plan hit
    bypasses the ``custom_min_bytes`` heuristic (the entry was measured
    at this size bucket), a miss degrades to the stock ``"xla"`` path.

    Falls back to ``"xla"`` when the requested backend has no implementation
    for this op, when the tensor is below the custom-path size cutover, or
    when a hierarchical backend is requested on a flat (n_dcn == 1) mesh —
    the same graceful degradation the reference's selector performed when
    NCCL/Gloo were compiled out.  ``explicit=True`` (a per-call backend
    request, as opposed to the config default) bypasses the size cutover but
    still degrades on topology/availability.
    """
    impls = _REGISTRY.get(op)
    if not impls:
        raise KeyError(f"no implementations registered for collective {op!r}")
    name = backend
    if name == "auto":
        planned = None
        if _plan_provider is not None:
            try:
                planned = _plan_provider(op, int(nbytes or 0), dtype, axes)
            except Exception:  # noqa: BLE001 — a plan must never crash a step
                planned = None
        if planned is None:
            name = "xla"
        else:
            # A measured plan decision carries the same authority as an
            # explicit per-call backend: no size cutover, but topology/
            # availability degradation below still applies.
            name = planned
            explicit = True
    if name != "xla":
        if (not explicit and nbytes is not None
                and nbytes < custom_min_bytes):
            name = "xla"
        elif name == "hierarchical" and n_dcn <= 1:
            # Topology degradation must be VISIBLE: a requested
            # two-level backend silently running flat is exactly the
            # misconfiguration (wrong dcn_size, collapsed mesh) that
            # otherwise only shows up as a missing perf win.
            _note_fallback(op, name, "flat mesh (n_dcn <= 1)")
            name = "xla"
        elif name not in impls:
            name = "xla"
    if name not in impls:
        raise KeyError(
            f"collective {op!r} has no {name!r} implementation "
            f"(available: {sorted(impls)})"
        )
    return impls[name]


def config_backend(op: str, cfg) -> Tuple[str, bool]:
    """Resolve the config-level backend for ``op``: per-op table first
    (a deliberate choice, carrying explicit/per-call authority), then
    the hierarchical flag, then the config default.  The ONE home of
    this precedence — shared by :func:`pick` and the eager "auto"
    trigger so they can never drift apart."""
    if cfg.backend_per_op:
        b = cfg.backend_per_op.get(op)
        if b is not None:
            return b, True
    return ("hierarchical" if cfg.hierarchical else cfg.backend), False


def pick(op: str, x, backend: Optional[str], axes: Tuple[str, ...],
         mesh=None, cfg=None) -> Callable:
    """Which implementation serves ``op`` on ``x`` (an array or its
    aval) over ``axes``: the per-call ``backend`` if given, else the
    config's (:func:`config_backend`), then :func:`select` with the
    payload's size and the mesh's real outer extent.  ``cfg`` / ``mesh``
    default to the runtime's."""
    explicit = backend is not None
    if cfg is not None or runtime.is_initialized():
        if cfg is None:
            cfg = runtime.config()
        if backend is None:
            # A per-op table entry bypasses the size cutover like a
            # per-call backend (topology fallback still applies).
            backend, explicit = config_backend(op, cfg)
        custom_min = cfg.custom_min_bytes
    else:
        backend = backend or "xla"
        custom_min = 0
    # Hierarchical staging only helps when the outer axis really spans more
    # than one slice; use the actual mesh extent, not the axis-name count.
    n_dcn = 1
    if len(axes) > 1:
        m = mesh
        if m is None and runtime.is_initialized():
            m = runtime.current_mesh()
        n_dcn = int(m.shape[axes[0]]) if (m is not None
                                          and axes[0] in m.shape) else 2
    return select(
        op,
        backend,
        nbytes=nbytes_of(x),
        custom_min_bytes=custom_min,
        n_dcn=n_dcn,
        explicit=explicit,
        dtype=getattr(x, "dtype", None),
        axes=axes,
    )


# (op, backend) pairs already warned about this process: the warning is
# one-time per pair (a hot loop degrading every dispatch must not spam),
# while the obs counter counts every degradation.
_warned_fallbacks: set = set()


def _note_fallback(op: str, backend: str, reason: str, *,
                   target: str = "'xla'") -> None:
    """Surface a topology/availability degradation: a one-time
    ``RuntimeWarning`` per (op, backend) plus the
    ``tm_selector_fallback_total`` counter when obs is on — so
    ``obs_tool`` dumps show a requested "hierarchical" that silently
    ran flat (ISSUE 8 satellite; docs/HIERARCHICAL.md).  ``target``
    names what actually ran: :func:`select` degrades to the stock
    'xla' impl, while the error-feedback flat-span callers degrade to
    the plain uncompressed sync path (which routes through the
    selector as usual)."""
    key: Tuple[str, str] = (op, backend)
    if key not in _warned_fallbacks:
        _warned_fallbacks.add(key)
        warnings.warn(
            f"collective {op!r}: {backend!r} requested but degraded "
            f"to {target} ({reason}); check dcn_size/mesh_shape "
            f"if a two-level topology was intended",
            RuntimeWarning, stacklevel=4)
    if runtime.effective_config().obs != "off":
        from . import obs

        obs.record_selector_fallback(op, backend)


def name_of(op: str, impl: Callable) -> str:
    """Reverse lookup: the backend name a resolved implementation was
    registered under (telemetry labels — ``torchmpi_tpu.obs``).
    Implementations not in the registry report ``"custom"``."""
    for b, f in _REGISTRY.get(op, {}).items():
        if f is impl:
            return b
    return "custom"


def nbytes_of(x) -> int:
    """Total payload bytes of ``x`` — a single array OR any pytree of
    arrays, summed across leaves, so gradient-tree callers get real
    sizes for cutover/bucketing decisions.  Leaves without shape/dtype
    (python scalars, None) contribute 0, preserving the old behavior of
    returning 0 for non-arrays."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
    import jax

    total = 0
    for leaf in jax.tree.leaves(x):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total
