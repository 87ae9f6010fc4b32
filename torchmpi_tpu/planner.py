"""CollectivePlan: one cached planner for the whole dispatch path.

TorchMPI's core performance trick was a *resource cache* (SURVEY.md
§8.4.5): plan a collective once — buffers, communicator, algorithm —
and replay the plan on every later call.  Five subsystems grew around
this library's dispatch path (tuning, fusion, analysis, obs, faults,
overlap) and each call used to re-derive its decisions from all of them
in sequence: fusion grouping, ``selector.nbytes_of`` tree walks,
tuning-plan lookups, the static cutover, then per-site obs/faults
string compares — with only the compiled executable memoized ad hoc.

This module lifts the full decision record into an explicit, immutable
:class:`CollectivePlan`, computed once per key and replayed thereafter:

- **key** — ``(kind, op, pytree structure + leaf avals, mesh, backend,
  static params, config epoch)``.  Two calls with the same tree
  *structure* but different values share a plan; a different mesh, a
  pushed communicator, or any :func:`runtime.set_config` (which bumps
  the epoch) misses and re-plans.
- **record** — the dtype-grouped fusion buckets with precomputed nbytes
  and layouts (:class:`~torchmpi_tpu.fusion.FusedSpec`), the selector/
  tuning backend choice *per bucket*, the cached rank-major sharding,
  the compiled executable (eager mode), the static-analysis verdict,
  and pre-resolved obs/faults enablement — so "off" costs zero
  branches at replay (one ``is None`` check), not one string compare
  per layer per site.
- **replay** — the minimal residual work: one table lookup, then the
  pre-bound closure.

This module holds the table, the record, and the builders that need
only ``fusion`` and ``selector`` below them: the nine ``*_in_axis``
verbs (hence ``async_in_axis`` on top of them), the bucketed
``gradsync.synchronize_gradients``, the decision record of
``make_overlapped_grad_fn`` (which hands in its own bucket
assignment), the ZeRO flatten/reduce-scatter leg, and the serving
replica rows.  The eager rank-major plan (``collectives.plan_for``) is
built in ``collectives``, beside the staging and placement code it
binds, through :func:`get_or_build`.  Nothing here imports
``collectives`` or ``parallel``.  Invalidation has ONE point:
:func:`invalidate` (``collectives.clear_cache`` and ``runtime.stop``
route here; ``set_config`` bumps the epoch *and* routes here) — the
seam serving, elasticity, and cross-slice topology (ROADMAP items 2-4)
hang their lifecycle off.  See docs/PLANNER.md.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import fusion, runtime, selector

# ---------------------------------------------------------------------------
# The plan table: the ONE cache behind the dispatch path
# (collectives._jit_cache / _sharding_cache are aliases of it).  Reads
# are lock-free dict gets (GIL-atomic); builds run under an RLock —
# re-entrant because building an eager backend="auto" plan measures
# candidates by dispatching them, which plans recursively.
# ---------------------------------------------------------------------------

_lock = threading.RLock()
_table: Dict[tuple, "CollectivePlan"] = {}
_shardings: Dict[Mesh, NamedSharding] = {}
_stats = {"hits": 0, "misses": 0, "invalidations": 0}


class CollectivePlan:
    """Immutable decision record for one collective dispatch site.

    Built once by the ``plan_*`` functions below, then replayed: the
    fields are assigned at construction and never mutated afterwards
    (``hits`` is the one bookkeeping exception).  ``replay`` runs the
    pre-bound execution closure; decision-only plans (kinds
    ``overlap`` / ``flatspec``) carry no closure and are consumed via
    ``spec`` / ``impls`` / ``extra`` instead.
    """

    __slots__ = ("key", "kind", "op", "backend", "nbytes", "spec", "impls",
                 "extra", "staged", "obs", "faults", "guard", "watchdog",
                 "analysis", "epoch", "topology", "build_seconds", "hits",
                 "_replay", "_obs_hit")

    def __init__(self, key: tuple, kind: str, op: str, *,
                 backend: str = "", nbytes: int = 0,
                 spec: Optional[fusion.FusedSpec] = None,
                 impls: Optional[List[Callable]] = None,
                 extra: Optional[dict] = None,
                 staged: bool = False, obs: bool = False,
                 faults: bool = False, guard: bool = False,
                 watchdog: bool = False,
                 analysis: str = "off",
                 topology: str = "",
                 replay: Optional[Callable] = None) -> None:
        self.key = key
        self.kind = kind
        self.op = op
        self.backend = backend
        self.nbytes = int(nbytes)
        # Topology fingerprint ("n_dcn x n_ici ..." as "2x4"): the mesh
        # extents the plan's dispatch spans — what makes a flat-vs-
        # hierarchical decision visible per topology in dump-live
        # (ROADMAP item 4; docs/HIERARCHICAL.md).
        self.topology = topology
        self.spec = spec
        self.impls = impls
        self.extra = extra or {}
        self.staged = bool(staged)
        self.obs = bool(obs)
        self.faults = bool(faults)
        # Wire-integrity guard enablement, resolved at build like
        # obs/faults (docs/GUARD.md): guard="off" is one string compare
        # HERE — the replay closure carries no guard branch at all.
        self.guard = bool(guard)
        # Watchdog enablement, same build-time resolution
        # (docs/WATCHDOG.md): "off" is one string compare at build and
        # the replay closure carries ZERO watchdog branches; "on" binds
        # the in-flight window (staged) / deferred-raise boundary
        # (direct) into the closure itself.
        self.watchdog = bool(watchdog)
        self.analysis = analysis
        self.epoch = runtime.config_epoch()
        self.build_seconds = 0.0
        self.hits = 0
        self._replay = replay
        # Pre-bound hit counter (one dict op per replay when obs is on,
        # nothing at all when off — resolved at build, like every other
        # decision in the record).
        self._obs_hit: Optional[Callable] = None
        if self.obs:
            from . import obs as _obs

            self._obs_hit = _obs.registry().counter_handle(
                "tm_plan_hit_total", op=op, kind=kind)

    def replay(self, x):
        """Execute the planned dispatch for one same-structure input."""
        return self._replay(x)

    def describe(self) -> dict:
        """JSON-ready row for ``plan_tool.py dump-live`` / debugging."""
        return {
            "kind": self.kind, "op": self.op, "backend": self.backend,
            "nbytes": self.nbytes,
            "launches": (len(self.impls) if self.impls
                         else (self.spec.n_launches
                               if self.spec is not None else 1)),
            "staged": self.staged, "obs": self.obs, "faults": self.faults,
            "guard": self.guard, "watchdog": self.watchdog,
            "analysis": self.analysis, "epoch": self.epoch,
            "topology": self.topology,
            "build_ms": round(self.build_seconds * 1e3, 3),
            "hits": self.hits,
        }


def invalidate() -> None:
    """THE invalidation point: drop every plan and cached sharding.

    ``collectives.clear_cache()`` and ``runtime.stop()`` route here, as
    does ``runtime.set_config`` (via clear_cache, after bumping the
    config epoch).  Mesh identity changes need no explicit call — the
    mesh object is part of every key — but a caller tearing down a mesh
    can invalidate() to release the plans pinned to it.  Clears IN
    PLACE so module-level aliases of the table stay live."""
    with _lock:
        _table.clear()
        _shardings.clear()
        _stats["invalidations"] += 1


def stats() -> dict:
    """Cumulative table stats: ``hits`` / ``misses`` / ``entries`` /
    ``invalidations`` (process-level; survive invalidate())."""
    return dict(_stats, entries=len(_table))


def reset_stats() -> None:
    _stats["hits"] = 0
    _stats["misses"] = 0
    _stats["invalidations"] = 0


def describe() -> List[dict]:
    """One JSON-ready row per live plan (``plan_tool.py dump-live``)."""
    with _lock:
        return [p.describe() for p in _table.values()]


def rank_major_sharding(m: Mesh) -> NamedSharding:
    """Cached rank-major NamedSharding per mesh (part of every eager
    plan; also consulted by the staged/async placement paths)."""
    s = _shardings.get(m)
    if s is None:
        s = _shardings[m] = NamedSharding(m, P(m.axis_names))
    return s


# ---------------------------------------------------------------------------
# Shared lookup/build plumbing
# ---------------------------------------------------------------------------


def _lookup(key: tuple) -> Optional[CollectivePlan]:
    plan = _table.get(key)
    if plan is not None:
        _stats["hits"] += 1
        plan.hits += 1
        if plan._obs_hit is not None:
            plan._obs_hit()
    return plan


def get_or_build(key: tuple, builder: Callable[[], CollectivePlan]
                 ) -> CollectivePlan:
    """Lock-free hit, else build-and-insert under the planner lock.

    Builds are deliberately serialized (one at a time, lock held across
    the builder): a build can run a tuning backend="auto" measurement,
    and a concurrent build racing past tuning's ``measuring`` flag
    would freeze a statically-resolved backend into an auto plan and
    replay it forever.  The cost — a cold dispatch on another thread
    waits for an in-flight build — is a cold-start-only stall; the
    steady state never takes this lock.
    """
    plan = _lookup(key)
    if plan is not None:
        return plan
    with _lock:
        plan = _lookup(key)  # double-check: lost the build race
        if plan is not None:
            return plan
        t0 = time.monotonic()
        plan = builder()
        plan.build_seconds = time.monotonic() - t0
        _table[key] = plan
    _stats["misses"] += 1
    if plan.obs:
        from . import obs

        obs.record_plan("miss", plan.op, kind=plan.kind,
                        build_s=plan.build_seconds)
    return plan


def epoch() -> tuple:
    """The staleness component of every plan key: the config epoch
    (init/set_config/stop bumps) plus the selector registry generation
    (a runtime re-register strands plans that resolved the old impl)."""
    return (runtime.config_epoch(), selector.generation())


def _cfg():
    return runtime.config() if runtime.is_initialized() else None


def _avals(leaves) -> tuple:
    """Hashable (shape, dtype) signature of a list of array leaves."""
    return tuple((tuple(int(d) for d in leaf.shape),
                  np.dtype(leaf.dtype).name) for leaf in leaves)


def topology_of(mesh=None, sizes=None) -> str:
    """The ``n_dcn x n_ici``-style topology fingerprint of a dispatch
    ("2x4" two-level; "8" flat), stored on every :class:`CollectivePlan`
    (and shown by ``plan_tool.py dump-live``) so a flat-vs-hierarchical
    choice reads as a per-topology decision, not an opaque cache row.
    ONE home: :func:`torchmpi_tpu.tuning.fingerprint.topology`, the same
    extents the tuning-plan keys carry via ``mesh_key`` — the planner's
    fingerprint and the plan DB's can never drift apart."""
    from .tuning import fingerprint

    return fingerprint.topology(mesh=mesh, sizes=sizes)


def _topo_sizes(mesh, axes: Tuple[str, ...]) -> Optional[Tuple[int, ...]]:
    """Trace-bound axis extents reordered to MESH order for the
    topology label: ``("ici", "dcn")`` and ``("dcn", "ici")`` calls
    over one device span must read as ONE topology (the same
    normalization :func:`fingerprint.mesh_key` applies to the plan-DB
    keys).  Axes not named by the mesh (a different user mesh) keep
    their caller order — the trace-context sizes are still correct."""
    sizes = _axis_sizes(axes)
    if mesh is None or sizes is None:
        return sizes
    try:
        if all(a in mesh.shape for a in axes):
            order = {a: i for i, a in enumerate(mesh.shape)}
            return tuple(s for _, s in sorted(
                zip(axes, sizes), key=lambda p: order[p[0]]))
    except Exception:  # noqa: BLE001 — a label must never fail a plan
        pass
    return sizes


def _axis_sizes(axes: Tuple[str, ...]) -> Optional[Tuple[int, ...]]:
    """The bound sizes of ``axes`` in the current trace context, or
    None outside any binding.  Part of every in-axis key: the same axis
    NAMES can be bound to different sizes by different user meshes, and
    a fused layout planned for one must never replay for the other."""
    try:
        return tuple(int(lax.axis_size(a)) for a in axes)
    except Exception:  # noqa: BLE001 — outside an axis binding
        return None


def _in_axis_recorder(cfg, op: str, nbytes: int, axes) -> Optional[Callable]:
    """Pre-resolved in-axis obs hook: None when obs is off (the replay
    then pays one ``is None`` check), else a bound recorder."""
    if cfg is None or cfg.obs == "off":
        return None
    import functools

    from . import obs

    return functools.partial(obs.record_in_axis, op, nbytes, axes)


# ---------------------------------------------------------------------------
# In-axis plans (the nine *_in_axis verbs; async_in_axis rides them)
# ---------------------------------------------------------------------------


def plan_in_axis(op: str, tree, axes: Tuple[str, ...],
                 backend: Optional[str], params: dict) -> CollectivePlan:
    """Plan (or replay-hit) one in-axis pytree collective over a
    non-empty tree of array leaves.

    Called at trace time; the plan replays across retraces, re-jits,
    and repeated step builds of the same tree structure."""
    leaves, treedef = jax.tree.flatten(tree)
    avals = _avals(leaves)
    mesh = runtime.current_mesh() if runtime.is_initialized() else None
    key = ("in_axis", op, treedef, avals, axes, _axis_sizes(axes), backend,
           tuple(sorted(params.items())), mesh, epoch())
    return get_or_build(
        key, lambda: _build_in_axis(key, op, tree, leaves, treedef, avals,
                                    axes, backend, params, mesh))


def _bucket_impls(op: str, spec: fusion.FusedSpec, backend, axes, mesh,
                  cfg) -> List[Callable]:
    """The selector/tuning backend choice per fused bucket, resolved
    from each bucket's true nbytes (iteration order == fuse_tree's)."""
    return [
        selector.pick(op, jax.ShapeDtypeStruct((hi - lo,), g.dtype),
                      backend, axes, mesh=mesh, cfg=cfg)
        for g in spec.groups for (lo, hi) in g.bounds
    ]


def _resolved_backend(op: str, backend: Optional[str],
                      impls: List[Callable]) -> str:
    """The backend name a plan row reports: the explicit argument when
    one was given, else the name the selector actually resolved for the
    (first) bucket — so ``dump-live`` shows a plan-driven
    "hierarchical" pick instead of an empty config default (build-time
    only; mixed per-bucket picks report the first + "+")."""
    if backend:
        return backend
    if not impls:
        return ""
    names = {selector.name_of(op, f) for f in impls}
    first = selector.name_of(op, impls[0])
    return first if len(names) == 1 else first + "+"


def _build_in_axis(key: tuple, op: str, tree, leaves, treedef, avals,
                   axes: Tuple[str, ...], backend: Optional[str],
                   params: dict, mesh) -> CollectivePlan:
    cfg = _cfg()
    eff = runtime.effective_config()
    obs_on = eff.obs != "off"
    nbytes = sum(int(np.prod(s)) * np.dtype(d).itemsize for s, d in avals)
    rec = _in_axis_recorder(eff, op, nbytes, axes)
    pd = dict(params)
    max_bytes = eff.fuse_max_bytes

    # Fused elementwise (allreduce/reduce/broadcast), unless fusion is
    # off (fuse_max_bytes == 0), the tree has one leaf, or the buckets
    # would be as many launches as the leaves.
    if (op in fusion.ELEMENTWISE_OPS and max_bytes > 0 and len(leaves) >= 2):
        spec = fusion.FusedSpec(tree, max_bytes=max_bytes)
        if spec.n_launches < spec.n_leaves:
            impls = _bucket_impls(op, spec, backend, axes, mesh, cfg)

            def _replay(tree):
                if rec is not None:
                    rec()
                return fusion.fuse_tree(op, tree, axes, spec=spec,
                                        impls=impls, **pd)

            return CollectivePlan(key, "in_axis-fused", op,
                                  backend=_resolved_backend(
                                      op, backend, impls),
                                  nbytes=nbytes,
                                  spec=spec, impls=impls, obs=obs_on,
                                  topology=topology_of(
                                      mesh, _topo_sizes(mesh, axes)),
                                  replay=_replay)

    # Fused reduce_scatter: tile-interleaved layout, leaf-granularity
    # buckets, where every leaf's leading dim divides by the span.
    if op == "reduce_scatter" and max_bytes > 0 and len(leaves) >= 2:
        sizes = _axis_sizes(axes)
        n = int(np.prod(sizes)) if sizes else 0
        if (n > 0 and all(len(s) >= 1 and s[0] % n == 0
                          for s, _ in avals)):
            spec = fusion.FusedSpec(tree, max_bytes=max_bytes)
            n_launches = sum(len(g.leaf_buckets) for g in spec.groups)
            if n_launches < spec.n_leaves:
                impls = [
                    selector.pick(
                        "reduce_scatter",
                        jax.ShapeDtypeStruct(
                            (sum(g.sizes[pos] for pos in bucket),),
                            g.dtype),
                        backend, axes, mesh=mesh, cfg=cfg)
                    for g in spec.groups for bucket in g.leaf_buckets
                ]

                def _replay(tree):
                    if rec is not None:
                        rec()
                    return fusion.fused_reduce_scatter(
                        tree, axes, spec=spec, impls=impls, n=n, **pd)

                return CollectivePlan(key, "in_axis-fused", op,
                                      backend=_resolved_backend(
                                          op, backend, impls),
                                      nbytes=nbytes, spec=spec,
                                      impls=impls, obs=obs_on,
                                      topology=topology_of(
                                          mesh, _topo_sizes(mesh, axes)),
                                      replay=_replay)

    # Per-leaf: one picked implementation per leaf.
    impls = [
        selector.pick(op, jax.ShapeDtypeStruct(s, d), backend, axes,
                      mesh=mesh, cfg=cfg)
        for s, d in avals
    ]

    def _replay(tree):
        if rec is not None:
            rec()
        ls = jax.tree.leaves(tree)
        return jax.tree.unflatten(
            treedef, [f(v, axes, **pd) for f, v in zip(impls, ls)])

    return CollectivePlan(key, "in_axis", op,
                          backend=_resolved_backend(op, backend, impls),
                          nbytes=nbytes, impls=impls, obs=obs_on,
                          topology=topology_of(mesh, _topo_sizes(mesh, axes)),
                          replay=_replay)


# ---------------------------------------------------------------------------
# Gradient-sync plans (gradsync._bucketed_allreduce / the overlap
# schedule's bucket assignment + per-bucket backend choice)
# ---------------------------------------------------------------------------


def plan_gradsync(grads, axes: Tuple[str, ...], *, op: str, n_buckets: int,
                  backend: Optional[str],
                  barrier: bool) -> CollectivePlan:
    """Plan the bucketed gradient allreduce of a non-empty tree:
    FusedSpec with the count-driven (``gradsync_buckets``) bucketing
    plus per-bucket backend choices, replayed across step builds."""
    leaves, treedef = jax.tree.flatten(grads)
    avals = _avals(leaves)
    mesh = runtime.current_mesh() if runtime.is_initialized() else None
    key = ("gradsync", treedef, avals, axes, _axis_sizes(axes), op,
           int(n_buckets), backend, bool(barrier), mesh, epoch())

    def build():
        cfg = _cfg()
        eff = runtime.effective_config()
        spec = fusion.FusedSpec(grads, n_buckets=n_buckets)
        impls = _bucket_impls("allreduce", spec, backend, axes, mesh, cfg)
        nbytes = sum(int(np.prod(s)) * np.dtype(d).itemsize
                     for s, d in avals)

        def _replay(tree):
            return fusion.fuse_tree("allreduce", tree, axes, spec=spec,
                                    impls=impls, barrier=barrier, op=op)

        return CollectivePlan(key, "gradsync", "allreduce",
                              backend=backend or "", nbytes=nbytes,
                              spec=spec, impls=impls,
                              topology=topology_of(mesh,
                                                   _topo_sizes(mesh, axes)),
                              obs=eff.obs != "off", replay=_replay)

    return get_or_build(key, build)


def plan_overlap(template_leaves, axes: Tuple[str, ...],
                 assign: Callable[[list, int], List[List[int]]], *, op: str,
                 backend: Optional[str], compress: Optional[str],
                 max_bytes: int,
                 dcn_codec: Optional[str] = None) -> CollectivePlan:
    """Decision-only plan for the backprop-overlap schedule: the bucket
    assignment ``assign(template_leaves, max_bytes)`` makes
    (``extra["firing"]``; the schedule's owner,
    ``gradsync.make_overlapped_grad_fn``, hands in its reverse-order
    rule) and each bucket's pre-picked allreduce implementation
    (``impls``, indexed in firing order).  The caller consumes both
    when building its custom_vjp chain.  With ``dcn_codec`` (the
    error-feedback path) the buckets dispatch the FIXED two-level
    schedule — no selector picks are made and the plan row reports the
    codec, not a backend that never runs."""
    avals = _avals(template_leaves)
    mesh = runtime.current_mesh() if runtime.is_initialized() else None
    key = ("overlap", avals, axes, op, backend, compress, int(max_bytes),
           dcn_codec, mesh, epoch())

    def build():
        cfg = _cfg()
        eff = runtime.effective_config()
        firing = assign(template_leaves, max_bytes)
        if dcn_codec is not None:
            impls = [None] * len(firing)
            label = f"dcn-{dcn_codec}"
        else:
            impls = []
            for bucket in firing:
                total = sum(int(np.prod(avals[i][0])) for i in bucket)
                wire_dt = (np.dtype("bfloat16") if compress == "bf16"
                           else np.dtype(avals[bucket[0]][1]))
                impls.append(selector.pick(
                    "allreduce", jax.ShapeDtypeStruct((total,), wire_dt),
                    backend, axes, mesh=mesh, cfg=cfg))
            label = backend or ""
        nbytes = sum(int(np.prod(s)) * np.dtype(d).itemsize
                     for s, d in avals)
        return CollectivePlan(key, "overlap", "allreduce",
                              backend=label, nbytes=nbytes,
                              impls=impls, obs=eff.obs != "off",
                              topology=topology_of(mesh,
                                                   _topo_sizes(mesh, axes)),
                              extra={"firing": firing,
                                     "max_bytes": int(max_bytes)})

    return get_or_build(key, build)


# ---------------------------------------------------------------------------
# Shared flatten/shard metadata (the ZeRO leg + gradsync FlatSpec users)
# ---------------------------------------------------------------------------


def flat_spec_for(tree, n_shards: int) -> fusion.FusedSpec:
    """Cached :class:`~torchmpi_tpu.fusion.FusedSpec` for ``(tree
    structure, n_shards)`` — the static flatten/pad/shard metadata the
    ZeRO update legs and ``zero.flat_spec`` used to rebuild on every
    trace.  Config-independent (no epoch in the key): the layout is a
    pure function of the avals and the shard count."""
    leaves, treedef = jax.tree.flatten(tree)
    avals = _avals(leaves)
    key = ("flatspec", treedef, avals, int(n_shards))

    def build():
        spec = fusion.FusedSpec(tree, int(n_shards))
        nbytes = sum(int(np.prod(s)) * np.dtype(d).itemsize
                     for s, d in avals)
        eff = runtime.effective_config()
        return CollectivePlan(key, "flatspec", "flatten",
                              nbytes=nbytes, spec=spec,
                              obs=eff.obs != "off",
                              extra={"n_shards": int(n_shards)})

    return get_or_build(key, build).spec


# ---------------------------------------------------------------------------
# Mesh-parallel serving replicas (torchmpi_tpu/serving/tp_engine.py)
# ---------------------------------------------------------------------------


def plan_serving_replica(replica: str, mesh, axes: Tuple[str, ...],
                         *, op: str = "tp_decode") -> CollectivePlan:
    """Decision-only plan row for one mesh-parallel serving replica:
    keyed per replica MESH via the topology fingerprint, so two
    replicas carved from different device slices — or the same replica
    after an elastic resize — read as distinct per-topology decisions
    in ``plan_tool.py dump-live`` instead of an opaque engine
    attribute.  The row records the sharded-decode dispatch choice
    (``shard_map`` over ``axes``); the engine's compiled executables
    key on the same (mesh, axis) tuple, so plan row and executable can
    never describe different topologies."""
    key = ("serving", replica, mesh, tuple(axes), op, epoch())

    def build():
        eff = runtime.effective_config()
        try:
            sizes = tuple(int(mesh.shape[a]) for a in axes)
        except Exception:  # noqa: BLE001 — a label must never fail a plan
            sizes = None
        return CollectivePlan(
            key, "serving", op, backend="shard_map",
            obs=eff.obs != "off",
            topology=topology_of(mesh, sizes),
            extra={"replica": replica, "axes": tuple(axes),
                   "devices": int(np.prod(mesh.devices.shape))})

    return get_or_build(key, build)
