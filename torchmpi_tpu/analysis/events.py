"""Jaxpr walker: turn a traced step function into a stream of
collective events.

The walker descends recursively through every higher-order primitive
that carries sub-jaxprs — ``pjit``, ``shard_map``, ``scan``, ``while``,
``cond``/``switch`` branches, ``custom_vjp``/``custom_jvp`` calls,
``remat`` — and records one :class:`CollectiveEvent` per collective
primitive it meets (``psum``/``pmin``/``pmax``, ``all_gather``,
``reduce_scatter``, ``ppermute``, ``all_to_all`` — everything the
``collectives.py`` wrappers lower to).

Alongside the events it maintains the two pieces of context the rules
need and a grep of the final HLO could never recover:

- **bound axes**: which mesh axis names are live at each event
  (``shard_map`` meshes, ``pmap`` axes, plus the ``axis_env`` the
  caller traced under) — rule D2's input.
- **rank taint**: a forward dataflow pass marking every intermediate
  value derived from ``axis_index`` (device rank).  A ``cond`` whose
  predicate is rank-tainted can take different branches on different
  devices of the same SPMD program — rule D1's input.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

# Collective primitives and where each keeps its axis names.  psum also
# covers pmean (psum + div) and the masked broadcast/reduce forms the
# in-axis wrappers lower to.
_COLLECTIVE_AXIS_PARAM = {
    "psum": "axes",
    "pmin": "axes",
    "pmax": "axes",
    "all_gather": "axis_name",
    "all_gather_invariant": "axis_name",
    "reduce_scatter": "axis_name",
    "psum_scatter": "axis_name",
    "ppermute": "axis_name",
    "all_to_all": "axis_name",
    "pgather": "axis_name",
}

# Primitives whose outputs are rank-derived by definition.
_RANK_SOURCES = ("axis_index",)


@dataclasses.dataclass
class CondFrame:
    """One enclosing ``cond``/``switch`` branch around an event."""

    site: int          # per-walk unique id of the cond equation
    branch: int        # which branch the event sits in
    n_branches: int
    pred_tainted: bool  # predicate is derived from axis_index/rank
    source: str = ""   # user frame of the cond itself


@dataclasses.dataclass
class CollectiveEvent:
    """One collective issued somewhere inside the traced step."""

    index: int                     # issue order over the whole walk
    primitive: str                 # jaxpr primitive name
    axes: Tuple[str, ...]          # named axes the collective spans
    nbytes: int                    # payload bytes (sum of array operands)
    dtype: str                     # first array operand's dtype name
    path: str                      # jaxpr traversal path
    source: str                    # user frame (file:line (fn)) or ""
    bound_axes: FrozenSet[str]     # axis names live at this point
    cond_stack: Tuple[CondFrame, ...] = ()
    region: int = 0                # id of the immediately containing jaxpr

    @property
    def unbound_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.axes if a not in self.bound_axes)

    @property
    def under_divergent_cond(self) -> bool:
        return any(f.pred_tainted for f in self.cond_stack)


def _user_source(source_info) -> str:
    """Best-effort ``file.py:line (fn)`` from an equation's source_info."""
    try:
        from jax._src import source_info_util

        fr = source_info_util.user_frame(source_info.traceback)
        if fr is None:
            return ""
        name = getattr(fr, "function_name", "") or ""
        return f"{fr.file_name}:{fr.start_line}" + (f" ({name})" if name
                                                    else "")
    except Exception:  # noqa: BLE001 — provenance is best-effort
        return ""


def _axis_names(params: dict, key: str) -> Tuple[str, ...]:
    v = params.get(key, ())
    if isinstance(v, str):
        return (v,)
    try:
        return tuple(a for a in v if isinstance(a, str))
    except TypeError:
        return ()


def _aval_nbytes(avals: Sequence) -> Tuple[int, str]:
    total, dtype = 0, ""
    for a in avals:
        shape = getattr(a, "shape", None)
        dt = getattr(a, "dtype", None)
        if shape is None or dt is None:
            continue
        total += int(np.prod(shape)) * np.dtype(dt).itemsize
        if not dtype:
            dtype = np.dtype(dt).name
    return total, dtype


def _subjaxprs(value) -> List:
    """Open ``Jaxpr``s reachable from one eqn param value."""
    out = []
    stack = [value]
    while stack:
        v = stack.pop()
        if hasattr(v, "eqns") and hasattr(v, "invars"):
            out.append(v)
        elif hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
            out.append(v.jaxpr)
        elif isinstance(v, (tuple, list)):
            stack.extend(v)
    return out


def _mesh_axis_names(mesh) -> Tuple[str, ...]:
    try:
        return tuple(str(a) for a in mesh.axis_names)
    except Exception:  # noqa: BLE001 — AbstractMesh variants
        try:
            return tuple(str(a) for a in dict(mesh.shape))
        except Exception:  # noqa: BLE001
            return ()


class _Walker:
    def __init__(self, bound_axes: FrozenSet[str]):
        self.events: List[CollectiveEvent] = []
        self.counter = 0
        self.cond_sites = 0
        self.region_ids: Dict[int, int] = {}
        self.initial_bound = bound_axes

    def _region(self, jaxpr) -> int:
        return self.region_ids.setdefault(id(jaxpr), len(self.region_ids))

    # -- taint plumbing ----------------------------------------------------

    @staticmethod
    def _tainted(v, taint: set) -> bool:
        # Literals carry no var identity and are never rank-derived.
        return not hasattr(v, "val") and v in taint

    def _any_tainted(self, vs, taint: set) -> bool:
        return any(self._tainted(v, taint) for v in vs)

    # -- the walk ----------------------------------------------------------

    def walk(self, jaxpr, *, bound: FrozenSet[str], taint: set,
             path: str, cond_stack: Tuple[CondFrame, ...]) -> set:
        """Walk one (open) jaxpr; returns the set of tainted outvars."""
        region = self._region(jaxpr)
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            in_tainted = self._any_tainted(eqn.invars, taint)

            if name in _RANK_SOURCES:
                taint.update(eqn.outvars)
                continue

            if name in _COLLECTIVE_AXIS_PARAM:
                axes = _axis_names(eqn.params,
                                   _COLLECTIVE_AXIS_PARAM[name])
                nbytes, dtype = _aval_nbytes(
                    [v.aval for v in eqn.invars if hasattr(v, "aval")])
                self.events.append(CollectiveEvent(
                    index=self.counter, primitive=name, axes=axes,
                    nbytes=nbytes, dtype=dtype, path=path,
                    source=_user_source(eqn.source_info),
                    bound_axes=bound, cond_stack=cond_stack,
                    region=region))
                self.counter += 1
                # A collective of rank-derived data still yields
                # rank-dependent output for gather-like ops; keep the
                # conservative flow.
                if in_tainted:
                    taint.update(eqn.outvars)
                continue

            if name in ("cond", "switch"):
                pred = eqn.invars[0]
                pred_tainted = self._tainted(pred, taint)
                branches = eqn.params.get("branches", ())
                site = self.cond_sites
                self.cond_sites += 1
                cond_src = _user_source(eqn.source_info)
                out_tainted = in_tainted
                for b, closed in enumerate(branches):
                    sub = getattr(closed, "jaxpr", closed)
                    sub_taint = set()
                    # Branch operands are eqn.invars[1:], positionally.
                    ops = eqn.invars[1:]
                    for sv, ov in zip(sub.invars, ops):
                        if self._tainted(ov, taint):
                            sub_taint.add(sv)
                    frame = CondFrame(site=site, branch=b,
                                      n_branches=len(branches),
                                      pred_tainted=pred_tainted,
                                      source=cond_src)
                    sub_out = self.walk(
                        sub, bound=bound, taint=sub_taint,
                        path=f"{path}/cond[{b}]",
                        cond_stack=cond_stack + (frame,))
                    out_tainted = out_tainted or bool(sub_out)
                # The selected branch depends on the predicate: a
                # rank-derived predicate makes every output
                # rank-derived.
                if out_tainted or pred_tainted:
                    taint.update(eqn.outvars)
                continue

            subs = []
            for v in eqn.params.values():
                subs.extend(_subjaxprs(v))

            if not subs:
                if in_tainted:
                    taint.update(eqn.outvars)
                continue

            # Higher-order primitive: bind axes for shard_map/pmap,
            # map taint across the boundary.
            sub_bound = bound
            if name == "shard_map":
                sub_bound = bound | set(
                    _mesh_axis_names(eqn.params.get("mesh")))
            elif name in ("xla_pmap", "pmap"):
                ax = eqn.params.get("axis_name")
                if isinstance(ax, str):
                    sub_bound = bound | {ax}

            out_tainted = False
            for sub in subs:
                sub_taint = set()
                if len(sub.invars) == len(eqn.invars):
                    # Positional match (pjit, shard_map, scan): precise.
                    for sv, ov in zip(sub.invars, eqn.invars):
                        if self._tainted(ov, taint):
                            sub_taint.add(sv)
                elif in_tainted:
                    # Unknown layout (while, custom_vjp consts):
                    # conservative — everything in is tainted.
                    sub_taint.update(sub.invars)
                sub_out = self.walk(
                    sub, bound=sub_bound, taint=sub_taint,
                    path=f"{path}/{name}", cond_stack=cond_stack)
                out_tainted = out_tainted or bool(sub_out)
            if out_tainted or in_tainted:
                taint.update(eqn.outvars)

        return {v for v in jaxpr.outvars if self._tainted(v, taint)}


def trace_events(closed_jaxpr, *,
                 bound_axes: Optional[Sequence[str]] = None
                 ) -> List[CollectiveEvent]:
    """Extract the collective-event stream from a ``ClosedJaxpr``.

    ``bound_axes``: axis names already live at the top level (the
    ``axis_env`` the caller traced under); axes bound by ``shard_map``/
    ``pmap`` equations inside are discovered during the walk.
    """
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    bound = frozenset(bound_axes or ())
    w = _Walker(bound)
    w.walk(jaxpr, bound=bound, taint=set(), path="", cond_stack=())
    return w.events
