"""Fused pytree collectives: dtype-grouped leaf coalescing.

TorchMPI's core perf trick was coalescing/chunking tensor traffic
(PAPER.md §4.2/§4.3: custom chunked-pipelined collectives, per-layer
async hooks feeding a coalescing engine); the in-axis API used to do the
opposite — ``jax.tree.map`` one collective launch per leaf, so a
transformer parameter tree issued hundreds of tiny collectives whose
per-leaf sizes also defeated the selector cutover and the tuning plans
(each leaf keyed at its tiny size, never the real transfer).

This module is the coalescing layer, the same shape as PyTorch DDP's
gradient-bucket fusion:

- Leaves are grouped **by dtype, never promoted** — a mixed fp32/bf16
  tree keeps bf16 leaves bf16 on the wire (the old ``FlatSpec``
  ``result_type`` concat upcast them all to fp32, doubling their bytes).
- Each group concatenates into a flat buffer split into size-bounded
  **buckets** (``config.fuse_max_bytes``; 0 disables fusion), and ONE
  selector-routed collective is issued per bucket — ``selector.select``
  and the tuning plans see the true fused nbytes, O(dtypes x buckets)
  launches instead of O(leaves).
- The result unflattens back to the original tree (original shapes;
  dtypes come out of the wire untouched because no promotion happened).

:class:`FusedSpec` is also the shared flatten metadata for the bucketed
gradient allreduce (``parallel/gradsync``) and the ZeRO shard layout
(``parallel/zero``) — it subsumes the old ``gradsync.FlatSpec``
(single-dtype trees produce byte-identical layouts; mixed-dtype trees
now lay out group-major with per-group padding so the per-dtype wire
legs and the promoted optimizer view can never disagree about which
extent a device owns).

Numerics: fusion never changes results.  The fused reductions are
elementwise over a repacked buffer, so every element sees the same
cross-device reduction order as the per-leaf launch — fused == per-leaf
bit-for-bit, per dtype (``tests/test_fusion.py`` asserts exact
equality, and that the lowered HLO collective count actually drops).
"""

from __future__ import annotations

import os
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import runtime

PyTree = Any

# ---------------------------------------------------------------------------
# Trace-time analysis hook (torchmpi_tpu.analysis, rule C1).  The
# analyzer installs a listener around its make_jaxpr trace; fused
# launches and the ZeRO reduce-scatter legs then describe their layout
# (spec-vs-tree agreement, barrier chain coverage, shard alignment) as
# plain dict records.  One None-check per *trace* when no listener is
# installed — zero per-step runtime cost.
# ---------------------------------------------------------------------------

_trace_listener: Optional[Any] = None


def set_trace_listener(fn):
    """Install (``fn``) or clear (``None``) the analysis record
    listener; returns the previous listener so nested checks restore
    it."""
    global _trace_listener
    prev = _trace_listener
    _trace_listener = fn
    return prev


def _emit_trace_record(record: dict) -> None:
    if _trace_listener is not None:
        _trace_listener(record)


def _record_source() -> str:
    """Best-effort user call-site (``file.py:line``) for a record —
    the first stack frame outside this package."""
    import traceback

    pkg = os.path.dirname(os.path.abspath(__file__))
    for fr in reversed(traceback.extract_stack()[:-2]):
        if not os.path.abspath(fr.filename).startswith(pkg):
            return f"{fr.filename}:{fr.lineno}"
    return ""


# In-axis ops with elementwise, shape-preserving semantics: reducing (or
# copying) a concatenated buffer is exactly the concatenation of the
# per-leaf results, so coalescing is transparent.  reduce_scatter has
# its own tile-interleaved path (:func:`fused_reduce_scatter`);
# gather/allgather/scatter/alltoall change shapes per-leaf and stay on
# the tree.map path.
ELEMENTWISE_OPS = ("allreduce", "reduce", "broadcast")


class _DtypeGroup:
    """One dtype's slice of a :class:`FusedSpec`: which leaves, their
    layout in the group-flat buffer, padding, and bucket bounds."""

    __slots__ = ("dtype", "indices", "shapes", "sizes", "total", "padded",
                 "shard", "bounds", "leaf_buckets")

    def __init__(self, dtype):
        self.dtype = dtype
        self.indices: List[int] = []   # positions in the flattened tree
        self.shapes: List[Tuple[int, ...]] = []
        self.sizes: List[int] = []
        self.total = 0

    @property
    def nbytes(self) -> int:
        return self.total * np.dtype(self.dtype).itemsize


def _proportional_buckets(groups: Sequence[_DtypeGroup], k: int) -> List[int]:
    """Distribute ~``k`` buckets across groups proportionally to their
    byte share, at least one each (single-group trees get exactly ``k``,
    preserving the pre-fusion ``gradsync_buckets`` contract)."""
    tot = sum(g.nbytes for g in groups) or 1
    return [max(1, min(max(1, g.total), round(k * g.nbytes / tot)))
            for g in groups]


class FusedSpec:
    """Static fusion metadata for one pytree.

    Layout is **group-major**: leaves grouped by dtype (first-seen
    order), each group concatenated flat in leaf order and padded to a
    multiple of ``n_shards``.  Bucketing within a group is either
    byte-bounded (``max_bytes``, the in-axis fusion knob) or
    count-driven (``n_buckets``, the ``gradsync_buckets`` contract).

    Also carries the promoted single-buffer view the ZeRO optimizer
    math runs in (``dtype``/``padded``/``shard`` — the wire stays
    per-dtype; only the local shard promotes): the drop-in replacement
    for the old ``gradsync.FlatSpec``.
    """

    def __init__(self, tree: PyTree, n_shards: int = 1, *,
                 max_bytes: Optional[int] = None,
                 n_buckets: Optional[int] = None):
        leaves, self.treedef = jax.tree.flatten(tree)
        self.n_leaves = len(leaves)
        self.n_shards = int(n_shards)
        self.shapes = [l.shape for l in leaves]
        self.dtypes = [np.dtype(l.dtype) for l in leaves]
        self.sizes = [int(np.prod(s)) for s in self.shapes]
        self.total = int(sum(self.sizes))
        self.dtype = jnp.result_type(*self.dtypes) if leaves else jnp.float32

        by_dtype = {}
        self.groups: List[_DtypeGroup] = []
        for i, (shape, dt, size) in enumerate(
                zip(self.shapes, self.dtypes, self.sizes)):
            g = by_dtype.get(dt)
            if g is None:
                g = by_dtype[dt] = _DtypeGroup(dt)
                self.groups.append(g)
            g.indices.append(i)
            g.shapes.append(shape)
            g.sizes.append(size)
            g.total += size
        for g in self.groups:
            g.padded = max(self.n_shards,
                           -(-g.total // self.n_shards) * self.n_shards)
            g.shard = g.padded // self.n_shards

        # Promoted view: per-group padding, group-major concat.
        self.padded = (sum(g.padded for g in self.groups)
                       or self.n_shards)
        self.shard = self.padded // self.n_shards

        # Element-granularity bucket bounds per group (for the
        # elementwise ops) ...
        if n_buckets is not None:
            ks = _proportional_buckets(self.groups,
                                       max(1, int(n_buckets)))
        elif max_bytes and max_bytes > 0:
            ks = [max(1, min(max(1, g.total),
                             -(-g.nbytes // int(max_bytes))))
                  for g in self.groups]
        else:
            ks = [1] * len(self.groups)
        for g, k in zip(self.groups, ks):
            edges = np.linspace(0, g.total, k + 1).astype(int)
            g.bounds = [(int(edges[i]), int(edges[i + 1]))
                        for i in range(k) if edges[i] < edges[i + 1]]
            if not g.bounds:  # all-empty group: one degenerate bucket
                g.bounds = [(0, g.total)]
        # ... and leaf-granularity buckets (for reduce_scatter, where a
        # bucket boundary inside a leaf would break tile alignment):
        # greedy first-fit in leaf order against the same byte bound.
        limit = int(max_bytes) if (max_bytes and max_bytes > 0) else 0
        for g in self.groups:
            itemsize = np.dtype(g.dtype).itemsize
            buckets, acc = [[]], 0
            for pos, size in enumerate(g.sizes):
                b = size * itemsize
                if buckets[-1] and limit and acc + b > limit:
                    buckets.append([])
                    acc = 0
                buckets[-1].append(pos)
                acc += b
            g.leaf_buckets = buckets

    @property
    def n_launches(self) -> int:
        """Collectives one fused elementwise op issues for this tree."""
        return sum(len(g.bounds) for g in self.groups)


def group_flat(leaves: Sequence, g: _DtypeGroup, *, pad: bool = False):
    """Concatenate ``g``'s leaves (native dtype, no promotion) into one
    flat buffer, optionally zero-padded to ``g.padded``."""
    parts = [leaves[i].reshape(-1) for i in g.indices]
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    if pad and g.padded > g.total:
        flat = jnp.pad(flat, (0, g.padded - g.total))
    return flat


def _unpack_group(flat, g: _DtypeGroup, out_leaves: List) -> None:
    """Slice ``g``'s leaves back out of its (reduced) flat buffer.  No
    dtype cast: the wire never promoted, so ``flat`` already has the
    right dtype (or the reducer's own promotion — int pmean -> f32 —
    which per-leaf launches produce identically)."""
    off = 0
    for i, shape, size in zip(g.indices, g.shapes, g.sizes):
        out_leaves[i] = flat[off:off + size].reshape(shape)
        off += size


# ---------------------------------------------------------------------------
# Fused elementwise collectives (allreduce / reduce / broadcast)
# ---------------------------------------------------------------------------


def fuse_tree(op_name: str, tree: PyTree, axes: Tuple[str, ...], *,
              spec: FusedSpec, impls: Sequence, barrier: bool = False,
              **params) -> PyTree:
    """One selector-routed collective per (dtype group x bucket).

    ``barrier=True`` chains each bucket's input on the previous bucket's
    output through ``lax.optimization_barrier`` — the
    ``gradsync_barrier`` overlap lever, unchanged, now applied to the
    group-native buffers instead of one promoted concat.  The chain
    crosses dtype-group boundaries (a group's first bucket depends on
    the previous group's last), so ALL buckets stay distinct through
    XLA's all-reduce combiner, exactly as the old single-concat chain
    kept them.

    ``spec`` and ``impls`` are the plan's record
    (torchmpi_tpu/planner.py): the layout, and one picked
    implementation per bucket in this function's iteration order
    (group-major, then bucket order).
    """
    leaves = jax.tree.leaves(tree)
    out_leaves: List = [None] * spec.n_leaves
    prev = None
    links = 0
    launch = 0
    for g in spec.groups:
        flat = group_flat(leaves, g)
        parts = []
        for lo, hi in g.bounds:
            part = flat[lo:hi]
            if barrier and prev is not None:
                part, _ = lax.optimization_barrier((part, prev))
                links += 1
            prev = impls[launch](part, axes, **params)
            launch += 1
            parts.append(prev)
        gout = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        _unpack_group(gout, g, out_leaves)
    if runtime.effective_config().obs != "off":
        from . import obs

        # Trace-time accounting: leaves coalesced, launches issued, and
        # wire bytes vs the promoted-concat layout fusion replaced.
        wire = sum(g.nbytes for g in spec.groups)
        promoted = spec.total * np.dtype(spec.dtype).itemsize
        obs.record_fusion(op_name, spec.n_leaves, spec.n_launches, wire,
                          max(0, promoted - wire))
    if _trace_listener is not None:
        _emit_trace_record(dict(
            kind="fuse_tree", op=op_name, axes=tuple(axes),
            source=_record_source(),
            spec_leaves=spec.n_leaves, tree_leaves=len(leaves),
            spec_dtypes=[np.dtype(d).name for d in spec.dtypes],
            tree_dtypes=[np.dtype(l.dtype).name for l in leaves
                         if hasattr(l, "dtype")],
            spec_sizes=list(spec.sizes),
            tree_sizes=[int(np.prod(l.shape)) for l in leaves
                        if hasattr(l, "shape")],
            n_launches=spec.n_launches, barrier=bool(barrier),
            barrier_links=links))
    return jax.tree.unflatten(spec.treedef, out_leaves)


# ---------------------------------------------------------------------------
# Fused reduce_scatter: tile-interleaved layout
# ---------------------------------------------------------------------------


def fused_reduce_scatter(tree: PyTree, axes: Tuple[str, ...], *,
                         spec: FusedSpec, n: int, impls: Sequence,
                         op: str = "sum") -> PyTree:
    """Fused reduce_scatter that keeps each leaf's own tiling, one
    collective per leaf bucket of ``spec``; ``impls`` holds the picked
    implementation of each, in group-major leaf-bucket order, and ``n``
    is the spanned axis-size product the tiling divides by.

    A scatter of a plain concat would hand device ``i`` one contiguous
    extent of the fused buffer — not each leaf's tile ``i``.  Instead
    each leaf is viewed as its ``n`` tiles (``leaf.reshape(n, -1)``)
    and the bucket concatenates ALONG the tile axis, so the scattered
    extent ``i`` is exactly ``[leaf0_tile_i | leaf1_tile_i | ...]`` —
    bit-for-bit the per-leaf result.  Requires every leaf's leading dim
    divisible by ``n`` (the planner checks it and plans per leaf
    otherwise)."""
    leaves = jax.tree.leaves(tree)
    out_leaves: List = [None] * spec.n_leaves
    launch = 0
    for g in spec.groups:
        for bucket in g.leaf_buckets:
            tiles = [leaves[g.indices[pos]].reshape(n, -1)
                     for pos in bucket]
            flat = (tiles[0] if len(tiles) == 1
                    else jnp.concatenate(tiles, axis=1)).reshape(-1)
            shard = impls[launch](flat, axes, op=op)
            launch += 1
            off = 0
            for pos in bucket:
                i, shape = g.indices[pos], g.shapes[pos]
                ts = g.sizes[pos] // n
                out_leaves[i] = shard[off:off + ts].reshape(
                    (shape[0] // n,) + tuple(shape[1:]))
                off += ts
    return jax.tree.unflatten(spec.treedef, out_leaves)


# ---------------------------------------------------------------------------
# ZeRO shard layout (the old gradsync.FlatSpec contract, group-major)
# ---------------------------------------------------------------------------


def flatten_tree(tree: PyTree, spec: FusedSpec) -> jax.Array:
    """Concat all leaves into one flat vector, promoted to
    ``spec.dtype``: group-major layout, each group zero-padded to a
    multiple of ``spec.n_shards``.  Single-dtype trees reproduce the
    old ``gradsync.flatten_tree`` layout exactly."""
    leaves = jax.tree.leaves(tree)
    parts = [group_flat(leaves, g, pad=True).astype(spec.dtype)
             for g in spec.groups]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def unflatten_tree(flat: jax.Array, spec: FusedSpec) -> PyTree:
    """Inverse of :func:`flatten_tree`: slice, reshape, and cast each
    leaf back to its original dtype (padding dropped)."""
    out_leaves: List = [None] * spec.n_leaves
    off = 0
    for g in spec.groups:
        gf = flat[off:off + g.padded]
        off += g.padded
        goff = 0
        for i, shape, size in zip(g.indices, g.shapes, g.sizes):
            out_leaves[i] = gf[goff:goff + size].reshape(shape).astype(
                spec.dtypes[i])
            goff += size
    return jax.tree.unflatten(spec.treedef, out_leaves)


def local_shard(tree: PyTree, spec: FusedSpec, index) -> jax.Array:
    """Device ``index``'s flat promoted shard: each dtype group's extent
    ``index``, concatenated in group order — THE ZeRO shard
    linearization, chosen so it equals what the per-group (native
    dtype) fused reduce_scatter hands each device, promoted."""
    leaves = jax.tree.leaves(tree)
    parts = []
    for g in spec.groups:
        flat = group_flat(leaves, g, pad=True).astype(spec.dtype)
        parts.append(lax.dynamic_slice(flat, (index * g.shard,),
                                       (g.shard,)))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def unflatten_shards(flat: jax.Array, spec: FusedSpec) -> PyTree:
    """Rebuild the tree from the all-gather of per-device
    :func:`local_shard` outputs (``flat`` is their rank-order concat,
    ``spec.n_shards * spec.shard`` elements): regroup each group's
    per-device extents back into its padded flat, then unflatten."""
    rows = flat.reshape(spec.n_shards, spec.shard)
    out_leaves: List = [None] * spec.n_leaves
    col = 0
    for g in spec.groups:
        gf = rows[:, col:col + g.shard].reshape(-1)
        col += g.shard
        goff = 0
        for i, shape, size in zip(g.indices, g.shapes, g.sizes):
            out_leaves[i] = gf[goff:goff + size].reshape(shape).astype(
                spec.dtypes[i])
            goff += size
    return jax.tree.unflatten(spec.treedef, out_leaves)
