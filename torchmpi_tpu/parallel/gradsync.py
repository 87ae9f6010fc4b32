"""Gradient/parameter synchronization for data-parallel SGD.

Rebuild of ``torchmpi.nn`` (SURVEY.md §3 C10, §4.3, reconstructed — reference
mount empty): ``synchronizeParameters(net)`` broadcast the parameters from
rank 0 at init; ``synchronizeGradients(net)`` allreduced gradParams after each
backward; an async variant overlapped per-layer allreduces with backprop.

TPU-native mapping:

- *Parameter sync* is a sharding statement: replicating the pytree over the
  mesh (``NamedSharding(mesh, P())``) makes every device hold rank-0's copy —
  the broadcast happens in the transfer.  An explicit in-axis broadcast is
  also provided for divergent-state repair (the reference's re-sync use case).
- *Gradient sync* is selector-routed ``allreduce_in_axis`` inside the jitted
  train step, so the hierarchical / custom backends apply to the hot path.
- *The async per-layer overlap* becomes **bucketing**: gradients are flattened
  into K buckets, each allreduced separately inside jit — XLA's latency-hiding
  scheduler overlaps bucket k's collective with bucket k+1's computation,
  playing the role of the reference's per-module hooks firing during backward.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import collectives, fusion, planner, runtime

PyTree = Any
AxisNames = Union[str, Tuple[str, ...]]


def _default_mesh(mesh: Optional[Mesh]) -> Mesh:
    return mesh if mesh is not None else runtime.current_mesh()


def _all_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


# ---------------------------------------------------------------------------
# Parameter synchronization (reference: mpinn.synchronizeParameters)
# ---------------------------------------------------------------------------


def synchronize_parameters(params: PyTree, *, mesh: Optional[Mesh] = None,
                           copy: bool = True) -> PyTree:
    """Replicate a parameter pytree across every device of the mesh.

    The reference broadcast ``net:parameters()`` from rank 0; here the
    replicating ``device_put`` *is* that broadcast (source: the controller's
    copy).  Returns the same values, now resident and replicated on the mesh.

    ``copy=True`` (default) breaks buffer aliasing with the input: a
    device_put of an on-device array can return an aliased buffer, and the
    usual next step donates the result into a train step — which would
    silently delete the caller's template.  This is an init-time op; the
    extra host round-trip is irrelevant.
    """
    m = _default_mesh(mesh)
    repl = NamedSharding(m, P())

    def put(a):
        if copy and isinstance(a, jax.Array):
            if a.is_fully_addressable:
                a = np.asarray(a)
            else:
                # Multi-host global array: host readback is impossible;
                # a device-side copy (fresh buffers, no donation) breaks
                # the aliasing just as well.
                a = jnp.copy(a)
        return jax.device_put(a, repl)

    return jax.tree.map(put, params)


def resynchronize_parameters_in_axis(params: PyTree, axis_names: AxisNames,
                                     *, root: int = 0,
                                     backend: Optional[str] = None) -> PyTree:
    """In-axis broadcast of params from ``root`` — for use inside shard_map
    when per-device state may have diverged (async PS training, debugging)."""
    return collectives.broadcast_in_axis(params, axis_names, root=root,
                                         backend=backend)


# ---------------------------------------------------------------------------
# Gradient synchronization (reference: mpinn.synchronizeGradients)
# ---------------------------------------------------------------------------


# The flatten/bucket/shard machinery is the fusion layer's FusedSpec —
# ONE definition shared by the fused in-axis collectives, the bucketed
# allreduce here, and ZeRO's shard layout (parallel/zero.py).  The old
# names stay importable: FlatSpec(tree, n_shards) is the same contract
# (single-dtype trees lay out byte-identically; mixed-dtype trees are
# now group-major so the wire never promotes).
FlatSpec = fusion.FusedSpec
flatten_tree = fusion.flatten_tree
unflatten_tree = fusion.unflatten_tree


def _wire_compress(compress, *, allowed=("bf16",), site: str):
    """Resolve/validate a wire-compression knob through the ONE shared
    helper (``torchmpi_tpu.compress.validate_wire`` — gradsync and zero
    used to each hand-roll the membership check).  The uncompressed
    fast path never imports the codec module."""
    if compress is None or compress in ("none", "off", ""):
        return None
    from .. import compress as _codec

    return _codec.validate_wire(compress, allowed=allowed, site=site)


def init_dcn_residuals(params_template: PyTree,
                       axis_names: Optional[AxisNames] = None, *,
                       mesh: Optional[Mesh] = None,
                       n_buckets: Optional[int] = None) -> List[jax.Array]:
    """Zero-initialized error-feedback residual state for
    :func:`synchronize_gradients` with a quantized DCN leg
    (docs/HIERARCHICAL.md): one f32 accumulator per gradient bucket,
    shaped ``[n_devices, shard]`` where ``shard`` is the bucket's
    ICI-scattered extent (the point where quantization happens).  Pass
    it through the train step sharded ``P(axes)`` on the leading axis
    and thread the returned state back in — the residual is persistent
    per-(site, bucket) state, exactly like optimizer state."""
    from .. import compress as _codec

    m = _default_mesh(mesh)
    if axis_names is None:
        axis_names = _all_axes(m)
    axes = _codec.ef_axes(axis_names)
    n_inner = int(m.shape[axes[1]])
    n_dev = int(np.prod([m.shape[a] for a in axes]))
    cfg = runtime.config() if runtime.is_initialized() else None
    if n_buckets is None:
        n_buckets = cfg.gradsync_buckets if cfg is not None else 1
    spec = fusion.FusedSpec(params_template, n_buckets=max(1, n_buckets))
    return _codec.init_residuals(
        _codec.expected_shards(
            [hi - lo for g in spec.groups for (lo, hi) in g.bounds],
            n_inner), n_dev)


def _dcn_ef_allreduce(grads: PyTree, axes: Tuple[str, ...], *, op: str,
                      n_buckets: int, codec: str, residuals
                      ) -> Tuple[PyTree, List]:
    """The error-feedback two-level gradient sync: per dtype-group
    bucket, reduce_scatter(ici) -> residual-corrected quantized
    allreduce(dcn) -> all_gather(ici) (``compress.ef_bucket_allreduce``
    — docs/HIERARCHICAL.md).  ``residuals`` is the per-bucket f32 state
    from :func:`init_dcn_residuals`; returns ``(synced, new_residuals)``
    with the new state in the old state's shapes."""
    from .. import compress

    outer, inner = axes
    spec = fusion.FusedSpec(grads, n_buckets=max(1, n_buckets))
    leaves = jax.tree.leaves(grads)
    launches = sum(len(g.bounds) for g in spec.groups)
    n_inner = lax.axis_size(inner)
    shard_lens = compress.expected_shards(
        [hi - lo for g in spec.groups for (lo, hi) in g.bounds], n_inner)
    res_list = compress.check_residuals(
        residuals, shard_lens, axes, site="synchronize_gradients",
        layout="the gradient bucket layout",
        init_hint="gradsync.init_dcn_residuals(params, ...) using the "
                  "SAME n_buckets/tree")
    from . import hierarchical

    min_bytes = runtime.effective_config().dcn_compress_min_bytes
    serialize = launches > 1 and hierarchical._serialize_collectives()
    out_leaves: List = [None] * spec.n_leaves
    new_res: List = []
    prev = None
    k = 0
    for g in spec.groups:
        flat = fusion.group_flat(leaves, g)
        parts = []
        for lo, hi in g.bounds:
            seg = flat[lo:hi]
            if serialize and prev is not None:
                # Each bucket is a psum_scatter/allreduce/all_gather
                # chain; unordered sibling chains deadlock the CPU
                # sim's blocking rendezvous (see
                # hierarchical._serialize_collectives) — chain bucket
                # i's input on bucket i-1's result there.
                seg, _ = lax.optimization_barrier((seg, prev))
            red, nr = compress.ef_bucket_allreduce(
                seg, outer, inner, codec, res_list[k], op=op,
                min_bytes=min_bytes)
            prev = red
            k += 1
            parts.append(red)
            new_res.append(nr)
        gout = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        fusion._unpack_group(gout, g, out_leaves)
    return jax.tree.unflatten(spec.treedef, out_leaves), new_res


def _bucketed_allreduce(grads: PyTree, axes: Tuple[str, ...], *, op: str,
                        n_buckets: int, backend: Optional[str],
                        barrier: bool = False) -> PyTree:
    """Per dtype group: concat -> ~K buckets -> one allreduce each ->
    unflatten (buckets distribute across groups by byte share; a
    single-dtype tree gets exactly K, the pre-fusion contract).

    The analog of the reference's async per-layer hooks (SURVEY §4.3): K
    independent collectives inside one jit give XLA the freedom to overlap
    them with surrounding compute.  Unlike the old promoted concat, each
    group reduces in its native dtype — a mixed fp32/bf16 tree keeps
    bf16 leaves bf16 on the wire.

    ``barrier=True`` chains each bucket's input on the previous bucket's
    output (across dtype groups too) through ``lax.optimization_barrier``,
    which keeps the K all-reduces DISTINCT through XLA's all-reduce
    combiner (measured: below the combine threshold the combiner
    otherwise merges every bucket into one collective) and issues them
    in order, so the
    latency-hiding scheduler can overlap bucket i's downstream use with
    bucket i+1's collective.  The cost is serialization of the
    collectives themselves; leave it off when one fused all-reduce is
    fastest (small models).

    The bucketing spec and per-bucket backend choices are planned once
    per gradient-tree structure and replayed across step builds
    (:func:`torchmpi_tpu.planner.plan_gradsync`).
    """
    if not jax.tree.leaves(grads):
        return grads
    return planner.plan_gradsync(grads, axes, op=op, n_buckets=n_buckets,
                                 backend=backend,
                                 barrier=barrier).replay(grads)


def synchronize_gradients(grads: PyTree, axis_names: Optional[AxisNames] = None,
                          *, op: Optional[str] = None,
                          n_buckets: Optional[int] = None,
                          backend: Optional[str] = None,
                          compress: Optional[str] = None,
                          barrier: Optional[bool] = None,
                          residuals=None,
                          dcn_compress: Optional[str] = None) -> PyTree:
    """Allreduce a gradient pytree across the data-parallel axes.

    For use inside a shard_map'd/jitted train step (the hot path).  Defaults:
    axes = every axis of the current world mesh; ``op`` = mean when
    ``config.gradsync_average`` (the reference allreduce-summed then divided
    by ``mpi.size()``); ``n_buckets`` from config.

    ``compress="bf16"`` halves bytes on the wire by reducing in bfloat16 and
    casting back — the lever that matters when the allreduce is DCN-bound
    (multi-slice scaling); gradients tolerate it in practice.  Config
    default: ``gradsync_compress``.

    ``barrier`` (config default ``gradsync_barrier``) keeps bucketed
    all-reduces distinct through XLA's combiner via optimization
    barriers — see :func:`_bucketed_allreduce`.

    With ``n_buckets <= 1`` the tree rides the fused in-axis allreduce
    (``config.fuse_max_bytes``): dtype-grouped coalescing, O(dtypes x
    buckets) launches instead of one per leaf, bit-identical results.

    ``residuals`` (state from :func:`init_dcn_residuals`) switches to
    the **error-feedback quantized DCN path** on a two-level mesh
    (docs/HIERARCHICAL.md): per-bucket reduce_scatter over ICI, the
    small shard crossing DCN quantized with ``dcn_compress`` (default
    ``config.dcn_compress`` — must not be off) after adding back the
    persistent residual, and the new quantization error returned as the
    next step's state: ``(synced_grads, new_residuals)``.  On a flat
    (``n_dcn <= 1``) span there is no DCN leg — the call degrades to
    the plain path and returns the residuals unchanged (the selector's
    topology-fallback counter notes it; being the plain path, it honors
    the config-level ``gradsync_compress``/``gradsync_barrier`` knobs
    exactly as a residual-free call would).  The two-level EF schedule
    itself is fixed: explicit ``backend=``/``compress=``/
    ``barrier=True`` raise, and config-level ``gradsync_compress``/
    ``gradsync_barrier`` do not apply to it (the DCN codec is the wire
    compression; the schedule orders its own DCN legs).
    """
    if axis_names is None:
        axis_names = _all_axes(runtime.current_mesh())
    axes = (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)
    cfg = runtime.config() if runtime.is_initialized() else None
    if op is None:
        op = "mean" if (cfg is None or cfg.gradsync_average) else "sum"
    if n_buckets is None:
        n_buckets = cfg.gradsync_buckets if cfg is not None else 1
    explicit_compress = compress is not None
    if compress is None and cfg is not None:
        compress = cfg.gradsync_compress
    compress = _wire_compress(compress, site="synchronize_gradients")
    explicit_barrier = barrier is not None
    if barrier is None:
        barrier = cfg.gradsync_barrier if cfg is not None else False
    if residuals is not None:
        if explicit_barrier and barrier:
            # Same contract as the resolve_ef backend=/compress=
            # policing: the EF collective is a fixed two-level schedule
            # that orders its own legs — silently dropping the knob
            # would be invisible to the caller.
            raise ValueError(
                "synchronize_gradients: barrier= does not combine with "
                "error-feedback residuals — the EF schedule orders its "
                "own collectives (the config-level gradsync_barrier "
                "knob is what the flat-span degradation honors)")
        # One shared activation gate (compress.resolve_ef): codec
        # required, explicit backend=/compress= raise — the EF path
        # dispatches a FIXED two-level schedule (config-level
        # gradsync_compress/gradsync_barrier do not apply to it; the
        # flat-span degradation below is the plain path and honors
        # them as usual — see the docstring).
        from .. import compress as _codec_mod

        codec = _codec_mod.resolve_ef(
            dcn_compress, cfg, site="synchronize_gradients",
            backend=backend, explicit_compress=explicit_compress,
            compress=compress)
        _codec_mod.ef_axes(axes)
        if lax.axis_size(axes[0]) <= 1:
            # Flat span: no DCN crossing to compress.  Same graceful
            # degradation as the selector's hierarchical fallback.  The
            # recursive plain-path call records the round under its own
            # (uncompressed) label — recording "dcn-<codec>" here would
            # double-count the round and claim a codec that never ran.
            # The resolved compress is passed through EXPLICITLY
            # ("none" when uncompressed) so an explicit compress="none"
            # opt-out is not re-resolved from config by the inner call.
            from .. import selector as _sel

            _sel._note_fallback("allreduce", "dcn-" + codec,
                                "flat mesh (n_dcn <= 1)",
                                target="the plain sync path")
            out = synchronize_gradients(grads, axes, op=op,
                                        n_buckets=n_buckets,
                                        backend=backend,
                                        compress=compress or "none",
                                        barrier=barrier)
            return out, residuals
        if cfg is not None and cfg.obs != "off":
            from .. import obs

            obs.record_gradsync(max(1, n_buckets), op, f"dcn-{codec}")
        synced, new_res = _dcn_ef_allreduce(grads, axes, op=op,
                                            n_buckets=n_buckets,
                                            codec=codec,
                                            residuals=residuals)
        if cfg is not None and cfg.guard in ("numeric", "full"):
            # Numeric tripwire on the synced output (docs/GUARD.md) —
            # trace-time gate, one fused reduction; off adds nothing.
            # The residuals revert to the PRE-step state under the same
            # verdict: a tripped round's error mass must not re-enter
            # the next step through the EF accumulator (code review).
            from .. import guard

            synced, new_res = guard.check_tree(
                synced, site="gradsync",
                aux=list(zip(new_res, residuals)))
        return synced, new_res
    if cfg is not None and cfg.obs != "off":
        from .. import obs

        obs.record_gradsync(n_buckets, op, compress)
    orig_dtypes = None
    if compress == "bf16":
        orig_dtypes = jax.tree.map(lambda g: g.dtype, grads)
        grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
    if n_buckets <= 1:
        out = collectives.allreduce_in_axis(grads, axes, op=op,
                                            backend=backend)
    else:
        out = _bucketed_allreduce(grads, axes, op=op, n_buckets=n_buckets,
                                  backend=backend, barrier=barrier)
    if orig_dtypes is not None:
        out = jax.tree.map(lambda g, d: g.astype(d), out, orig_dtypes)
    if cfg is not None and cfg.guard in ("numeric", "full"):
        # Numeric tripwire fused onto the synced gradients
        # (docs/GUARD.md): one sum-of-squares reduction over the round;
        # skip_step zeroes the whole update when tripped, raise
        # surfaces NumericAnomalyError.  Trace-time gate — guard="off"
        # adds zero branches to the compiled step.
        from .. import guard

        out = guard.check_tree(out, site="gradsync")
    return out


# ---------------------------------------------------------------------------
# Backprop-overlapped gradient sync (docs/OVERLAP.md).  The reference's
# async per-layer hooks fired an allreduce per module as its gradParams
# arrived during backward; the TPU-native equivalent wraps each gradient
# BUCKET's parameters in a custom_vjp whose backward rule IS the
# bucket's allreduce — the collective then sits in the backward graph at
# exactly the point where that bucket's cotangents are complete, and the
# latency-hiding scheduler hides it under the remaining backward
# compute.  An optimization-barrier token chain (the gradsync_barrier
# machinery, threaded through the custom_vjp rules) keeps the buckets
# distinct through XLA's all-reduce combiner and issues them in
# materialization order.
# ---------------------------------------------------------------------------


def overlap_bucket_bytes(mesh: Optional[Mesh] = None) -> int:
    """Byte bound for one overlap bucket: ``config.
    gradsync_overlap_bytes`` when set, else the tuning-plan-aligned
    bound (:func:`torchmpi_tpu.tuning.plan_bucket_bytes`) — the largest
    measured allreduce size bucket for this mesh when a plan is active,
    else ``fuse_max_bytes`` rounded down to a plan bucket edge.  Sizing
    from the plan's log2 buckets (instead of a fixed ``n_buckets``)
    keys every fired bucket to a collective size somebody measured."""
    cfg = runtime.effective_config()
    if cfg.gradsync_overlap_bytes > 0:
        return int(cfg.gradsync_overlap_bytes)
    from .. import tuning

    m = _default_mesh(mesh)
    return tuning.plan_bucket_bytes("allreduce", m,
                                    cfg.fuse_max_bytes or 32 * 1024 * 1024)


def assign_overlap_buckets(leaves: Sequence, max_bytes: int
                           ) -> List[List[int]]:
    """Reverse-parameter-order bucket assignment: walk the flattened
    tree's leaves LAST to FIRST — the order their cotangents
    materialize during backprop — starting a new bucket when the byte
    bound fills or the dtype changes (buckets stay dtype-pure, the
    fusion discipline: a mixed fp32/bf16 tree never promotes on the
    wire).  Returns buckets of leaf indices in FIRING order: bucket 0
    (the deepest layers) launches first."""
    max_bytes = max(1, int(max_bytes))
    buckets: List[List[int]] = []
    acc = 0
    cur_dt = None
    for i in range(len(leaves) - 1, -1, -1):
        leaf = leaves[i]
        b = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        if (not buckets or np.dtype(leaf.dtype) != cur_dt
                or acc + b > max_bytes):
            buckets.append([])
            acc = 0
            cur_dt = np.dtype(leaf.dtype)
        buckets[-1].append(i)
        acc += b
    return buckets


def _make_bucket_sync(idx: int, total: int, axes: Tuple[str, ...],
                      op: str, compress: Optional[str],
                      impl: Optional[Callable],
                      dcn_codec: Optional[str] = None):
    """One bucket's sync op: identity in forward, THE bucket's
    allreduce in backward.  ``token`` threads the optimization-barrier
    chain across buckets: the backward rule barriers its allreduce
    input on the incoming token (the previous-fired bucket's launch)
    and derives its outgoing token from the allreduce result — so the
    collectives stay distinct through the combiner and issue in firing
    order, each eligible the moment its cotangents exist.  ``impl`` is
    the plan's picked allreduce implementation for this bucket (None
    with ``dcn_codec``, whose two-level schedule is fixed).

    ``dcn_codec`` switches the backward rule to the error-feedback
    two-level allreduce (``compress.ef_bucket_allreduce``): the sync
    then takes a third ``res`` argument (this bucket's persistent f32
    residual) whose "cotangent" slot carries the NEW residual out —
    the state rides the AD graph, so it updates exactly when the
    bucket's collective fires, inside the backward pass."""

    def _pre(g, tok):
        """Shared bwd prologue: obs grads event, concat, barrier on the
        previous bucket's launch, obs launch event."""
        shapes = [x.shape for x in g]
        sizes = [int(np.prod(s)) for s in shapes]
        obs_on = runtime.effective_config().obs != "off"
        if obs_on:
            from .. import obs

            # Runtime evidence, not trace-time: the callback fires when
            # this bucket's cotangents materialize on each device — the
            # flight-ring ordering of grads/launch events across
            # buckets is the CPU-sim-checkable overlap invariant.
            jax.debug.callback(
                lambda *_a, _o=obs, _k=idx, _t=total:
                _o.record_overlap("grads", _k, _t),
                g[0].reshape(-1)[:1])
        flat = (g[0].reshape(-1) if len(g) == 1
                else jnp.concatenate([x.reshape(-1) for x in g]))
        flat, _ = lax.optimization_barrier((flat, tok))
        if obs_on:
            from .. import obs

            jax.debug.callback(
                lambda *_a, _o=obs, _k=idx, _t=total:
                _o.record_overlap("launch", _k, _t),
                flat[:1])
        return flat, shapes, sizes

    def _post(red, tok, shapes, sizes):
        """Shared bwd epilogue: outgoing token + per-leaf unflatten."""
        anchor = red[0] if sum(sizes) else tok
        tok_out, _ = lax.optimization_barrier((tok, anchor))
        out, off = [], 0
        for s, sz in zip(shapes, sizes):
            out.append(red[off:off + sz].reshape(s))
            off += sz
        return tuple(out), tok_out

    if dcn_codec is not None:
        outer, inner = axes

        @jax.custom_vjp
        def sync_ef(xs, token, res):
            return xs, token

        def fwd_ef(xs, token, res):
            return (xs, token), res

        def bwd_ef(res, cts):
            from .. import compress as _codec

            g, tok = cts
            flat, shapes, sizes = _pre(g, tok)
            red, new_res = _codec.ef_bucket_allreduce(
                flat, outer, inner, dcn_codec, res, op=op,
                min_bytes=runtime.effective_config()
                .dcn_compress_min_bytes)
            red = red.astype(flat.dtype)
            if runtime.effective_config().guard in ("numeric", "full"):
                # Numeric tripwire per overlap bucket (docs/GUARD.md):
                # fused into the same backward rule that fired the
                # collective — trace-time gate, zero cost when off.
                # The bucket's EF residual reverts to its pre-step
                # state under the same verdict (code review: a tripped
                # round's error mass must not ride the accumulator
                # into the next step).
                from .. import guard

                red, (new_res,) = guard.check_flat(
                    red, site="overlap", bucket=idx,
                    aux=[(new_res, res)])
            out, tok_out = _post(red, tok, shapes, sizes)
            return (out, tok_out, new_res)

        sync_ef.defvjp(fwd_ef, bwd_ef)
        return sync_ef

    @jax.custom_vjp
    def sync(xs, token):
        return xs, token

    def fwd(xs, token):
        return (xs, token), None

    def bwd(_, cts):
        g, tok = cts
        flat, shapes, sizes = _pre(g, tok)
        orig_dtype = flat.dtype
        if compress == "bf16":
            flat = flat.astype(jnp.bfloat16)
        red = impl(flat, axes, op=op)
        if compress == "bf16":
            red = red.astype(orig_dtype)
        if runtime.effective_config().guard in ("numeric", "full"):
            # Numeric tripwire per overlap bucket (docs/GUARD.md):
            # fused into the same backward rule that fired the
            # collective — trace-time gate, zero cost when off.
            from .. import guard

            red = guard.check_flat(red, site="overlap", bucket=idx)
        out, tok_out = _post(red, tok, shapes, sizes)
        return (out, tok_out)

    sync.defvjp(fwd, bwd)
    return sync


def init_overlap_dcn_residuals(params_template: PyTree,
                               axis_names: Optional[AxisNames] = None, *,
                               mesh: Optional[Mesh] = None,
                               max_bytes: Optional[int] = None
                               ) -> List[jax.Array]:
    """Zero-initialized error-feedback residual state for
    :func:`make_overlapped_grad_fn` with a quantized DCN leg: one f32
    accumulator per FIRING-ORDER overlap bucket (the reverse-parameter
    ``assign_overlap_buckets`` layout), shaped ``[n_devices, shard]``
    like :func:`init_dcn_residuals`."""
    from .. import compress as _codec

    m = _default_mesh(mesh)
    if axis_names is None:
        axis_names = _all_axes(m)
    axes = _codec.ef_axes(axis_names)
    n_inner = int(m.shape[axes[1]])
    n_dev = int(np.prod([m.shape[a] for a in axes]))
    leaves = jax.tree.leaves(params_template)
    if max_bytes is None:
        max_bytes = overlap_bucket_bytes(m)
    firing = assign_overlap_buckets(leaves, max_bytes)
    return _codec.init_residuals(
        _codec.expected_shards(
            [sum(int(np.prod(leaves[i].shape)) for i in bucket)
             for bucket in firing], n_inner), n_dev)


def make_overlapped_grad_fn(loss_fn: Callable, params_template: PyTree,
                            axis_names: Optional[AxisNames] = None, *,
                            mesh: Optional[Mesh] = None,
                            op: Optional[str] = None,
                            backend: Optional[str] = None,
                            compress: Optional[str] = None,
                            has_aux: bool = False,
                            max_bytes: Optional[int] = None,
                            residuals: bool = False,
                            dcn_compress: Optional[str] = None) -> Callable:
    """Build a ``value_and_grad`` whose gradients come back ALREADY
    allreduced, with each bucket's collective fired inside the backward
    pass as its cotangents materialize (the DDP overlap schedule; the
    reference's async per-layer hooks).

    For use INSIDE a shard_map'd/jitted train step, where
    ``synchronize_gradients`` would otherwise run after the full
    backward::

        vag = gradsync.make_overlapped_grad_fn(loss_fn, params, axes)
        loss, grads = vag(params, batch)      # grads are synced

    ``params_template`` supplies leaf shapes/dtypes for the bucket
    assignment — the traced ``params`` themselves work (the recipes
    step builders do exactly that), as does an ``eval_shape`` tree.
    Buckets are assigned in reverse parameter order (:func:
    `assign_overlap_buckets`) and sized from the tuning-plan size
    buckets (:func:`overlap_bucket_bytes`) unless ``max_bytes`` is
    given.  Defaults: ``op`` from ``config.gradsync_average``,
    ``compress`` from ``config.gradsync_compress`` — exactly
    :func:`synchronize_gradients`'s, and the results are bit-identical
    to it (test-asserted; the fused reductions are elementwise over
    the same cross-device order).

    Extra positional args flow through: ``vag(params, *batch)`` calls
    ``loss_fn(params, *batch)``.  ``has_aux`` follows
    ``jax.value_and_grad``.

    ``residuals=True`` arms the **error-feedback quantized DCN leg**
    (``dcn_compress``, default ``config.dcn_compress`` — must not be
    off; docs/HIERARCHICAL.md): each bucket's backward-pass collective
    becomes the two-level EF allreduce, and the returned callable takes
    the residual state as its SECOND argument —
    ``vag(params, residuals, *batch) -> (loss, (grads,
    new_residuals))`` — with the new state emerging through the
    residual slot of ``value_and_grad`` (the state update happens
    inside the backward pass, exactly when the bucket fires).  Build
    the state with :func:`init_overlap_dcn_residuals` using the same
    template/``max_bytes``.  On a flat (``n_dcn <= 1``) mesh the
    builder degrades to the plain overlap schedule — same calling
    convention, residuals handed back unchanged, the selector's
    topology-fallback counter notes it.  Explicit ``backend=``/
    ``compress=`` raise with ``residuals=True`` (the EF buckets run a
    fixed two-level schedule).
    """
    if axis_names is None:
        axis_names = _all_axes(_default_mesh(mesh))
    axes = (axis_names,) if isinstance(axis_names, str) \
        else tuple(axis_names)
    cfg = runtime.config() if runtime.is_initialized() else None
    if op is None:
        op = "mean" if (cfg is None or cfg.gradsync_average) else "sum"
    explicit_compress = compress is not None
    if compress is None and cfg is not None:
        compress = cfg.gradsync_compress
    compress = _wire_compress(compress, site="make_overlapped_grad_fn")
    codec = None
    ef_passthrough = False
    if residuals:
        # Same shared activation gate as synchronize_gradients
        # (compress.resolve_ef): codec required, explicit
        # backend=/compress= raise — the EF buckets run a FIXED
        # two-level schedule.
        from .. import compress as _codec_mod

        codec = _codec_mod.resolve_ef(
            dcn_compress, cfg, site="make_overlapped_grad_fn",
            backend=backend, explicit_compress=explicit_compress,
            compress=compress)
        _codec_mod.ef_axes(axes)
        if int(_default_mesh(mesh).shape[axes[0]]) <= 1:
            # Flat span: no DCN crossing to compress.  Degrade AT BUILD
            # TIME to the plain overlap schedule (bit-identical grads,
            # no pointless quantization) and thread the residual state
            # through unchanged — the same graceful fallback as
            # synchronize_gradients/zero, counted the same way.
            from .. import selector as _sel

            _sel._note_fallback("allreduce", "dcn-" + codec,
                                "flat mesh (n_dcn <= 1)",
                                target="the plain overlap schedule")
            codec = None
            ef_passthrough = True
    template_leaves, template_def = jax.tree.flatten(params_template)
    if not template_leaves:
        raise ValueError("make_overlapped_grad_fn: empty parameter tree")
    if max_bytes is None:
        max_bytes = overlap_bucket_bytes(mesh)
    # Bucket assignment + per-bucket backend choice, planned once per
    # (template avals, axes, knobs) and replayed across builder calls
    # (torchmpi_tpu/planner.py — a decision-only plan).  The EF path
    # uses the firing assignment only: its collective is the fixed
    # two-level schedule, not a selector pick.
    oplan = planner.plan_overlap(template_leaves, axes,
                                 assign_overlap_buckets, op=op,
                                 backend=backend, compress=compress,
                                 max_bytes=max_bytes, dcn_codec=codec)
    firing = oplan.extra["firing"]
    total = len(firing)
    syncs = [_make_bucket_sync(k, total, axes, op, compress,
                               impl=oplan.impls[k], dcn_codec=codec)
             for k in range(total)]
    if cfg is not None and cfg.obs != "off":
        from .. import obs

        obs.record_gradsync(total, op,
                            f"dcn-{codec}" if codec else compress)

    def _chain(params, res_list, *args):
        leaves, treedef = jax.tree.flatten(params)
        if len(leaves) != len(template_leaves):
            raise ValueError(
                f"make_overlapped_grad_fn: params tree has {len(leaves)} "
                f"leaves, template had {len(template_leaves)}")
        token = jnp.zeros((), jnp.float32)
        new = list(leaves)
        # Forward chain order is REVERSE firing order: AD traverses the
        # token chain backwards, so the bucket applied last — bucket 0,
        # the deepest layers — fires first.
        for k in range(total - 1, -1, -1):
            xs = tuple(leaves[i] for i in firing[k])
            if res_list is None:
                xs, token = syncs[k](xs, token)
            else:
                xs, token = syncs[k](xs, token, res_list[k])
            for i, v in zip(firing[k], xs):
                new[i] = v
        return loss_fn(jax.tree.unflatten(treedef, new), *args)

    if codec is None:
        def wrapped_loss(params, *args):
            return _chain(params, None, *args)

        plain = jax.value_and_grad(wrapped_loss, has_aux=has_aux)
        if not ef_passthrough:
            return plain

        def degraded_ef(params, residual_state, *args):
            # Flat-span EF degradation: plain overlapped grads, the
            # caller's residual state handed back unchanged in the EF
            # calling convention ((loss, (grads, residuals))).
            out, grads = plain(params, *args)
            return out, (grads, residual_state)

        return degraded_ef

    from .. import compress as _codec_mod

    # Expected per-bucket residual extents (the shared
    # compress.expected_shards formula init_overlap_dcn_residuals
    # builds with), so a wrong-SIZE state fails here with provenance
    # instead of as a raw reshape error deep in the backward pass.
    _ef_n_inner = int(_default_mesh(mesh).shape[axes[1]])
    _ef_shards = _codec_mod.expected_shards(
        [sum(int(np.prod(template_leaves[i].shape)) for i in bucket)
         for bucket in firing], _ef_n_inner)

    def wrapped_loss_ef(params, residual_state, *args):
        res_list = _codec_mod.check_residuals(
            residual_state, _ef_shards, axes,
            site="make_overlapped_grad_fn",
            layout="the overlap bucket layout",
            init_hint="gradsync.init_overlap_dcn_residuals(template, "
                      "...) using the SAME template/max_bytes")
        return _chain(params, res_list, *args)

    # The residual argnum rides value_and_grad: its "gradient" IS the
    # new residual state (fabricated by the custom_vjp bwd rules), so
    # callers get (loss, (grads, new_residuals)) from one call.
    return jax.value_and_grad(wrapped_loss_ef, argnums=(0, 1),
                              has_aux=has_aux)


def accumulate_gradients(loss_fn: Callable, params: PyTree, *batch: Any,
                         n_accum: int) -> Tuple[Any, PyTree]:
    """Microbatched gradient accumulation inside jit: split each batch
    array's leading axis into ``n_accum`` equal microbatches, run
    ``loss_fn(params, *microbatch) -> scalar loss`` under ``lax.scan``,
    and return ``(mean_loss, mean_grads)`` — numerically the full-batch
    gradient (for batch-size-independent losses like means over examples)
    at 1/n_accum the activation memory.

    The standard lever when the per-chip batch that keeps the MXU busy
    does not fit in HBM; composes with :func:`synchronize_gradients` /
    ``zero.update`` exactly like a plain ``value_and_grad`` result.
    """
    if n_accum <= 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        return loss, grads

    def split(x):
        lead = x.shape[0]
        if lead % n_accum != 0:
            raise ValueError(
                f"batch leading axis {lead} not divisible by "
                f"n_accum={n_accum}")
        return x.reshape(n_accum, lead // n_accum, *x.shape[1:])

    mbs = tuple(jax.tree.map(split, b) for b in batch)
    zero_g = jax.tree.map(jnp.zeros_like, params)
    # Carry dtype from the loss itself (f64 under x64, bf16 losses, ...).
    mb0 = tuple(jax.tree.map(lambda x: x[0], b) for b in mbs)
    loss_aval = jax.eval_shape(loss_fn, params, *mb0)
    init_loss = jnp.zeros(loss_aval.shape, loss_aval.dtype)

    def body(carry, mb):
        loss_sum, g_sum = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, *mb)
        return (loss_sum + loss,
                jax.tree.map(jnp.add, g_sum, grads)), None

    (loss_sum, g_sum), _ = jax.lax.scan(body, (init_loss, zero_g), mbs)
    inv = 1.0 / n_accum
    return loss_sum * inv, jax.tree.map(lambda g: g * inv, g_sum)


# ---------------------------------------------------------------------------
# Data-parallel step builder: the end-to-end TorchMPI recipe
# (broadcast params once; each step: local grads -> allreduce -> sgd)
# ---------------------------------------------------------------------------


def data_parallel_step(
    step_fn: Callable,
    *,
    mesh: Optional[Mesh] = None,
    batch_argnums: Sequence[int] = (2,),
    donate_argnums: Sequence[int] = (0, 1),
    max_inflight: Optional[int] = None,
    check_vma: bool = False,
) -> Callable:
    """Wrap ``step_fn(params, opt_state, batch, ...)`` into a jitted SPMD step.

    ``step_fn`` is written from one device's perspective on its local batch
    shard and must call :func:`synchronize_gradients` on its grads — exactly
    the reference's training-loop shape (SURVEY §4.3) with the allreduce
    inside the compiled step.  Params/opt_state are replicated; arguments
    listed in ``batch_argnums`` are sharded on their leading axis over all
    mesh axes.

    ``max_inflight`` bounds the number of dispatched-but-unfinished steps.
    XLA's CPU backend runs each simulated device's collective on a shared
    thread pool; an unbounded async queue can starve a collective rendezvous
    of its participant threads and abort the process, so the CPU default is a
    conservative 2 (double buffering).  On real TPU the default is 16 — deep
    enough to hide dispatch latency, bounded enough to cap device-memory
    pressure from donated buffers.
    """
    m = _default_mesh(mesh)
    axes = _all_axes(m)
    repl = P()
    shard = P(axes)

    def spec_for(i):
        return shard if i in set(batch_argnums) else repl

    def wrapped(*args):
        in_specs = tuple(spec_for(i) for i in range(len(args)))
        # check_vma stays False by default: under JAX's VMA type system,
        # differentiating replicated params against sharded batches makes
        # autodiff insert its own psum (the broadcast's transpose), so
        # gradients arrive pre-summed and an explicit synchronize_gradients
        # would be skipped/miscounted.  This library's contract is the
        # reference's: gradients are per-device until the user syncs them.
        # The cost: a step_fn that forgets synchronize_gradients returns
        # device 0's un-synced values silently — which is also exactly what
        # the reference did if you forgot synchronizeGradients.
        fn = shard_map(step_fn, mesh=m, in_specs=in_specs,
                       out_specs=repl, check_vma=check_vma)
        out = fn(*args)
        return out, completion_token(out)

    jitted = jax.jit(wrapped, donate_argnums=tuple(donate_argnums))
    # Opt-in static analysis (Config.analysis; docs/ANALYSIS.md): check
    # each new argument-shape signature once — the same cadence as jit's
    # own compile cache — before the delegate dispatches it.  Off (the
    # default) wraps nothing: the steady-state path is unchanged.
    cfg = runtime.config() if runtime.is_initialized() else None
    mode = getattr(cfg, "analysis", "off") if cfg is not None else "off"
    if mode in ("warn", "error"):
        from .. import analysis

        jitted = analysis.wrap_step(jitted, wrapped,
                                    label="data_parallel_step", mode=mode)
    stepper = throttle_dispatch(jitted, mesh=m, max_inflight=max_inflight)
    if cfg is not None and cfg.obs != "off":
        # Build-time gate (the never-imported-when-off discipline): the
        # per-call cost when on is one ring append marking the step
        # boundary BEFORE dispatch — the window edge obs_tool
        # attribute budgets against.
        from .. import obs

        obs.record_step_build("data_parallel_step")
        inner = stepper
        counter = [0]

        def stepper(*args):  # noqa: F811 — deliberate rebind
            obs.record_step("data_parallel_step", counter[0])
            counter[0] += 1
            return inner(*args)

        stepper.jitted = jitted
    if cfg is not None and cfg.guard in ("numeric", "full"):
        # The numeric tripwire's raise-policy boundary (docs/GUARD.md):
        # a tripped bucket is zeroed in-graph, and the deferred typed
        # error surfaces HERE, on the eager side of the dispatch — up
        # to max_inflight steps after the trip (the in-flight window).
        # Build-time gate: guard="off" returns the bare stepper.
        from .. import guard

        def guarded(*args):
            out = stepper(*args)
            guard.raise_pending()
            return out

        guarded.jitted = jitted
        return guarded
    return stepper


def completion_token(out: PyTree):
    """Scalar derived from a step's outputs — depends on them, is never
    returned to the caller, hence never donated back in: always safe to
    block on.  Pair with :func:`throttle_dispatch` (step builders return
    ``(out, completion_token(out))`` from their jitted body)."""
    leaves = jax.tree.leaves(out)
    return (jnp.ravel(leaves[0])[0].astype(jnp.float32)
            if leaves else jnp.float32(0))


def throttle_dispatch(jitted: Callable, *, mesh: Optional[Mesh] = None,
                      max_inflight: Optional[int] = None) -> Callable:
    """Bound the dispatched-but-unfinished step window of a jitted step that
    returns ``(out, completion_token)`` — see :func:`data_parallel_step` for
    why (CPU collective-rendezvous starvation; device-memory pressure from
    donated buffers).  Returns a callable yielding ``out`` only.

    The program's own step span, on the profiler's clock: every call into
    ``jitted`` (the enqueue, not the step's execution) sits in a
    ``jax.profiler.StepTraceAnnotation("tm.step", step_num=<n>)``, ``n``
    counting this stepper's calls from 0, and every wait for a step that
    is still running when the in-flight window is full in a
    ``TraceAnnotation("tm.step.throttle")``: the number of those spans is
    the number of steps the throttle held back (a token that is ready when
    its turn comes is dropped without one: past the first ``max_inflight``
    calls the window is always full, of steps that mostly finished).
    Unconditional: with no profiler attached an annotation is a flag test
    (docs/OBSERVABILITY.md, "What a profile shows").  ``obs.record_step``
    is the host-clock ring, gated by ``Config.obs``; this is neither."""
    if max_inflight is None:
        m = _default_mesh(mesh)
        platform = list(m.devices.flat)[0].platform
        max_inflight = 2 if platform == "cpu" else 16

    from collections import deque
    from itertools import count

    window: deque = deque()
    step_nums = count()

    def throttled(*args):
        # Throttle *before* dispatch so donated inputs are still live.
        while len(window) >= max_inflight:
            token = window.popleft()
            if token.is_ready():    # finished long ago: nothing held back
                jax.block_until_ready(token)
            else:
                with jax.profiler.TraceAnnotation("tm.step.throttle"):
                    jax.block_until_ready(token)
        with jax.profiler.StepTraceAnnotation("tm.step",
                                              step_num=next(step_nums)):
            out, token = jitted(*args)
        window.append(token)
        return out

    throttled.jitted = jitted  # escape hatch for benchmarking raw dispatch
    return throttled
