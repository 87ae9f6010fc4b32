"""Wire codecs for the inter-slice (DCN) leg of two-level collectives.

Multi-slice reality is a bandwidth cliff: ICI moves hundreds of GB/s per
chip, DCN a fraction of that (SNIPPETS.md [1]'s GSPMD pattern scales to
6000-chip superclusters by treating the two tiers differently).  The
two-level allreduce already sends only ``1/ici_n`` of the tensor over
DCN (reduce_scatter over ICI first — ``parallel/hierarchical.py``);
this module narrows that residual DCN payload further with scaled
integer/fp8 wire codecs, the deep-gradient-compression trade:

- **Only the post-reduce_scatter shard crossing DCN is quantized.**
  The ICI legs always run in the tensor's native dtype — the fusion
  discipline (never promote, never narrow where bandwidth is free).
- **Per-bucket scale**: ``int8``/``fp8`` payloads carry one f32 scale
  per bucket (``scale = amax / qmax``); the inter-slice sum runs as an
  all-gather of the quantized shards + scales with a local decoded
  reduction, so every rank computes the identical result from the
  identical wire bytes (no re-quantization between slices).
- **Error feedback** (the gradient-sync paths): a persistent
  per-(site, bucket) residual accumulator is added back before
  quantization and refilled with the new quantization error, so the
  bias of repeated rounding cancels over steps instead of accumulating
  — threaded as explicit state through
  ``gradsync.synchronize_gradients(residuals=...)``,
  ``gradsync.make_overlapped_grad_fn(residuals=...)``, and the ZeRO
  update legs (``dcn_residuals=...``).  Residuals are f32 regardless of
  the wire dtype (the error is below the wire's own precision).

Opt-in via ``Config.dcn_compress`` ("off"/"bf16"/"int8"/"fp8") +
``Config.dcn_compress_min_bytes``; **never imported when off** — the
same discipline as analysis/obs/faults: every call site resolves the
codec at trace/plan-build time behind one string compare, so a build
that never opts in pays zero import cost and dispatches bit-identically
(subprocess-asserted in tests/test_compress.py).

This module is also THE home of wire-compression validation
(:func:`validate_wire`): ``gradsync.py`` and ``zero.py`` used to each
hand-roll ``compress not in (None, "none", "bf16")``.

See docs/HIERARCHICAL.md for the codec semantics, the error-feedback
caveats (at-least-once delivery, restart), and the evidence workflow.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from . import fusion, runtime

# Codec name -> wire dtype.  fp8 is e4m3 (the gradient-friendly wide-
# mantissa variant).
CODECS = ("bf16", "int8", "fp8")
_WIRE_DTYPES = {
    "bf16": jnp.bfloat16,
    "int8": jnp.int8,
    "fp8": getattr(jnp, "float8_e4m3fn", None),
}
# Largest representable magnitude per quantized codec (the scale
# denominator): int8 symmetric [-127, 127]; e4m3fn tops out at 448.
_QMAX = {"int8": 127.0, "fp8": 448.0}


def validate_wire(value, *, allowed: Sequence[str] = CODECS,
                  site: str = "compress") -> Optional[str]:
    """Canonicalize a wire-compression knob: ``None``/"none"/"off"/""
    mean uncompressed (returns None); anything else must name a codec
    in ``allowed`` (case-insensitive) or this raises.  The ONE
    validation point for ``gradsync_compress`` (``allowed=("bf16",)``
    — the legacy whole-wire cast) and ``dcn_compress`` (all codecs)."""
    if value is None:
        return None
    v = str(value).strip().lower()
    if v in ("none", "off", ""):
        return None
    if v not in allowed:
        raise ValueError(
            f"{site}: unknown compression {value!r} "
            f"(allowed: {', '.join(allowed)} or none)")
    if _WIRE_DTYPES.get(v) is None:
        raise ValueError(
            f"{site}: codec {v!r} needs jnp.float8_e4m3fn, which this "
            f"jax build lacks")
    return v


def resolve_dcn(cfg) -> Optional[str]:
    """The active DCN codec from a Config (None when off)."""
    return validate_wire(getattr(cfg, "dcn_compress", "off"),
                         site="config.dcn_compress")


def resolve_ef(dcn_compress, cfg, *, site: str, backend=None,
               explicit_compress: bool = False, compress=None,
               allow_backend: bool = False) -> str:
    """Resolve + police one error-feedback entry point's knobs — THE
    shared activation gate for ``synchronize_gradients(residuals=)``,
    ``make_overlapped_grad_fn(residuals=True)``, and the ZeRO
    ``dcn_residuals=`` legs.  Returns the codec, never None: residual
    state without an active codec is an error.  The EF collective is a
    fixed two-level schedule, so an explicit ``backend=`` raises unless
    the caller routes *other* legs with it (``allow_backend`` — ZeRO's
    parameter all_gather), and an explicit resolved ``compress=`` (the
    legacy ICI wire cast) always raises rather than being silently
    dropped."""
    if dcn_compress is None and cfg is not None:
        dcn_compress = getattr(cfg, "dcn_compress", "off")
    codec = validate_wire(dcn_compress, site=f"{site}(dcn_compress)")
    if codec is None:
        raise ValueError(
            f"{site}: residual state given but no DCN codec active — "
            f"set Config.dcn_compress (or pass dcn_compress=) to "
            f"bf16|int8|fp8")
    if backend is not None and not allow_backend:
        raise ValueError(
            f"{site}: backend= does not combine with error-feedback "
            f"residuals — the EF collective is the fixed two-level "
            f"hierarchical schedule")
    if explicit_compress and compress is not None:
        raise ValueError(
            f"{site}: compress= does not combine with error-feedback "
            f"residuals — on this path the wire compression is the "
            f"DCN codec (dcn_compress)")
    return codec


def ef_axes(axis_names) -> Tuple[str, str]:
    """Validate/split the ``(outer, inner)`` axis pair every
    error-feedback entry point requires — the ONE home of the check
    (``gradsync``/``zero`` used to each hand-roll it)."""
    axes = ((axis_names,) if isinstance(axis_names, str)
            else tuple(axis_names))
    if len(axes) != 2:
        raise ValueError(
            f"DCN error feedback needs (outer, inner) axes, got {axes}")
    return axes[0], axes[1]


def init_residuals(shard_sizes: Sequence[int], n_dev: int) -> list:
    """Zero-initialized error-feedback accumulators: one f32
    ``[n_dev, shard]`` buffer per bucket.  The ONE place the residual
    buffer layout is defined — the ``init_*_residuals`` helpers in
    ``gradsync``/``zero`` all build through here, so a layout change
    lands everywhere at once."""
    return [jnp.zeros((int(n_dev), int(s)), jnp.float32)
            for s in shard_sizes]


def expected_shards(extents: Sequence[int], n_inner: int) -> list:
    """Per-bucket ICI-scattered residual extents — ``ceil(extent /
    n_inner)``, the point where quantization happens.  The ONE formula
    shared by the ``init_*_residuals`` helpers and every EF entry
    point's structural validation (a drifted copy would reject state
    its own init helper built)."""
    n = max(1, int(n_inner))
    return [-(-int(e) // n) for e in extents]


def wire_itemsize(codec: str) -> int:
    return np.dtype(_WIRE_DTYPES[codec]).itemsize


def wire_nbytes_of(n_elems: int, codec: str) -> int:
    """Bytes one device puts on the DCN wire for an ``n_elems`` bucket:
    the quantized payload plus the f32 scale (bf16 carries none)."""
    return int(n_elems) * wire_itemsize(codec) + (
        0 if codec == "bf16" else 4)


def encode(x, codec: str) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Quantize one bucket onto the wire.  Returns ``(payload, scale)``
    — ``scale`` is a scalar f32 for int8/fp8, None for bf16 (a plain
    cast).  ``x`` is promoted to f32 before scaling so bf16 inputs
    quantize from their exact values."""
    xf = x.astype(jnp.float32)
    if codec == "bf16":
        return xf.astype(jnp.bfloat16), None
    qmax = _QMAX[codec]
    amax = jnp.max(jnp.abs(xf)) if xf.size else jnp.float32(0)
    # The tiny floor keeps an all-zero bucket from dividing by zero; it
    # decodes back to exactly zero either way.
    scale = jnp.maximum(amax / qmax, jnp.float32(1e-30))
    if codec == "int8":
        q = jnp.clip(jnp.round(xf / scale), -qmax, qmax).astype(jnp.int8)
    else:
        q = (xf / scale).astype(_WIRE_DTYPES["fp8"])
    return q, scale


def decode(payload, scale, dtype=jnp.float32):
    """Inverse of :func:`encode` (up to the codec's rounding)."""
    if scale is None:
        return payload.astype(dtype)
    return (payload.astype(jnp.float32) * scale).astype(dtype)


def host_encode(x, codec: str):
    """Numpy twin of :func:`encode` for host-side payloads (the
    hot-state replication tier — docs/HOTSTATE.md — quantizes state
    deltas that already live in host RAM; a device round trip per
    streamed leaf would cost more than the quantization saves).  Same
    math, same tiny-floor scale, so a host encode decodes identically
    to a device encode of the same values."""
    xf = np.asarray(x, dtype=np.float32)
    if codec == "bf16":
        # No numpy bf16: keep the wire dtype discipline by truncating
        # the mantissa in uint32 space (round-to-nearest-even is what
        # jnp does; truncation here is fine — host bf16 is unused by
        # the exact-delta path, which is int8 + correction).
        u = xf.view(np.uint32)
        return ((u + 0x7FFF + ((u >> 16) & 1)) >> 16).astype(np.uint16), \
            None
    qmax = _QMAX[codec]
    amax = float(np.max(np.abs(xf))) if xf.size else 0.0
    scale = np.float32(max(amax / qmax, 1e-30))
    q = np.clip(np.round(xf / scale), -qmax, qmax).astype(np.int8)
    return q, scale


def host_decode(payload, scale, dtype=np.float32):
    """Inverse of :func:`host_encode` (up to the codec's rounding)."""
    if scale is None:
        u = payload.astype(np.uint32) << 16
        return u.view(np.float32).astype(dtype)
    return (payload.astype(np.float32) * np.float32(scale)).astype(dtype)


def _leg_record(op: str, codec: str, nbytes: int, wire_nbytes: int,
                min_bytes: int, axes, **extra) -> dict:
    """The one ``kind="dcn_compress"`` record schema (analysis rule C2
    reads these — a field rename lands here and in ``rules._rule_c2``
    only)."""
    return dict(kind="dcn_compress", op=op, codec=codec,
                nbytes=int(nbytes), wire_nbytes=int(wire_nbytes),
                min_bytes=int(min_bytes), axes=tuple(axes),
                source=fusion._record_source(), **extra)


def note_leg(op: str, codec: Optional[str], payload_nbytes: int,
             wire_nbytes: int, axes, *, min_bytes: int = 0) -> None:
    """Trace-time accounting for one DCN leg: the obs wire-byte
    counters (the CPU-sim-assertable win ``collectives_bench.py
    --dcn-compare`` reads) and the analysis C2 record.  Gated here so
    call sites stay one-liners; runs at trace only, never per step."""
    name = codec or "none"
    if runtime.effective_config().obs != "off":
        from . import obs

        obs.record_dcn(op, name, wire_nbytes, payload_nbytes)
    if fusion._trace_listener is not None:
        fusion._emit_trace_record(_leg_record(
            op, name, payload_nbytes, wire_nbytes, min_bytes, axes))


def note_skipped(op: str, codec: str, nbytes: int, axes, *,
                 min_bytes: int = 0, incompatible: bool = False) -> None:
    """Trace-time C2 evidence for a DCN leg that ran UNCOMPRESSED
    despite an active codec (incompatible op/payload, or below the
    ``dcn_compress_min_bytes`` floor): wire == payload, and no obs
    record — the caller's uncompressed dispatch accounts for itself."""
    if fusion._trace_listener is not None:
        extra = {"incompatible": True} if incompatible else {}
        fusion._emit_trace_record(_leg_record(
            op, codec, nbytes, nbytes, min_bytes, axes, **extra))


def dcn_allreduce(shard, outer: str, codec: str, *, residual=None,
                  op: str = "sum"):
    """Allreduce the ICI-scattered shard across slices (the DCN leg) on
    a quantized wire.  Returns ``(sum, new_residual)``.

    ``bf16`` rides a plain cast + psum (half the wire, one launch).
    ``int8``/``fp8`` all-gather the quantized shards + per-bucket
    scales over ``outer`` and reduce the decoded values locally — every
    slice computes the identical f32 sum from the identical wire bytes,
    so no slice ever re-quantizes another's contribution.

    ``residual`` (f32, shard-shaped) arms error feedback: it is added
    to the shard before quantization and ``new_residual`` is the new
    quantization error (``None`` in, ``None`` out).  ``op`` must be
    ``sum`` — mean scaling is the caller's (it owns the global count).
    """
    if op != "sum":
        raise ValueError(
            f"compressed DCN leg supports op='sum', got {op!r}")
    out_dtype = shard.dtype
    xf = shard.astype(jnp.float32)
    if residual is not None:
        xf = xf + residual.reshape(xf.shape).astype(jnp.float32)
    payload, scale = encode(xf, codec)
    if codec == "bf16":
        tot = lax.psum(payload, outer).astype(jnp.float32)
    else:
        from .parallel import hierarchical

        qs = lax.all_gather(payload, outer, axis=0, tiled=False)
        sin = scale
        if hierarchical._serialize_collectives():
            # Unordered sibling collectives deadlock the CPU sim's
            # blocking rendezvous (see hierarchical._serialize_collectives)
            # — chain the scale gather after the payload gather there.
            sin, _ = lax.optimization_barrier((sin, qs))
        ss = lax.all_gather(sin, outer, axis=0, tiled=False)
        tot = jnp.sum(qs.astype(jnp.float32) * ss[:, None], axis=0)
    new_residual = None
    if residual is not None:
        new_residual = xf - decode(payload, scale, jnp.float32)
    return tot.astype(out_dtype), new_residual


def ef_bucket_allreduce(flat, outer: str, inner: str, codec: str,
                        residual, *, op: str = "sum",
                        min_bytes: int = 0):
    """One bucket's two-level allreduce with error feedback:
    reduce_scatter(ici) -> EF-quantized allreduce(dcn) ->
    all_gather(ici).  ``flat`` is the bucket's 1-D concat (native
    dtype), ``residual`` this device's f32 accumulator (reshapeable to
    the shard: ``ceil(len/ici_n)`` elements).  A DCN shard below
    ``min_bytes`` (``config.dcn_compress_min_bytes``) crosses
    uncompressed with the residual passed through unchanged — the same
    floor the plain hierarchical path applies, with the C2 INFO
    evidence.  Returns ``(reduced_flat, new_residual)`` with the
    residual in the input residual's shape/dtype.  The gradient-sync
    EF entry point (``gradsync``/``zero``/the overlap schedule build
    on this)."""
    if op not in ("sum", "mean"):
        raise ValueError(
            f"error-feedback allreduce supports sum|mean, got {op!r}")
    n_i = lax.axis_size(inner)
    n_o = lax.axis_size(outer)
    length = flat.shape[0]
    pad = (-length) % n_i
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    shard = lax.psum_scatter(flat, inner, scatter_dimension=0, tiled=True)
    shard_nbytes = shard.size * shard.dtype.itemsize
    if min_bytes and shard_nbytes < int(min_bytes):
        note_skipped("allreduce", codec, shard_nbytes, (outer, inner),
                     min_bytes=min_bytes)
        if runtime.effective_config().obs != "off":
            from . import obs

            obs.record_dcn("allreduce", "none", shard_nbytes,
                           shard_nbytes)
        tot = lax.psum(shard, outer)
        new_res = residual
    else:
        note_leg("allreduce", codec, shard_nbytes,
                 wire_nbytes_of(shard.size, codec), (outer, inner),
                 min_bytes=min_bytes)
        tot, new_res = dcn_allreduce(shard, outer, codec,
                                     residual=residual.reshape(-1))
        new_res = new_res.reshape(residual.shape).astype(residual.dtype)
    full = lax.all_gather(tot, inner, axis=0, tiled=True)
    if pad:
        full = full[:length]
    if op == "mean":
        full = full / (n_i * n_o)
    return full, new_res


def ef_group_reduce_scatter(g_flat, outer: str, inner: str, codec: str,
                            residual, *, min_bytes: int = 0):
    """One dtype group's two-level ZeRO gradient leg with error
    feedback: deliver this device its ``_axis_index``-linearized flat
    shard of the summed group, quantizing only the DCN crossing.

    ``g_flat`` is the group's padded flat buffer (length divisible by
    ``n_outer * n_inner``).  The naive ici-then-dcn reduce_scatter
    would hand each device an ICI-MAJOR extent, but the persistent ZeRO
    state layout (``fusion.local_shard``) is dcn-major — so the buffer
    is pre-permuted (a pure relabeling; the reduction is elementwise)
    such that the cheap-first staging still lands every device on its
    dcn-major extent.  Returns ``(flat_shard [len/n], new_residual)``;
    the residual covers the ICI-scattered intermediate
    (``len/n_inner`` f32 elements), where the quantization happens.
    """
    n_i = lax.axis_size(inner)
    n_o = lax.axis_size(outer)
    sub = g_flat.shape[0] // (n_i * n_o)
    perm = g_flat.reshape(n_o, n_i, sub).swapaxes(0, 1).reshape(-1)
    s = lax.psum_scatter(perm, inner, scatter_dimension=0, tiled=True)
    s_nbytes = s.size * s.dtype.itemsize
    if min_bytes and s_nbytes < int(min_bytes):
        # Below the config floor: the DCN crossing runs uncompressed
        # with the residual passed through unchanged (C2 INFO).
        note_skipped("reduce_scatter", codec, s_nbytes, (outer, inner),
                     min_bytes=min_bytes)
        if runtime.effective_config().obs != "off":
            from . import obs

            obs.record_dcn("reduce_scatter", "none", s_nbytes, s_nbytes)
        tot = lax.psum(s, outer)
        new_res = residual
    else:
        note_leg("reduce_scatter", codec, s_nbytes,
                 wire_nbytes_of(s.size, codec), (outer, inner),
                 min_bytes=min_bytes)
        tot, new_res = dcn_allreduce(s, outer, codec,
                                     residual=residual.reshape(-1))
        new_res = new_res.reshape(residual.shape).astype(residual.dtype)
    shard = lax.dynamic_slice(tot, (lax.axis_index(outer) * sub,), (sub,))
    return shard, new_res


class ResidualMismatchError(ValueError):
    """Raised by the EF entry points when threaded residual state does
    not match the bucket layout.  A distinct type (still a ValueError
    for callers) so ``analysis.check`` can convert exactly this raise
    into its C2 finding without swallowing unrelated trace errors."""


def residual_note(expected: int, got: int, ok: bool, axes) -> None:
    """Trace-time record of an error-feedback residual binding for the
    analysis C2 rule: how many residual buffers the bucket layout
    expects vs what the caller threaded, and whether shapes lined up."""
    if fusion._trace_listener is not None:
        fusion._emit_trace_record(dict(
            kind="dcn_residual", expected=int(expected), got=int(got),
            ok=bool(ok), axes=tuple(axes),
            source=fusion._record_source()))


def check_residuals(residuals, want: Sequence[int], axes, *, site: str,
                    layout: str, init_hint: str) -> list:
    """Coerce + structurally validate one EF entry point's residual
    state against the expected per-bucket shard extents (the
    :func:`expected_shards` values) — the ONE home of the check for
    ``gradsync``/the overlap schedule/``zero``.  Emits the C2 evidence
    record BEFORE raising, so the analyzer reports the mismatch with
    provenance even though the runtime raise is what the user first
    hits.  Returns the coerced per-bucket list on success."""
    import jax

    res_list = (list(residuals) if isinstance(residuals, (list, tuple))
                else jax.tree.leaves(residuals))
    ok = (len(res_list) == len(want)
          and all(int(np.prod(r.shape)) == int(w)
                  for r, w in zip(res_list, want)))
    residual_note(len(want), len(res_list), ok, axes)
    if not ok:
        raise ResidualMismatchError(
            f"{site}: DCN residual state does not match {layout} "
            f"({len(res_list)} buffers of sizes "
            f"{[int(np.prod(r.shape)) for r in res_list]} vs "
            f"{len(want)} bucket(s) needing shard sizes {list(want)}) "
            f"— build the state with {init_hint}")
    return res_list
