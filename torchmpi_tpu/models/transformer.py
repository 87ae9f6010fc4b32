"""Decoder-only Transformer LM with pluggable sequence-parallel attention.

Not in the reference (pre-transformer library — SURVEY.md §6.7); this is the
long-context model family the TPU rebuild adds, wired to the
sequence-parallel attention strategies in ``parallel/sequence.py``:

- ``attn_impl="local"``   — ordinary full attention (single device / no SP)
- ``attn_impl="flash"``   — Pallas blocked flash attention (ops/flash.py):
  same math as local, [T, T] scores never materialize
- ``attn_impl="ring"``    — blockwise ring attention over ``seq_axis``
- ``attn_impl="ring_flash"`` — ring attention whose per-step local blocks
  run the Pallas flash kernel (long local shards without [T, T] blocks)
- ``attn_impl="ulysses"`` — all-to-all head-scatter attention over ``seq_axis``
- ``attn_impl="ulysses_flash"`` — ulysses with Pallas flash local blocks

With ``seq_axis`` set, the model is meant to run inside ``shard_map`` with
the sequence dimension sharded over that mesh axis; everything except
attention is position-local, so only the attention call communicates.
bfloat16-friendly: set ``dtype=jnp.bfloat16`` for MXU-width matmuls with
float32 parameters and softmax statistics.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..parallel import expert as eplib
from ..parallel import sequence as seqlib
from .generate import clamp_slot_positions

AxisNames = Union[str, Tuple[str, ...]]


def apply_rope(x, pos, *, base: float = 10000.0):
    """Rotary position embedding (RoPE): rotate feature pairs of ``x``
    ([B, T, H, D], D even) by angles ``pos[t] * base**(-2i/D)``.  Applied
    to q and k before attention — relative positions then live in the
    dot products, so no learned position table exists and decode just
    rotates each new token by its absolute position (``pos`` may be
    traced: cache index, ring-shard offset).  ``pos`` is [T] (one
    position per timestep, shared across the batch) or [B, T] (per-ROW
    positions — the slot-indexed continuous-batching decode, where every
    cache row sits at its own depth)."""
    D = x.shape[-1]
    if D % 2:
        raise ValueError(f"rope requires an even head_dim, got {D}")
    half = D // 2
    inv = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # [T, half] or [B, T, half]; the head axis is inserted below and the
    # leading batch axis (when absent) broadcasts — bitwise identical to
    # the historical [1, T, 1, half] layout for 1-D pos.
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos = jnp.cos(ang)[..., None, :]  # [(B,) T, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


class SPAttention(nn.Module):
    num_heads: int
    head_dim: int
    attn_impl: str = "local"
    seq_axis: Optional[AxisNames] = None
    dtype: jnp.dtype = jnp.float32
    decode: bool = False
    max_len: int = 0
    # Sliding-window attention (Mistral-style): each query sees itself
    # plus the window-1 tokens before it.  Supported by every impl:
    # local/flash (banded O(T*window) kernel grids), ring/ring_flash
    # (global-position band; the flash blocks skip fully-out-of-window
    # work at runtime — the dense ring masks but still pays its einsum,
    # and all n rotations run either way), ulysses/ulysses_flash (banded
    # grids on each head shard), and decode (the cache mask applies the
    # same band).
    window: Optional[int] = None
    # Grouped-query attention: fewer kv heads than q heads (None = MHA).
    # Each kv head serves num_heads/num_kv_heads consecutive q heads;
    # the decode KV cache stores only num_kv_heads — the serving-memory
    # win GQA exists for.  Supported by the "local"/"flash" impls for
    # both training and decode; sequence-parallel impls reject it.
    num_kv_heads: Optional[int] = None
    # Rotary position embeddings: rotate q/k by absolute positions
    # (pos_offset + local index; decode uses the cache index).  The
    # caller (TransformerLM(pos_emb="rope")) then adds no position table.
    rope: bool = False
    rope_base: float = 10000.0
    use_bias: bool = True

    @nn.compact
    def __call__(self, x, pos_offset=0):  # x: [B, T_local, E]
        B, T, E = x.shape
        bias, base = self.use_bias, self.rope_base
        H, D = self.num_heads, self.head_dim
        Hkv = self.num_kv_heads if self.num_kv_heads is not None else H
        if Hkv != H:
            from ..ops.flash import _gqa_group

            _gqa_group(H, Hkv)  # validates divisibility
            if self.attn_impl not in ("local", "flash"):
                raise ValueError(
                    f"num_kv_heads= supports attn_impl='local'/'flash' "
                    f"(got {self.attn_impl!r})")
            q = nn.DenseGeneral((H, D), axis=-1, dtype=self.dtype,
                                use_bias=bias,
                                name="q")(x).astype(jnp.float32)
            kv = nn.DenseGeneral((2, Hkv, D), axis=-1, dtype=self.dtype,
                                 use_bias=bias, name="kv")(x)
            k = kv[:, :, 0].astype(jnp.float32)
            v = kv[:, :, 1].astype(jnp.float32)
        else:
            qkv = nn.DenseGeneral((3, H, D), axis=-1, dtype=self.dtype,
                                  use_bias=bias, name="qkv")(x)
            q, k, v = (qkv[:, :, 0].astype(jnp.float32),
                       qkv[:, :, 1].astype(jnp.float32),
                       qkv[:, :, 2].astype(jnp.float32))
        if self.rope and not self.decode:
            rpos = pos_offset + jnp.arange(T)
            q = apply_rope(q, rpos, base=base)
            k = apply_rope(k, rpos, base=base)
        if self.decode:
            # Autoregressive KV-cache step: x is the NEW token(s) ([B, 1]
            # in the steady state); keys/values append into this layer's
            # [B, max_len] cache and q attends over the filled prefix.
            # NOT a ring buffer: the caller must keep total decoded length
            # <= max_len (generate() pre-checks; past it,
            # dynamic_update_slice clamps and outputs silently corrupt).
            #
            # Two cache layouts:
            # - "local": single-device, full [B, max_len, H, D] cache.
            # - "ulysses"/"ulysses_flash" with seq_axis (inside shard_map
            #   — the generate_parallel path): HEAD-SHARDED cache — each
            #   device caches H/n heads over the full sequence and
            #   computes attention for them, outputs all_gather back
            #   along the head dim.  The Ulysses decode analog: KV-cache
            #   memory per device is 1/n of the dense layout, the
            #   constraint that actually binds long-context serving.
            # Ring impls have no decode path (their sequence-sharded
            # cache cannot serve one new global token a step).
            ulysses = (self.attn_impl in ("ulysses", "ulysses_flash")
                       and self.seq_axis is not None)
            # "flash" is accepted as an alias of "local" here: decode
            # attends against the cache with the einsum below either
            # way (the train-time kernel never runs in decode), so a
            # flash-trained model serves without rebinding attn_impl.
            if self.attn_impl not in ("local", "flash") and not ulysses:
                raise ValueError(
                    f"decode=True supports attn_impl='local'/'flash' (or "
                    f"'ulysses' under generate_parallel), got "
                    f"{self.attn_impl!r}")
            if self.max_len <= 0:
                raise ValueError("decode=True needs max_len > 0")
            h_cache = Hkv  # GQA: the cache stores only the kv heads
            if ulysses:
                # (GQA cannot reach here: Hkv != H already restricted
                # attn_impl to local/flash above.)
                n_sp = lax.axis_size(self.seq_axis)
                if H % n_sp != 0:
                    raise ValueError(
                        f"ulysses decode needs num_heads {H} divisible "
                        f"by axis size {n_sp}")
                h_cache = H // n_sp
                h0 = lax.axis_index(self.seq_axis) * h_cache
                q = lax.dynamic_slice_in_dim(q, h0, h_cache, 2)
                k = lax.dynamic_slice_in_dim(k, h0, h_cache, 2)
                v = lax.dynamic_slice_in_dim(v, h0, h_cache, 2)
            # Slot-indexed decode (the continuous-batching serving path,
            # torchmpi_tpu/serving/): a 1-D ``pos_offset`` gives every
            # batch row its OWN cache position, so one [S, 1] step can
            # advance S in-flight requests sitting at different depths.
            # The internal ``idx`` counter is neither read nor advanced
            # — the slot engine owns per-row positions.
            po = jnp.asarray(pos_offset)
            per_row = po.ndim == 1
            ck = self.variable("cache", "k", jnp.zeros,
                               (B, self.max_len, h_cache, D), jnp.float32)
            cv = self.variable("cache", "v", jnp.zeros,
                               (B, self.max_len, h_cache, D), jnp.float32)
            idx = self.variable("cache", "idx",
                                lambda: jnp.zeros((), jnp.int32))
            # Write indices route through THE clamp chokepoint
            # (generate.clamp_slot_positions): identity for the valid
            # range the callers guarantee, but it makes the cache writes
            # below statically certifiable (analysis rules S1/S2) —
            # without it an out-of-range index would CLAMP inside
            # dynamic_update_slice and corrupt the last rows silently.
            start = clamp_slot_positions(idx.value, self.max_len, T)
            starts = (clamp_slot_positions(po.astype(jnp.int32),
                                           self.max_len, T)
                      if per_row else None)  # [B]
            if self.rope:
                # Rotate by absolute cache positions, THEN cache: the
                # cache holds rotated keys, so old entries never need
                # re-rotation as decoding advances.
                rpos = (starts[:, None] + jnp.arange(T) if per_row
                        else start + jnp.arange(T))
                q = apply_rope(q, rpos, base=base)
                k = apply_rope(k, rpos, base=base)
            if per_row:
                row_upd = jax.vmap(
                    lambda c, u, s: lax.dynamic_update_slice(c, u,
                                                             (s, 0, 0)))
                ck.value = row_upd(ck.value, k, starts)
                cv.value = row_upd(cv.value, v, starts)
            else:
                ck.value = lax.dynamic_update_slice(ck.value, k,
                                                    (0, start, 0, 0))
                cv.value = lax.dynamic_update_slice(cv.value, v,
                                                    (0, start, 0, 0))
                idx.value = start + T
            if T > 1 and not per_row:
                # Prefill block (generate's one full-prompt pass onto a
                # FRESH cache): causal attention within the block —
                # O(T^2), not O(T * max_len) against the mostly-empty
                # cache (at max_len 8k and Tp 256 that's 32x wasted score
                # FLOPs/memory).  Assumes start == 0, which is the only
                # way the scalar-offset serving path produces T > 1;
                # chunked prefill with history would need the
                # cache-prefix form.  Per-row T > 1 (the speculative
                # verify step: [S, K+1] tokens at per-slot depths) takes
                # the cache-masked branch below instead — its k/v were
                # just written at rows' own offsets, and the per-row
                # causal mask bounds each query at its own depth.
                o = seqlib.reference_attention(q, k, v, causal=True,
                                               window=self.window)
            else:
                # Steady-state single-token step: query the filled cache.
                # Causal mask over the cache: query t attends to cache
                # positions <= start + t.  Per-row (slot) decode masks
                # each row at its own depth — stale cache beyond a row's
                # filled prefix is -inf'd out, which is what makes slot
                # REUSE bit-identical to a fresh cache without zeroing.
                kv_pos = jnp.arange(self.max_len)
                if per_row:
                    q_pos = starts[:, None] + jnp.arange(T)  # [B, T]
                    mask = kv_pos[None, None, :] <= q_pos[:, :, None]
                    if self.window is not None:
                        mask &= (kv_pos[None, None, :]
                                 > q_pos[:, :, None] - self.window)
                    m_gqa, m_mha = mask[:, None, None], mask[:, None]
                else:
                    q_pos = start + jnp.arange(T)
                    mask = kv_pos[None, :] <= q_pos[:, None]  # [T, max_len]
                    if self.window is not None:
                        # Sliding window over the cache: same band the
                        # training mask applied, so decode logits match
                        # the trained distribution past the window.  (The
                        # cache still stores max_len entries; a rolling
                        # buffer is a memory optimization, not a
                        # semantics change.)
                        mask &= kv_pos[None, :] > q_pos[:, None] - self.window
                    m_gqa, m_mha = mask[None, None, None], mask[None, None]
                if h_cache != q.shape[2]:
                    # GQA (q has more heads than the cache — under
                    # ulysses decode q was head-sliced to h_cache too,
                    # so this is GQA only): GROUP the einsum instead of
                    # materializing a repeated full-H KV temporary per
                    # decode step — the cache stays Hkv-headed on the
                    # wire and in the dot.
                    g_rep = q.shape[2] // h_cache
                    qg = q.reshape(B, T, h_cache, g_rep, D)
                    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                                   ck.value) / (D ** 0.5)
                    s = jnp.where(m_gqa, s, -jnp.inf)
                    p = jax.nn.softmax(s, axis=-1)
                    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, cv.value)
                    o = o.reshape(B, T, q.shape[2], D)
                else:
                    s = jnp.einsum("bqhd,bkhd->bhqk", q,
                                   ck.value) / (D ** 0.5)
                    s = jnp.where(m_mha, s, -jnp.inf)
                    p = jax.nn.softmax(s, axis=-1)
                    o = jnp.einsum("bhqk,bkhd->bqhd", p, cv.value)
            if ulysses:
                # Heads back together in rank order (= original order).
                o = lax.all_gather(o, self.seq_axis, axis=2, tiled=True)
        elif self.attn_impl == "local":
            o = seqlib.reference_attention(q, k, v, causal=True,
                                           window=self.window)
        elif self.attn_impl == "flash":
            from ..ops.flash import flash_attention_grad

            o = flash_attention_grad(q, k, v, causal=True,
                                     window=self.window)
        elif self.attn_impl == "ring":
            o = seqlib.ring_attention(q, k, v, self.seq_axis, causal=True,
                                      window=self.window)
        elif self.attn_impl == "ring_flash":
            o = seqlib.ring_attention(q, k, v, self.seq_axis, causal=True,
                                      block_impl="flash",
                                      window=self.window)
        elif self.attn_impl == "ulysses":
            o = seqlib.ulysses_attention(q, k, v, self.seq_axis, causal=True,
                                         window=self.window)
        elif self.attn_impl == "ulysses_flash":
            o = seqlib.ulysses_attention(q, k, v, self.seq_axis, causal=True,
                                         block_impl="flash",
                                         window=self.window)
        else:
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}")
        o = o.astype(self.dtype).reshape(B, T, H * D)
        return nn.Dense(E, dtype=self.dtype, use_bias=bias, name="out")(o)


class MoEMLP(nn.Module):
    """Expert-parallel MLP: tokens routed over ``expert_axis`` with the
    all-to-all dispatch of parallel/expert.py.

    Parameter note: expert weights are declared GLOBAL ([n_experts, ...])
    and each device slices its own block by axis index, so the module works
    under the replicated-params recipes unchanged.  Compute and
    communication are true EP (tokens cross devices, each device runs only
    its experts); parameter MEMORY is not sharded — for memory-scaled EP,
    shard these params over the expert axis via shard_map in_specs instead.

    The device count comes from the axis itself (static at trace time), so
    params can never disagree with the dispatch topology.
    """

    experts_per_device: int
    mlp_ratio: int = 4
    expert_axis: Optional[AxisNames] = None
    capacity_factor: float = 2.0
    k: int = 1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):  # x: [B, T, E]
        B, T, E = x.shape
        axes = ((self.expert_axis,) if isinstance(self.expert_axis, str)
                else tuple(self.expert_axis))
        n_devices = 1
        for a in axes:
            n_devices *= lax.axis_size(a)
        n_experts = self.experts_per_device * n_devices
        gate_w = self.param("gate", nn.initializers.lecun_normal(),
                            (E, n_experts), jnp.float32)
        H = E * self.mlp_ratio
        w1 = self.param("w1", nn.initializers.lecun_normal(),
                        (n_experts, E, H), jnp.float32)
        w2 = self.param("w2", nn.initializers.lecun_normal(),
                        (n_experts, H, E), jnp.float32)
        start = lax.axis_index(axes) * self.experts_per_device
        w1_local = lax.dynamic_slice_in_dim(w1, start,
                                            self.experts_per_device, 0)
        w2_local = lax.dynamic_slice_in_dim(w2, start,
                                            self.experts_per_device, 0)

        def expert_fn(params_e, tokens):
            a, b = params_e
            return jnp.tanh(tokens @ a) @ b

        tokens = x.reshape(B * T, E)
        out, aux = eplib.moe_layer(tokens, gate_w, expert_fn,
                                   (w1_local, w2_local), self.expert_axis,
                                   capacity_factor=self.capacity_factor,
                                   k=self.k, return_aux=True)
        # Per-device load-balance loss, available to training code via
        # model.apply(..., mutable=["losses"]) -> aux["losses"]; scale
        # (typ. 1e-2) and add to the task loss.  Not sown at init so the
        # init-returned variables stay params-only (training code treats
        # them wholesale as optimizer state).
        if not self.is_initializing():
            self.sow("losses", "moe_load_balance", aux)
        return out.reshape(B, T, E).astype(self.dtype)


class ExpertFFN(nn.Module):
    """Top-k gated (ReGLU) expert feed-forward whose weights exist only for
    the experts ``held`` = ``(first, count)`` of ``n_experts`` (None: all):
    one chip's share of an expert-parallel layer, routed over all experts
    by a float32 router, dropless (``parallel/expert.held_experts``).  What
    the experts held elsewhere would add is left out; under an expert axis
    the exchange that brings it in wraps this module.

    ``router_in`` is what the router reads: ``Block`` hands it the
    attention's normed input, the block's pre-attention state.  The
    counters ``routes_held``, ``rows_computed`` and ``rows_moved``, the
    chosen ``experts`` and the float32 ``router_logits`` are sown to the
    ``moe`` collection (``mutable=["moe"]``), as ``MoEMLP`` sows its loss.
    """

    n_experts: int
    k: int
    width: int
    held: Optional[Tuple[int, int]] = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u, router_in):  # both [B, T, E]
        B, T, E = u.shape
        first, count = self.held or (0, self.n_experts)
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(),
                            (E, self.n_experts), jnp.float32)
        w_gate = self.param("w_gate", init, (count, E, self.width),
                            jnp.float32)
        w_up = self.param("w_up", init, (count, E, self.width), jnp.float32)
        w_down = self.param("w_down", init, (count, self.width, E),
                            jnp.float32)
        with jax.named_scope("route"):
            logits = jnp.dot(router_in.reshape(B * T, E).astype(jnp.float32),
                             router, precision=lax.Precision.HIGHEST)
        out, stats = eplib.held_experts(
            u.reshape(B * T, E).astype(self.dtype), logits, self.k, first,
            w_gate, w_up, w_down)
        if not self.is_initializing():
            for name, value in {**stats, "router_logits": logits}.items():
                self.sow("moe", name, value)
        return out.reshape(B, T, E)


def _norm(kind: str, eps: float):
    if kind == "layernorm":
        return nn.LayerNorm(epsilon=eps, dtype=jnp.float32)
    if kind == "rmsnorm":
        return nn.RMSNorm(epsilon=eps, dtype=jnp.float32)
    raise ValueError(f"unknown norm {kind!r}")


class Block(nn.Module):
    num_heads: int
    head_dim: int
    mlp_ratio: int = 4
    attn_impl: str = "local"
    seq_axis: Optional[AxisNames] = None
    # When set, the MLP becomes an expert-parallel MoE over this axis.
    moe_axis: Optional[AxisNames] = None
    moe_experts_per_device: int = 1
    moe_capacity_factor: float = 2.0
    moe_k: int = 1
    dtype: jnp.dtype = jnp.float32
    decode: bool = False
    max_len: int = 0
    window: Optional[int] = None
    num_kv_heads: Optional[int] = None
    rope: bool = False
    rope_base: float = 10000.0
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    use_bias: bool = True
    # n_experts > 0: the feed-forward is an ExpertFFN (top-``moe_k`` of
    # ``n_experts``, ``experts_held`` of them here, each ``expert_width``
    # wide) whose router reads the attention's input.
    n_experts: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    expert_width: int = 0

    @nn.compact
    def __call__(self, x, pos_offset=0):
        E = x.shape[-1]
        a = _norm(self.norm, self.norm_eps)(x)
        x = x + SPAttention(self.num_heads, self.head_dim, self.attn_impl,
                            self.seq_axis, self.dtype, decode=self.decode,
                            max_len=self.max_len, window=self.window,
                            num_kv_heads=self.num_kv_heads,
                            rope=self.rope, rope_base=self.rope_base,
                            use_bias=self.use_bias)(a, pos_offset)
        h = _norm(self.norm, self.norm_eps)(x)
        if self.n_experts:
            return x + ExpertFFN(self.n_experts, self.moe_k,
                                 self.expert_width, self.experts_held,
                                 dtype=self.dtype)(h, a)
        if self.moe_axis is not None:
            return x + MoEMLP(self.moe_experts_per_device, self.mlp_ratio,
                              self.moe_axis,
                              capacity_factor=self.moe_capacity_factor,
                              k=self.moe_k, dtype=self.dtype)(h)
        h = nn.Dense(E * self.mlp_ratio, dtype=self.dtype,
                     use_bias=self.use_bias)(h)
        h = nn.gelu(h)
        return x + nn.Dense(E, dtype=self.dtype, use_bias=self.use_bias)(h)


class TransformerLM(nn.Module):
    """Causal LM.  With ``seq_axis``, position embeddings use each shard's
    global offset, supplied as ``pos_offset`` (device-local sequence start)."""

    vocab: int = 256
    embed: int = 128
    depth: int = 2
    num_heads: int = 8
    head_dim: int = 16
    max_len: int = 4096
    attn_impl: str = "local"
    seq_axis: Optional[AxisNames] = None
    moe_axis: Optional[AxisNames] = None
    moe_experts_per_device: int = 1
    moe_capacity_factor: float = 2.0
    moe_k: int = 1
    dtype: jnp.dtype = jnp.float32
    # Autoregressive serving: decode=True switches attention to the KV
    # cache ("cache" collection; see models/generate.py for the loop).
    decode: bool = False
    # Sliding-window attention width (see SPAttention.window).
    window: Optional[int] = None
    # Grouped-query attention kv-head count (see SPAttention.num_kv_heads).
    num_kv_heads: Optional[int] = None
    # Position encoding: "learned" (absolute table, the default) or
    # "rope" (rotary embeddings applied to q/k in every attention layer;
    # no position table - max_len then only bounds the decode cache).
    pos_emb: str = "learned"
    # RoPE's base, the norm ("layernorm" | "rmsnorm") with its epsilon, and
    # whether the projections and the MLP carry a bias.
    rope_base: float = 10000.0
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    use_bias: bool = True
    # Per-layer layouts, one entry a layer (None: every layer alike): a
    # layer with 0 in ``window_layout`` attends over the whole context, one
    # with 0 in ``rope_layout`` rotates nothing (no positions at all).
    window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    # Sparse experts told which they hold (see Block / ExpertFFN): top
    # ``moe_k`` of ``n_experts``, ``experts_held`` = (first, count) here.
    n_experts: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    expert_width: int = 0

    @nn.compact
    def __call__(self, tokens, pos_offset=0, return_prehead: bool = False):
        # tokens: [B, T_local] int32
        B, T = tokens.shape
        x = nn.Embed(self.vocab, self.embed, dtype=self.dtype)(tokens)
        if self.pos_emb == "learned":
            table = nn.Embed(self.max_len, self.embed, dtype=self.dtype,
                             name="pos_embed")
            po = jnp.asarray(pos_offset)
            if po.ndim == 1:
                # Per-row offsets (slot-indexed decode): each batch row
                # embeds its own absolute position.
                x = x + table(po[:, None] + jnp.arange(T)[None])
            else:
                x = x + table(pos_offset + jnp.arange(T))[None]
        elif self.pos_emb != "rope":
            raise ValueError(f"unknown pos_emb {self.pos_emb!r}")
        for name in ("window_layout", "rope_layout"):
            layout = getattr(self, name)
            if layout is not None and len(layout) != self.depth:
                raise ValueError(f"{name} has {len(layout)} entries for "
                                 f"{self.depth} layers")
        for i in range(self.depth):
            windowed = self.window_layout is None or self.window_layout[i]
            rotated = self.rope_layout is None or self.rope_layout[i]
            x = Block(self.num_heads, self.head_dim,
                      attn_impl=self.attn_impl, seq_axis=self.seq_axis,
                      moe_axis=self.moe_axis,
                      moe_experts_per_device=self.moe_experts_per_device,
                      moe_capacity_factor=self.moe_capacity_factor,
                      moe_k=self.moe_k, dtype=self.dtype,
                      decode=self.decode, max_len=self.max_len,
                      window=self.window if windowed else None,
                      num_kv_heads=self.num_kv_heads,
                      rope=self.pos_emb == "rope" and bool(rotated),
                      rope_base=self.rope_base, norm=self.norm,
                      norm_eps=self.norm_eps, use_bias=self.use_bias,
                      n_experts=self.n_experts,
                      experts_held=self.experts_held,
                      expert_width=self.expert_width)(x, pos_offset)
        x = _norm(self.norm, self.norm_eps)(x)
        # Bias-free explicit unembedding (standard for LMs) so callers can
        # feed (pre-head activations, head matrix) to the fused
        # linear+cross-entropy kernel (ops/xent.py) and never materialize
        # [B*T, vocab] logits.
        head = self.param("head", nn.initializers.lecun_normal(),
                          (self.embed, self.vocab), jnp.float32)
        if return_prehead:
            return x, head
        return x @ head
