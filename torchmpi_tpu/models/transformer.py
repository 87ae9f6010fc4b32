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

import functools
import math
from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
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


def prefill_runs_flash(T: int, per_row: bool,
                       platform: Optional[str] = None) -> bool:
    """Which attention a ``decode=True`` call of :class:`SPAttention` runs
    for its NEW tokens: the flash forward kernel (``ops/flash``; True) or
    dense scores (False).  THE one rule: the layer decides by it at trace
    time and ``serving/engine.py`` counts by it
    (``stats["prefill_kernel_tokens"]``).  Not an ``attn_impl``, a
    ``Config`` field or an environment variable: what the code can observe.

    - ``T`` (static) and ``per_row``: only a prompt block on a FRESH cache
      (a scalar offset and ``T > 1``: ``generate``'s one-pass prefill,
      ``slot_prefill``) attends within itself, which is what the kernel
      computes.  A single token, the speculative verify step ``[S, K+1]``
      and the prefix-hit ``slot_extend`` (both per-row) attend against the
      CACHE and stay dense.
    - ``platform``: ``"tpu"`` where a Pallas kernel is compiled for the
      chip, ``"cpu"`` where it would run in the interpreter (None: what
      ``ops/ring._interpret_mode`` decides for every kernel of the
      library).  The interpreter is correct but slow, so the CPU keeps the
      dense form.  On the chip every ``T > 1`` takes the kernel, the
      smallest buckets too: a 30-layer prefill of 64 to 512 tokens takes
      the same time either way, within a millisecond, and from 1024 up the
      kernel wins (PERF.md section 6, PR 32), so there is no threshold.
    """
    if platform is None:
        from ..ops import ring

        platform = "cpu" if ring._interpret_mode() else "tpu"
    return platform == "tpu" and T > 1 and not per_row


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret"))
def _prompt_attention(q, k, v, *, window, block_q, block_k, interpret):
    """The prompt block's kernel call, traced ONCE a program: every layer
    hands it the same shapes, and a jitted function met again inside a trace
    is a call of the jaxpr it already has.  Without this each of a model's
    layers traces the kernel and lowers it to Mosaic anew, which a process
    pays before it can even look its program up in the compile cache: on
    the chip 26 s of a WARM set-up for four 30-layer programs, every one of
    them a cache hit (PERF.md section 6, PR 32).  The compiler inlines the
    calls: the program is the same.  What ``flash_attention`` would read
    from the runtime while tracing (the blocks, interpret mode) is an
    argument here, so that it is part of what the trace is keyed by
    (``Config.flash_prescale``, off by default, is still read inside)."""
    from ..ops.flash import flash_attention

    return flash_attention(q, k, v, causal=True, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


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
            # "flash" and "local" are ONE thing here, so a flash-trained
            # model serves without rebinding attn_impl: a token attends
            # against the cache with the einsum below, a prompt block
            # within itself, through the flash forward kernel or dense
            # scores as prefill_runs_flash says (the platform and the
            # static T decide, not attn_impl).
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
                # cache-prefix form (this kernel with a q_offset).
                # Per-row T > 1 (the speculative verify step: [S, K+1]
                # tokens at per-slot depths; the prefix-hit extend) takes
                # the cache-masked branch below instead — its k/v were
                # just written at rows' own offsets, and the per-row
                # causal mask bounds each query at its own depth.
                #
                # Two producers of the same o.  On the chip the flash
                # forward kernel, float32 at its door as in training:
                # the kv heads are not repeated and no [H, T, T] score
                # array exists (1.6 GB a layer at 24 heads and 4096
                # tokens).  A right-padded prompt needs nothing more:
                # the mask is causal, so a real token never sees a pad.
                # Where the kernel would be interpreted (the CPU), dense
                # scores.
                if prefill_runs_flash(T, per_row):
                    from .. import runtime
                    from ..ops import ring

                    block_q, block_k = runtime.resolve_blocks(
                        None, None, "flash_block_q", "flash_block_k")
                    o = _prompt_attention(
                        q, k, v, window=self.window, block_q=block_q,
                        block_k=block_k, interpret=ring._interpret_mode())
                else:
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


def yarn_rope(dim: int, base: float, yarn=None):
    """RoPE frequencies of ``dim // 2`` rotary pairs and the two YaRN
    factors, as ``deepseek_v3`` computes them: ``yarn`` is ``(factor,
    original_max_position, beta_fast, beta_slow, mscale, mscale_all_dim)``
    (None: plain RoPE).  Pairs that turn more than ``beta_fast`` times over
    the original positions keep their frequency, those that turn less than
    ``beta_slow`` times are divided by ``factor``, a linear ramp between.
    Returns ``(inv_freq [dim // 2] float32, the factor on cos and sin, the
    factor m on the softmax scale: scores are scaled by m squared)``."""
    freqs = float(base) ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return np.float32(1.0 / freqs), 1.0, 1.0
    factor, original, fast, slow, mscale, mscale_all = yarn

    def turns_at(rotations):        # the pair that turns so often
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    def m(scale):
        return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0

    low = max(math.floor(turns_at(fast)), 0)
    high = min(math.ceil(turns_at(slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high - low) or 0.001),
                   0, 1)
    inv = ramp / (factor * freqs) + (1 - ramp) / freqs
    return np.float32(inv), m(mscale) / m(mscale_all), m(mscale_all)


def apply_rope_pairs(x, pos, inv_freq, factor: float = 1.0):
    """RoPE over INTERLEAVED pairs: features ``(2i, 2i + 1)`` of ``x``
    ([B, T, H, D]) turn by ``pos * inv_freq[i]``; ``pos`` [T] or [B, T];
    ``factor`` scales cos and sin (YaRN's)."""
    ang = pos.astype(jnp.float32)[..., None] * inv_freq
    cos = (jnp.cos(ang) * factor)[..., None, :]
    sin = (jnp.sin(ang) * factor)[..., None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Module):
    """Attention over a low-rank LATENT of the keys and values
    (``deepseek_v3``'s, without a query latent), no bias anywhere:

        q = x W_q                       [T, H, D], D = nope + rope
        [c_raw, k_r] = x W_kva          rank + rope;  c = RMSNorm(c_raw)
        [k_n, v] = c W_kvb              [T, H, nope + v_dim]

    ``k_r`` is ONE rotary key for all heads; RoPE (interleaved pairs, YaRN
    frequencies) turns it and the last ``rope`` features of every query
    head.  Scores are ``(q_n . k_n + q_r . k_r) * D**-0.5 * m**2``, causal
    over the whole context.  With ``gate`` the heads' output is multiplied
    by ``sigmoid(x W_g)`` before the output projection.

    ``decode=True`` keeps a latent cache: ``c`` [B, max_len, rank] and the
    turned ``k_rope`` [B, max_len, rope], float32, no heads.  A block of
    prompt tokens on a fresh cache (scalar ``pos_offset``, T > 1) expands
    ``k_n`` and ``v`` per head and attends within the block
    (``latent_expand``); every other step attends ABSORBED over the cache
    (``latent_absorb``): ``q_lat = q_n W_uk^T``, scores over ``c`` and
    ``k_rope``, ``o = (P c) W_uv``, with ``W_kvb = [W_uk, W_uv]``: a key or
    a value per head never exists.  Positions, the per-row slot form and
    the cache's write indices follow :class:`SPAttention`."""

    num_heads: int
    head_dim: int                 # nope + rope: a query's and a key's width
    rope_dim: int
    v_dim: int
    rank: int
    dtype: jnp.dtype = jnp.float32
    decode: bool = False
    max_len: int = 0
    rope_base: float = 10000.0
    yarn: Optional[Tuple[float, ...]] = None
    norm_eps: float = 1e-6
    gate: bool = False

    @nn.compact
    def __call__(self, x, pos_offset=0):  # x: [B, T, E]
        B, T, E = x.shape
        H, D, dr, dv, r = (self.num_heads, self.head_dim, self.rope_dim,
                           self.v_dim, self.rank)
        dn = D - dr
        inv_freq, cs, m = yarn_rope(dr, self.rope_base, self.yarn)
        scale = D ** -0.5 * m * m

        def dense(features, name):
            return nn.DenseGeneral(features, axis=-1, dtype=self.dtype,
                                   use_bias=False, name=name)

        w_kvb = self.param("kv_b", nn.initializers.lecun_normal(),
                           (r, H, dn + dv), jnp.float32)
        po = jnp.asarray(pos_offset)
        per_row = self.decode and po.ndim == 1
        if self.decode:
            if self.max_len <= 0:
                raise ValueError("decode=True needs max_len > 0")
            cc = self.variable("cache", "c", jnp.zeros,
                               (B, self.max_len, r), jnp.float32)
            ckr = self.variable("cache", "k_rope", jnp.zeros,
                                (B, self.max_len, dr), jnp.float32)
            idx = self.variable("cache", "idx",
                                lambda: jnp.zeros((), jnp.int32))
            # the write indices go through THE clamp (see SPAttention)
            start = clamp_slot_positions(idx.value, self.max_len, T)
            starts = (clamp_slot_positions(po.astype(jnp.int32),
                                           self.max_len, T)
                      if per_row else None)  # [B]
            rpos = (starts[:, None] + jnp.arange(T) if per_row
                    else start + jnp.arange(T))
        else:
            rpos = pos_offset + jnp.arange(T)
        q = dense((H, D), "q")(x).astype(jnp.float32)
        q_n = q[..., :dn]
        q_r = apply_rope_pairs(q[..., dn:], rpos, inv_freq, cs)
        with jax.named_scope("latent_down"):
            kva = dense(r + dr, "kv_a")(x).astype(jnp.float32)
            c = nn.RMSNorm(epsilon=self.norm_eps, dtype=jnp.float32,
                           name="kv_norm")(kva[..., :r])
            k_r = apply_rope_pairs(kva[:, :, None, r:], rpos, inv_freq,
                                   cs)[:, :, 0]
        if self.decode:
            if per_row:
                row_upd = jax.vmap(
                    lambda cache, u, s: lax.dynamic_update_slice(
                        cache, u, (s, 0)))
                cc.value = row_upd(cc.value, c, starts)
                ckr.value = row_upd(ckr.value, k_r, starts)
            else:
                cc.value = lax.dynamic_update_slice(cc.value, c,
                                                    (0, start, 0))
                ckr.value = lax.dynamic_update_slice(ckr.value, k_r,
                                                     (0, start, 0))
                idx.value = start + T
        if not self.decode or (T > 1 and not per_row):
            # training, or a block of prompt tokens on a fresh cache:
            # keys and values per head, attention within the block
            with jax.named_scope("latent_expand"):
                kv = jnp.einsum("btr,rhd->bthd", c.astype(self.dtype),
                                w_kvb.astype(self.dtype)
                                ).astype(jnp.float32)
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_r[:, :, None], (B, T, H, dr))], axis=-1)
            o = seqlib.reference_attention(
                jnp.concatenate([q_n, q_r], axis=-1), k, kv[..., dn:],
                causal=True, scale=scale)
        else:
            with jax.named_scope("latent_absorb"):
                w = w_kvb.astype(jnp.float32)
                q_lat = jnp.einsum("bthd,rhd->bthr", q_n, w[..., :dn])
                s = (jnp.einsum("bthr,bkr->bhtk", q_lat, cc.value)
                     + jnp.einsum("bthd,bkd->bhtk", q_r, ckr.value)) * scale
                q_pos = rpos if per_row else rpos[None]       # [B | 1, T]
                mask = jnp.arange(self.max_len) <= q_pos[..., None]
                p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf),
                                   axis=-1)
                o_lat = jnp.einsum("bhtk,bkr->bthr", p, cc.value)
                o = jnp.einsum("bthr,rhd->bthd", o_lat, w[..., dn:])
        o = o.reshape(B, T, H * dv)
        if self.gate:
            with jax.named_scope("attn_gate"):
                o = o * jax.nn.sigmoid(
                    dense(H * dv, "gate")(x).astype(jnp.float32))
        return dense(E, "out")(o.astype(self.dtype))


def _per_row_block_refused(mixer: str) -> str:
    return (f"a {mixer} cannot take a block of tokens at per-row "
            "depths (speculative verify, prefix-cache extend): its "
            "state cannot be un-updated")


def _causal_depthwise_conv(x, taps, conv, true_len):
    """``x`` [B, T, C] through ``taps`` [K, C], tap k reading the input
    K - 1 - k positions back.  ``conv``: the ``cache`` variable of the last
    K - 1 inputs, or None.  With it, one token (T == 1) shifts the cached
    rows by one; a block starts from zeros and leaves the last K - 1 LIVE
    rows (``true_len``, traced; None: all of the block)."""
    K, T = taps.shape[0], x.shape[1]
    if conv is not None and T == 1:
        rows = jnp.concatenate([conv.value, x], axis=1)        # [B, K]
        conv.value = rows[:, 1:]
        return (rows * taps).sum(1, keepdims=True)
    rows = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    if conv is not None:
        live = T if true_len is None else true_len
        conv.value = lax.dynamic_slice_in_dim(
            rows, clamp_slot_positions(live, T + K - 1, K - 1), K - 1,
            axis=1)
    return sum(rows[:, k:k + T] * taps[k] for k in range(K))


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """The state-space-duality form of Mamba-2's recurrence (arXiv:2405.21060,
    section 6), from a ZERO state, a head ``h`` of group ``h // (H / G)``:

        S_t = exp(dt_t a_h) S_(t-1) + dt_t x_t (outer) b_t;   y_t = S_t c_t

    ``x`` [B, T, H, P], ``dt`` [B, T, H] (0 at a position leaves the state
    as it was: that is how a padded position is told), ``a`` [H] negative,
    ``b`` and ``c`` [B, T, G, N]; all float32, and every decay is an
    ``exp`` of a float32 difference of cumulative sums.  T is padded to a
    multiple of ``chunk`` with such dead positions.  Within a chunk the
    outputs are a masked ``[chunk, chunk]`` product of ``c b^T`` with the
    decays, a chunk's own end state is a product of the same kind, and the
    states pass from chunk to chunk by a scan over ``T / chunk`` steps.
    Heads lead and (position, position) are the minor axes, which is what
    the chip's tiles want.  Returns ``(y [B, T, H, P], the state after the
    last position [B, H, P, N])``."""
    B, T, H, P = x.shape
    G, N = b.shape[-2:]
    R, Q = H // G, chunk
    pad = -T % Q
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (T + pad) // Q
    xg = x.reshape(B, nc, Q, G, R, P).transpose(0, 1, 3, 4, 2, 5)
    dtg = dt.reshape(B, nc, Q, G, R).transpose(0, 1, 3, 4, 2)  # [B,nc,G,R,Q]
    bg = b.reshape(B, nc, Q, G, N).transpose(0, 1, 3, 2, 4)    # [B,nc,G,Q,N]
    cg = c.reshape(B, nc, Q, G, N).transpose(0, 1, 3, 2, 4)
    cum = jnp.cumsum(dtg * a.reshape(G, R, 1), axis=-1)        # inclusive
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    # decay from j to i inside a chunk (i >= j), times what j puts in
    seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf)
    cb = jnp.einsum("bcgin,bcgjn->bcgij", cg, bg)
    w = cb[:, :, :, None] * jnp.exp(seg) * dtg[..., None, :]
    y = jnp.einsum("bcgrij,bcgrjp->bcgrip", w, xg)
    # a chunk's own end state, and the scan that passes states on
    to_end = jnp.exp(cum[..., -1:] - cum) * dtg                # [B,nc,G,R,Q]
    own = jnp.einsum("bcgrjp,bcgjn->bcgrpn", xg * to_end[..., None], bg)
    through = jnp.exp(cum[..., -1])                            # [B,nc,G,R]

    def pass_on(state, chunk_of):
        mine, decay = chunk_of
        return decay[..., None, None] * state + mine, state

    final, before = lax.scan(
        pass_on, jnp.zeros((B, G, R, P, N), jnp.float32),
        (own.swapaxes(0, 1), through.swapaxes(0, 1)))
    y = y + (jnp.einsum("bcgin,bcgrpn->bcgrip", cg, before.swapaxes(0, 1))
             * jnp.exp(cum)[..., None])
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(B, T + pad, H, P)[:, :T]
    return y, final.reshape(B, H, P, N)


class Mamba2Mixer(nn.Module):
    """A Mamba-2 mixer (``nemotron_h``'s ``M`` layer), no bias on either
    projection:

        [z, xBC, dt] = u W_in          widths H P, H P + 2 G N, H
        xBC <- silu(conv(xBC))         causal, depthwise, ``conv_width`` taps
                                       and a bias; split x, b, c
        dt <- softplus(dt + dt_bias);  a = -exp(A_log)
        S_t = exp(dt_t a) S_(t-1) + dt_t x_t (outer) b_t
        y_t = S_t c_t + D x_t          a head, state S [P, N], zero at first
        y <- RMSNorm_grouped(y * silu(z))   the gate, THEN the norm over
                                            groups of H P / G channels
        out = y W_out

    Everything between the two projections is float32.  A block of tokens
    runs :func:`ssd_chunked`.  ``decode=True`` keeps what the recurrence
    carries in the ``cache`` collection, and NEITHER leaf has a token axis
    (``models.generate.STATE_LEAVES``): ``ssm_state`` [B, H, P, N] and
    ``conv_state`` [B, conv_width - 1, H P + 2 G N], the last inputs of the
    convolution.  A prompt block (T > 1, a scalar ``pos_offset``: a FRESH
    cache, as :class:`SPAttention` assumes) starts from zero and leaves the
    state after its last LIVE token: with ``true_len`` (traced; None: all
    of it) the positions from there on get ``dt = 0``, which changes
    nothing (``exp(0 a) = 1``, ``0 x b = 0``), and the convolution's state
    is the last live rows.  One token (T == 1) is ONE recurrent update of
    every row; no position enters.  A block at per-row depths (speculation's
    verify step, the prefix cache's extend) would need a state that can be
    un-updated or copied by fragment: refused here and by the engine."""

    num_heads: int
    head_dim: int
    state: int
    groups: int
    conv_width: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, u, pos_offset=0, true_len=None):  # u: [B, T, E]
        B, T, E = u.shape
        H, P, N, G, K = (self.num_heads, self.head_dim, self.state,
                         self.groups, self.conv_width)
        inner, wide = H * P, H * P + 2 * G * N
        f32 = jnp.float32

        def param(name, init, shape):
            return self.param(name, init, shape, f32).astype(f32)

        with jax.named_scope("ssm_in_proj"):
            proj = nn.Dense(inner + wide + H, dtype=self.dtype,
                            use_bias=False, name="in_proj")(u)
        z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + wide],
                      proj[..., inner + wide:])
        taps = param("conv_kernel", nn.initializers.lecun_normal(), (K, wide))
        conv_bias = param("conv_bias", nn.initializers.zeros, (wide,))
        dt = jax.nn.softplus(dt.astype(f32) + param(
            "dt_bias", nn.initializers.zeros, (H,)))
        a = -jnp.exp(param("A_log", nn.initializers.zeros, (H,)))
        skip = param("D", nn.initializers.ones, (H,))
        scale = param("norm_scale", nn.initializers.ones, (inner,))
        xbc = xbc.astype(f32)
        if self.decode:
            if T > 1 and jnp.ndim(pos_offset) == 1:
                raise ValueError(_per_row_block_refused("Mamba2Mixer"))
            ssm = self.variable("cache", "ssm_state", jnp.zeros,
                                (B, H, P, N), f32)
            conv = self.variable("cache", "conv_state", jnp.zeros,
                                 (B, K - 1, wide), f32)
        step = self.decode and T == 1
        with jax.named_scope("ssm_conv"):
            xbc = jax.nn.silu(_causal_depthwise_conv(
                xbc, taps, conv if self.decode else None, true_len)
                + conv_bias)
        x = xbc[..., :inner].reshape(B, T, H, P)
        b = xbc[..., inner:inner + G * N].reshape(B, T, G, N)
        c = xbc[..., inner + G * N:].reshape(B, T, G, N)
        if step:
            with jax.named_scope("ssm_step"):
                xg = x.reshape(B, G, H // G, P)
                dtg = dt.reshape(B, G, H // G)
                state = (jnp.exp(dtg * a.reshape(G, -1))[..., None, None]
                         * ssm.value.reshape(B, G, H // G, P, N)
                         + (dtg[..., None] * xg)[..., None]
                         * b.reshape(B, G, 1, 1, N))
                ssm.value = state.reshape(B, H, P, N)
                y = jnp.einsum("bgrpn,bgn->bgrp", state,
                               c.reshape(B, G, N)).reshape(B, T, H, P)
        else:
            with jax.named_scope("ssm_scan"):
                if true_len is not None:
                    dt = jnp.where(jnp.arange(T)[:, None] < true_len, dt, 0.0)
                y, state = ssd_chunked(x, dt, a, b, c, self.chunk)
                if self.decode:
                    ssm.value = state
        with jax.named_scope("ssm_gate_norm"):
            y = ((y + skip[:, None] * x).reshape(B, T, inner)
                 * jax.nn.silu(z.astype(f32))).reshape(B, T, G, inner // G)
            y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + self.norm_eps)
            y = y.reshape(B, T, inner) * scale
        with jax.named_scope("ssm_out_proj"):
            return nn.Dense(E, dtype=self.dtype, use_bias=False,
                            name="out_proj")(y.astype(self.dtype))


def selective_scan(x, delta, a, b, c, chunk: int):
    """Mamba-1's selective scan (arXiv:2312.00752, section 3.2 and algorithm
    2), from a ZERO state; a channel ``d`` has its own decay for each of its
    ``N`` states, and ``b`` and ``c`` are shared by all channels:

        h_t[n, d] = exp(delta_t[d] a[n, d]) h_(t-1)[n, d]
                    + delta_t[d] x_t[d] b_t[n];      y_t[d] = h_t[:, d] . c_t

    ``x`` and ``delta`` [B, T, Di] (``delta`` 0 at a position leaves the
    state as it was: that is how a padded position is told), ``a`` [N, Di]
    negative, ``b`` and ``c`` [B, T, N]; all float32.  It is the recurrence
    as written, one token after another: every decay is an ``exp`` of ONE
    float32 product, no cumulative product or its quotient, so nothing
    under- or overflows that the recurrence itself would not.  T is padded
    to a multiple of ``chunk`` with dead positions; a ``lax.scan`` over the
    ``T / chunk`` pieces carries ``h`` [B, N, Di], and a piece computes its
    ``[chunk, B, N, Di]`` decays and inputs at once and then takes its
    ``chunk`` updates in order (unrolled: one fused pass a piece, ``h`` not
    written out between its tokens).  Nothing of ``[T, N, Di]`` is ever
    held.  The channels are the minor axis: a state of 16 would fill an
    eighth of a 128-lane tile.  Returns ``(y [B, T, Di], the state after the
    last position [B, N, Di])``."""
    B, T, Di = x.shape
    N = a.shape[0]
    pad = -T % chunk
    if pad:
        x, delta, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                          for v in (x, delta, b, c))
    pieces = tuple(
        v.reshape(B, (T + pad) // chunk, chunk, -1).transpose(1, 2, 0, 3)
        for v in (x, delta, b, c))                    # [nc, chunk, B, .]

    def piece(h, now):
        x_q, dt_q, b_q, c_q = now
        decay = jnp.exp(dt_q[:, :, None, :] * a)      # [chunk, B, N, Di]
        put = (dt_q * x_q)[:, :, None, :] * b_q[..., None]
        ys = []
        for q in range(chunk):
            h = decay[q] * h + put[q]
            ys.append((h * c_q[q][..., None]).sum(-2))
        return h, jnp.stack(ys)

    final, y = lax.scan(piece, jnp.zeros((B, N, Di), jnp.float32), pieces)
    return y.reshape(T + pad, B, Di).swapaxes(0, 1)[:, :T], final


class MambaMixer(nn.Module):
    """A Mamba-1 mixer as ``jamba`` has it (arXiv:2312.00752 with Jamba's
    three inner norms, arXiv:2403.19887), ``Di = inner`` channels, ``N =
    state``, ``R = dt_rank``; no bias on the four projections:

        [x, z] = u W_in                      widths Di, Di: x FIRST, then the
                                             gate
        x <- silu(conv(x) + b_conv)          causal, depthwise, ``conv_width``
                                             taps
        [dt, B, C] = x W_x                   widths R, N, N
        dt, B, C <- RMSNorm(dt), RMSNorm(B), RMSNorm(C)     each a weight
        delta = softplus(dt W_dt + b_dt)     [T, Di];   A = -exp(A_log)
        h_t = exp(delta_t (outer) A) h_(t-1) + (delta_t x_t) (outer) B_t
        y_t = h_t C_t + D x_t                h [Di, N], zero at first
        out = (y * silu(z)) W_out

    Everything between the two outer projections is float32.  ``A_log`` is
    kept as published, [Di, N]; the STATE is held channels-minor, which is
    what the chip's 128-lane tiles want.  The three regimes and the cache
    contract are :class:`Mamba2Mixer`'s: a block of tokens (a training-
    shaped call or a prompt block, a scalar ``pos_offset``: a FRESH cache)
    runs :func:`selective_scan` from zero and, with ``decode=True``, leaves
    the state after its last LIVE token (``true_len``: the positions from
    there on get ``delta = 0``, and the convolution's state is the last live
    rows); one token (T == 1) is ONE recurrent update of every row; a block
    at per-row depths is refused.  The ``cache`` leaves have NO token axis
    (``models.generate.STATE_LEAVES``): ``ssm_state`` [B, N, Di] and
    ``conv_state`` [B, conv_width - 1, Di], float32."""

    inner: int
    state: int
    dt_rank: int
    conv_width: int = 4
    chunk: int = 16
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, u, pos_offset=0, true_len=None):  # u: [B, T, E]
        B, T, E = u.shape
        Di, N, R, K = self.inner, self.state, self.dt_rank, self.conv_width
        f32 = jnp.float32

        def param(name, init, shape):
            return self.param(name, init, shape, f32).astype(f32)

        def dense(features, name, dtype):
            return nn.Dense(features, dtype=dtype, use_bias=False, name=name)

        def norm(name):
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=f32, name=name)

        with jax.named_scope("ssm_in_proj"):
            proj = dense(2 * Di, "in_proj", self.dtype)(u)
        x, z = proj[..., :Di].astype(f32), proj[..., Di:]
        taps = param("conv_kernel", nn.initializers.lecun_normal(), (K, Di))
        conv_bias = param("conv_bias", nn.initializers.zeros, (Di,))
        dt_bias = param("dt_bias", nn.initializers.zeros, (Di,))
        a = -jnp.exp(param("A_log", nn.initializers.zeros, (Di, N))).T
        skip = param("D", nn.initializers.ones, (Di,))
        if self.decode:
            if T > 1 and jnp.ndim(pos_offset) == 1:
                raise ValueError(_per_row_block_refused("MambaMixer"))
            ssm = self.variable("cache", "ssm_state", jnp.zeros,
                                (B, N, Di), f32)
            conv = self.variable("cache", "conv_state", jnp.zeros,
                                 (B, K - 1, Di), f32)
        step = self.decode and T == 1
        with jax.named_scope("ssm_conv"):
            x = jax.nn.silu(_causal_depthwise_conv(
                x, taps, conv if self.decode else None, true_len)
                + conv_bias)
        with jax.named_scope("ssm_dt_bc"):
            dbc = dense(R + 2 * N, "x_proj", f32)(x)
            dt = norm("dt_norm")(dbc[..., :R])
            b = norm("b_norm")(dbc[..., R:R + N])
            c = norm("c_norm")(dbc[..., R + N:])
            delta = jax.nn.softplus(dense(Di, "dt_proj", f32)(dt) + dt_bias)
        if step:
            with jax.named_scope("ssm_step"):
                dt1, x1 = delta[:, 0, None, :], x[:, 0, None, :]  # [B, 1, Di]
                state = (jnp.exp(dt1 * a) * ssm.value
                         + (dt1 * x1) * b[:, 0, :, None])
                ssm.value = state
                y = (state * c[:, 0, :, None]).sum(-2)[:, None]
        else:
            with jax.named_scope("ssm_scan"):
                if true_len is not None:
                    delta = jnp.where(jnp.arange(T)[:, None] < true_len,
                                      delta, 0.0)
                y, state = selective_scan(x, delta, a, b, c, self.chunk)
                if self.decode:
                    ssm.value = state
        with jax.named_scope("ssm_gate"):
            y = (y + skip * x) * jax.nn.silu(z.astype(f32))
        with jax.named_scope("ssm_out_proj"):
            return dense(E, "out_proj", self.dtype)(y.astype(self.dtype))


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


def relu2(x):
    """``relu(x) ** 2``: ``nemotron_h``'s feed-forward activation."""
    return jnp.square(jax.nn.relu(x))


class ExpertFFN(nn.Module):
    """Top-k gated expert feed-forward whose weights exist only for the
    experts ``held`` = ``(first, count)`` of ``n_experts`` (None: all): one
    chip's share of an expert-parallel layer, routed over all experts by a
    float32 router, dropless (``parallel/expert.held_experts``).  What the
    experts held elsewhere would add is left out; under an expert axis the
    exchange that brings it in wraps this module.

    ``router_in`` is what the router reads: ``Block`` hands it the
    attention's normed input or the feed-forward's own (``Block.
    router_reads``).  ``gate`` names the rule that turns the router's
    logits into the chosen experts and their weights (``"softmax"``:
    ``parallel/expert.softmax_gate``; ``"sigmoid"``: ``sigmoid_gate`` with
    the selection-only ``router_bias`` and ``route_scale``), ``act`` the experts'
    activation: ``"relu"`` (ReGLU) and ``"silu"`` (SwiGLU) on the gate
    product of a three-matrix expert, ``"relu2"`` (``relu(.) ** 2``) on the
    up product of a NON-GATED one, which has two matrices and no
    ``w_gate``.  ``latent_width`` > 0 puts the routed part into a latent:
    ``latent_in`` [E -> latent] before the experts, which are that wide at
    their doors, ``latent_out`` [latent -> E] after their weighted sum
    (scopes ``moe_latent_in``, ``moe_latent_out``; plain maps, no bias);
    the router still reads ``router_in``.
    ``shared_width`` > 0 adds a feed-forward of that width, gated as the
    experts are or not, on the full hidden, that every token goes through
    (the shared experts as one; scope ``shared_experts``).  The counters
    ``routes_held``, ``rows_computed``
    and ``rows_moved``, the chosen ``experts`` and the float32
    ``router_logits`` are sown to the ``moe`` collection
    (``mutable=["moe"]``), as ``MoEMLP`` sows its loss.
    """

    n_experts: int
    k: int
    width: int
    held: Optional[Tuple[int, int]] = None
    dtype: jnp.dtype = jnp.float32
    act: str = "relu"
    gate: str = "softmax"
    route_scale: float = 1.0
    shared_width: int = 0
    latent_width: int = 0

    @nn.compact
    def __call__(self, u, router_in):  # both [B, T, E]
        B, T, E = u.shape
        first, count = self.held or (0, self.n_experts)
        gated = self.act != "relu2"
        act = relu2 if self.act == "relu2" else getattr(jax.nn, self.act)
        door = self.latent_width or E       # an expert's input and output
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(),
                            (E, self.n_experts), jnp.float32)
        w_gate = (self.param("w_gate", init, (count, door, self.width),
                             jnp.float32) if gated else None)
        w_up = self.param("w_up", init, (count, door, self.width),
                          jnp.float32)
        w_down = self.param("w_down", init, (count, self.width, door),
                            jnp.float32)

        def dense(features, name):
            return nn.Dense(features, dtype=self.dtype, use_bias=False,
                            name=name)

        with jax.named_scope("route"):
            logits = jnp.dot(router_in.reshape(B * T, E).astype(jnp.float32),
                             router, precision=lax.Precision.HIGHEST)
        if self.gate == "sigmoid":
            bias = self.param("router_bias", nn.initializers.zeros,
                              (self.n_experts,), jnp.float32)
            gate = functools.partial(
                eplib.sigmoid_gate, bias=bias.astype(jnp.float32),
                scale=self.route_scale)
        elif self.gate == "softmax":
            gate = eplib.softmax_gate
        else:
            raise ValueError(f"unknown gate {self.gate!r}")
        x = u.reshape(B * T, E).astype(self.dtype)
        routed_in = x
        if self.latent_width:
            with jax.named_scope("moe_latent_in"):
                routed_in = dense(self.latent_width, "latent_in")(x)
        out, stats = eplib.held_experts(
            routed_in, logits, self.k, first, w_gate, w_up, w_down,
            gate=gate, act=act)
        if self.latent_width:
            with jax.named_scope("moe_latent_out"):
                out = dense(E, "latent_out")(out)
        if self.shared_width:
            with jax.named_scope("shared_experts"):
                if gated:
                    hidden = (act(dense(self.shared_width, "shared_gate")(x))
                              * dense(self.shared_width, "shared_up")(x))
                else:
                    hidden = act(dense(self.shared_width, "shared_up")(x))
                out = out + dense(E, "shared_down")(hidden)
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
    # wide) whose router reads the attention's input, or the feed-forward's
    # own with ``router_reads="ffn_input"``.
    n_experts: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    expert_width: int = 0
    expert_act: str = "relu"
    expert_gate: str = "softmax"
    route_scale: float = 1.0
    shared_width: int = 0
    router_reads: str = "attention_input"
    # kv_rank > 0: latent attention (see LatentAttention); ``head_dim`` is
    # then a query's width, ``rope_dim`` of it rotary.
    kv_rank: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    yarn: Optional[Tuple[float, ...]] = None
    attn_gate: bool = False
    # The dense feed-forward: "gelu" (two matrices, ``mlp_ratio`` wide) or
    # "swiglu" (gated, three matrices, ``mlp_width`` wide).
    mlp: str = "gelu"
    mlp_width: int = 0
    # FarSkip wiring: each sub-block reads the stream as it stood BEFORE
    # the previous sub-block's output was added.  The block then takes and
    # returns the pair (stream, stream one sub-block back).
    farskip: bool = False
    # ``kind`` in capitals or "*": the layer is ONE sub-block under one norm
    # with a residual (``nemotron_h``'s letters): "M" a Mamba2Mixer of the
    # ``ssm`` sizes (heads, head_dim, state, groups, conv_width, chunk), "*"
    # the attention above, "E" the ExpertFFN (``expert_latent`` wide at its
    # experts' doors).  None or a small letter: a mixer THEN the block's own
    # feed-forward (``jamba``'s layers): "a", as None, the attention above,
    # "m" a MambaMixer of the ``mamba`` sizes (inner, state, dt_rank,
    # conv_width, chunk).
    kind: Optional[str] = None
    ssm: Optional[Tuple[int, ...]] = None
    expert_latent: int = 0
    mamba: Optional[Tuple[int, ...]] = None

    def _attention(self):
        if self.kv_rank:
            if self.attn_impl not in ("local", "flash") or (
                    self.attn_impl != "local" and not self.decode):
                raise ValueError(
                    f"latent attention runs attn_impl='local' (got "
                    f"{self.attn_impl!r}): ops/flash.py has no separate qk "
                    f"and v head sizes")
            return LatentAttention(
                self.num_heads, self.head_dim, self.rope_dim, self.v_dim,
                self.kv_rank, dtype=self.dtype, decode=self.decode,
                max_len=self.max_len, rope_base=self.rope_base,
                yarn=self.yarn, norm_eps=self.norm_eps, gate=self.attn_gate)
        return SPAttention(self.num_heads, self.head_dim, self.attn_impl,
                           self.seq_axis, self.dtype, decode=self.decode,
                           max_len=self.max_len, window=self.window,
                           num_kv_heads=self.num_kv_heads,
                           rope=self.rope, rope_base=self.rope_base,
                           use_bias=self.use_bias)

    @nn.compact
    def __call__(self, x, pos_offset=0, lag=None, true_len=None):
        # (no helper method calls a submodule here: flax would put the
        # method's name into every operation's path, ``/Block_n/Dense_n/``,
        # which the benchmark's readers find operations by)
        if self.router_reads not in ("attention_input", "ffn_input"):
            raise ValueError(f"unknown router_reads {self.router_reads!r}")
        E = x.shape[-1]
        if self.kind not in (None, "a", "m"):
            if self.farskip:
                raise ValueError("farskip wires attention-then-feed-forward "
                                 "blocks, not a layer pattern")
            a = _norm(self.norm, self.norm_eps)(x)
            if self.kind == "M":
                h = Mamba2Mixer(*self.ssm, norm_eps=self.norm_eps,
                                dtype=self.dtype, decode=self.decode)(
                                    a, pos_offset, true_len)
            elif self.kind == "*":
                h = self._attention()(a, pos_offset)
            elif self.kind == "E":
                h = ExpertFFN(
                    self.n_experts, self.moe_k, self.expert_width,
                    self.experts_held, dtype=self.dtype, act=self.expert_act,
                    gate=self.expert_gate, route_scale=self.route_scale,
                    shared_width=self.shared_width,
                    latent_width=self.expert_latent)(a, a)
            else:
                raise ValueError(f"unknown layer kind {self.kind!r} "
                                 f"(M, *, E; a, m)")
            return x + h
        a = _norm(self.norm, self.norm_eps)(lag if self.farskip else x)
        if self.kind == "m":
            mid = x + MambaMixer(*self.mamba, norm_eps=self.norm_eps,
                                 dtype=self.dtype, decode=self.decode)(
                                     a, pos_offset, true_len)
        else:
            mid = x + self._attention()(a, pos_offset)
        h = _norm(self.norm, self.norm_eps)(x if self.farskip else mid)

        def dense(features):
            return nn.Dense(features, dtype=self.dtype,
                            use_bias=self.use_bias)

        if self.n_experts:
            h = ExpertFFN(
                self.n_experts, self.moe_k, self.expert_width,
                self.experts_held, dtype=self.dtype, act=self.expert_act,
                gate=self.expert_gate, route_scale=self.route_scale,
                shared_width=self.shared_width,
                latent_width=self.expert_latent)(
                    h, h if self.router_reads == "ffn_input" else a)
        elif self.moe_axis is not None:
            h = MoEMLP(self.moe_experts_per_device, self.mlp_ratio,
                       self.moe_axis,
                       capacity_factor=self.moe_capacity_factor,
                       k=self.moe_k, dtype=self.dtype)(h)
        # (a module is named when it is made: in the order it is used)
        elif self.mlp == "swiglu":
            gate = dense(self.mlp_width)(h)
            hidden = jax.nn.silu(gate) * dense(self.mlp_width)(h)
            h = dense(E)(hidden)
        elif self.mlp == "gelu":
            hidden = nn.gelu(dense(E * self.mlp_ratio)(h))
            h = dense(E)(hidden)
        else:
            raise ValueError(f"unknown mlp {self.mlp!r}")
        out = mid + h
        return (out, mid) if self.farskip else out


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
    # The expert layer's rules (see ExpertFFN): the experts' activation,
    # the gate with its scale, the shared experts' width, what the router
    # reads; and how many LEADING layers keep a dense feed-forward.
    expert_act: str = "relu"
    expert_gate: str = "softmax"
    route_scale: float = 1.0
    shared_width: int = 0
    router_reads: str = "attention_input"
    dense_layers: int = 0
    # Latent attention (see LatentAttention): ``kv_rank`` > 0 turns it on,
    # ``head_dim`` is then a query's width with ``rope_dim`` of it rotary,
    # ``v_dim`` a value's; ``yarn`` the six YaRN numbers (``yarn_rope``).
    kv_rank: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    yarn: Optional[Tuple[float, ...]] = None
    attn_gate: bool = False
    # The dense feed-forward ("gelu" | "swiglu" of ``mlp_width``) and the
    # FarSkip residual wiring (see Block).
    mlp: str = "gelu"
    mlp_width: int = 0
    farskip: bool = False
    # A hybrid's layers (see Block.kind), one letter a layer, ``depth`` of
    # them: "M" a Mamba-2 mixer of the ``ssm_*`` sizes, "*" attention, "E"
    # the expert layer; each ONE sub-block under one norm.  Small letters
    # are a mixer THEN the feed-forward: "a" attention, "m" a Mamba-1 mixer
    # of ``ssm_expand`` x ``embed`` channels with ``ssm_state`` states a
    # channel, a ``ssm_dt_rank``-wide time step, ``ssm_conv`` taps and
    # ``ssm_chunk`` tokens a piece of its scan.  None: every layer is
    # attention then a feed-forward.  ``expert_latent`` > 0: the routed
    # experts work in a latent of that width (see ExpertFFN).
    # ``pos_emb="none"``: no position table and no rotation (the mixers
    # carry the order).
    layer_pattern: Optional[str] = None
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    expert_latent: int = 0
    ssm_expand: int = 2
    ssm_dt_rank: int = 0
    # The head IS the embedding (no ``head`` parameter): logits are
    # ``x @ embedding^T``, the embedding read in the compute type.
    tie_head: bool = False

    @nn.compact
    def __call__(self, tokens, pos_offset=0, return_prehead: bool = False,
                 true_len=None):
        # tokens: [B, T_local] int32; ``true_len`` (traced): how many of a
        # right-padded prompt's positions are real, for the layers that
        # carry a state past the block (see Mamba2Mixer)
        B, T = tokens.shape
        embed = nn.Embed(self.vocab, self.embed, dtype=self.dtype)
        x = embed(tokens)
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
        elif self.pos_emb not in ("rope", "none"):
            raise ValueError(f"unknown pos_emb {self.pos_emb!r}")
        for name in ("window_layout", "rope_layout", "layer_pattern"):
            layout = getattr(self, name)
            if layout is not None and len(layout) != self.depth:
                raise ValueError(f"{name} has {len(layout)} entries for "
                                 f"{self.depth} layers")
        lag = x
        for i in range(self.depth):
            windowed = self.window_layout is None or self.window_layout[i]
            rotated = self.rope_layout is None or self.rope_layout[i]
            sparse = i >= self.dense_layers
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
                      n_experts=self.n_experts if sparse else 0,
                      experts_held=self.experts_held,
                      expert_width=self.expert_width,
                      expert_act=self.expert_act,
                      expert_gate=self.expert_gate,
                      route_scale=self.route_scale,
                      shared_width=self.shared_width,
                      router_reads=self.router_reads,
                      kv_rank=self.kv_rank, rope_dim=self.rope_dim,
                      v_dim=self.v_dim, yarn=self.yarn,
                      attn_gate=self.attn_gate, mlp=self.mlp,
                      mlp_width=self.mlp_width, farskip=self.farskip,
                      kind=self.layer_pattern and self.layer_pattern[i],
                      ssm=(self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                           self.ssm_groups, self.ssm_conv, self.ssm_chunk),
                      expert_latent=self.expert_latent,
                      mamba=(self.ssm_expand * self.embed, self.ssm_state,
                             self.ssm_dt_rank, self.ssm_conv,
                             self.ssm_chunk))(
                          x, pos_offset, lag, true_len)
            if self.farskip:
                x, lag = x
        x = _norm(self.norm, self.norm_eps)(x)
        # Bias-free explicit unembedding (standard for LMs) so callers can
        # feed (pre-head activations, head matrix) to the fused
        # linear+cross-entropy kernel (ops/xent.py) and never materialize
        # [B*T, vocab] logits.  A tied head is the embedding's transpose.
        if self.tie_head:
            head = jnp.asarray(embed.embedding, self.dtype).T
        else:
            head = self.param("head", nn.initializers.lecun_normal(),
                              (self.embed, self.vocab), jnp.float32)
        if return_prehead:
            return x, head
        return x @ head
