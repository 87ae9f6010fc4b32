"""Plain reference of what a served token of ``k-exaone-236b-a23b-serve`` was
chosen from: the logits of the model at given positions of one sequence
(prompt + served tokens, teacher-forced) by its FULL forward pass over the
whole sequence: float32 at the highest matmul precision, no cache, no
batching, no kernel, no import of the program.  Layer by layer, each layer one
jitted call; inside a layer the attention goes a block of ``Q_BLOCK`` queries
at a time and the feed-forward a block of ``F_BLOCK`` tokens at a time, so
that a context of 16k fits on the chip beside the weights (a whole [64, 16384,
16384] score array would be 69 GB).  The sequence is right-padded to a power
of two (at least ``PAD_MIN``): causality makes that harmless, and the padded
lengths, and so the compiles, stay few.

Every SHAPE is read from the weights handed in (heads, key/value heads, the
held experts, widths); the shapeless constants (top-k, the routed scale,
which layers have a window and which rotate) come from the configuration's
own file, so the rehearsal's sizes change nothing here.  The four numbers
``served_check`` hands over are ``depth``, ``window``, ``rope_base`` and
``eps``.

The block, as EXAONE 4.0 writes it (``Exaone4DecoderLayer``; the published
config is ``exaone_moe``), hidden E, H query and Hkv key/value heads of D:

    attention on x (NO norm before it):
      q = x W_q -> [T, H, D];  k, v = x W_kv -> [T, Hkv, D]
      q, k <- RMSNorm_D(q), RMSNorm_D(k)    (per head, one weight a side)
      L layers (window 128): RoPE on q and k (rotate-half, base rope_theta),
        position i sees keys j with i - window < j <= i
      G layers: NO RoPE, position i sees every j <= i
      o = softmax(q k^T / sqrt(D)) v  (query head h: key head h // (H/Hkv))
      mid = x + RMSNorm(o W_o)                               (post-norm)
    feed-forward on mid (NO norm before it):
      layer 0: f = W_down (silu(W_gate mid) * (W_up mid))    (18,432 wide)
      layers 1..: s = sigmoid(mid W_r) over all 128 experts in float32;
        chosen = top-8 of s + b (b, the selection-only bias, only selects);
        w_i = s_i / (sum of the chosen s + 1e-20) * 2.5;
        f = sum over the HELD chosen experts of w_i expert_i(mid)
            + shared(mid)                      (each a SwiGLU of 2,048)
      out = mid + RMSNorm(f)                                  (post-norm)
    the final RMSNorm, then the head (this chip's 19,200 rows).

Departures from the published model, all in the configuration file too:
this chip's share of the expert layers (16 of 128 computed; what the other
112 would add is left out, here as in the program), of the vocabulary (rows
0-19,199), of the depth (layers 0-4); no multi-token prediction; the
post-norm, QK-norm and global NoPE read from the EXAONE 4.0 family
(``assumed``); the router reads ``mid`` as it stands.

``lower`` is the controls' side: ``True`` every matrix rounded to
float8_e4m3fn in arithmetic (``transformer_lm_served._fp8``: one scale a
tensor, one an expert in the stacked expert weights), handed to the same
functions; ``"kv_bf16"`` the weights as they are and each layer's keys and
values rounded to bfloat16 where the program would cache them (after the
QK-norm and the rotation): what a cache one precision below the file's
``cache_dtype`` would serve.
"""

import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.transformer_lm_served import _fp8

PAD_MIN = 256
Q_BLOCK = 256
F_BLOCK = 1024
HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32

with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs",
        "k-exaone-236b-a23b-serve.json")) as _f:
    _CFG = json.load(_f)
TOP_K = _CFG["num_experts_per_tok"]
ROUTED_SCALE = _CFG["routed_scaling_factor"]
WINDOWED = _CFG["window_layout"]
ROTATED = _CFG["rope_layout"]
FIRST_HELD = _CFG["experts_held"][0]


def _mm(x, w, spec):
    return jnp.einsum(spec, x, w.astype(F32), precision=HIGHEST)


def _rms(x, scale, eps):
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(F32))


def _rope(x, base):
    """x [T, heads, D]; position = row; pairs (i, i + D/2) (rotate-half)."""
    half = x.shape[-1] // 2
    inv = base ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv, F32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, p, *, window, rotated, rope_base, eps, kv_bf16):
    T = x.shape[0]
    q = _mm(x, p["q"]["kernel"], "te,ehd->thd")
    kv = _mm(x, p["kv"]["kernel"], "te,ecgd->tcgd")
    q = _rms(q, p["RMSNorm_0"]["scale"], eps)
    k = _rms(kv[:, 0], p["RMSNorm_1"]["scale"], eps)
    v = kv[:, 1]
    if rotated:
        q, k = _rope(q, rope_base), _rope(k, rope_base)
    if kv_bf16:
        k, v = (a.astype(jnp.bfloat16).astype(F32) for a in (k, v))
    H, D = q.shape[1:]
    Hkv = k.shape[1]
    qg = q.reshape(T, Hkv, H // Hkv, D)
    # keys a block of queries may see: the ``window`` before it and itself
    # (L), or all of the sequence, the later ones masked (G)
    span = Q_BLOCK + window if window else T
    if window:
        k = jnp.pad(k, ((window, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((window, 0), (0, 0), (0, 0)))

    def block(i):
        at = i * Q_BLOCK
        qb = jax.lax.dynamic_slice_in_dim(qg, at, Q_BLOCK)
        kb = jax.lax.dynamic_slice_in_dim(k, at, span) if window else k
        vb = jax.lax.dynamic_slice_in_dim(v, at, span) if window else v
        q_pos = at + jnp.arange(Q_BLOCK)[:, None]
        k_pos = (at - window if window else 0) + jnp.arange(span)[None, :]
        seen = (k_pos <= q_pos) & (k_pos >= 0)
        if window:
            seen &= k_pos > q_pos - window
        s = jnp.einsum("qhgd,khd->hgqk", qb, kb, precision=HIGHEST) / D ** 0.5
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), vb,
                          precision=HIGHEST).reshape(Q_BLOCK, H * D)

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK)).reshape(T, H * D)
    return _mm(o, p["out"]["kernel"], "tf,fe->te")


def _swiglu(h, gate, up, down):
    return _mm(jax.nn.silu(_mm(h, gate, "te,ef->tf"))
               * _mm(h, up, "te,ef->tf"), down, "tf,fe->te")


def _experts(h, p):
    """The held experts' part of the expert layer and the shared expert."""
    scores = jax.nn.sigmoid(_mm(h, p["router"], "te,en->tn"))
    _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(F32), TOP_K)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * ROUTED_SCALE
    dense = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(weights)   # [T, n]
    held = dense[:, FIRST_HELD:FIRST_HELD + p["w_gate"].shape[0]]

    def add(total, e):
        gate, up, down, w = e
        return total + w[:, None] * _swiglu(h, gate, up, down), None

    routed, _ = jax.lax.scan(
        add, jnp.zeros_like(h), (p["w_gate"], p["w_up"], p["w_down"], held.T))
    return routed + _swiglu(h, p["shared_gate"]["kernel"],
                            p["shared_up"]["kernel"],
                            p["shared_down"]["kernel"])


def _ffn(h, p):
    if "ExpertFFN_0" in p:
        return _experts(h, p["ExpertFFN_0"])
    return _swiglu(h, p["Dense_0"]["kernel"], p["Dense_1"]["kernel"],
                   p["Dense_2"]["kernel"])


@partial(jax.jit, static_argnames=("window", "rotated", "rope_base", "eps",
                                   "kv_bf16"))
def _block(p, x, *, window, rotated, rope_base, eps, kv_bf16):
    o = _attention(x, p["SPAttention_0"], window=window, rotated=rotated,
                   rope_base=rope_base, eps=eps, kv_bf16=kv_bf16)
    mid = x + _rms(o, p["RMSNorm_0"]["scale"], eps)
    f = jax.lax.map(lambda h: _ffn(h, p), mid.reshape(
        -1, min(F_BLOCK, mid.shape[0]), mid.shape[-1])).reshape(mid.shape)
    return mid + _rms(f, p["RMSNorm_1"]["scale"], eps)


@jax.jit
def _embed(table, tokens):
    return table.astype(F32)[tokens]


@partial(jax.jit, static_argnames=("eps",))
def _head(norm, head, x, *, eps):
    return jnp.dot(_rms(x, norm["scale"], eps), head.astype(F32),
                   precision=HIGHEST)


def padded_length(n):
    """The power of two the sequence is padded to (at least ``PAD_MIN``)."""
    return max(PAD_MIN, 1 << max(0, n - 1).bit_length())


def logits(params, tokens, rows, *, depth, window, rope_base, eps,
           lower=False):
    """``tokens`` [T] int -> float32 logits [len(rows), V] at positions
    ``rows`` (row r predicts token r + 1).  ``lower``: a control (above)."""
    if lower is True:
        params = _Lowered(params)
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    padded = np.zeros(padded_length(tokens.size), np.int32)
    padded[:tokens.size] = tokens
    x = _embed(params["Embed_0"]["embedding"], jnp.asarray(padded))
    with jax.default_matmul_precision("highest"):
        for i in range(depth):
            x = _block(params[f"Block_{i}"], x,
                       window=window if WINDOWED[i] else 0,
                       rotated=bool(ROTATED[i]), rope_base=rope_base,
                       eps=eps, kv_bf16=lower == "kv_bf16")
        keep = np.zeros(max(64, 1 << max(0, rows.size - 1).bit_length()),
                        np.int32)                       # few head shapes
        keep[:rows.size] = rows
        return _head(params["RMSNorm_0"], params["head"],
                     x[jnp.asarray(keep)], eps=eps)[:rows.size]


class _Lowered:
    """``params`` with each top-level entry lowered when it is asked for."""

    def __init__(self, params):
        self.params = params

    def __getitem__(self, key):
        return lowered({key: self.params[key]})[key]


@jax.jit
def lowered(tree):
    """Every matrix of ``tree`` rounded to float8_e4m3fn: the stacked
    expert weights an expert at a time, every other leaf of two or more
    axes whole; norms and the router's bias as they are."""
    def visit(path, w):
        if path[-1].key in ("w_gate", "w_up", "w_down"):
            return jax.vmap(_fp8)(w)
        return _fp8(w) if w.ndim >= 2 else w
    return jax.tree_util.tree_map_with_path(visit, tree)
