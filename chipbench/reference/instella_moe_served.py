"""Plain reference of what a served token of ``instella-moe-16b-a3b-serve``
was chosen from: the logits of the model at given positions of one sequence
(prompt + served tokens, teacher-forced) by its FULL forward pass: float32
at the highest matmul precision, dense attention over per-head keys and
values, every expert computed for every token and weighted (0 where it was
not chosen), no cache, no absorbed products, no kernels, no batching, no
import of the program.  Layer by layer, each layer one jitted call (one
compile a padded length and layer kind; the sequence is right-padded to a
multiple of ``PAD``, which causality makes harmless).

Every SHAPE is read from the weights handed in (heads, the rotary split,
the latent rank, experts, widths); the shapeless constants (top-k, the
routed scale, YaRN) come from the configuration's own file, so the
rehearsal's sizes change nothing here.  The four numbers ``served_check``
hands over are ``depth``, ``window`` (None: this model has none),
``rope_base`` and ``eps``.

The layer, as ``deepseek_v3`` computes it (hidden E, H heads):

    attention on a = RMSNorm(.):
      q = a W_q -> [T, H, nope + rope];  [c_raw, k_r] = a W_kva;
      c = RMSNorm(c_raw);  [k_n, v] = c W_kvb -> [T, H, nope + v]
      RoPE, pairs (2i, 2i+1) interleaved, YaRN frequencies, on q's last
      ``rope`` features and on k_r (ONE rotary key for all heads)
      scores = (q_n . k_n + q_r . k_r) * (nope + rope)**-0.5 * m**2,
      m = 0.1 * mscale_all_dim * ln(factor) + 1;  causal;  o = P v
      o <- o * sigmoid(a W_g)   (``gated_attention``, ASSUMED, below)
      out = o W_o
    feed-forward on h = RMSNorm(.):
      layer 0: W_down (silu(W_gate h) * (W_up h))
      layers 1..: s = sigmoid(h W_r) in float32; chosen = top-k of s + b;
      w_i = s_i / (sum of the chosen s + 1e-20) * routed_scaling_factor;
      sum_i w_i expert_i(h)  +  shared(h)     (each a SwiGLU as layer 0's)

The three flags of the published config that carry no sizes, each as the
configuration file states it under ``assumed``:

- ``gated_attention``: form G1 of "Gated Attention for Large Language
  Models" (arXiv:2505.06708): an elementwise sigmoid gate of the normed
  input on the heads' output, before W_o; W_g is [E, H * v].
- ``qk_layernorm``: read as Megatron-Core's flag for latent attention: the
  RMSNorm on the compressed latent ``c`` above (``q_lora_rank`` is null, so
  there is no query latent to norm) and nothing more.
- ``farskip``: after "FarSkip-Collective" (arXiv:2511.11505): sub-block s
  reads the stream as it stood before sub-block s-1's output was added:
  r_1 = r_0 + f_1(norm_1(r_0)); r_s = r_(s-1) + f_s(norm_s(r_(s-2))).

Departures from the published model, all in the configuration file too:
multi-token prediction is not run (``num_nextn_predict_layers`` 1 -> 0);
the head is untied (as published); positions start at 0 for every request.

``lower=True`` is the control's side: every matrix rounded to
float8_e4m3fn in arithmetic (``transformer_lm_served._fp8``: one scale a
tensor, here one an expert in the stacked expert weights), handed to the
same functions.
"""

import json
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.transformer_lm_served import _fp8

PAD = 512
HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32

with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs",
        "instella-moe-16b-a3b-serve.json")) as _f:
    _CFG = json.load(_f)
TOP_K = _CFG["num_experts_per_tok"]
ROUTED_SCALE = _CFG["routed_scaling_factor"]
YARN = _CFG["rope_scaling"]


def _mm(x, w, spec):
    return jnp.einsum(spec, x, w.astype(F32), precision=HIGHEST)


def _rms(x, scale, eps):
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(F32))


def _yarn(dim, base):
    """Frequencies of the ``dim // 2`` rotary pairs, the factor on cos and
    sin, and m (``transformers``' ``_compute_yarn_parameters`` and
    ``yarn_get_mscale``)."""
    factor, original = YARN["factor"], YARN["original_max_position_embeddings"]
    freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    low = max(math.floor(correction_dim(YARN["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(YARN["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = 1 / (factor * freqs) * ramp + 1 / freqs * (1 - ramp)
    return (np.asarray(inv, np.float32),
            mscale(YARN["mscale"]) / mscale(YARN["mscale_all_dim"]),
            mscale(YARN["mscale_all_dim"]))


def _rope(x, inv_freq, factor):
    """x [T, ..., D] with pairs (2i, 2i+1); position = row."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = (jnp.cos(ang) * factor).reshape(shape), \
        (jnp.sin(ang) * factor).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _attention(a, p, rope_base, eps):
    T = a.shape[0]
    rank = p["kv_norm"]["scale"].shape[0]
    rope = p["kv_a"]["kernel"].shape[1] - rank
    heads, qk = p["q"]["kernel"].shape[1:]
    nope = qk - rope
    inv_freq, factor, m = _yarn(rope, rope_base)
    q = _mm(a, p["q"]["kernel"], "te,ehd->thd")
    kva = _mm(a, p["kv_a"]["kernel"], "te,ef->tf")
    c = _rms(kva[:, :rank], p["kv_norm"]["scale"], eps)
    kv = _mm(c, p["kv_b"], "tr,rhd->thd")
    k_n, v = kv[..., :nope], kv[..., nope:]
    q_r = _rope(q[..., nope:], inv_freq, factor)
    k_r = _rope(kva[:, rank:], inv_freq, factor)
    s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_n, precision=HIGHEST)
         + jnp.einsum("qhd,kd->hqk", q_r, k_r, precision=HIGHEST))
    s = s * (qk ** -0.5 * m * m)
    pos = jnp.arange(T)
    s = jnp.where((pos[None, :] <= pos[:, None])[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(T, -1)
    if "gate" in p:
        o = o * jax.nn.sigmoid(_mm(a, p["gate"]["kernel"], "te,ef->tf"))
    return _mm(o, p["out"]["kernel"], "tf,fe->te")


def _swiglu(h, gate, up, down):
    return _mm(jax.nn.silu(_mm(h, gate, "te,ef->tf"))
               * _mm(h, up, "te,ef->tf"), down, "tf,fe->te")


def _experts(h, p):
    scores = jax.nn.sigmoid(_mm(h, p["router"], "te,en->tn"))
    _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(F32), TOP_K)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * ROUTED_SCALE
    dense = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(weights)   # [T, n]

    def add(total, e):
        gate, up, down, w = e
        return total + w[:, None] * _swiglu(h, gate, up, down), None

    routed, _ = jax.lax.scan(
        add, jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"], dense.T))
    return routed + _swiglu(h, p["shared_gate"]["kernel"],
                            p["shared_up"]["kernel"],
                            p["shared_down"]["kernel"])


@partial(jax.jit, static_argnames=("rope_base", "eps"))
def _block(p, x, lag, *, rope_base, eps):
    """Sub-blocks 2i+1 and 2i+2 in FarSkip's wiring: ``x`` is the stream,
    ``lag`` the stream one sub-block back -> the same pair after them."""
    mid = x + _attention(_rms(lag, p["RMSNorm_0"]["scale"], eps),
                         p["LatentAttention_0"], rope_base, eps)
    h = _rms(x, p["RMSNorm_1"]["scale"], eps)
    if "ExpertFFN_0" in p:
        out = mid + _experts(h, p["ExpertFFN_0"])
    else:
        out = mid + _swiglu(h, p["Dense_0"]["kernel"], p["Dense_1"]["kernel"],
                            p["Dense_2"]["kernel"])
    return out, mid


@jax.jit
def _embed(table, tokens):
    return table.astype(F32)[tokens]


@partial(jax.jit, static_argnames=("eps",))
def _head(norm, head, x, *, eps):
    return jnp.dot(_rms(x, norm["scale"], eps), head.astype(F32),
                   precision=HIGHEST)


def logits(params, tokens, rows, *, depth, window, rope_base, eps,
           lower=False):
    """``tokens`` [T] int -> float32 logits [len(rows), V] at positions
    ``rows`` (row r predicts token r + 1).  ``lower``: the control, every
    matrix through ``lowered`` as it is used."""
    if window is not None:
        raise ValueError("this model attends over the whole context")
    if not _CFG["farskip"]:
        raise ValueError("the reference is written for farskip's wiring")
    if lower:
        params = _Lowered(params)
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    padded = np.zeros(-(-tokens.size // PAD) * PAD, np.int32)
    padded[:tokens.size] = tokens
    x = _embed(params["Embed_0"]["embedding"], jnp.asarray(padded))
    lag = x
    for i in range(depth):
        x, lag = _block(params[f"Block_{i}"], x, lag, rope_base=rope_base,
                        eps=eps)
    keep = np.zeros(-(-rows.size // 64) * 64, np.int32)   # few head shapes
    keep[:rows.size] = rows
    return _head(params["RMSNorm_0"], params["head"], x[jnp.asarray(keep)],
                 eps=eps)[:rows.size]


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
