"""Plain reference of what a served token of
``nemotron3-super-120b-a12b-serve`` was chosen from: the logits of the model
at given positions of one sequence (prompt + served tokens, teacher-forced)
by its FULL forward pass: float32 at the highest matmul precision, the
state-space mixer as the RECURRENCE over time (``lax.scan``, one token a
step: no chunks, so it shares no algebra with the program's prompt path),
dense attention over per-head keys and values, every held expert computed
for every token and weighted (0 where it was not chosen), no cache, no
kernels, no batching, no import of the program.  Layer by layer, each layer
one jitted call (one compile a padded length and layer kind; the sequence is
right-padded to a multiple of ``PAD``, which causality makes harmless: a
recurrence is causal too).

Every SHAPE is read from the weights handed in (a layer's KIND from the
module its block holds; heads, head sizes, the state, the taps, the experts
held, every width); the shapeless constants (top-k, the routed scale, the
mixer's groups, the first expert held) come from the configuration's own
file, so the rehearsal's sizes keep those.  The four numbers
``served_check`` hands over are ``depth``, ``window`` (None: this model has
none), ``rope_base`` (not used: no positional signal, ``assumed``) and
``eps``.

A layer is ``x <- x + f(RMSNorm(x))``, ``f`` one of three
(``nemotron_h``; hidden E):

``M``, the Mamba-2 mixer on u [T, E]; H heads of P, G groups, state N:
    [z, xBC, dt] = u W_in            widths H P, H P + 2 G N, H
    xBC <- silu(conv(xBC))           causal, depthwise, 4 taps, a bias
    split x [T, H, P], B [T, G, N], C [T, G, N]; head h reads group
    h // (H / G);  dt <- softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_(t-1) + dt_t x_t (outer) B_t,  S_0 = 0
    y_t = S_t C_t + D x_t
    y <- RMSNorm_grouped(y * silu(z))   over groups of H P / G channels:
                                        the gate first, then the norm
    out = y W_out
``*``, attention: q [T, Hq, D], k and v [T, Hkv, D] (GQA), no bias, NO
    positional signal, scores * D**-0.5, causal over the whole context,
    softmax in float32.
``E``, the latent expert layer on u:
    s = sigmoid(u W_r) over all experts of the router;
    chosen = top-k of s + b;  w_i = s_i / (sum of the chosen s + 1e-20)
    * routed_scaling_factor;  l = u W_fc1  (the latent);
    routed = sum over the chosen i HELD HERE of w_i W_down,i relu(W_up,i l)^2
    out = routed W_fc2 + W_sd relu(W_su u)^2      (the shared expert)
    The experts held are [first, first + count) of the router's width:
    what the others would add is left out, as in the program.

Departures from the published model, all in the configuration file too:
multi-token prediction is not run; the chip's share of the experts and of
the vocabulary; 11 of 88 layers.

``lower=True`` is the control's side: every matrix rounded to
float8_e4m3fn in arithmetic (``transformer_lm_served._fp8``: one scale a
tensor, one an expert in the stacked expert weights), handed to the same
functions; vectors (norms, ``A_log``, ``D``, ``dt_bias``, the router's
bias) as they are.
"""

import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.transformer_lm_served import _fp8

PAD = 256
HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32

with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs",
        "nemotron3-super-120b-a12b-serve.json")) as _f:
    _CFG = json.load(_f)
TOP_K = _CFG["num_experts_per_tok"]
ROUTED_SCALE = _CFG["routed_scaling_factor"]
GROUPS = _CFG["n_groups"]
FIRST_HELD = _CFG["experts_held"][0]


def _mm(x, w, spec):
    return jnp.einsum(spec, x, w.astype(F32), precision=HIGHEST)


def _rms(x, scale, eps):
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(F32))


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def mixer(u, p, eps, groups=GROUPS):
    """``u`` [T, E] -> ([T, E], the state after the last row [H, P, N])."""
    T = u.shape[0]
    heads = p["A_log"].shape[0]
    inner = p["norm_scale"].shape[0]
    taps, wide = p["conv_kernel"].shape
    P, N = inner // heads, (wide - inner) // (2 * groups)
    proj = _mm(u, p["in_proj"]["kernel"], "te,ef->tf")
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + wide],
                  proj[:, inner + wide:])
    # a channel at a time: out[t] = sum_k w[k] xBC[t - (taps - 1) + k]
    xbc = jax.lax.conv_general_dilated(
        xbc.T[None], p["conv_kernel"].astype(F32).T[:, None], (1,),
        [(taps - 1, 0)], feature_group_count=wide, precision=HIGHEST)[0].T
    xbc = jax.nn.silu(xbc + p["conv_bias"].astype(F32))
    x = xbc[:, :inner].reshape(T, heads, P)
    b = xbc[:, inner:inner + groups * N].reshape(T, groups, N)
    c = xbc[:, inner + groups * N:].reshape(T, groups, N)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["A_log"].astype(F32))
    skip = p["D"].astype(F32)

    def token(state, now):
        x_t, b_t, c_t, dt_t = now
        b_h = jnp.repeat(b_t, heads // groups, axis=0)      # [H, N]
        c_h = jnp.repeat(c_t, heads // groups, axis=0)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, (state * c_h[:, None, :]).sum(-1) + skip[:, None] * x_t

    last, y = jax.lax.scan(token, jnp.zeros((heads, P, N), F32),
                           (x, b, c, dt))
    y = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(T, inner) * p["norm_scale"].astype(F32)
    return _mm(y, p["out_proj"]["kernel"], "tf,fe->te"), last


def attention(a, p):
    T = a.shape[0]
    q = _mm(a, p["q"]["kernel"], "te,ehd->thd")
    kv = _mm(a, p["kv"]["kernel"], "te,eshd->tshd")
    k, v = kv[:, 0], kv[:, 1]
    group = q.shape[1] // k.shape[1]
    qg = q.reshape(T, k.shape[1], group, -1)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k,
                   precision=HIGHEST) * q.shape[-1] ** -0.5
    pos = jnp.arange(T)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(T, -1)
    return _mm(o, p["out"]["kernel"], "tf,fe->te")


def gate(u, p, top_k=TOP_K, scale=ROUTED_SCALE):
    """-> the weights of ALL the router's experts [T, n]: 0 where an expert
    was not chosen."""
    scores = jax.nn.sigmoid(_mm(u, p["router"], "te,en->tn"))
    _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(F32), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale
    return jnp.zeros_like(scores).at[
        jnp.arange(u.shape[0])[:, None], chosen].set(weights)


def shared_expert(u, p):
    return _mm(_relu2(_mm(u, p["shared_up"]["kernel"], "te,ef->tf")),
               p["shared_down"]["kernel"], "tf,fe->te")


def routed(u, p, first=FIRST_HELD, top_k=TOP_K, scale=ROUTED_SCALE):
    """The routed part of the experts ``[first, first + count)`` that ``p``
    holds, back in the hidden width (the shared expert is not in it)."""
    count = p["w_up"].shape[0]
    weights = gate(u, p, top_k, scale)[:, first:first + count]
    latent = _mm(u, p["latent_in"]["kernel"], "te,el->tl")

    def add(total, e):
        up, down, w = e
        out = _mm(_relu2(_mm(latent, up, "tl,lf->tf")), down, "tf,fl->tl")
        return total + w[:, None] * out, None

    total, _ = jax.lax.scan(add, jnp.zeros_like(latent),
                            (p["w_up"], p["w_down"], weights.T))
    return _mm(total, p["latent_out"]["kernel"], "tl,le->te")


@partial(jax.jit, static_argnames=("eps",))
def _block(p, x, *, eps):
    u = _rms(x, p["RMSNorm_0"]["scale"], eps)
    if "Mamba2Mixer_0" in p:
        return x + mixer(u, p["Mamba2Mixer_0"], eps)[0]
    if "SPAttention_0" in p:
        return x + attention(u, p["SPAttention_0"])
    e = p["ExpertFFN_0"]
    return x + routed(u, e) + shared_expert(u, e)


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
    if lower:
        params = _Lowered(params)
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    padded = np.zeros(-(-tokens.size // PAD) * PAD, np.int32)
    padded[:tokens.size] = tokens
    x = _embed(params["Embed_0"]["embedding"], jnp.asarray(padded))
    for i in range(depth):
        x = _block(params[f"Block_{i}"], x, eps=eps)
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
    axes whole; vectors as they are."""
    def visit(path, w):
        if path[-1].key in ("w_up", "w_down"):
            return jax.vmap(_fp8)(w)
        return _fp8(w) if w.ndim >= 2 else w
    return jax.tree_util.tree_map_with_path(visit, tree)
