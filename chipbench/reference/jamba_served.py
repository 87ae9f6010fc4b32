"""Plain reference of what a served token of ``jamba2-3b-serve`` was chosen
from: the logits of the model at given positions of one sequence (prompt +
served tokens, teacher-forced) by its FULL forward pass: float32 at the
highest matmul precision, the Mamba-1 mixer as the RECURRENCE over time
(``lax.scan``, one token a step over a ``[Di, N]`` state: no chunks, the
published layout, so it shares neither algebra nor layout with the
program's prompt path), dense causal attention, the tied head, no cache, no
kernels, no batching, no import of the program.  Layer by layer, each layer
one jitted call (one compile a padded length and layer kind; the sequence is
right-padded to a multiple of ``PAD``, which causality makes harmless: a
recurrence is causal too).

Every SHAPE is read from the weights handed in (a layer's KIND from the
module its block holds; the channels, the state, the time step's rank, the
taps, the heads from the kernels), so the rehearsal's sizes need nothing
said.  The four numbers ``served_check`` hands over are ``depth``,
``window`` (None: this model has none), ``rope_base`` (not used: no
positional signal, ``assumed``) and ``eps`` (every RMSNorm's).

``E`` hidden, ``Di`` channels, ``N`` states a channel, ``R`` the time
step's rank, ``K`` taps.  A layer, for both kinds:

    h   = x + mixer(RMSNorm_in(x))
    out = h + W_down(silu(W_gate n) * W_up n),    n = RMSNorm_ff(h), no bias

After the last layer one RMSNorm; logits = x_final embedding^T (tied).

The Mamba-1 mixer on u [T, E] (arXiv:2312.00752; the three inner norms are
Jamba's, arXiv:2403.19887):

    [x, z]   = u W_in                    W_in [E, 2 Di], no bias; x FIRST
    x        = silu(conv(x) + b_conv)    causal, depthwise, K taps, a bias
    [dt,B,C] = x W_x                     W_x [Di, R + 2 N], no bias
    dt, B, C = RMSNorm_dt(dt), RMSNorm_B(B), RMSNorm_C(C)   each a weight
    delta    = softplus(dt W_dt + b_dt)  W_dt [R, Di]; delta [T, Di]
    A        = -exp(A_log)               [Di, N]
    h_t      = exp(delta_t (outer) A) * h_(t-1) + (delta_t * x_t) (outer) B_t
    y_t      = h_t C_t + D * x_t         h [Di, N], zero at first
    out      = (y * silu(z)) W_out       W_out [Di, E], no bias

Attention: q [T, Hq, D], ONE key/value head [T, 1, D] (read from the
kernels), no bias, NO positional signal, scores * D**-0.5, causal over the
whole context, softmax in float32.

Departures from the published modelling code, all in the configuration file
too (``assumed``): no positional signal in the attention; the time step's
bias is a leaf of its own beside a bias-free ``dt_proj`` (the same sum); the
decay and the input are computed as written above, a token at a time, where
the published code runs a fused kernel (the same recurrence).

``lower=True`` is the control's side: every matrix rounded to float8_e4m3fn
in arithmetic (``transformer_lm_served._fp8``: one scale a tensor; the
embedding, which is the head, too), handed to the same functions; vectors
(norms, biases, ``D``, the taps) and ``A_log`` as they are.  ``state_dtype``
is the other control's: the recurrent state rounded to that type after every
token, which is what a cache one precision below the file's would hold.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.transformer_lm_served import _fp8

PAD = 256
HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
MATRICES = ("kernel", "embedding")


def _mm(x, w, spec):
    return jnp.einsum(spec, x, w.astype(F32), precision=HIGHEST)


def _rms(x, scale, eps):
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(F32))


def mixer(u, p, eps, state_dtype=None):
    """``u`` [T, E] -> ([T, E], the state after the last row [Di, N])."""
    inner, n = p["A_log"].shape
    taps = p["conv_kernel"].shape[0]
    rank = p["dt_proj"]["kernel"].shape[0]
    proj = _mm(u, p["in_proj"]["kernel"], "te,ef->tf")
    x, z = proj[:, :inner], proj[:, inner:]
    # a channel at a time: out[t] = sum_k w[k] x[t - (taps - 1) + k]
    x = jax.lax.conv_general_dilated(
        x.T[None], p["conv_kernel"].astype(F32).T[:, None], (1,),
        [(taps - 1, 0)], feature_group_count=inner, precision=HIGHEST)[0].T
    x = jax.nn.silu(x + p["conv_bias"].astype(F32))
    dbc = _mm(x, p["x_proj"]["kernel"], "tf,fr->tr")
    dt = _rms(dbc[:, :rank], p["dt_norm"]["scale"], eps)
    b = _rms(dbc[:, rank:rank + n], p["b_norm"]["scale"], eps)
    c = _rms(dbc[:, rank + n:], p["c_norm"]["scale"], eps)
    delta = jax.nn.softplus(_mm(dt, p["dt_proj"]["kernel"], "tr,rf->tf")
                            + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["A_log"].astype(F32))                    # [Di, N]

    def token(h, now):
        x_t, delta_t, b_t, c_t = now
        h = (jnp.exp(delta_t[:, None] * a) * h
             + (delta_t * x_t)[:, None] * b_t[None, :])
        if state_dtype is not None:
            h = h.astype(state_dtype).astype(F32)
        return h, jnp.dot(h, c_t, precision=HIGHEST)

    last, y = jax.lax.scan(token, jnp.zeros((inner, n), F32),
                           (x, delta, b, c))
    y = (y + p["D"].astype(F32) * x) * jax.nn.silu(z)
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


def feed_forward(n, p):
    """``Dense_0`` the gate, ``Dense_1`` up, ``Dense_2`` down."""
    gate = _mm(n, p["Dense_0"]["kernel"], "te,ef->tf")
    up = _mm(n, p["Dense_1"]["kernel"], "te,ef->tf")
    return _mm(jax.nn.silu(gate) * up, p["Dense_2"]["kernel"], "tf,fe->te")


@partial(jax.jit, static_argnames=("eps", "state_dtype"))
def _block(p, x, *, eps, state_dtype=None):
    u = _rms(x, p["RMSNorm_0"]["scale"], eps)
    if "MambaMixer_0" in p:
        h = x + mixer(u, p["MambaMixer_0"], eps, state_dtype)[0]
    else:
        h = x + attention(u, p["SPAttention_0"])
    return h + feed_forward(_rms(h, p["RMSNorm_1"]["scale"], eps), p)


@jax.jit
def _embed(table, tokens):
    return table.astype(F32)[tokens]


@partial(jax.jit, static_argnames=("eps",))
def _head(norm, table, x, *, eps):
    return jnp.einsum("te,ve->tv", _rms(x, norm["scale"], eps),
                      table.astype(F32), precision=HIGHEST)


def logits(params, tokens, rows, *, depth, window, rope_base, eps,
           lower=False, state_dtype=None):
    """``tokens`` [T] int -> float32 logits [len(rows), V] at positions
    ``rows`` (row r predicts token r + 1).  ``lower``: the control, every
    matrix through ``lowered`` as it is used."""
    if window is not None:
        raise ValueError("this model attends over the whole context")
    if "head" in params:
        raise ValueError("this model's head is its embedding: the weights "
                         "carry a head of their own")
    if lower:
        params = _Lowered(params)
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    padded = np.zeros(-(-tokens.size // PAD) * PAD, np.int32)
    padded[:tokens.size] = tokens
    table = params["Embed_0"]["embedding"]
    x = _embed(table, jnp.asarray(padded))
    for i in range(depth):
        x = _block(params[f"Block_{i}"], x, eps=eps, state_dtype=state_dtype)
    keep = np.zeros(-(-rows.size // 64) * 64, np.int32)   # few head shapes
    keep[:rows.size] = rows
    return _head(params["RMSNorm_0"], table, x[jnp.asarray(keep)],
                 eps=eps)[:rows.size]


class _Lowered:
    """``params`` with each top-level entry lowered when it is asked for."""

    def __init__(self, params):
        self.params = params

    def __contains__(self, key):
        return key in self.params

    def __getitem__(self, key):
        return lowered({key: self.params[key]})[key]


@jax.jit
def lowered(tree):
    """Every matrix of ``tree`` (kernels, the embedding) rounded to
    float8_e4m3fn with one scale a tensor; vectors and ``A_log`` as they
    are."""
    def visit(path, w):
        return _fp8(w) if path[-1].key in MATRICES else w
    return jax.tree_util.tree_map_with_path(visit, tree)
