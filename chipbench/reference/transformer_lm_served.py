"""Plain reference of what a served token was chosen from: the logits of
the decoder-only LM at given positions of one sequence (prompt + served
tokens, teacher-forced), by the full forward pass of
``reference/transformer_lm.py``: float32 at the highest matmul precision,
dense attention with the window, no cache, no batching, no import of the
program.  Layer by layer, each layer one jitted call (one compile per
padded length; the sequence is right-padded to a multiple of ``PAD``, which
causality makes harmless), so that 30 layers at 4608 tokens fit beside the
weights and compile in seconds.

``lowered`` is the control's side: the same weights one precision below
bfloat16, float8_e4m3fn with one scale a tensor (the step that would tempt a
later PR), handed to the same functions.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import transformer_lm as base

PAD = 512


@partial(jax.jit, static_argnames=("window", "rope_base", "eps"))
def _block(p, x, *, window, rope_base, eps):
    x = x + base._attention(base._ln(x, p["LayerNorm_0"], eps),
                            p["SPAttention_0"], window, rope_base)
    h = base._proj(base._ln(x, p["LayerNorm_1"], eps), p["Dense_0"],
                   "te,ef->tf")
    return x + base._proj(jax.nn.gelu(h, approximate=True), p["Dense_1"],
                          "tf,fe->te")


@jax.jit
def _embed(table, tokens):
    return table.astype(jnp.float32)[tokens]


@partial(jax.jit, static_argnames=("eps",))
def _head(norm, head, x, *, eps):
    return jnp.dot(base._ln(x, norm, eps), head.astype(jnp.float32),
                   precision=base.HIGHEST)


def logits(params, tokens, rows, *, depth, window, rope_base, eps,
           lower=False):
    """``tokens`` [T] int -> float32 logits [len(rows), V] at positions
    ``rows`` (row r predicts token r + 1).  ``lower``: the control, every
    matrix through ``lowered`` as it is used (a second copy of the weights
    would not fit beside the first)."""
    if lower:
        params = _Lowered(params)
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    padded = np.zeros(-(-tokens.size // PAD) * PAD, np.int32)
    padded[:tokens.size] = tokens
    x = _embed(params["Embed_0"]["embedding"], jnp.asarray(padded))
    for i in range(depth):
        x = _block(params[f"Block_{i}"], x, window=window,
                   rope_base=rope_base, eps=eps)
    keep = np.zeros(-(-rows.size // 64) * 64, np.int32)   # few head shapes
    keep[:rows.size] = rows
    return _head(params["LayerNorm_0"], params["head"],
                 x[jnp.asarray(keep)], eps=eps)[:rows.size]


class _Lowered:
    """``params`` with each top-level entry lowered when it is asked for."""

    def __init__(self, params):
        self.params = params

    def __getitem__(self, key):
        return lowered({key: self.params[key]})[key]


def _fp8(w):
    """``w`` rounded to float8_e4m3fn (4 significant bits, exponents -6 to
    8, subnormals below, round half to even) with one scale a tensor that
    puts its largest magnitude on 448.  In arithmetic and not through the
    type: on the chip XLA (``xla_allow_excess_precision``) removes a
    narrowing cast that is widened again at once, and the control would
    read what the reference reads."""
    x = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / 448.0
    x = x / scale
    _, e = jnp.frexp(x)                      # x = m * 2**e, 0.5 <= |m| < 1
    step = jnp.exp2(jnp.maximum(e, -5).astype(jnp.float32) - 4.0)
    return (jnp.round(x / step) * step * scale).astype(w.dtype)


@jax.jit
def lowered(tree):
    """Every matrix of ``tree`` (kernels, the head, the embedding) rounded to
    float8_e4m3fn with one scale a tensor; biases and norms as they are."""
    def visit(path, w):
        return _fp8(w) if path[-1].key in ("kernel", "head",
                                           "embedding") else w
    return jax.tree_util.tree_map_with_path(visit, tree)
