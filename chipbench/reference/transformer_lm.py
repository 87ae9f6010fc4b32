"""Plain reference of the decoder-only LM's next-token loss.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision:
dense masked attention (no flash kernel), explicit logits and a float32
logsumexp (no fused cross-entropy), no ``torchmpi_tpu`` import, no flax
module.  It reads the parameter tree the library's ``TransformerLM``
owns and follows the StarCoder2 block (arXiv:2402.19173): pre-LayerNorm
with bias, biased q / kv / out projections, grouped-query attention with
rotary positions and one sliding window, a ratio-4 tanh-GELU MLP, a final
LayerNorm and an unembedding.  Departures from the published model are
the program's, listed in the configuration file under ``assumed``; the
two that change arithmetic arrive here as ``rope_base`` and ``eps``.
"""

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _ln(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _proj(x, p, spec):
    return (jnp.einsum(spec, x, p["kernel"].astype(jnp.float32),
                       precision=HIGHEST) + p["bias"])


def _rope(x, base):
    """x: [T, H, D]; rotate halves by position * base**(-i / (D/2))."""
    t, _, d = x.shape
    half = d // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, p, window, rope_base):
    q = _rope(_proj(x, p["q"], "te,ehd->thd"), rope_base)       # [T, H, D]
    kv = _proj(x, p["kv"], "te,echd->tchd")                     # [T, 2, Hkv, D]
    k, v = _rope(kv[:, 0], rope_base), kv[:, 1]
    t, h, d = q.shape
    group = h // k.shape[1]          # consecutive q heads share a kv head
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / (d ** 0.5)
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None]
    keep = ki <= qi
    if window is not None:
        keep &= ki > qi - window
    a = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST)
    return _proj(o.reshape(t, h * d), p["out"], "tf,fe->te")


def prehead(params, tokens, *, depth, window, rope_base, eps):
    """tokens [T] -> final-LayerNorm activations [T, E]."""
    x = params["Embed_0"]["embedding"].astype(jnp.float32)[tokens]
    for i in range(depth):
        p = params[f"Block_{i}"]
        x = x + _attention(_ln(x, p["LayerNorm_0"], eps),
                           p["SPAttention_0"], window, rope_base)
        h = _proj(_ln(x, p["LayerNorm_1"], eps), p["Dense_0"], "te,ef->tf")
        x = x + _proj(jax.nn.gelu(h, approximate=True), p["Dense_1"],
                      "tf,fe->te")
    return _ln(x, params["LayerNorm_0"], eps)


def loss(params, tokens, **kw):
    """Mean next-token cross-entropy of one sequence ``tokens`` [T]."""
    x = prehead(params, tokens, **kw)[:-1]
    lg = jnp.dot(x, params["head"].astype(jnp.float32), precision=HIGHEST)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    return (lse - jnp.take_along_axis(lg, tokens[1:, None], 1)[:, 0]).mean()
