"""Plain reference of SmallThinker-21BA3B-Instruct's next-token loss.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision:
dense masked attention, a Python loop over the six routes with
``jnp.take`` of each route's expert weights (no sorting, no grouping),
explicit logits and a float32 logsumexp, no ``torchmpi_tpu`` import, no
flax module.  It reads the parameter tree the library's ``TransformerLM``
owns and follows the published block
(huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct, ``config.json``),
``x`` being ``[T, hidden]`` and ``l`` the layer:

    h   = RMSNorm(x; g1, eps)
    r   = h @ W_r                                  # the router reads the
    q, k, v = h @ W_q, h @ W_k, h @ W_v            #   PRE-attention input
    if rope_layout[l]:  q, k = RoPE(q, k; theta)
    mask = causal, and (key > query - window) if sliding_window_layout[l]
    x1  = x + softmax(q k^T / sqrt(head_dim) + mask) v @ W_o
    u   = RMSNorm(x1; g2, eps)
    s, e = top_k(r);  p = softmax(s)               # over the k chosen
    x2  = x1 + sum_j p_j W_down[e_j] (relu(W_gate[e_j] u) * (W_up[e_j] u))

then a final RMSNorm and an untied head.  No bias anywhere.

The share of a deployment is taken as the program takes it: the expert
weights in the tree are those of the experts ``[first, first + count)``
(``held``; the count is the weights' leading size) and a route to any
other expert adds nothing; the head and the embedding are the vocabulary
slice's.  With ``held = (0, n_experts)`` and the whole vocabulary this is
the uncut model.

``round_router_to`` / ``round_experts_to`` round the operands of the
router's product / of the experts' three products to a narrower type
before the float32 product: what a program that computed them in that
type would read.  The tolerance in the configuration file is set from
those readings (``chipbench/tools/smallthinker_precision.py``); the
comparison itself leaves them None.
"""

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _rms(x, p, eps):
    return x * lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * p["scale"]


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def _rope(x, base):
    """x: [T, H, D]; rotate halves by position * base**(-i / (D/2))."""
    t, _, d = x.shape
    half = d // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, p, *, window, rope_base):
    """h [T, E] -> [T, E]; ``window`` None is full causal attention,
    ``rope_base`` None is no positions."""
    w = {k: v["kernel"].astype(jnp.float32) for k, v in p.items()}
    q = jnp.einsum("te,ehd->thd", h, w["q"], precision=HIGHEST)
    kv = jnp.einsum("te,echd->tchd", h, w["kv"], precision=HIGHEST)
    k, v = kv[:, 0], kv[:, 1]
    if rope_base is not None:
        q, k = _rope(q, rope_base), _rope(k, rope_base)
    t, heads, d = q.shape
    group = heads // k.shape[1]      # consecutive q heads share a kv head
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / (d ** 0.5)
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None]
    keep = ki <= qi
    if window is not None:
        keep &= ki > qi - window
    a = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST)
    return jnp.dot(o.reshape(t, heads * d), w["out"], precision=HIGHEST)


def router_logits(h, p, round_router_to=None):
    return jnp.dot(_rounded(h, round_router_to),
                   _rounded(p["router"].astype(jnp.float32), round_router_to),
                   precision=HIGHEST)


def experts(u, probs, chosen, p, first, round_experts_to=None):
    """sum over the routes to an expert in ``[first, first + count)`` of
    ``p_j W_down[e_j] (relu(W_gate[e_j] u) * (W_up[e_j] u))``, one route
    at a time, each token with its own copy of its expert's weights."""
    w = {n: _rounded(p[n].astype(jnp.float32), round_experts_to)
         for n in ("w_gate", "w_up", "w_down")}
    count = w["w_gate"].shape[0]
    u = _rounded(u, round_experts_to)
    out = jnp.zeros_like(u)
    for j in range(chosen.shape[1]):
        local = chosen[:, j] - first
        held = (local >= 0) & (local < count)
        at = jnp.where(held, local, 0)
        gate = jnp.einsum("te,tef->tf", u, jnp.take(w["w_gate"], at, 0),
                          precision=HIGHEST)
        up = jnp.einsum("te,tef->tf", u, jnp.take(w["w_up"], at, 0),
                        precision=HIGHEST)
        hidden = _rounded(jax.nn.relu(gate) * up, round_experts_to)
        y = jnp.einsum("tf,tfe->te", hidden, jnp.take(w["w_down"], at, 0),
                       precision=HIGHEST)
        out += jnp.where(held, probs[:, j], 0.0)[:, None] * y
    return out


def experts_blocked(u, probs, chosen, p, first, token_block=None,
                    round_experts_to=None):
    """``experts`` computed ``token_block`` tokens at a time: a route's
    weights are copied per token (3 x 7.9 MB at the published widths),
    which is more than one chip holds for a whole sequence."""
    def part(args):
        return experts(*args, p, first, round_experts_to)

    if token_block is None:
        return part((u, probs, chosen))
    blocks = tuple(a.reshape(-1, token_block, a.shape[-1])
                   for a in (u, probs, chosen))
    return lax.map(part, blocks).reshape(u.shape)


def layer(x, p, *, k, held, window, rope_base, eps, token_block=None,
          round_router_to=None, round_experts_to=None):
    """One block: x [T, E] -> (x2 [T, E], what the expert layer read,
    chose and returned: ``router_in``, ``experts_in``, ``experts_out``
    [T, E], ``router_logits`` [T, n_experts] and ``chosen`` [T, k])."""
    h = _rms(x, p["RMSNorm_0"], eps)
    r = router_logits(h, p["ExpertFFN_0"], round_router_to)
    scores, chosen = lax.top_k(r, k)
    probs = jax.nn.softmax(scores, axis=-1)       # over the k chosen
    x = x + attention(h, p["SPAttention_0"], window=window,
                      rope_base=rope_base)
    u = _rms(x, p["RMSNorm_1"], eps)
    out = experts_blocked(u, probs, chosen, p["ExpertFFN_0"], held[0],
                          token_block, round_experts_to)
    return x + out, {"router_in": h, "router_logits": r, "chosen": chosen,
                     "experts_in": u, "experts_out": out}


def prehead(params, tokens, *, window_layout, rope_layout, window, rope_base,
            **kw):
    """tokens [T] -> (final-RMSNorm activations [T, E], every layer's
    record as ``layer`` returns it)."""
    x = params["Embed_0"]["embedding"].astype(jnp.float32)[tokens]
    layers = []
    for i, (windowed, rotated) in enumerate(zip(window_layout, rope_layout)):
        x, kept = layer(x, params[f"Block_{i}"],
                        window=window if windowed else None,
                        rope_base=rope_base if rotated else None, **kw)
        layers.append(kept)
    return _rms(x, params["RMSNorm_0"], kw["eps"]), layers


def logits(params, tokens, **kw):
    x, _ = prehead(params, tokens, **kw)
    return jnp.dot(x, params["head"].astype(jnp.float32), precision=HIGHEST)


def loss(params, tokens, *, with_aux=False, **kw):
    """Mean next-token cross-entropy of one sequence ``tokens`` [T] over
    the vocabulary the head has; ``with_aux`` adds the final activations
    (``prehead``), the chosen experts [L, T, k] (``experts``) and every
    layer's record (``layers``)."""
    x, layers = prehead(params, tokens, **kw)
    lg = jnp.dot(x[:-1], params["head"].astype(jnp.float32),
                 precision=HIGHEST)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    value = (lse - jnp.take_along_axis(lg, tokens[1:, None], 1)[:, 0]).mean()
    if not with_aux:
        return value
    return value, {"prehead": x, "layers": layers,
                   "experts": jnp.stack([la["chosen"] for la in layers])}
