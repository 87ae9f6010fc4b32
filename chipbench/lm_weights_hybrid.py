"""``lm_weights_experts`` for a hybrid whose layers are a state-space mixer,
attention or an expert layer (``nemotron_h``), which has leaves that module
has no rule for.  The same contract: the benchmark makes the tree from the
seed, the program and the plain reference are handed the SAME tree, only
names and shapes are taken from the model, every leaf is drawn on the device
in the type it is served in, one jitted call a block (one compiled program a
KIND of block) and one for the rest.  Every other leaf
``lm_weights_experts`` has a rule for is drawn by THAT rule (kernels, the
head, the router, the stacked expert weights, norms, the embedding).  Two
kinds of leaf have rules of their own here.

The selection-only bias of the router:

    router_bias  normal * 0.01

``lm_weights_experts`` draws it ``normal * 0.1``, which suits a top-6 of 64.
A top-22 of 512 chooses in the sigmoid's upper tail, where the scores of a
token's best thirty experts lie within 0.1 of one another: a bias that wide
DECIDES the choice, every token takes nearly the same experts, and a pooled
step of 68 slots touches 366 of its 640 held experts where even routing
touches 604, and 10 more or fewer from one seed's bias to the next (my chip
runs and simulation, PR 33, PERF.md section 6).  A trained model's bias
exists to BALANCE the load; 0.01 still changes which experts some tokens
choose (so that a bias left out shows) and leaves the load even.

A Mamba-2 mixer's own leaves, as ``mamba_ssm``'s ``Mamba2`` initialises them
from the published config's ``time_step_min``, ``time_step_max`` and
``time_step_floor`` (0.001, 0.1, 1e-4):

    A_log        log(uniform[1, 16])             (a = -exp(A_log): -1 to -16)
    dt_bias      the inverse softplus of a dt drawn log-uniform in
                 [0.001, 0.1] and floored at 1e-4: a token's decay
                 exp(dt a) then lies between 0.2 and 0.999
    D            1 + normal * 0.02               (published: ones)
    norm_scale   1 + normal * 0.02               (the gated norm's weight)
    conv_kernel  normal / sqrt(taps)             (fan_in: the first axis)
    conv_bias    normal * 0.02                   (so that one left out shows)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import lm_weights, lm_weights_experts

TIME_STEP_MIN, TIME_STEP_MAX, TIME_STEP_FLOOR = 0.001, 0.1, 1e-4


def _leaf(key, name, shape, dtype):
    if name == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(TIME_STEP_MIN),
            math.log(TIME_STEP_MAX)))
        dt = jnp.maximum(dt, TIME_STEP_FLOOR)
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "conv_kernel":
        x = jax.random.normal(key, shape, jnp.float32) / shape[0] ** 0.5
    elif name == "conv_bias":
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif name in ("D", "norm_scale"):
        x = 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif name == "router_bias":
        x = 0.01 * jax.random.normal(key, shape, jnp.float32)
    else:
        return lm_weights_experts._leaf(key, name, shape, dtype)
    return x.astype(dtype)


def _draw(key, shapes, dtype):
    """A tree of ShapeDtypeStructs -> a tree of drawn leaves."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = [_leaf(jax.random.fold_in(key, i), path[-1].key, s.shape, dtype)
           for i, (path, s) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def make(model, key, dtype=jnp.bfloat16):
    shapes = lm_weights.shapes_of(model)
    blocks = sorted((k for k in shapes if k.startswith("Block_")),
                    key=lambda k: int(k.split("_")[1]))
    rest = {k: v for k, v in shapes.items() if k not in blocks}
    # ``jax.jit`` keys its cache on the tree of shapes: one compile a kind
    draw = jax.jit(lambda k, like: _draw(k, like, dtype))
    params = draw(jax.random.fold_in(key, len(blocks)), rest)
    for i, name in enumerate(blocks):
        params[name] = draw(jax.random.fold_in(key, i), shapes[name])
    return params
