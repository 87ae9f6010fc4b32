"""``lm_weights`` for a model whose blocks differ (leading dense layers
before the expert ones) and that has leaves ``lm_weights`` has no rule for
(an expert layer's stacked weights and router, latent attention's
up-projection).  The same contract: the benchmark makes the tree from the
seed, the program and the plain reference are handed the SAME tree, only
names and shapes are taken from the model, every leaf is drawn on the
device in the type it is served in, one jitted call a block (one compiled
program a KIND of block) and one for the rest.  The rules, by a leaf's name:

    kernel, head, kv_b, router   normal / sqrt(fan_in)   (fan_in: first axis)
    w_gate, w_up, w_down         normal / sqrt(fan_in)   (stacked by expert:
                                                          fan_in the second)
    bias                         normal * 0.02
    router_bias                  normal * 0.1    (the selection-only bias:
                                 wide enough to change which experts are
                                 chosen for some tokens, sigmoid scores
                                 being 0.27 to 0.73 for most)
    scale                        1 + normal * 0.02
    embedding                    normal                  (unit rows)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import lm_weights

FAN_IN_AXIS = {"kernel": 0, "head": 0, "kv_b": 0, "router": 0,
               "w_gate": 1, "w_up": 1, "w_down": 1}


def _leaf(key, name, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if name in FAN_IN_AXIS:
        x = x / (shape[FAN_IN_AXIS[name]] ** 0.5)
    elif name == "bias":
        x = 0.02 * x
    elif name == "router_bias":
        x = 0.1 * x
    elif name == "scale":
        x = 1.0 + 0.02 * x
    elif name != "embedding":
        raise ValueError(f"no rule for a leaf named {name!r}")
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
