"""The served model's weights, made by the benchmark from the seed.

The program and the plain reference are handed the SAME tree: neither makes
it.  The tree has the layout of ``models.TransformerLM`` (only the names and
shapes are taken from the model, through ``jax.eval_shape``: no value), every
leaf is drawn on the device in the type it is served in, one jitted call a
block (one compiled program, ``depth`` dispatches) and one for the rest:

    kernel      normal / sqrt(fan_in)      (fan_in = the first axis)
    bias        normal * 0.02              (so that a bias left out shows)
    scale       1 + normal * 0.02
    embedding   normal                     (unit rows)
    head        normal / sqrt(hidden)      (logits of unit scale)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _leaf(key, name, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if name == "kernel" or name == "head":
        x = x / (shape[0] ** 0.5)
    elif name == "bias":
        x = 0.02 * x
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


def shapes_of(model):
    """The parameter tree's names and shapes (no value is computed)."""
    twin = model.clone(attn_impl="local", decode=False)
    return jax.eval_shape(
        lambda: twin.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32)))["params"]


def make(model, key, dtype=jnp.bfloat16):
    shapes = shapes_of(model)
    blocks = sorted((k for k in shapes if k.startswith("Block_")),
                    key=lambda k: int(k.split("_")[1]))
    rest = {k: v for k, v in shapes.items() if k not in blocks}
    if any(shapes[k] != shapes[blocks[0]] for k in blocks):
        raise ValueError("blocks of different shapes: draw them one by one")
    draw_block = jax.jit(lambda k: _draw(k, shapes[blocks[0]], dtype))
    params = jax.jit(lambda k: _draw(k, rest, dtype))(
        jax.random.fold_in(key, len(blocks)))
    for i, name in enumerate(blocks):
        params[name] = draw_block(jax.random.fold_in(key, i))
    return params
