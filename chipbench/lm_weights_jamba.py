"""``lm_weights`` for a model whose layers are a Mamba-1 mixer or attention,
each followed by a dense feed-forward, under a TIED head (``jamba``), which
has leaves that module has no rule for.  The same contract: the benchmark
makes the tree from the seed, the program and the plain reference are handed
the SAME tree, only names and shapes are taken from the model, every leaf is
drawn on the device in the type it is served in, one jitted call a block (one
compiled program a KIND of block) and one for the rest.  Every leaf
``lm_weights`` has a rule for is drawn by THAT rule (kernels by their fan-in,
norm weights ``1 + normal x 0.02``, which covers the mixer's three inner
norms, so that a norm left out shows).  There is NO ``head`` leaf: the
model's head is its embedding, and a tree that carries one is refused.  So
the embedding is drawn by ``lm_weights``' rule for a HEAD:

    embedding    normal / sqrt(hidden)           (rows of unit norm: logits
                                                  of unit scale)

and not by its rule for an embedding (``normal``, unit-variance entries).
Under that rule a tied head scores the token just read ``|e|^2 / rms(x)``,
about 340 at hidden 2560 where every other logit has the scale 50: the model
repeats its input whatever the precision, and the cell's comparison read a
widest gap of 0.0 for the program, for the float8 control and for a bfloat16
state alike (my chip runs, PR 37, ``tools/readings/pr37_first_rule_*``).
With rows of unit norm the token's own row adds 0.1 to its logit and the
choice is the layers'.

A Mamba-1 mixer's own leaves, as ``mamba_ssm``'s ``Mamba`` initialises them
(``dt_min`` 0.001, ``dt_max`` 0.1, ``dt_init_floor`` 1e-4: the published
config carries no ``time_step_*`` keys, so these are ``assumed``), with
``lm_weights_hybrid``'s rules where the leaf is the same thing:

    A_log        log(uniform[1, 16])             (a = -exp(A_log): -1 to -16;
                 mamba_ssm's S4D-real start is log(1..16) along the state,
                 the same range in a fixed order)
    dt_bias      the inverse softplus of a delta drawn log-uniform in
                 [0.001, 0.1] and floored at 1e-4
    D            1 + normal * 0.02               (published: ones)
    conv_kernel  normal / sqrt(taps)             (fan_in: the first axis)
    conv_bias    normal * 0.02                   (so that one left out shows)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import lm_weights, lm_weights_hybrid

OWN = ("A_log", "dt_bias", "D", "conv_kernel", "conv_bias")


def _leaf(key, name, shape, dtype):
    if name == "head":
        raise ValueError("a tied model has no head leaf")
    if name == "embedding":         # [vocab, hidden]: the head, transposed
        x = jax.random.normal(key, shape, jnp.float32) / shape[1] ** 0.5
        return x.astype(dtype)
    rule = lm_weights_hybrid if name in OWN else lm_weights
    return rule._leaf(key, name, shape, dtype)


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
