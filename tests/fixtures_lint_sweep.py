"""Default lint sweep for ``scripts/lint_collectives.py``: the shipped
decode/serving entry points, declared as ``LINT_TARGETS`` so the CLI
traces them (never executes) and runs the full rule pack — including
the S1/S2 cache-slice rules — on every invocation with no arguments.
The CLI must exit 0 on this file; a regression that reintroduces an
unclamped cache write (PR 17 class) turns the default sweep red.

Not a pytest module.  Params and caches are zero/ShapeDtypeStruct
trees: tracing only needs shapes and dtypes, so nothing here runs a
forward pass or touches an accelerator.
"""

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import TransformerLM
from torchmpi_tpu.models import generate as _generate_fn  # noqa: F401
from torchmpi_tpu.models.tp_generate import _block_decode, \
    _block_decode_rows

import importlib

_gen = importlib.import_module("torchmpi_tpu.models.generate")

# -- dense single-device model (ReplicaEngine shapes) ---------------------

_SLOTS = 2          # pool rows
_SLOT_TOKENS = 16   # per-slot cache depth
_K = 2              # draft length for the verify forward

_model = TransformerLM(vocab=50, embed=32, depth=2, num_heads=4,
                       head_dim=8, max_len=64, pos_emb="rope")
_dmodel = _model.clone(decode=True, max_len=_SLOT_TOKENS)


def _zeros_like_tree(shapes):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


_params = _zeros_like_tree(jax.eval_shape(
    lambda: _model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32)))["params"])

# The pool cache's SHAPES from the decode model's cache spec — the
# construction ReplicaEngine starts from (serving/engine.py), so the
# sweep traces exactly the operand shapes the serving loop feeds.  No
# arrays: the pooled programs consume the pool they are handed, and three
# targets below share this one.
_pool_cache = jax.eval_shape(
    lambda: _dmodel.init(
        jax.random.PRNGKey(0), jnp.zeros((_SLOTS, 1), jnp.int32),
        pos_offset=jnp.zeros((_SLOTS,), jnp.int32)))["cache"]
_one_cache = _zeros_like_tree(jax.eval_shape(
    lambda: _dmodel.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
        pos_offset=jnp.zeros((1,), jnp.int32)))["cache"])


def _sweep_generate(prompt):
    return _gen.generate(_model, _params, prompt, 4)


def _sweep_prefill(prompt, true_len):
    return _gen.slot_prefill(_dmodel, _params, prompt,
                             true_len=true_len)


def _sweep_decode(cache, tokens, positions):
    return _gen.slot_decode_step(_dmodel, _params, cache, tokens,
                                 positions)


def _sweep_verify(cache, tokens, positions):
    return _gen.slot_verify_step(_dmodel, _params, cache, tokens,
                                 positions)


def _sweep_write(pool_cache, one_cache, slot):
    return _gen._slot_write_jit(pool_cache, one_cache, slot)


# -- mesh-parallel per-device block bodies (TPReplicaEngine shapes) -------

_HL = 2     # local heads under axis_env [("tp", 2)] with num_heads=4
_DH = 8
_D = 32
_F = 32     # per-device MLP hidden width


def _sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


_TP_PARAMS = {
    "ln1": (_sds(_D), _sds(_D)),
    "ln2": (_sds(_D), _sds(_D)),
    "wq": _sds(_D, _HL * _DH), "wk": _sds(_D, _HL * _DH),
    "wv": _sds(_D, _HL * _DH), "wo": _sds(_HL * _DH, _D),
    "w1": _sds(_D, _F), "w2": _sds(_F, _D),
}
_TP_CACHE = (_sds(1, _SLOT_TOKENS, _HL, _DH),
             _sds(1, _SLOT_TOKENS, _HL, _DH))
_TP_CACHE_ROWS = (_sds(_SLOTS, _SLOT_TOKENS, _HL, _DH),
                  _sds(_SLOTS, _SLOT_TOKENS, _HL, _DH))


def _sweep_tp_decode(x, p, cache, pos):
    return _block_decode(x, p, cache, pos, "tp", 4)


def _sweep_tp_decode_rows(x, p, cache, pos_rows):
    return _block_decode_rows(x, p, cache, pos_rows, "tp", 4)


_i32 = jnp.int32

LINT_TARGETS = [
    dict(fn=_sweep_generate,
         args=(_sds(1, 5, dtype=_i32),),
         label="sweep_generate"),
    dict(fn=_sweep_prefill,
         args=(_sds(1, 8, dtype=_i32), _sds(dtype=_i32)),
         label="sweep_slot_prefill"),
    dict(fn=_sweep_decode,
         args=(_pool_cache, _sds(_SLOTS, dtype=_i32),
               _sds(_SLOTS, dtype=_i32)),
         label="sweep_slot_decode"),
    dict(fn=_sweep_verify,
         args=(_pool_cache, _sds(_SLOTS, _K + 1, dtype=_i32),
               _sds(_SLOTS, dtype=_i32)),
         label="sweep_slot_verify"),
    dict(fn=_sweep_write,
         args=(_pool_cache, _one_cache, _sds(dtype=_i32)),
         label="sweep_slot_write"),
    dict(fn=_sweep_tp_decode,
         args=(_sds(1, 1, _D), _TP_PARAMS, _TP_CACHE,
               _sds(dtype=_i32)),
         axis_env=[("tp", 2)],
         label="sweep_tp_block_decode"),
    dict(fn=_sweep_tp_decode_rows,
         args=(_sds(_SLOTS, 1, _D), _TP_PARAMS, _TP_CACHE_ROWS,
               _sds(_SLOTS, dtype=_i32)),
         axis_env=[("tp", 2)],
         label="sweep_tp_block_decode_rows"),
]
