"""What a ``TransformerLM`` says of each of its layers
(``models.transformer.Layer``, from ``TransformerLM.layers``): the module
each ``Block`` builds from it makes the parameters it made before there was
a description, and, under ``decode=True``, the cache the description names
(the serving engine books the pool by it)."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from torchmpi_tpu.models import TransformerLM  # noqa: E402


def _config(name):
    with open(os.path.join(harness.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


INSTELLA = _config("instella-moe-16b-a3b-serve")
NEMOTRON = _config("nemotron3-super-120b-a12b-serve")
EXAONE = _config("k-exaone-236b-a23b-serve")

# The reduced sizes of the per-model tests (test_<model>.py), a model each,
# with what each layer caches under decode=True and the sha256 of the
# parameter tree's listing ("path shape dtype" lines, sorted) as the model
# made it before its layers had descriptions.
CASES = {
    "gqa_window": (dict(
        vocab=43, embed=32, depth=2, num_heads=4, num_kv_heads=2, head_dim=8,
        max_len=96, window=12, pos_emb="rope"),
        ("ring", "ring"), "0f2c5dd4e1184a68"),
    "learned_mha": (dict(
        vocab=64, embed=32, depth=2, num_heads=4, head_dim=8, max_len=32),
        ("tokens", "tokens"), "3983bb6af13bfe39"),
    "smallthinker": (dict(
        vocab=64, embed=32, depth=4, num_heads=4, head_dim=8, num_kv_heads=2,
        max_len=64, window=8, pos_emb="rope", rope_base=1.5e6,
        norm="rmsnorm", norm_eps=1e-6, use_bias=False,
        window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1), n_experts=8,
        expert_width=16, moe_k=3),
        ("tokens", "ring", "ring", "ring"), "1fb2aa5d95d26517"),
    "instella": (dict(
        vocab=256, embed=64, depth=3, num_heads=4, head_dim=16, max_len=128,
        rope_base=INSTELLA["rope_theta"], norm_eps=INSTELLA["norm_epsilon"],
        norm="rmsnorm", use_bias=False, pos_emb="rope", kv_rank=32,
        rope_dim=8, v_dim=16, yarn=tuple(INSTELLA["yarn"]), attn_gate=True,
        mlp="swiglu", mlp_width=128, dense_layers=1, n_experts=8,
        moe_k=INSTELLA["num_experts_per_tok"], expert_width=32,
        expert_act="silu", expert_gate="sigmoid",
        route_scale=INSTELLA["routed_scaling_factor"], shared_width=64,
        router_reads="ffn_input", farskip=True),
        ("tokens",) * 3, "a95a645f1fbe2013"),
    "nemotron_h": (dict(
        vocab=256, embed=64, depth=5, num_heads=4, head_dim=16,
        num_kv_heads=2, max_len=128, norm_eps=NEMOTRON["norm_epsilon"],
        norm="rmsnorm", use_bias=False, pos_emb="none",
        layer_pattern="ME*ME", ssm_heads=16, ssm_head_dim=8, ssm_state=16,
        ssm_groups=NEMOTRON["n_groups"], ssm_conv=NEMOTRON["conv_kernel"],
        ssm_chunk=16, n_experts=64, experts_held=(0, 16),
        moe_k=NEMOTRON["num_experts_per_tok"], expert_width=32,
        expert_act="relu2", expert_gate="sigmoid",
        route_scale=NEMOTRON["routed_scaling_factor"], shared_width=48,
        expert_latent=24),
        ("state", None, "tokens", "state", None), "e716ee990b6dd895"),
    "jamba": (dict(
        vocab=256, embed=64, depth=5, num_heads=4, head_dim=16,
        num_kv_heads=1, max_len=128, norm_eps=1e-6, norm="rmsnorm",
        use_bias=False, pos_emb="none", layer_pattern="mmamm", ssm_expand=2,
        ssm_state=16, ssm_dt_rank=4, ssm_conv=4, ssm_chunk=16,
        mlp="swiglu", mlp_width=96, tie_head=True),
        ("state", "state", "tokens", "state", "state"), "4493892425e2a3d6"),
    "exaone": (dict(
        vocab=256, embed=64, depth=5, num_heads=4, num_kv_heads=2,
        head_dim=16, max_len=128, rope_base=EXAONE["rope_theta"],
        norm_eps=EXAONE["norm_epsilon"], norm="rmsnorm", use_bias=False,
        pos_emb="rope", window=8,
        window_layout=tuple(EXAONE["window_layout"]),
        rope_layout=tuple(EXAONE["rope_layout"]), mlp="swiglu",
        mlp_width=128, dense_layers=EXAONE["first_k_dense_replace"],
        n_experts=32, experts_held=(0, 4),
        moe_k=EXAONE["num_experts_per_tok"], expert_width=32,
        expert_act="silu", expert_gate="sigmoid",
        route_scale=EXAONE["routed_scaling_factor"], shared_width=32,
        router_reads="ffn_input", post_norm=True, qk_norm=True),
        ("ring", "ring", "ring", "tokens", "ring"), "dcd1bfe805e4f162"),
}


def _listing(tree):
    rows = sorted("%s %s %s" % ("/".join(k.key for k in path), a.shape,
                                a.dtype)
                  for path, a in jax.tree_util.tree_leaves_with_path(tree))
    return hashlib.sha256("\n".join(rows).encode()).hexdigest()[:16]


@pytest.mark.parametrize("case", sorted(CASES))
def test_each_layer_names_its_cache_and_keeps_its_parameters(case):
    kwargs, kinds, listed = CASES[case]
    model = TransformerLM(**kwargs)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    assert _listing(params) == listed
    # one description a layer, the same each time it is asked
    layers = model.layers()
    assert layers == model.layers() and hash(layers) == hash(model.layers())
    assert tuple(layer.cache for layer in layers) == kinds
    # ... and each names what the layer's modules cache under decode=True
    dmodel = model.clone(decode=True)
    cache = jax.eval_shape(lambda: dmodel.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
        pos_offset=jnp.zeros((2,), jnp.int32)))["cache"]
    assert set(cache) <= {f"Block_{i}" for i in range(model.depth)}
    for i, layer in enumerate(dmodel.layers()):
        rows = {a.shape[1] for a in jax.tree.leaves(cache.get(f"Block_{i}"))
                if a.ndim}
        if layer.cache is None:
            assert not rows, i
        elif layer.cache == "tokens":
            assert rows == {model.max_len}, i
        elif layer.cache == "ring":
            assert rows == {layer.window} and layer.window < model.max_len
        else:                       # a state: no token axis
            assert rows and model.max_len not in rows, i
