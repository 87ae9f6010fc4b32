"""Instella-MoE-16B-A3B-Base's block on the served path, at small sizes on
the CPU, seeded random weights, against the plain reference
(``chipbench/reference/instella_moe_served.py``): latent attention with its
latent cache (prefill expanded, decode absorbed), the sigmoid gate, SwiGLU
experts with the shared experts, leading dense layers, FarSkip's wiring.
The reference takes its shapeless constants (top-6, the scale 2.5, YaRN)
from the configuration's own file, so the model here takes them from it too.
"""

import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness, lm_weights_experts  # noqa: E402
from torchmpi_tpu import obs, serving  # noqa: E402
from torchmpi_tpu.models import TransformerLM, transformer  # noqa: E402
from torchmpi_tpu.models.generate import slot_prefill, slot_write  # noqa: E402
from torchmpi_tpu.parallel import expert as ep  # noqa: E402

MANIFEST = harness.load_manifest()
CELL = "imoe-16b-serve-conv-sat"
REF = harness.load_module(MANIFEST, "reference", "instella_moe_served")
with open(os.path.join(harness.BENCH, "configs",
                       "instella-moe-16b-a3b-serve.json")) as _f:
    CFG = json.load(_f)
K, SCALE, BASE, EPS = (CFG["num_experts_per_tok"],
                       CFG["routed_scaling_factor"], CFG["rope_theta"],
                       CFG["norm_epsilon"])
DEPTH, RANK, ROPE, VOCAB = 3, 32, 8, 256
KW = dict(depth=DEPTH, window=None, rope_base=BASE, eps=EPS)
# float32 on both sides: what is left is the order of the sums (the program
# absorbs W_kvb into the query and the output, sorts the routes, adds the
# experts' rows in sorted order).  It reads 2e-6 to 4e-6 on logits of unit
# scale; a router in bfloat16 reads 4e-3 and float8 experts 5e-2 (below).
ATOL = 1e-4


def model(**kw):
    return TransformerLM(**{**dict(
        vocab=VOCAB, embed=64, depth=DEPTH, num_heads=4, head_dim=16,
        max_len=128, dtype=jnp.float32, rope_base=BASE, norm_eps=EPS,
        norm="rmsnorm", use_bias=False, pos_emb="rope", kv_rank=RANK,
        rope_dim=ROPE, v_dim=16, yarn=tuple(CFG["yarn"]), attn_gate=True,
        mlp="swiglu", mlp_width=128, dense_layers=1, n_experts=8, moe_k=K,
        expert_width=32, expert_act="silu", expert_gate="sigmoid",
        route_scale=SCALE, shared_width=64, router_reads="ffn_input",
        farskip=True), **kw})


@pytest.fixture(scope="module")
def weights():
    return lm_weights_experts.make(model(), jax.random.PRNGKey(0),
                                   jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (40,), 0,
                                         VOCAB))


def reference(params, tokens, **kw):
    return np.asarray(REF.logits(params, tokens, np.arange(tokens.size),
                                 **{**KW, **kw}))


def decoded(params, tokens, prompt=16, slot=1):
    """Prefill ``prompt`` tokens, write the latent cache into slot 1 of a
    pool of two, then decode token by token through the pooled step's
    model call: the logits at every position from ``prompt - 1`` on."""
    dm = model().clone(decode=True, max_len=64)
    cache, _ = slot_prefill(dm, params, tokens[None, :prompt])
    pool = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: dm.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
            pos_offset=jnp.zeros((2,), jnp.int32)))["cache"])
    pool = slot_write(pool, cache, slot)
    step = jax.jit(lambda c, t, p: dm.apply(
        {"params": params, "cache": c}, t, pos_offset=p, mutable=["cache"]))
    rows = []
    for t in range(prompt, tokens.size):
        toks = np.zeros((2, 1), np.int32)
        pos = np.zeros((2,), np.int32)
        toks[slot], pos[slot] = tokens[t], t
        logits, updated = step(pool, jnp.asarray(toks), jnp.asarray(pos))
        pool = updated["cache"]
        rows.append(np.asarray(logits)[slot, 0])
    return np.stack(rows), pool


# ------------------------------------------------ (a) (b) against the reference


def test_full_forward_pass_matches_the_reference(weights, tokens):
    got = np.asarray(model().apply({"params": weights}, tokens[None]))[0]
    assert np.abs(got - reference(weights, tokens)).max() < ATOL


def test_prefill_then_absorbed_decode_matches_the_reference_everywhere(
        weights, tokens):
    got, _ = decoded(weights, tokens)
    want = reference(weights, tokens)[16:]
    assert np.abs(got - want).max() < ATOL


@pytest.mark.parametrize("lowered", ["router_bf16", "experts_fp8"])
def test_one_precision_below_fails_the_tolerance(weights, tokens, lowered):
    """The tolerance is tight enough: the same program on a router rounded
    to bfloat16, or on experts rounded to float8, is outside it."""
    low = jax.tree.map(lambda x: x, weights)
    for i in range(1, DEPTH):
        layer = dict(low[f"Block_{i}"]["ExpertFFN_0"])
        if lowered == "router_bf16":
            layer["router"] = layer["router"].astype(jnp.bfloat16).astype(
                jnp.float32)
        else:
            for name in ("w_gate", "w_up", "w_down"):
                layer[name] = jax.vmap(REF._fp8)(layer[name])
        low[f"Block_{i}"] = {**low[f"Block_{i}"], "ExpertFFN_0": layer}
    got, _ = decoded(low, tokens)
    assert np.abs(got - reference(weights, tokens)[16:]).max() > 10 * ATOL


# ------------------------------------------------------------ (c) the cache


def test_cache_is_the_latent_and_one_rotary_key_a_token_and_layer(weights,
                                                                   tokens):
    _, pool = decoded(weights, tokens)
    for i in range(DEPTH):
        (name, layer), = pool[f"Block_{i}"].items()
        assert name == "LatentAttention_0"
        assert {k: v.shape for k, v in layer.items()} == {
            "c": (2, 64, RANK), "k_rope": (2, 64, ROPE), "idx": ()}
    assert not any(leaf.ndim > 3 for leaf in jax.tree.leaves(pool))
    eng = serving.ReplicaEngine(model(), weights, slots=2, slot_tokens=64,
                                name="gauge")
    assert eng.cache_bytes_per_token == DEPTH * (RANK + ROPE) * 4
    assert obs.registry().gauge("tm_serving_cache_bytes_per_token",
                                replica="gauge") == DEPTH * (RANK + ROPE) * 4
    dense = TransformerLM(vocab=VOCAB, embed=64, depth=2, num_heads=4,
                          head_dim=16, num_kv_heads=2, max_len=64,
                          pos_emb="rope")
    params = dense.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    assert serving.ReplicaEngine(
        dense, params, slots=2, slot_tokens=64,
        name="dense").cache_bytes_per_token == 2 * 2 * 2 * 16 * 4


# ------------------------------------------------------------- (d) the gate


def test_bias_selects_and_never_weighs():
    logits = jax.random.normal(jax.random.PRNGKey(2), (50, 64))
    scores = np.asarray(jax.nn.sigmoid(logits))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (64,))
    plain, w_plain = ep.sigmoid_gate(logits, K, jnp.zeros(64), SCALE)
    chosen, w = ep.sigmoid_gate(logits, K, bias, SCALE)
    chosen, w = np.asarray(chosen), np.asarray(w)
    assert (np.sort(chosen) != np.sort(np.asarray(plain))).any()
    picked = np.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True) * SCALE, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), SCALE, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w_plain).sum(-1), SCALE, rtol=1e-6)
    np.testing.assert_array_equal(
        np.sort(chosen), np.sort(np.argsort(-(scores + np.asarray(bias)),
                                            axis=-1)[:, :K]))
    # SmallThinker's gate, beside it: softmax over the chosen logits
    e, p = ep.softmax_gate(logits, K)
    np.testing.assert_allclose(
        np.asarray(p), jax.nn.softmax(
            jnp.take_along_axis(logits, e, axis=-1), axis=-1), rtol=1e-6)


# ---------------------------------------------------- (e) (g) the expert layer


def expert_layer(held=None, shared=32, n=64):
    return transformer.ExpertFFN(n, K, 16, held, act="silu", gate="sigmoid",
                                 route_scale=SCALE, shared_width=shared)


@pytest.fixture(scope="module")
def layer_weights():
    h = jnp.zeros((1, 4, 32))
    shapes = jax.eval_shape(
        lambda: expert_layer().init(jax.random.PRNGKey(0), h, h))["params"]
    return lm_weights_experts._draw(jax.random.PRNGKey(4), shapes,
                                    jnp.float32)


@pytest.mark.parametrize("rows", [1, 5, 300])
def test_expert_layer_at_decode_and_prefill_row_counts(layer_weights, rows):
    """``T * k`` of 6, 30 and 1800 rows: under one gather block, a buffer
    that is no multiple of 8, and over a block."""
    h = jax.random.normal(jax.random.PRNGKey(rows), (1, rows, 32))
    got, sown = expert_layer().apply({"params": layer_weights}, h, h,
                                     mutable=["moe"])
    want = REF._experts(h[0], layer_weights)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-5
    assert int(sown["moe"]["routes_held"][0]) == rows * K


def test_eight_shares_of_eight_experts_add_up_to_the_whole_layer(
        layer_weights):
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 32))
    total = 0
    for share in range(8):
        first = 8 * share
        mine = {k: v for k, v in layer_weights.items()
                if share == 0 or not k.startswith("shared_")}
        for name in ("w_gate", "w_up", "w_down"):
            mine[name] = layer_weights[name][first:first + 8]
        # every share routes over all 64; the shared experts counted once
        total = total + expert_layer(
            (first, 8), shared=32 if share == 0 else 0).apply(
                {"params": mine}, h, h)
    want = REF._experts(h[0], layer_weights)
    assert np.abs(np.asarray(total[0]) - np.asarray(want)).max() < 1e-5


# ---------------------------------------------------------------- (f) FarSkip


class _BlockAsItWas(nn.Module):
    """``Block``'s forward pass as the parent commit wrote it (dense
    path), with the modules made in the same order."""
    heads: int
    head_dim: int

    @nn.compact
    def __call__(self, x):
        E = x.shape[-1]
        a = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32)(x)
        x = x + transformer.SPAttention(self.heads, self.head_dim, "local",
                                        rope=True)(a, 0)
        h = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32)(x)
        h = nn.gelu(nn.Dense(E * 4)(h))
        return x + nn.Dense(E)(h)


def test_farskip_off_is_the_block_as_it_was_and_on_follows_the_recurrence():
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 32))
    block = transformer.Block(4, 8, rope=True)
    params = block.init(jax.random.PRNGKey(7), x)["params"]
    was = _BlockAsItWas(4, 8)
    assert (jax.tree.structure(params) == jax.tree.structure(
        was.init(jax.random.PRNGKey(7), x)["params"]))
    np.testing.assert_array_equal(
        np.asarray(block.apply({"params": params}, x)),
        np.asarray(was.apply({"params": params}, x)))
    # on: r_s = r_(s-1) + f_s(norm_s(r_(s-2))), r_1 = r_0 + f_1(norm_1(r_0))
    far = transformer.Block(4, 8, rope=True, farskip=True)
    p1, p2 = (block.init(jax.random.PRNGKey(s), x)["params"] for s in (8, 9))

    def subs(p):
        def norm(i, v):
            return nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32).apply(
                {"params": p[f"LayerNorm_{i}"]}, v)

        def attn(v):
            return transformer.SPAttention(4, 8, "local", rope=True).apply(
                {"params": p["SPAttention_0"]}, norm(0, v), 0)

        def mlp(v):
            h = nn.Dense(128).apply({"params": p["Dense_0"]}, norm(1, v))
            return nn.Dense(32).apply({"params": p["Dense_1"]}, nn.gelu(h))

        return attn, mlp

    f = [*subs(p1), *subs(p2)]
    r = [x, x + f[0](x)]
    for s in range(2, 5):
        r.append(r[s - 1] + f[s - 1](r[s - 2]))
    out, lag = far.apply({"params": p1}, x, 0, x)
    out, lag = far.apply({"params": p2}, out, 0, lag)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r[4]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lag), np.asarray(r[3]), atol=1e-5)


def test_block_operations_keep_the_paths_the_readers_find_them_by():
    """``/Block_n/Dense_n/`` and ``/Block_n/SPAttention_n/`` are what the
    benchmark's scope readers match: no helper method of ``Block`` may put
    its own name between a block and its submodules."""
    import re

    lm = TransformerLM(vocab=64, embed=32, depth=1, num_heads=4, head_dim=8,
                       max_len=32, pos_emb="rope")
    toks = jnp.zeros((1, 8), jnp.int32)
    params = lm.init(jax.random.PRNGKey(0), toks)["params"]
    text = jax.jit(lambda p, t: lm.apply({"params": p}, t)).lower(
        params, toks).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    assert any("/Block_0/Dense_0/" in p for p in paths)
    assert any("/Block_0/SPAttention_0/" in p for p in paths)
    assert not any("Block_0._" in p for p in paths)


# ------------------------------------------------- (h) (i) through the server
# (the rehearsal as the driver calls it, ``run.py --rehearse``, is run in
# ``tests/test_chipbench_names.py``, beside the other cells' rehearsals)


@pytest.fixture(scope="module")
def rehearsed():
    if jax.default_backend() != "cpu":
        pytest.skip("the rehearsal's sizes are for the CPU")
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    cell = harness.resolve(MANIFEST, CELL, rehearse=True)
    return cell, harness.load_module(MANIFEST, "runners",
                                     cell.config["runner"])


def test_two_slots_share_the_pool_and_the_counters_count(weights, tokens):
    """Through ``serving.Server``: two requests decode side by side in one
    pool, each token the reference's own first choice, and the decode step's
    expert counters count the live rows only."""
    obs.reset()
    server = serving.Server(model(), weights, replicas=1, slots=3,
                            slot_tokens=64, prefill_bucket=8, sample=0.0,
                            spec_k=0, prefix_cache=0, slo_ttft_us=0,
                            autoscale=0)
    reqs = [serving.Request(rid=f"r{i}", prompt=tokens[4 * i:4 * i + n],
                            max_new=m, eos_id=None, arrival_s=0.0)
            for i, (n, m) in enumerate(((9, 10), (14, 6)))]
    done = {r.rid: r for r in server.run_trace(reqs)}
    for r in reqs:
        seq = np.concatenate([r.prompt, done[r.rid].tokens])
        lg = np.asarray(REF.logits(
            weights, seq[:-1], np.arange(r.prompt.size - 1, seq.size - 1),
            **KW))
        assert (lg.argmax(-1) == np.asarray(done[r.rid].tokens)).all()
    engine = server.router.live()[0]
    steps = engine.stats["steps"]
    registry = obs.registry()
    assert registry.counter_total("tm_moe_decode_steps_total") == steps
    # 9 + 5 token-steps of decoding (the first token is the prefill's),
    # top-6 each, in each of the two expert layers
    for layer in ("Block_1/ExpertFFN_0", "Block_2/ExpertFFN_0"):
        assert registry.counter("tm_moe_decode_routes_total",
                                layer=layer) == (9 + 5) * K
        touched = registry.counter("tm_moe_experts_touched_total",
                                   layer=layer)
        assert steps * K <= touched <= min(8 * steps, (9 + 5) * K)
    obs.reset()


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_control_one_precision_below_comes_out_not_correct(rehearsed, seed):
    from chipbench import served_check

    cell, runner = rehearsed
    s = runner.served(cell, seed, 1.0)
    limit = cell.config["tolerance"]["logit_gap"]
    picked = served_check.sample(s.records, seed, 1000)
    program = served_check.gaps(cell, s.params, picked)
    control = served_check.gaps(cell, s.params, picked, control=True)
    assert program["served_tokens"] > 150
    assert program["widest_gap"] <= limit < control["widest_gap"]
    assert control["widest_gap"] > 3 * program["widest_gap"]
    _, compared, correct = runner.judge(cell, seed, s)
    assert correct and list(compared)[:2] == ["off_the_top_share",
                                              "worst_request_off_share"]
    assert compared["off_the_top_share"] == [
        0.0, cell.config["tolerance"]["off_the_top_share"]]
    # the control by the shares alone: over the sample, and in the one
    # request it spoils most
    tol = {**cell.config["tolerance"], "logit_gap": None}
    shares, verdict = runner.shares(control, {}, True, tol)
    assert verdict is False
    assert shares["off_the_top_share"][0] > 2 * tol["off_the_top_share"]


def test_judge_records_a_gap_without_a_limit_and_judges_the_shares(
        rehearsed):
    """The chip's configuration gives ``logit_gap`` no limit (bfloat16
    flips a token's sixth expert: the widest gap cannot tell the program
    from the control there): the gap is recorded beside null, the verdict
    rests on the shares, and one spoiled request of the sample is enough."""
    cell, runner = rehearsed
    s = runner.served(cell, 31, 0.5)
    tol = cell.config["tolerance"]
    cell.config["tolerance"] = {**tol, "logit_gap": None}
    try:
        checked, compared, correct = runner.judge(cell, 31, s)
    finally:
        cell.config["tolerance"] = tol
    assert correct and compared["logit_gap"][1] is None
    json.dumps(compared)
    worst = max((r for r in checked["requests"]
                 if r["served"] >= tol["request_min_tokens"]),
                key=lambda r: r["served"])
    worst["off_the_top"] = worst["served"]          # one request all wrong
    shares, verdict = runner.shares(checked, {}, True, tol)
    assert verdict is False
    assert shares["worst_request_off_share"] == [
        1.0, tol["worst_request_off_share"]]
