"""NVIDIA-Nemotron-3-Super-120B-A12B's three kinds of layer on the served
path, at small sizes on the CPU, seeded random weights
(``chipbench/lm_weights_hybrid.py``), float32, against the plain reference
(``chipbench/reference/nemotron_h_served.py``): the Mamba-2 mixer (a prompt in
chunks, a pooled step one recurrent update a slot, a state with no token
axis), GQA attention without positions, the latent expert layer that holds a
share of its non-gated experts.  The reference takes its shapeless constants
(top-22, the scale 5, 8 groups, the first expert held) from the
configuration's own file, so the model here takes them from it too.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness, lm_weights_hybrid  # noqa: E402
from torchmpi_tpu import obs, serving  # noqa: E402
from torchmpi_tpu.models import TransformerLM, transformer  # noqa: E402
from torchmpi_tpu.models.generate import (  # noqa: E402
    slot_prefill, slot_write)
from torchmpi_tpu.parallel import expert as ep  # noqa: E402

MANIFEST = harness.load_manifest()
CELL = "nm3s-120b-serve-chat-sat"
REF = harness.load_module(MANIFEST, "reference", "nemotron_h_served")
with open(os.path.join(harness.BENCH, "configs",
                       "nemotron3-super-120b-a12b-serve.json")) as _f:
    CFG = json.load(_f)
K, SCALE, GROUPS, EPS = (CFG["num_experts_per_tok"],
                         CFG["routed_scaling_factor"], CFG["n_groups"],
                         CFG["norm_epsilon"])
PATTERN, VOCAB, ROUTER, HELD = "ME*ME", 256, 64, 16
# the mixer at the test's sizes: 16 heads of 8, a state of 16, 4 taps
HEADS, HEAD_DIM, STATE, TAPS = 16, 8, 16, CFG["conv_kernel"]
WIDE = HEADS * HEAD_DIM + 2 * GROUPS * STATE
KW = dict(depth=len(PATTERN), window=None, rope_base=CFG["rope_theta"],
          eps=EPS)
# float32 on both sides: what is left is the order of the sums (the program
# runs a prompt in chunks, sorts the routes, adds the experts' rows in
# sorted order).  It reads 2.4e-6 to 3.1e-6 on logits of unit scale; float8
# mixers read 0.62, float8 experts 0.19 and a bfloat16 router 1.6e-3 (below).
ATOL = 1e-4


def model(**kw):
    return TransformerLM(**{**dict(
        vocab=VOCAB, embed=64, depth=len(PATTERN), num_heads=4, head_dim=16,
        num_kv_heads=2, max_len=128, dtype=jnp.float32, norm_eps=EPS,
        norm="rmsnorm", use_bias=False, pos_emb="none",
        layer_pattern=PATTERN, ssm_heads=HEADS, ssm_head_dim=HEAD_DIM,
        ssm_state=STATE, ssm_groups=GROUPS, ssm_conv=TAPS, ssm_chunk=16,
        n_experts=ROUTER, experts_held=(0, HELD), moe_k=K, expert_width=32,
        expert_act="relu2", expert_gate="sigmoid", route_scale=SCALE,
        shared_width=48, expert_latent=24), **kw})


@pytest.fixture(scope="module")
def weights():
    return lm_weights_hybrid.make(model(), jax.random.PRNGKey(0),
                                  jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (60,), 0,
                                         VOCAB))


def reference(params, tokens, **kw):
    return np.asarray(REF.logits(params, tokens, np.arange(tokens.size),
                                 **{**KW, **kw}))


def pool_of(dm, slots):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: dm.init(
            jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32),
            pos_offset=jnp.zeros((slots,), jnp.int32)))["cache"])


@pytest.mark.parametrize("cell", ["imoe-16b-serve-conv-sat",
                                  "sc2-3b-serve-sat"])
def test_hybrid_runner_draws_the_other_served_models_as_they_were(cell):
    """The thin runners bind ``lm_weights`` of the ONE ``serve_open_loop``
    module, and the last one imported in a process wins: so what this
    cell's runner binds hands every model without a layer pattern to what
    was bound before, and only a hybrid to ``lm_weights_hybrid``."""
    from chipbench import lm_weights, lm_weights_experts

    runner = harness.load_module(MANIFEST, "runners",
                                 "serve_open_loop_hybrid")
    bound = runner.base.lm_weights
    other = harness.build_model(harness.resolve(MANIFEST, cell,
                                                rehearse=True))
    theirs = (lm_weights_experts if cell.startswith("imoe")
              else lm_weights).make(other, jax.random.PRNGKey(3), jnp.float32)
    mine = bound.make(other, jax.random.PRNGKey(3), jnp.float32)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    hybrid = bound.make(model(), jax.random.PRNGKey(0), jnp.float32)
    bias = np.asarray(hybrid["Block_1"]["ExpertFFN_0"]["router_bias"])
    assert 0.005 < bias.std() < 0.02        # the narrower selection bias


# ----------------------------------------------------------- (a) the mixer


def mixer(chunk=128, **kw):
    return transformer.Mamba2Mixer(HEADS, HEAD_DIM, STATE, GROUPS, TAPS,
                                   chunk, norm_eps=EPS, **kw)


@pytest.fixture(scope="module")
def mixer_weights():
    u = jnp.zeros((1, 4, 64))
    shapes = jax.eval_shape(
        lambda: mixer().init(jax.random.PRNGKey(0), u))["params"]
    return lm_weights_hybrid._draw(jax.random.PRNGKey(2), shapes,
                                   jnp.float32)


@pytest.mark.parametrize("T", [1, 7, 128, 129, 300])
def test_chunked_prompt_equals_the_recurrence(mixer_weights, T):
    """Outputs and the state after the last token: the chunked form (chunks
    of 128: under one chunk, one whole, one and a token, three with a
    remainder) against the reference's scan, one token a step."""
    u = jax.random.normal(jax.random.PRNGKey(T), (1, T, 64))
    want, last = REF.mixer(u[0], mixer_weights, EPS)
    got = mixer().apply({"params": mixer_weights}, u)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5)
    _, kept = mixer(decode=True).apply({"params": mixer_weights}, u,
                                       mutable=["cache"])
    np.testing.assert_allclose(np.asarray(kept["cache"]["ssm_state"][0]),
                               np.asarray(last), atol=2e-5)


@pytest.mark.parametrize("T,padded", [(77, 128), (3, 16), (2, 16)])
def test_a_padded_prompt_with_its_true_length_leaves_the_unpadded_state(
        mixer_weights, T, padded):
    """Bit for bit: positions from ``true_len`` on get ``dt = 0``, which
    multiplies the state by 1 and adds 0, and the convolution's state is
    the last three LIVE inputs (zeros before a prompt shorter than that).
    Without the true length the padding's tokens are in the state."""
    u = jax.random.normal(jax.random.PRNGKey(T), (1, padded, 64))
    layer = mixer(chunk=32, decode=True)

    def kept(u, **kw):
        return layer.apply({"params": mixer_weights}, u, mutable=["cache"],
                           **kw)[1]["cache"]

    plain, told, untold = (kept(u[:, :T]), kept(u, true_len=jnp.int32(T)),
                           kept(u))
    for name in told:                    # a mixer caches its state alone
        np.testing.assert_array_equal(np.asarray(told[name]),
                                      np.asarray(plain[name]))
        assert not np.array_equal(np.asarray(untold[name]),
                                  np.asarray(plain[name]))
    assert told["conv_state"].shape == (1, TAPS - 1, WIDE)
    if T < TAPS - 1:
        assert not np.asarray(told["conv_state"][0, :TAPS - 1 - T]).any()


def test_a_block_at_per_row_depths_is_refused(mixer_weights):
    u = jnp.zeros((2, 3, 64))
    with pytest.raises(ValueError, match="cannot be un-updated"):
        mixer(decode=True).apply({"params": mixer_weights}, u,
                                 jnp.zeros((2,), jnp.int32),
                                 mutable=["cache"])


# --------------------------------------------- (b) (c) against the reference


def test_full_forward_pass_matches_the_reference(weights, tokens):
    got = np.asarray(model().apply({"params": weights}, tokens[None]))[0]
    assert np.abs(got - reference(weights, tokens)).max() < ATOL


def served(params, requests, slots=4, bucket=16):
    """``requests``: ``(slot, tokens, prompt length)`` in admission order;
    a slot named twice is admitted again when its first request has
    retired.  Each prompt is prefilled right-padded to ``bucket`` with its
    true length and written into its slot; the pool then steps all slots
    together, teacher-forced, an idle slot fed token 0 at position 0 as
    the engine feeds it -> {request index: logits from its prompt's last
    position on}."""
    dm = model().clone(decode=True, max_len=64)
    pool = pool_of(dm, slots)
    step = jax.jit(lambda c, t, p: dm.apply(
        {"params": params, "cache": c}, t, pos_offset=p, mutable=["cache"]))
    prehead = jax.jit(lambda t, n: dm.apply(
        {"params": params}, t, pos_offset=0, true_len=n, mutable=["cache"]))
    waiting = list(enumerate(requests))
    live, rows = {}, {i: [] for i in range(len(requests))}
    while waiting or live:
        for i, (slot, toks, prompt) in list(waiting):
            if slot in live:
                continue
            waiting.remove((i, (slot, toks, prompt)))
            padded = np.zeros((1, -(-prompt // bucket) * bucket), np.int32)
            padded[0, :prompt] = toks[:prompt]
            cache, _ = slot_prefill(dm, params, padded, true_len=prompt)
            logits, _ = prehead(jnp.asarray(padded), jnp.int32(prompt))
            rows[i].append(np.asarray(logits)[0, prompt - 1])
            pool = slot_write(pool, cache, slot)
            live[slot] = [i, toks, prompt]
        toks_in = np.zeros((slots, 1), np.int32)
        pos = np.zeros((slots,), np.int32)
        for slot, (i, toks, at) in live.items():
            toks_in[slot], pos[slot] = toks[at], at
        logits, updated = step(pool, jnp.asarray(toks_in), jnp.asarray(pos))
        pool = updated["cache"]
        for slot, (i, toks, at) in list(live.items()):
            rows[i].append(np.asarray(logits)[slot, 0])
            live[slot][2] += 1
            if live[slot][2] == toks.size:
                del live[slot]              # retired: the slot is free
    return {i: np.stack(r) for i, r in rows.items()}


def test_prefill_then_pooled_decode_matches_the_reference_everywhere(
        weights, tokens):
    """Three slots at different depths, an idle slot between them, and slot
    2 admitted AGAIN after its first request retired: the second request
    starts from its own prompt's state, not from what the first left."""
    requests = [(0, tokens[:40], 9), (2, tokens[5:30], 17),
                (3, tokens[10:60], 30), (2, tokens[20:50], 12)]
    got = served(weights, requests)
    for i, (_, toks, prompt) in enumerate(requests):
        want = reference(weights, toks)[prompt - 1:]
        assert got[i].shape == want.shape
        assert np.abs(got[i] - want).max() < ATOL, i


def test_slot_write_overwrites_every_leaf_of_the_slot(weights, tokens):
    """An idle slot's state may hold anything (the pooled step updates it
    with token 0 at position 0): admission overwrites every leaf."""
    dm = model().clone(decode=True, max_len=64)
    pool = jax.tree.map(lambda p: jnp.full(p.shape, jnp.nan, p.dtype)
                        if p.ndim else p, pool_of(dm, 3))
    cache, _ = slot_prefill(dm, weights, tokens[None, :16], true_len=11)
    pool = slot_write(pool, cache, 1)
    for p, o in zip(jax.tree.leaves(pool), jax.tree.leaves(cache)):
        if p.ndim:
            np.testing.assert_array_equal(np.asarray(p[1]), np.asarray(o[0]))
            assert np.isnan(np.asarray(p[0])).all()


def lowered(weights, what):
    low = jax.tree.map(lambda x: x, weights)
    for i, kind in enumerate(PATTERN):
        block = dict(low[f"Block_{i}"])
        if kind == "M" and what == "mixers_fp8":
            block["Mamba2Mixer_0"] = REF.lowered(block["Mamba2Mixer_0"])
        elif kind == "E" and what == "experts_fp8":
            layer = dict(block["ExpertFFN_0"])
            for name in ("w_up", "w_down"):
                layer[name] = jax.vmap(REF._fp8)(layer[name])
            block["ExpertFFN_0"] = layer
        elif kind == "E" and what == "router_bf16":
            layer = dict(block["ExpertFFN_0"])
            layer["router"] = layer["router"].astype(jnp.bfloat16).astype(
                jnp.float32)
            block["ExpertFFN_0"] = layer
        low[f"Block_{i}"] = block
    return low


@pytest.mark.parametrize("what", ["mixers_fp8", "experts_fp8",
                                  "router_bf16"])
def test_one_precision_below_fails_the_tolerance(weights, tokens, what):
    """The tolerance is tight enough: the same program on mixers or experts
    rounded to float8, or on a router rounded to bfloat16, is outside it."""
    got = served(lowered(weights, what), [(1, tokens[:40], 16)])[0]
    assert np.abs(got - reference(weights, tokens[:40])[15:]).max() \
        > 10 * ATOL


# ----------------------------------------------------- (d) (e) (f) the experts


def expert_layer(held=(0, HELD), shared=48, n=ROUTER):
    return transformer.ExpertFFN(n, K, 32, held, act="relu2", gate="sigmoid",
                                 route_scale=SCALE, shared_width=shared,
                                 latent_width=24)


@pytest.fixture(scope="module")
def layer_weights():
    """A WHOLE layer: all 64 experts of the router held."""
    h = jnp.zeros((1, 4, 64))
    shapes = jax.eval_shape(lambda: expert_layer((0, ROUTER)).init(
        jax.random.PRNGKey(0), h, h))["params"]
    assert "w_gate" not in shapes and "shared_gate" not in shapes
    return lm_weights_hybrid._draw(jax.random.PRNGKey(4), shapes,
                                   jnp.float32)


def test_four_shares_of_sixteen_experts_add_up_to_the_whole_layer(
        layer_weights):
    """The share is the model's: four chips' parts, each routed over all 64
    with its own 16 held, the shared expert counted once (the latent
    projections are every chip's own: the routed part is linear up to
    ``latent_out``, which has no bias), add up to what the uncut reference
    gives for the whole layer."""
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64))
    total = 0
    for share in range(4):
        first = 16 * share
        mine = {k: v for k, v in layer_weights.items()
                if share == 0 or not k.startswith("shared_")}
        for name in ("w_up", "w_down"):
            mine[name] = layer_weights[name][first:first + 16]
        part, sown = expert_layer(
            (first, 16), shared=48 if share == 0 else 0).apply(
                {"params": mine}, h, h, mutable=["moe"])
        total = total + part
        # each share alone is the reference's share
        want = REF.routed(h[0], mine, first=first) + (
            REF.shared_expert(h[0], mine) if share == 0 else 0)
        assert np.abs(np.asarray(part[0]) - np.asarray(want)).max() < 1e-5
        assert 0 < int(sown["moe"]["routes_held"][0]) < 24 * K
    whole = (REF.routed(h[0], layer_weights, first=0)
             + REF.shared_expert(h[0], layer_weights))
    assert np.abs(np.asarray(total[0]) - np.asarray(whole)).max() < 2e-5


def test_gate_selects_by_the_bias_and_weighs_without_it(layer_weights):
    """Top-22 of ``s + b``; the weights are the chosen ``s`` over their sum
    over ALL 22 (held here or not), times 5: through the layer's own sown
    choice and the reference's dense weights."""
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 30, 64))
    _, sown = expert_layer((16, 16)).apply(
        {"params": {**layer_weights,
                    "w_up": layer_weights["w_up"][16:32],
                    "w_down": layer_weights["w_down"][16:32]}},
        h, h, mutable=["moe"])
    chosen = np.sort(np.asarray(sown["moe"]["experts"][0]))
    scores = np.asarray(jax.nn.sigmoid(sown["moe"]["router_logits"][0]))
    bias = np.asarray(layer_weights["router_bias"])
    np.testing.assert_array_equal(
        chosen, np.sort(np.argsort(-(scores + bias), axis=-1)[:, :K]))
    assert (chosen != np.sort(np.argsort(-scores, axis=-1)[:, :K])).any()
    dense = np.asarray(REF.gate(h[0], layer_weights))
    assert ((dense > 0).sum(-1) == K).all()
    np.testing.assert_allclose(dense.sum(-1), SCALE, rtol=1e-5)
    picked = np.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        np.take_along_axis(dense, chosen, axis=-1),
        picked / picked.sum(-1, keepdims=True) * SCALE, rtol=1e-5)


@pytest.mark.parametrize("rows", [1, 3, 300])
def test_two_matrix_expert_at_rows_an_expert(rows):
    """``held_experts`` with no ``w_gate``: ``w_down relu(w_up u) ** 2``,
    at 1, 3 and 300 rows an expert (4 experts, every one held, top-1 of a
    router that deals the tokens out evenly)."""
    n, d, f = 4, 24, 40
    keys = jax.random.split(jax.random.PRNGKey(rows), 3)
    u = jax.random.normal(keys[0], (n * rows, d))
    w_up = jax.random.normal(keys[1], (n, d, f)) / d ** 0.5
    w_down = jax.random.normal(keys[2], (n, f, d)) / f ** 0.5
    logits = jax.nn.one_hot(jnp.arange(n * rows) % n, n) * 4.0
    out, stats = ep.held_experts(u, logits, 1, 0, None, w_up, w_down,
                                 act=transformer.relu2)
    e = np.arange(n * rows) % n
    hidden = np.maximum(np.einsum("td,tdf->tf", u, w_up[e]), 0) ** 2
    want = np.einsum("tf,tfd->td", hidden, np.asarray(w_down)[e])
    assert int(stats["rows_computed"]) == n * rows
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)


def test_three_matrix_callers_are_unchanged():
    """A gated expert through the same door, positional as before."""
    n, d, f, t = 4, 16, 24, 10
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    u = jax.random.normal(keys[0], (t, d))
    w_gate, w_up = (jax.random.normal(k, (n, d, f)) / d ** 0.5
                    for k in keys[1:3])
    w_down = jax.random.normal(keys[3], (n, f, d)) / f ** 0.5
    logits = jax.random.normal(keys[4], (t, n))
    out, _ = ep.held_experts(u, logits, 2, 0, w_gate, w_up, w_down)
    e, p = ep.softmax_gate(logits, 2)
    want = sum(
        np.asarray(p)[:, j, None] * np.einsum(
            "tf,tfd->td",
            np.maximum(np.einsum("td,tdf->tf", u, w_gate[e[:, j]]), 0)
            * np.einsum("td,tdf->tf", u, w_up[e[:, j]]),
            np.asarray(w_down)[e[:, j]])
        for j in range(2))
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)


# --------------------------------------------------------- (g) (h) the engine


@pytest.mark.parametrize("asked", [dict(prefix_cache=4), dict(spec_k=2)])
def test_engine_refuses_what_a_recurrent_state_cannot_serve(weights, asked):
    with pytest.raises(ValueError, match="Block_0/Mamba2Mixer_0/"
                       "(conv|ssm)_state"):
        serving.ReplicaEngine(model(), weights, slots=2, slot_tokens=64,
                              **asked)
    # a model without such a state still takes both
    dense = TransformerLM(vocab=VOCAB, embed=32, depth=1, num_heads=2,
                          head_dim=16, max_len=64, pos_emb="rope")
    params = dense.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    serving.ReplicaEngine(dense, params, slots=2, slot_tokens=64, **asked)


def test_engine_books_tokens_and_state_apart(weights):
    eng = serving.ReplicaEngine(model(), weights, slots=3, slot_tokens=64,
                                name="hybrid")
    # ONE attention layer's keys and values, 2 heads of 16, float32
    assert eng.cache_bytes_per_token == 2 * 2 * 16 * 4
    # two mixers' states and convolution inputs
    per_slot = 2 * (HEADS * HEAD_DIM * STATE + (TAPS - 1) * WIDE) * 4
    assert eng.state_bytes_per_slot == per_slot
    gauge = obs.registry().gauge
    assert gauge("tm_serving_cache_bytes_per_token", replica="hybrid") == 256
    assert gauge("tm_serving_state_bytes_per_slot",
                 replica="hybrid") == per_slot
    dense = TransformerLM(vocab=VOCAB, embed=64, depth=2, num_heads=4,
                          head_dim=16, num_kv_heads=2, max_len=64,
                          pos_emb="rope")
    params = dense.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = serving.ReplicaEngine(dense, params, slots=2, slot_tokens=64,
                                name="dense")
    assert (eng.cache_bytes_per_token, eng.state_bytes_per_slot) == (
        2 * 2 * 2 * 16 * 4, 0)
    assert gauge("tm_serving_state_bytes_per_slot", replica="dense") == 0


def _sown(chosen, n):
    chosen = jnp.asarray(chosen, jnp.int32)
    return {"Block_1": {"ExpertFFN_0": {
        "experts": (chosen,),
        "router_logits": (jnp.zeros((chosen.shape[0], n)),)}}}


def test_decode_counts_counts_the_held_experts_only():
    chosen = [[0, 5, 9], [4, 5, 6], [7, 1, 2], [4, 4, 4]]
    live = jnp.asarray([True, True, False, False])
    # held [4, 8): row 0 chose 5; row 1 chose 4, 5, 6; rows 2, 3 are idle
    counts = np.asarray(ep.decode_counts(_sown(chosen, 12), live, (4, 4)))
    assert counts.tolist() == [[3, 6, 4]]
    # an idle row's held choice is neither touched nor a route
    live = jnp.asarray([True, False, False, False])
    assert np.asarray(ep.decode_counts(_sown(chosen, 12), live,
                                       (4, 4))).tolist() == [[1, 3, 1]]


def test_decode_counts_is_the_old_count_where_all_are_held():
    chosen = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (9, 6), 0,
                                           64))
    live = np.arange(9) % 3 != 1
    counts = np.asarray(ep.decode_counts(_sown(chosen, 64),
                                         jnp.asarray(live)))
    touched = np.unique(chosen[live]).size
    assert counts.tolist() == [[touched, 6 * 6, 6 * 6]]
    assert np.asarray(ep.decode_counts(
        _sown(chosen, 64), jnp.asarray(live), (0, 64))).tolist() == [
            [touched, 36, 36]]
    assert ep.decode_counts({}, jnp.asarray(live)) is None


def test_server_decodes_the_references_tokens_and_counts_held_routes(
        weights, tokens):
    """Through ``serving.Server``: three requests side by side in one pool
    of four, one slot reused, bucketed prefill: each token the reference's
    own first choice; the three counters of the pooled step."""
    obs.reset()
    server = serving.Server(model(), weights, replicas=1, slots=2,
                            slot_tokens=64, prefill_bucket=8, sample=0.0,
                            spec_k=0, prefix_cache=0, slo_ttft_us=0,
                            autoscale=0)
    reqs = [serving.Request(rid=f"r{i}", prompt=tokens[4 * i:4 * i + n],
                            max_new=m, eos_id=None, arrival_s=0.0)
            for i, (n, m) in enumerate(((9, 10), (14, 6), (5, 8)))]
    done = {r.rid: r for r in server.run_trace(reqs)}
    for r in reqs:
        seq = np.concatenate([r.prompt, done[r.rid].tokens])
        lg = np.asarray(REF.logits(
            weights, seq[:-1], np.arange(r.prompt.size - 1, seq.size - 1),
            **KW))
        assert (lg.argmax(-1) == np.asarray(done[r.rid].tokens)).all()
    registry = obs.registry()
    steps = server.router.live()[0].stats["steps"]
    assert registry.counter_total("tm_moe_decode_steps_total") == steps
    for layer in ("Block_1/ExpertFFN_0", "Block_4/ExpertFFN_0"):
        routes = registry.counter("tm_moe_decode_routes_total", layer=layer)
        held = registry.counter("tm_moe_decode_routes_held_total",
                                layer=layer)
        touched = registry.counter("tm_moe_experts_touched_total",
                                   layer=layer)
        assert routes == (9 + 5 + 7) * K        # the prefill's token is not
        assert 0 < held < routes and 0 < touched <= min(held, HELD * steps)
    obs.reset()


# ------------------------------------------------------ (i) the rehearsal


@pytest.fixture(scope="module")
def rehearsed():
    if jax.default_backend() != "cpu":
        pytest.skip("the rehearsal's sizes are for the CPU")
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    cell = harness.resolve(MANIFEST, CELL, rehearse=True)
    return cell, harness.load_module(MANIFEST, "runners",
                                     cell.config["runner"])


@pytest.mark.parametrize("seed", [13, 2**31 + 7])
def test_rehearsal_is_correct_and_its_float8_control_is_not(rehearsed, seed):
    from chipbench import served_check

    cell, runner = rehearsed
    s = runner.served(cell, seed, 1.0)
    tol = cell.config["tolerance"]
    picked = served_check.sample(s.records, seed, 1000)
    program = served_check.gaps(cell, s.params, picked)
    control = served_check.gaps(cell, s.params, picked, control=True)
    assert program["served_tokens"] > 150
    assert program["widest_gap"] <= tol["logit_gap"] < control["widest_gap"]
    _, compared, correct = runner.judge(cell, seed, s)
    assert correct and compared["off_the_top_share"][0] == 0.0
    shares, verdict = runner.shares(control, {}, True,
                                    {**tol, "logit_gap": None})
    assert verdict is False
    assert shares["off_the_top_share"][0] > 2 * tol["off_the_top_share"]
