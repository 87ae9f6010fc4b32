"""K-EXAONE-236B-A23B's block on the served path, at small sizes on the CPU,
seeded random weights, against the plain reference
(``chipbench/reference/exaone_moe_served.py``): window layers whose keys and
values are a RING of ``window`` rows beside a global layer that keeps every
position, post-norm blocks with per-head QK-norm, no rotation in the global
layer, a dense leading layer and one chip's share of a sigmoid top-8 expert
layer with its shared expert.  The reference takes its shapeless constants
(top-8, the scale 2.5, which layers have a window and which rotate) from the
configuration's own file, so the model here takes them from it too.
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

MANIFEST = harness.load_manifest()
REF = harness.load_module(MANIFEST, "reference", "exaone_moe_served")
with open(os.path.join(harness.BENCH, "configs",
                       "k-exaone-236b-a23b-serve.json")) as _f:
    CFG = json.load(_f)
K, SCALE, BASE, EPS = (CFG["num_experts_per_tok"],
                       CFG["routed_scaling_factor"], CFG["rope_theta"],
                       CFG["norm_epsilon"])
DEPTH, WINDOW, VOCAB, ROUTER, HELD = 5, 8, 256, 32, 4
KW = dict(depth=DEPTH, window=WINDOW, rope_base=BASE, eps=EPS)
# float32 on both sides: what is left is the order of the sums (the ring's
# rows in another order than the positions', the sorted routes).  It reads
# under 1e-5 on logits of unit scale.
ATOL = 1e-4


def model(**kw):
    return TransformerLM(**{**dict(
        vocab=VOCAB, embed=64, depth=DEPTH, num_heads=4, num_kv_heads=2,
        head_dim=16, max_len=128, dtype=jnp.float32, rope_base=BASE,
        norm_eps=EPS, norm="rmsnorm", use_bias=False, pos_emb="rope",
        window=WINDOW, window_layout=tuple(CFG["window_layout"]),
        rope_layout=tuple(CFG["rope_layout"]), mlp="swiglu", mlp_width=128,
        dense_layers=CFG["first_k_dense_replace"], n_experts=ROUTER,
        experts_held=(0, HELD), moe_k=K, expert_width=32, expert_act="silu",
        expert_gate="sigmoid", route_scale=SCALE, shared_width=32,
        router_reads="ffn_input", post_norm=True, qk_norm=True), **kw})


@pytest.fixture(scope="module")
def weights():
    return lm_weights_experts.make(model(), jax.random.PRNGKey(0),
                                   jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (64,), 0,
                                         VOCAB))


def reference(params, tokens, **kw):
    return np.asarray(REF.logits(params, tokens, np.arange(tokens.size),
                                 **{**KW, **kw}))


def _pool(dm, slots):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: dm.init(
            jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32),
            pos_offset=jnp.zeros((slots,), jnp.int32)))["cache"])


def _padded(prompt, bucket):
    out = np.zeros((1, bucket), np.int32)
    out[0, :prompt.size] = prompt
    return out


def served_logits(params, seqs, prompts, buckets, slots, pool=None,
                  max_len=64):
    """Each sequence's prompt prefilled right-padded to its bucket (its true
    length told) and written into its slot of one pool, then every sequence
    decoded teacher-forced through the pooled per-row step, ALL AT ONCE
    (slots at different depths in one call): -> each sequence's logits
    from its last prompt position on, and the pool."""
    dm = model().clone(decode=True, max_len=max_len)
    pool = _pool(dm, 3) if pool is None else pool
    for seq, n, bucket, slot in zip(seqs, prompts, buckets, slots):
        one, _ = slot_prefill(dm, params, _padded(seq[:n], bucket),
                              true_len=n)
        pool = slot_write(pool, one, slot)
    step = jax.jit(lambda c, t, p: dm.apply(
        {"params": params, "cache": c}, t, pos_offset=p, mutable=["cache"]))
    got = [[] for _ in seqs]
    for i in range(max(s.size - n for s, n in zip(seqs, prompts))):
        toks = np.zeros((3, 1), np.int32)
        pos = np.zeros((3,), np.int32)
        for seq, n, slot in zip(seqs, prompts, slots):
            if n + i < seq.size:
                toks[slot], pos[slot] = seq[n + i], n + i
        logits, updated = step(pool, jnp.asarray(toks), jnp.asarray(pos))
        pool = updated["cache"]
        for j, (seq, n, slot) in enumerate(zip(seqs, prompts, slots)):
            if n + i < seq.size:
                got[j].append(np.asarray(logits)[slot, 0])
    return [np.stack(g) for g in got], pool


# ------------------------------------------------------- against the reference


def test_full_forward_pass_matches_the_reference(weights, tokens):
    got = np.asarray(model().apply({"params": weights}, tokens[None]))[0]
    assert np.abs(got - reference(weights, tokens)).max() < ATOL


def test_prefill_then_pooled_decode_matches_the_reference_through_wraps(
        weights, tokens):
    # prompts of 13 and 21 (multiples of neither the window, 8, nor their
    # buckets, 16 and 32: the pads must not land in the ring), decoded to
    # position 63 and 50: five and three wraps of the ring past the prompt,
    # the two slots at different depths in every pooled step
    seqs = [tokens, np.roll(tokens, 7)[:51]]
    got, _ = served_logits(weights, seqs, (13, 21), (16, 32), (0, 2))
    for seq, n, logits in zip(seqs, (13, 21), got):
        want = reference(weights, seq)[n:]
        assert np.abs(logits - want).max() < ATOL


def test_a_reused_slot_serves_what_a_fresh_one_serves(weights, tokens):
    # a long request leaves every row of slot 1's ring and 40 positions of
    # its global cache behind; a shorter one written over it decodes what
    # it decodes from a zero pool, bit for bit
    long, short = tokens[:48], np.roll(tokens, 3)[:30]
    _, used = served_logits(weights, [long], (40,), (64,), (1,))
    fresh, _ = served_logits(weights, [short], (11,), (16,), (1,))
    reused, _ = served_logits(weights, [short], (11,), (16,), (1,),
                              pool=used)
    np.testing.assert_array_equal(reused[0], fresh[0])


@pytest.mark.parametrize("where", ["block", "model"])
def test_post_norm_and_qk_norm_match_the_reference(weights, where):
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 1024, 64))
    if where == "block":
        # the global expert layer alone (layer 3: no window, no rotation)
        p = weights["Block_3"]
        got = transformer.Block(model().layers()[3]).apply({"params": p}, x)
        want = REF._block(p, x[0], window=0, rotated=False, rope_base=BASE,
                          eps=EPS, kv_bf16=False)
        assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-4
    else:
        # the QK-norm's weights are read: scaling one changes the logits
        p = jax.tree.map(lambda a: a, weights)
        p["Block_2"]["SPAttention_0"]["RMSNorm_1"]["scale"] = (
            2.0 * p["Block_2"]["SPAttention_0"]["RMSNorm_1"]["scale"])
        toks = np.arange(20) % VOCAB
        moved = np.abs(np.asarray(model().apply({"params": p}, toks[None]))[0]
                       - reference(weights, toks)).max()
        assert moved > 100 * ATOL
        assert np.abs(np.asarray(model().apply({"params": p}, toks[None]))[0]
                      - reference(p, toks)).max() < ATOL


class _BlockAsItWas(nn.Module):
    """``Block``'s pre-norm forward pass as the parent commit wrote it (an
    RMSNorm before each sub-block, a SwiGLU), with the modules made in the
    same order."""

    @nn.compact
    def __call__(self, x):
        E = x.shape[-1]
        a = nn.RMSNorm(epsilon=EPS, dtype=jnp.float32)(x)
        x = x + transformer.SPAttention(4, 16, "local", num_kv_heads=2,
                                        rope=True, use_bias=False)(a, 0)
        h = nn.RMSNorm(epsilon=EPS, dtype=jnp.float32)(x)
        dense = [nn.Dense(w, use_bias=False) for w in (128, 128, E)]
        return x + dense[2](jax.nn.silu(dense[0](h)) * dense[1](h))


def test_a_block_that_names_neither_keeps_its_tree_and_its_program():
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64))
    lm = transformer.TransformerLM(
        embed=64, depth=1, num_heads=4, head_dim=16, num_kv_heads=2,
        pos_emb="rope", norm="rmsnorm", norm_eps=EPS, use_bias=False,
        mlp="swiglu", mlp_width=128)
    block = transformer.Block(lm.layers()[0])
    params = block.init(jax.random.PRNGKey(7), x)["params"]
    was = _BlockAsItWas()
    assert jax.tree.structure(params) == jax.tree.structure(
        was.init(jax.random.PRNGKey(7), x)["params"])
    assert "RMSNorm_0" not in params["SPAttention_0"]
    np.testing.assert_array_equal(
        np.asarray(block.apply({"params": params}, x)),
        np.asarray(was.apply({"params": params}, x)))
    # both flags: one norm a side of q and k, the block's two now after
    (both,) = lm.clone(post_norm=True, qk_norm=True).layers()
    flagged = transformer.Block(both).init(jax.random.PRNGKey(7), x)["params"]
    assert set(flagged) == set(params)
    assert set(flagged["SPAttention_0"]) == set(params["SPAttention_0"]) | {
        "RMSNorm_0", "RMSNorm_1"}
    assert flagged["SPAttention_0"]["RMSNorm_0"]["scale"].shape == (16,)


# ------------------------------------------------------------ the expert share


@pytest.fixture(scope="module")
def layer_weights():
    h = jnp.zeros((1, 4, 64))
    shapes = jax.eval_shape(lambda: transformer.ExpertFFN(
        ROUTER, K, 32, None, act="silu", gate="sigmoid", route_scale=SCALE,
        shared_width=32).init(jax.random.PRNGKey(0), h, h))["params"]
    return lm_weights_experts._draw(jax.random.PRNGKey(4), shapes,
                                    jnp.float32)


def test_eight_shares_add_up_to_the_whole_layer(layer_weights):
    # eight chips of 4 of the 32 experts each: every share routes over all
    # 32, and the shared expert, which every chip computes alike, is
    # counted once
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64))
    total = 0
    for share in range(8):
        first = HELD * share
        mine = {k: v for k, v in layer_weights.items()
                if share == 0 or not k.startswith("shared_")}
        for name in ("w_gate", "w_up", "w_down"):
            mine[name] = layer_weights[name][first:first + HELD]
        total = total + transformer.ExpertFFN(
            ROUTER, K, 32, (first, HELD), act="silu", gate="sigmoid",
            route_scale=SCALE, shared_width=32 if share == 0 else 0).apply(
                {"params": mine}, h, h)
    want = REF._experts(h[0], layer_weights)     # the uncut layer: all 32
    assert np.abs(np.asarray(total[0]) - np.asarray(want)).max() < 1e-5


# ----------------------------------------------------- the cache and the engine


def test_window_layers_hold_rings_and_the_engine_books_them_a_slot(weights):
    obs.reset()
    eng = serving.ReplicaEngine(model(), weights, slots=3, slot_tokens=64,
                                name="kex")
    cache = eng._cache
    for i, windowed in enumerate(CFG["window_layout"]):
        rows = WINDOW if windowed else 64
        for leaf in ("k", "v"):
            assert cache[f"Block_{i}"]["SPAttention_0"][leaf].shape == (
                3, rows, 2, 16)
    row = 2 * 2 * 16 * 4                       # a position's k and v, float32
    assert eng.cache_bytes_per_token == row    # the one global layer
    assert eng.ring_bytes_per_slot == 4 * WINDOW * row
    assert eng.state_bytes_per_slot == 0
    gauge = obs.registry().gauge
    assert gauge("tm_serving_ring_bytes_per_slot",
                 replica="kex") == eng.ring_bytes_per_slot
    assert gauge("tm_serving_cache_bytes_per_token", replica="kex") == row
    # a live session reads min(depth + 1, window) rows of each ring layer
    eng._sessions = {0: serving.engine.Session(None, 0, 0, 5),
                     2: serving.engine.Session(None, 2, 0, 30)}
    eng._count_step(eng._sessions)
    assert eng.stats["window_rows_live"] == 4 * (6 + WINDOW)
    obs.reset()


@pytest.mark.parametrize("asked", [dict(prefix_cache=4), dict(spec_k=2)])
def test_engine_refuses_what_a_ring_cannot_serve(weights, asked):
    with pytest.raises(ValueError, match="ring of 8 rows .*Block_0/"
                       "SPAttention_0/[kv]"):
        serving.ReplicaEngine(model(), weights, slots=2, slot_tokens=64,
                              **asked)
    # the same model over a slot no longer than its window keeps no ring
    serving.ReplicaEngine(model(max_len=WINDOW), weights, slots=2,
                          slot_tokens=WINDOW, **asked)


def test_a_ring_refuses_a_block_at_per_slot_depths(weights):
    # a verify or an extend would overwrite positions its earlier queries
    # still see: the engine never sends one (above), the layer refuses it
    from torchmpi_tpu.models.generate import slot_verify_step

    dm = model().clone(decode=True, max_len=64)
    with pytest.raises(ValueError, match="ring of 8 rows takes one token"):
        slot_verify_step(dm, weights, _pool(dm, 2),
                         np.zeros((2, 3), np.int32), np.array([20, 30]))


def ring_rows_of(lm, slot_tokens):
    """Each attention layer's cache rows when ``lm`` decodes in slots of
    ``slot_tokens``."""
    dm = lm.clone(decode=True, max_len=slot_tokens)
    cache = jax.eval_shape(lambda: dm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
        pos_offset=jnp.zeros((1,), jnp.int32)))["cache"]
    return [cache[b]["SPAttention_0"]["k"].shape[1] for b in sorted(
        cache, key=lambda name: int(name.split("_")[1]))]


def test_the_ring_rule_is_the_window_against_the_slot():
    assert ring_rows_of(model(), 64) == [WINDOW] * 3 + [64, WINDOW]
    assert ring_rows_of(model(), WINDOW) == [WINDOW] * 5
    assert ring_rows_of(model(window=None), 64) == [64] * 5


def test_served_tokens_are_the_references_first_choice(weights, tokens):
    """Through ``serving.Server`` with bucketed prefill: two requests decode
    side by side, each token the reference's own first choice, and the
    counter of live ring rows counts what their depths say."""
    obs.reset()
    server = serving.Server(model(), weights, replicas=1, slots=3,
                            slot_tokens=64, prefill_bucket=8, sample=0.0,
                            spec_k=0, prefix_cache=0, slo_ttft_us=0,
                            autoscale=0)
    reqs = [serving.Request(rid=f"r{i}", prompt=tokens[4 * i:4 * i + n],
                            max_new=m, eos_id=None, arrival_s=0.0)
            for i, (n, m) in enumerate(((11, 20), (19, 12)))]
    done = {r.rid: r for r in server.run_trace(reqs)}
    for r in reqs:
        seq = np.concatenate([r.prompt, done[r.rid].tokens])
        lg = np.asarray(REF.logits(
            weights, seq[:-1], np.arange(r.prompt.size - 1, seq.size - 1),
            **KW))
        assert (lg.argmax(-1) == np.asarray(done[r.rid].tokens)).all()
    engine = server.router.live()[0]
    assert engine.stats["window_rows_live"] == 4 * WINDOW * engine.stats[
        "live_slot_steps"]                   # every prompt is past the window
    obs.reset()


# ------------------------------------ a window shorter than the slot elsewhere


def test_starcoder2_rehearsal_with_a_ring_serves_the_windowed_tokens():
    """``starcoder2-3b-serve`` at its rehearsal's sizes: a window of 32
    against a slot of 96 now makes every layer's keys and values a ring of
    32 rows.  Its served tokens are what the whole forward pass's windowed
    attention chooses, as they were with every position cached."""
    cell = harness.resolve(MANIFEST, "sc2-3b-serve-sat", rehearse=True)
    cfg = cell.config
    lm = harness.build_model(cell).clone(dtype=jnp.float32)
    assert cfg["sliding_window"] < cfg["serving"]["slot_tokens"]
    assert ring_rows_of(lm, cfg["serving"]["slot_tokens"]) == [
        cfg["sliding_window"]] * cfg["num_hidden_layers"]
    params = lm.init(jax.random.PRNGKey(5),
                     jnp.zeros((1, 8), jnp.int32))["params"]
    server = serving.Server(lm, params, replicas=1, slots=2,
                            slot_tokens=cfg["serving"]["slot_tokens"],
                            prefill_bucket=8, sample=0.0, spec_k=0,
                            prefix_cache=0, slo_ttft_us=0, autoscale=0)
    rng = np.random.RandomState(9)
    reqs = [serving.Request(rid=f"s{i}", prompt=rng.randint(
        0, cfg["vocab_size"], size=n).astype(np.int32), max_new=m,
        eos_id=None, arrival_s=0.0) for i, (n, m) in enumerate(
            ((45, 40), (20, 30)))]
    done = {r.rid: r for r in server.run_trace(reqs)}
    full = jax.jit(lambda t: lm.apply({"params": params}, t))
    for r in reqs:
        seq = np.concatenate([r.prompt, done[r.rid].tokens])
        want = np.asarray(full(seq[None, :-1]))[0, r.prompt.size - 1:]
        assert (want.argmax(-1) == np.asarray(done[r.rid].tokens)).all()


# ------------------------------------------------ a long prompt's expert layer


def test_a_long_prompts_expert_layer_routed_in_chunks_is_the_whole(
        layer_weights, monkeypatch):
    """Where a call's worst-case sorted buffer is over the budget (a prompt
    of 16,384 tokens x 8 routes x 6,144 wide would be 3.2 GB), the tokens go
    through the expert layer in chunks: every token's output and the
    layer's counts are the unchunked call's."""
    from torchmpi_tpu.ops import moe
    from torchmpi_tpu.parallel import expert

    assert expert.token_chunks(16384, 6144, 8) == 4
    assert expert.token_chunks(8192, 2560, 6) == 1     # st-21b: as it was
    assert moe.token_parts(8192, 2560) == 1
    assert moe.token_parts(4096, 6144) == 2            # a chunk's sums
    h = jax.random.normal(jax.random.PRNGKey(8), (1, 64, 64))
    layer = transformer.ExpertFFN(ROUTER, K, 32, (4, HELD), act="silu",
                                  gate="sigmoid", route_scale=SCALE)
    mine = {k: v for k, v in layer_weights.items()
            if not k.startswith("shared_")}
    for name in ("w_gate", "w_up", "w_down"):
        mine[name] = layer_weights[name][4:4 + HELD]
    whole, sown = layer.apply({"params": mine}, h, h, mutable=["moe"])
    monkeypatch.setattr(expert, "_BUFFER_BYTES", 16 * K * 64 * 4)
    assert expert.token_chunks(64, 64, K) == 4
    parts, sown_parts = layer.apply({"params": mine}, h, h, mutable=["moe"])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-6, atol=1e-6)
    for name in ("routes_held", "rows_computed", "experts"):
        np.testing.assert_array_equal(np.asarray(sown_parts["moe"][name][0]),
                                      np.asarray(sown["moe"][name][0]))
