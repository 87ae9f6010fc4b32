"""AI21-Jamba2-3B's layers on the served path, at small sizes on the CPU,
seeded random weights (``chipbench/lm_weights_jamba.py``), float32, against
the plain reference (``chipbench/reference/jamba_served.py``): the Mamba-1
mixer (a prompt's selective scan in pieces, a pooled step one recurrent
update a slot, a state with no token axis held channels-minor) inside
mixer-then-feed-forward blocks, attention with ONE key/value head and no
positions, a tied head.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness, lm_weights_jamba  # noqa: E402
from torchmpi_tpu import obs, serving  # noqa: E402
from torchmpi_tpu.models import TransformerLM, transformer  # noqa: E402
from torchmpi_tpu.models.generate import (  # noqa: E402
    STATE_LEAVES, slot_prefill, slot_write)

MANIFEST = harness.load_manifest()
CELL = "jamba2-3b-serve-reason-sat"
REF = harness.load_module(MANIFEST, "reference", "jamba_served")
PATTERN, VOCAB, EMBED, EPS = "mmamm", 256, 64, 1e-6
# the mixer at the test's sizes: 128 channels, 16 states, a rank of 4, 4 taps
INNER, STATE, RANK, TAPS, CHUNK = 2 * EMBED, 16, 4, 4, 16
KW = dict(depth=len(PATTERN), window=None, rope_base=10000.0, eps=EPS)
# float32 on both sides: what is left is the order of the sums and the
# state's layout.  It reads 2e-5 to 4e-5 on logits of scale 8 (a tied head
# over unit rows: sqrt(64)); float8 matrices read 1.5 and a bfloat16 state
# 0.03 (below).
ATOL = 2e-4


def model(**kw):
    return TransformerLM(**{**dict(
        vocab=VOCAB, embed=EMBED, depth=len(PATTERN), num_heads=4,
        head_dim=16, num_kv_heads=1, max_len=128, dtype=jnp.float32,
        norm_eps=EPS, norm="rmsnorm", use_bias=False, pos_emb="none",
        layer_pattern=PATTERN, ssm_expand=2, ssm_state=STATE,
        ssm_dt_rank=RANK, ssm_conv=TAPS, ssm_chunk=CHUNK, mlp="swiglu",
        mlp_width=96, tie_head=True), **kw})


@pytest.fixture(scope="module")
def weights():
    return lm_weights_jamba.make(model(), jax.random.PRNGKey(0), jnp.float32)


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
                                  "sc2-3b-serve-sat",
                                  "nm3s-120b-serve-chat-sat"])
def test_jamba_runner_draws_the_other_served_models_as_they_were(cell):
    """The thin runners bind names of the ONE ``serve_open_loop`` module;
    this cell's runner imports the hybrid's first, so the bindings nest in
    one order whichever a process imports first: a model with a Mamba-1
    mixer gets ``lm_weights_jamba``, a ``nemotron_h`` hybrid
    ``lm_weights_hybrid``, any other ``lm_weights_experts``."""
    from chipbench import lm_weights_experts, lm_weights_hybrid

    runner = harness.load_module(MANIFEST, "runners", "serve_open_loop_jamba")
    assert harness.load_module(MANIFEST, "runners",
                               "serve_open_loop_hybrid") is runner.hybrid
    bound = runner.base.lm_weights
    other = harness.build_model(harness.resolve(MANIFEST, cell,
                                                rehearse=True))
    theirs = (lm_weights_hybrid if cell.startswith("nm3s")
              else lm_weights_experts).make(other, jax.random.PRNGKey(3),
                                            jnp.float32)
    mine = bound.make(other, jax.random.PRNGKey(3), jnp.float32)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    own = bound.make(model(), jax.random.PRNGKey(0), jnp.float32)
    a_log = np.asarray(own["Block_0"]["MambaMixer_0"]["A_log"])
    assert a_log.shape == (INNER, STATE)            # as published
    assert 0.0 <= a_log.min() and a_log.max() <= np.log(16.0)
    # one judge a kind of tolerance: a dense model's ONE limit is judged by
    # serve_open_loop's own judge, the shares by the experts' runner's
    mine_cell = harness.resolve(MANIFEST, CELL, rehearse=True)
    assert "off_the_top_share" not in mine_cell.config["tolerance"]


# ----------------------------------------------------------- (a) the mixer


def mixer(chunk=CHUNK, **kw):
    return transformer.MambaMixer(INNER, STATE, RANK, TAPS, chunk,
                                  norm_eps=EPS, **kw)


@pytest.fixture(scope="module")
def mixer_weights():
    u = jnp.zeros((1, 4, EMBED))
    shapes = jax.eval_shape(
        lambda: mixer().init(jax.random.PRNGKey(0), u))["params"]
    return lm_weights_jamba._draw(jax.random.PRNGKey(2), shapes, jnp.float32)


@pytest.mark.parametrize("T", [1, 7, 16, 17, 50])
def test_scan_in_pieces_equals_the_recurrence(mixer_weights, T):
    """Outputs and the state after the last token: the scan in pieces of 16
    (under one piece, one whole, one and a token, three with a remainder)
    against the reference's scan, one token a step; the program holds the
    state channels-minor, the reference as published."""
    u = jax.random.normal(jax.random.PRNGKey(T), (1, T, EMBED))
    want, last = REF.mixer(u[0], mixer_weights, EPS)
    got = mixer().apply({"params": mixer_weights}, u)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5)
    _, kept = mixer(decode=True).apply({"params": mixer_weights}, u,
                                       mutable=["cache"])
    assert kept["cache"]["ssm_state"].shape == (1, STATE, INNER)
    np.testing.assert_allclose(np.asarray(kept["cache"]["ssm_state"][0]),
                               np.asarray(last).T, atol=2e-5)


@pytest.mark.parametrize("chunk", [4, 16])
def test_scan_is_the_recurrence_where_a_cumulative_product_would_underflow(
        chunk):
    """Decays down to exp(-16 * 40): a quotient of cumulative products is
    0 / 0 there; the recurrence as written forgets and goes on."""
    T, Di, N = 37, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (2, T, Di))
    delta = 40.0 * jax.random.uniform(ks[1], (2, T, Di))
    b, c = (jax.random.normal(k, (2, T, N)) for k in ks[2:])
    a = -jnp.linspace(1.0, 16.0, N * Di).reshape(N, Di)
    y, last = transformer.selective_scan(x, delta, a, b, c, chunk)
    h = np.zeros((2, N, Di))
    for t in range(T):
        dt = np.asarray(delta[:, t, None, :], np.float64)
        h = (np.exp(dt * np.asarray(a)) * h
             + dt * np.asarray(x[:, t, None, :]) * np.asarray(b[:, t, :, None]))
        np.testing.assert_allclose(
            np.asarray(y[:, t]), (h * np.asarray(c[:, t, :, None])).sum(1),
            rtol=1e-4, atol=1e-4)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(last), h, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("T,padded", [(21, 32), (3, 16), (2, 16)])
def test_a_padded_prompt_with_its_true_length_leaves_the_unpadded_state(
        mixer_weights, T, padded):
    """Bit for bit: positions from ``true_len`` on get ``delta = 0``, which
    multiplies the state by 1 and adds 0, and the convolution's state is
    the last three LIVE inputs (zeros before a prompt shorter than that).
    Without the true length the padding's tokens are in the state."""
    u = jax.random.normal(jax.random.PRNGKey(T), (1, padded, EMBED))
    layer = mixer(chunk=8, decode=True)

    def kept(u, **kw):
        return layer.apply({"params": mixer_weights}, u, mutable=["cache"],
                           **kw)[1]["cache"]

    plain, told, untold = (kept(u[:, :T]), kept(u, true_len=jnp.int32(T)),
                           kept(u))
    for name in STATE_LEAVES:
        np.testing.assert_array_equal(np.asarray(told[name]),
                                      np.asarray(plain[name]))
        assert not np.array_equal(np.asarray(untold[name]),
                                  np.asarray(plain[name]))
    assert told["conv_state"].shape == (1, TAPS - 1, INNER)
    if T < TAPS - 1:
        assert not np.asarray(told["conv_state"][0, :TAPS - 1 - T]).any()


def test_a_block_at_per_row_depths_is_refused(mixer_weights):
    u = jnp.zeros((2, 3, EMBED))
    with pytest.raises(ValueError, match="a MambaMixer cannot take a block "
                       "of tokens at per-row depths.*cannot be un-updated"):
        mixer(decode=True).apply({"params": mixer_weights}, u,
                                 jnp.zeros((2,), jnp.int32),
                                 mutable=["cache"])


def test_one_letter_a_layer_chooses_the_mixer_before_the_feed_forward(
        weights):
    """``layer_pattern`` is the ONE field: a small letter is a mixer THEN
    the block's own feed-forward (``a`` what None has always been), a
    capital ONE sub-block; both kinds of layer hold the same three
    feed-forward matrices, found by the benchmark as ``/Block_n/Dense_n/``."""
    for i, kind in enumerate(PATTERN):
        block = weights[f"Block_{i}"]
        assert ("MambaMixer_0" in block) == (kind == "m")
        assert ("SPAttention_0" in block) == (kind == "a")
        assert {"Dense_0", "Dense_1", "Dense_2", "RMSNorm_0",
                "RMSNorm_1"} < set(block)
    plain = dict(vocab=VOCAB, embed=32, depth=2, num_heads=2, head_dim=16,
                 max_len=64, pos_emb="rope")
    toks = jnp.zeros((1, 8), jnp.int32)
    none = TransformerLM(**plain).init(jax.random.PRNGKey(0), toks)
    lettered = TransformerLM(**plain, layer_pattern="aa").init(
        jax.random.PRNGKey(0), toks)
    assert jax.tree.structure(none) == jax.tree.structure(lettered)
    for a, b in zip(jax.tree.leaves(none), jax.tree.leaves(lettered)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="unknown layer kind 'x'"):
        TransformerLM(**plain, layer_pattern="ax").init(
            jax.random.PRNGKey(0), toks)


# --------------------------------------------- (b) (c) against the reference


def test_full_forward_pass_matches_the_reference(weights, tokens):
    got = np.asarray(model().apply({"params": weights}, tokens[None]))[0]
    assert np.abs(got - reference(weights, tokens)).max() < ATOL


def served(params, requests, slots=4, bucket=16, after=None):
    """``requests``: ``(slot, tokens, prompt length)`` in admission order;
    a slot named twice is admitted again when its first request has
    retired.  Each prompt is prefilled right-padded to ``bucket`` with its
    true length and written into its slot; the pool then steps all slots
    together, teacher-forced, an idle slot fed token 0 at position 0 as
    the engine feeds it -> {request index: logits from its prompt's last
    position on}.  ``after``: applied to every cache a prefill or a step
    hands back (a control's rounding)."""
    after = after or (lambda cache: cache)
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
            pool = slot_write(pool, after(cache), slot)
            live[slot] = [i, toks, prompt]
        toks_in = np.zeros((slots, 1), np.int32)
        pos = np.zeros((slots,), np.int32)
        for slot, (i, toks, at) in live.items():
            toks_in[slot], pos[slot] = toks[at], at
        logits, updated = step(pool, jnp.asarray(toks_in), jnp.asarray(pos))
        pool = after(updated["cache"])
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
    written = 0
    for p, o in zip(jax.tree.leaves(pool), jax.tree.leaves(cache)):
        if p.ndim:
            np.testing.assert_array_equal(np.asarray(p[1]), np.asarray(o[0]))
            assert np.isnan(np.asarray(p[0])).all()
            written += 1
    assert written == 2 * PATTERN.count("m") + 2 * PATTERN.count("a")


def test_tied_model_has_no_head_leaf_and_feeds_the_fused_xent(
        weights, tokens):
    """``tie_head``: no ``head`` parameter; ``return_prehead`` hands back
    the embedding's transpose, which ``ops/xent.py`` takes as any head, and
    a gradient through it reaches the embedding from both of its uses."""
    import optax

    from torchmpi_tpu.ops.xent import fused_linear_cross_entropy

    assert "head" not in weights
    assert "head" in model(tie_head=False).init(
        jax.random.PRNGKey(0), tokens[None, :8])["params"]
    toks = jnp.asarray(tokens[None, :17])

    def loss_fused(p):
        h, head = model().apply({"params": p}, toks, return_prehead=True)
        assert head.shape == (EMBED, VOCAB)
        return fused_linear_cross_entropy(
            h[:, :-1].reshape(-1, EMBED), head, toks[:, 1:].reshape(-1),
            block_n=8, block_v=128).mean()

    def loss_logits(p):
        logits = model().apply({"params": p}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], toks[:, 1:]).mean()

    lf, gf = jax.value_and_grad(loss_fused)(weights)
    ll, gl = jax.value_and_grad(loss_logits)(weights)
    np.testing.assert_allclose(float(lf), float(ll), rtol=2e-5)
    table = np.asarray(gf["Embed_0"]["embedding"])
    np.testing.assert_allclose(table, np.asarray(gl["Embed_0"]["embedding"]),
                               rtol=3e-4, atol=3e-5)
    # a token never seen as an input still gets a gradient: from the head
    unseen = np.setdiff1d(np.arange(VOCAB), np.asarray(toks))
    assert np.abs(table[unseen]).max() > 0


def bf16_state(cache):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x.astype(jnp.bfloat16).astype(x.dtype)
        if path[-1].key == "ssm_state" else x, cache)


@pytest.mark.parametrize("what", ["matrices_fp8", "state_bf16"])
def test_one_precision_below_fails_the_tolerance(weights, tokens, what):
    """The tolerance is tight enough: the same program on matrices rounded
    to float8, or with its recurrent state rounded to bfloat16 after every
    prefill and step, is outside it; and the reference's own controls
    (``lower``, ``state_dtype``) read as far off."""
    after = bf16_state if what == "state_bf16" else None
    got = served(weights if after else REF.lowered(weights),
                 [(1, tokens[:40], 16)], after=after)[0]
    want = reference(weights, tokens[:40])
    assert np.abs(got - want[15:]).max() > 10 * ATOL
    control = reference(weights, tokens[:40], **(
        dict(state_dtype=jnp.bfloat16) if after else dict(lower=True)))
    assert np.abs(control - want).max() > 10 * ATOL


# --------------------------------------------------------- (d) (e) the engine


@pytest.mark.parametrize("asked", [dict(prefix_cache=4), dict(spec_k=2)])
def test_engine_refuses_what_a_recurrent_state_cannot_serve(weights, asked):
    with pytest.raises(ValueError, match="Block_0/MambaMixer_0/"
                       "(conv|ssm)_state"):
        serving.ReplicaEngine(model(), weights, slots=2, slot_tokens=64,
                              **asked)


def test_engine_books_tokens_and_state_apart(weights):
    eng = serving.ReplicaEngine(model(), weights, slots=3, slot_tokens=64,
                                name="jamba")
    # ONE attention layer's keys and values, ONE head of 16, float32
    assert eng.cache_bytes_per_token == 2 * 1 * 16 * 4
    # four mixers' states and convolution inputs
    per_slot = PATTERN.count("m") * (STATE * INNER + (TAPS - 1) * INNER) * 4
    assert eng.state_bytes_per_slot == per_slot
    gauge = obs.registry().gauge
    assert gauge("tm_serving_cache_bytes_per_token", replica="jamba") == 128
    assert gauge("tm_serving_state_bytes_per_slot",
                 replica="jamba") == per_slot
    # and the benchmark's count from shapes alone agrees with the engine's
    from chipbench import flops_jamba_serve as counts

    sizes = dict(hidden_size=EMBED, intermediate_size=96,
                 layer_pattern=PATTERN, mamba_expand=2, mamba_d_state=STATE,
                 mamba_dt_rank=RANK, mamba_d_conv=TAPS,
                 num_attention_heads=4, num_key_value_heads=1, head_dim=16,
                 vocab_size=VOCAB)
    assert counts.state_bytes_per_slot(**sizes) == per_slot
    assert counts.parameters(**sizes) == sum(
        x.size for x in jax.tree.leaves(weights))


def test_server_decodes_the_references_tokens_and_counts_live_slot_steps(
        weights, tokens):
    """Through ``serving.Server``: three requests side by side in a pool of
    two, one slot reused, bucketed prefill: each token the reference's own
    first choice; ``stats["live_slot_steps"]`` adds every pooled step's live
    sessions (a request's first token is its prefill's, the others a step's
    each), mirrored as ``tm_serving_live_slot_steps_total`` where telemetry
    is on."""
    obs.reset()
    obs.activate("metrics")
    try:
        server = serving.Server(model(), weights, replicas=1, slots=2,
                                slot_tokens=64, prefill_bucket=8, sample=0.0,
                                spec_k=0, prefix_cache=0, slo_ttft_us=0,
                                autoscale=0)
        asked = ((9, 10), (14, 6), (5, 8))
        reqs = [serving.Request(rid=f"r{i}", prompt=tokens[4 * i:4 * i + n],
                                max_new=m, eos_id=None, arrival_s=0.0)
                for i, (n, m) in enumerate(asked)]
        done = {r.rid: r for r in server.run_trace(reqs)}
        for r in reqs:
            seq = np.concatenate([r.prompt, done[r.rid].tokens])
            lg = np.asarray(REF.logits(
                weights, seq[:-1],
                np.arange(r.prompt.size - 1, seq.size - 1), **KW))
            assert (lg.argmax(-1) == np.asarray(done[r.rid].tokens)).all()
        stats = server.router.live()[0].stats
        assert stats["live_slot_steps"] == sum(m - 1 for _, m in asked)
        assert stats["steps"] <= stats["live_slot_steps"] <= 2 * stats["steps"]
        assert obs.registry().counter_total(
            "tm_serving_live_slot_steps_total") == stats["live_slot_steps"]
    finally:
        obs.deactivate()
        obs.reset()


# ------------------------------------------------------ (f) the rehearsal


@pytest.fixture(scope="module")
def rehearsed():
    if jax.default_backend() != "cpu":
        pytest.skip("the rehearsal's sizes are for the CPU")
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    cell = harness.resolve(MANIFEST, CELL, rehearse=True)
    return cell, harness.load_module(MANIFEST, "runners",
                                     cell.config["runner"])


@pytest.mark.parametrize("seed", [13, 2**31 + 7])
def test_rehearsal_is_correct_and_its_controls_are_not(rehearsed, seed):
    from chipbench import served_check

    cell, runner = rehearsed
    s = runner.served(cell, seed, 1.0)
    limit = cell.config["tolerance"]["logit_gap"]
    picked = served_check.sample(s.records, seed, 1000)
    program = served_check.gaps(cell, s.params, picked)
    control = served_check.gaps(cell, s.params, picked, control=True)
    assert program["served_tokens"] > 30
    assert program["widest_gap"] <= limit < control["widest_gap"]
    checked, compared, correct = runner.judge(cell, seed, s)
    assert correct and list(compared)[0] == "logit_gap"
    assert compared["logit_gap"] == [program["widest_gap"], limit]
    # the long answers of the warm-up stretch, judged from a position on:
    # the mean gap of the tokens off the reference's top (none here: the
    # rehearsal computes in float32), and far over its limit for the control
    tol = cell.config["tolerance"]
    start = tol["long_from_position"]
    far = runner.long_answers(s.records, tol["long_requests"], start)
    assert 0 < len(far) <= tol["long_requests"]
    assert all(r.phase == "warm" and len(r.tokens) > start for r in far)
    assert [len(r.tokens) for r in far] == sorted(
        (len(r.tokens) for r in far), reverse=True)
    tokens = sum(len(r.tokens) - start for r in far)
    assert checked["long_answers"] == {
        "requests": len(far), "served_tokens": tokens, "off_the_top": 0,
        "gap_when_off_the_top": 0.0}
    assert compared["long_gap_when_off_the_top"] == [
        0.0, tol["long_gap_when_off_the_top"]]
    assert compared["long_tokens_checked"] == [tokens,
                                               tol["long_min_tokens"]]
    assert tokens >= tol["long_min_tokens"]
    lowered = runner.gaps_when_off_the_top(cell, s.params, far, start,
                                           control=True)
    assert lowered["served_tokens"] == tokens and lowered["off_the_top"] > 5
    assert (lowered["gap_when_off_the_top"]
            > 3 * tol["long_gap_when_off_the_top"])
    # too few tokens to tell the states apart: not called correct
    cell.config["tolerance"] = {**tol, "long_min_tokens": 10 ** 6}
    try:
        assert not runner.judge(cell, seed, s)[2]
    finally:
        cell.config["tolerance"] = tol
    # the window's live slots a step are the harness's own; the engine's
    # count, over the warm-up too, is among the run's counters
    assert 0 < s.stats["live_slots_per_step"] <= 4
    assert s.counters["live_slot_steps"] >= s.counters["steps"] > 0
