"""SmallThinker-21BA3B-Instruct's block through ``TransformerLM`` against the
plain reference (``chipbench/reference/smallthinker.py``): RMSNorm, no
bias, a per-layer layout of window and positions, a router fed from before
the attention, top-k softmax over the chosen, ReGLU experts of which only
the held ones exist (``parallel/expert.held_experts``).  Small sizes,
seeded weights, float32, on the CPU.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from torchmpi_tpu.models.transformer import (Block, ExpertFFN,  # noqa: E402
                                             TransformerLM)
from torchmpi_tpu.ops.xent import fused_linear_cross_entropy  # noqa: E402
from torchmpi_tpu.parallel import expert as ep  # noqa: E402

REF = harness.load_module(harness.load_manifest(), "reference",
                          "smallthinker")

V, E, H, HKV, D = 64, 32, 4, 2, 8
N_EXPERTS, K, WIDTH = 8, 3, 16
WINDOW, SEQ, BASE, EPS = 8, 24, 1.5e6, 1e-6
LAYOUT = (0, 1, 1, 1)            # layer 0 full and without positions


def lm(held=None, vocab=V, layout=LAYOUT, **kw):
    return TransformerLM(
        vocab=vocab, embed=E, depth=len(layout), num_heads=H, head_dim=D,
        num_kv_heads=HKV, max_len=64, window=WINDOW, pos_emb="rope",
        rope_base=BASE, norm="rmsnorm", norm_eps=EPS, use_bias=False,
        window_layout=layout, rope_layout=layout, n_experts=N_EXPERTS,
        experts_held=held, expert_width=WIDTH, moe_k=K, **kw)


def ref_kw(held=(0, N_EXPERTS), layout=LAYOUT):
    return dict(window_layout=layout, rope_layout=layout, window=WINDOW,
                rope_base=BASE, eps=EPS, k=K, held=held)


def init(model, seed=0):
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # lecun-normal embeddings of 1/sqrt(E) would leave the first layer's
    # input tiny beside what attention and the experts add: unit rows
    return {**params, "Embed_0": {
        "embedding": params["Embed_0"]["embedding"] * E ** 0.5}}


def tokens(seed=1, vocab=V):
    return jax.random.randint(jax.random.PRNGKey(seed), (SEQ,), 0, vocab)


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_no_bias_no_layernorm_in_the_tree():
    params = init(lm((2, 4)))
    names = {"/".join(str(k.key) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert not any(n.endswith("bias") or "LayerNorm" in n for n in names)
    assert params["Block_0"]["ExpertFFN_0"]["router"].shape == (E, N_EXPERTS)
    # only the four experts held exist
    assert params["Block_0"]["ExpertFFN_0"]["w_gate"].shape == (4, E, WIDTH)
    assert params["Block_0"]["ExpertFFN_0"]["w_down"].shape == (4, WIDTH, E)


# ------------------------------------ (a) the model against the reference


@pytest.mark.parametrize("held", [(0, N_EXPERTS), (2, 4)],
                         ids=["uncut", "share"])
@pytest.mark.parametrize("attn_impl", ["local", "flash"])
def test_loss_and_gradients_match_the_reference(attn_impl, held):
    model = lm(held, attn_impl=attn_impl)
    params, tok = init(lm(held)), tokens()

    def program(p):
        # the benchmark step's loss: pre-head activations and the head
        # through the fused linear + cross-entropy kernel
        x, head = model.apply({"params": p}, tok[None], return_prehead=True)
        return fused_linear_cross_entropy(x[0, :-1], head, tok[1:]).mean()

    def reference(p):
        return REF.loss(p, tok, **ref_kw(held))

    got, g_got = jax.value_and_grad(program)(params)
    want, g_want = jax.value_and_grad(reference)(params)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    flat_got = dict(jax.tree_util.tree_flatten_with_path(g_got)[0])
    for path, w in jax.tree_util.tree_flatten_with_path(g_want)[0]:
        assert float(jnp.linalg.norm(w)) > 0, path
        assert rel(flat_got[path], w) < 1e-4, path


def test_logits_match_the_reference():
    model, tok = lm(), tokens()
    params = init(model)
    got = model.apply({"params": params}, tok[None])[0]
    assert rel(got, REF.logits(params, tok, **ref_kw())) < 1e-5


# --------------------------------------- (b) the share ties to the model


def test_four_shares_of_a_layer_sum_to_the_uncut_reference():
    block = dict(num_heads=H, head_dim=D, num_kv_heads=HKV, window=WINDOW,
                 rope=True, rope_base=BASE, norm="rmsnorm", norm_eps=EPS,
                 use_bias=False, n_experts=N_EXPERTS, expert_width=WIDTH,
                 moe_k=K)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, SEQ, E))
    whole = Block(**block).init(jax.random.PRNGKey(4), x)["params"]
    want, _ = REF.layer(x[0], whole, k=K, held=(0, N_EXPERTS), window=WINDOW,
                        rope_base=BASE, eps=EPS)
    # what every share computes alike (the residual and the attention) is
    # counted once
    alike = x[0] + REF.attention(
        REF._rms(x[0], whole["RMSNorm_0"], EPS), whole["SPAttention_0"],
        window=WINDOW, rope_base=BASE)
    count = N_EXPERTS // 4
    parts = []
    for first in range(0, N_EXPERTS, count):
        ffn = whole["ExpertFFN_0"]
        mine = {**whole, "ExpertFFN_0": {
            "router": ffn["router"],
            **{n: ffn[n][first:first + count]
               for n in ("w_gate", "w_up", "w_down")}}}
        out = Block(**block, experts_held=(first, count)).apply(
            {"params": mine}, x)[0]
        parts.append(out - alike)
        # a share alone is not the layer
        assert rel(out, want) > 1e-2
    assert rel(alike + sum(parts), want) < 1e-5


def test_four_vocabulary_slices_concatenate_to_the_uncut_logits():
    slice_v = V // 4
    whole, tok = init(lm()), tokens(vocab=slice_v)     # ids from slice 0
    want = REF.logits(whole, tok, **ref_kw())          # [T, V]
    got = []
    for s in range(4):
        rows = slice(s * slice_v, (s + 1) * slice_v)
        mine = {**whole, "head": whole["head"][:, rows], "Embed_0": {
            "embedding": whole["Embed_0"]["embedding"][:slice_v]}}
        got.append(lm(vocab=slice_v).apply({"params": mine}, tok[None])[0])
    assert rel(jnp.concatenate(got, axis=-1), want) < 1e-5


# ------------------------------- (c) dropless under the worst imbalance


def expert_weights(count, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (count, E, WIDTH)) / E ** 0.5,
            jax.random.normal(ks[1], (count, E, WIDTH)) / E ** 0.5,
            jax.random.normal(ks[2], (count, WIDTH, E)) / WIDTH ** 0.5)


def dense_oracle(u, logits, k, first, w_gate, w_up, w_down):
    """Every held expert on every token, masked by the routing."""
    scores, chosen = jax.lax.top_k(logits, k)
    probs = jax.nn.softmax(scores, -1)
    out = jnp.zeros_like(u)
    for local in range(w_gate.shape[0]):
        weight = jnp.where(chosen == first + local, probs, 0).sum(-1)
        y = (jax.nn.relu(u @ w_gate[local]) * (u @ w_up[local])) \
            @ w_down[local]
        out += weight[:, None] * y
    return out


RIGGED = {
    # every route of every token goes to the three held experts: the
    # buffer's worst case, T * k rows all live
    "all_held": (np.r_[0, 0, 9, 9, 9, 0, 0, 0], 40 * K),
    # every token's three routes go to experts held elsewhere
    "none_held": (np.r_[9, 9, 0, 0, 0, 9, 0, 0], 0),
    # one held expert takes every token, the two others none
    "one_expert": (np.r_[9, 9, 0, 0, 9, 0, 0, 0], 40),
}


@pytest.mark.parametrize("case", sorted(RIGGED))
def test_no_route_to_a_held_expert_is_dropped(case):
    bias, want_routes = RIGGED[case]
    tokens_n, first, count = 40, 2, 3
    u = jax.random.normal(jax.random.PRNGKey(6), (tokens_n, E))
    logits = (0.1 * jax.random.normal(jax.random.PRNGKey(7),
                                      (tokens_n, N_EXPERTS)) + bias)
    weights = expert_weights(count)
    out, stats = jax.jit(ep.held_experts, static_argnums=(2, 3))(
        u, logits, K, first, *weights)
    assert int(stats["routes_held"]) == want_routes
    assert int(stats["rows_computed"]) == want_routes
    want = dense_oracle(u, logits, K, first, *weights)
    if want_routes:
        assert rel(out, want) < 1e-5
    else:
        assert not np.asarray(out).any() and not np.asarray(want).any()


def test_gradients_of_the_held_part_match_the_dense_oracle():
    tokens_n, first, count = 40, 2, 3
    u = jax.random.normal(jax.random.PRNGKey(6), (tokens_n, E))
    logits = jax.random.normal(jax.random.PRNGKey(7), (tokens_n, N_EXPERTS))
    weights = expert_weights(count)
    cot = jax.random.normal(jax.random.PRNGKey(8), (tokens_n, E))

    def scalar(fn):
        return lambda u, lg, *w: (fn(u, lg, K, first, *w) * cot).sum()

    got = jax.grad(scalar(lambda *a: ep.held_experts(*a)[0]),
                   argnums=range(5))(u, logits, *weights)
    want = jax.grad(scalar(dense_oracle), argnums=range(5))(u, logits,
                                                            *weights)
    for g, w in zip(got, want):
        assert rel(g, w) < 1e-5


def test_a_range_outside_the_router_is_refused():
    u, logits = jnp.zeros((4, E)), jnp.zeros((4, N_EXPERTS))
    with pytest.raises(ValueError, match="do not fit a router over 8"):
        ep.held_experts(u, logits, K, 6, *expert_weights(3))
    with pytest.raises(ValueError, match="entries for 4 layers"):
        init(lm(layout=LAYOUT).clone(window_layout=(0, 1)))


# ------------------------------ (d) the counters read what the routing did


def test_counters_read_what_the_routing_did():
    held = (2, 4)
    model, tok = lm(held), tokens()
    params = init(model)
    _, sown = model.apply({"params": params}, tok[None], mutable=["moe"])
    _, aux = REF.loss(params, tok, with_aux=True, **ref_kw(held))
    total = 0
    for i in range(len(LAYOUT)):
        layer = sown["moe"][f"Block_{i}"]["ExpertFFN_0"]
        (chosen,), (routes,), (rows,) = (
            layer["experts"], layer["routes_held"], layer["rows_computed"])
        assert chosen.shape == (SEQ, K)
        # the program chose the experts the reference chose
        assert np.array_equal(np.sort(chosen, -1),
                              np.sort(aux["experts"][i], -1))
        in_range = int(((chosen >= held[0])
                        & (chosen < held[0] + held[1])).sum())
        assert int(routes) == in_range == int(rows)
        total += in_range
    # half the experts are held: about half the routes, neither none nor all
    assert 0 < total < len(LAYOUT) * SEQ * K
    # without the collection nothing is sown and the output is the same
    plain = model.apply({"params": params}, tok[None])
    assert isinstance(plain, jax.Array)


def test_expert_module_keeps_its_name_and_scopes_in_the_program():
    """``tf_op`` carries the flax module and the four scopes, which is how
    the benchmark's ``moe_*_ms_per_step`` metrics find them."""
    ffn = ExpertFFN(N_EXPERTS, K, WIDTH, held=(2, 4))
    u = jnp.zeros((1, SEQ, E))
    params = ffn.init(jax.random.PRNGKey(0), u, u)
    text = jax.jit(lambda p, u: ffn.apply(p, u, u)).lower(
        params, u).as_text(debug_info=True)
    for scope in ("route", "dispatch", "experts", "combine"):
        assert f"ExpertFFN/{scope}/" in text or \
            f"ExpertFFN/checkpoint/{scope}/" in text, scope


# ------------------------------------------------ (e) the per-layer layout


def one_layer(layout, **kw):
    model = lm(layout=layout, **kw)
    return model, init(lm(layout=layout))


def test_a_layer_without_positions_ignores_the_rope_base():
    tok = tokens()
    for layout, moves in (((0,), False), ((1,), True)):
        model, params = one_layer(layout)
        a = model.apply({"params": params}, tok[None])
        b = model.clone(rope_base=1e4).apply({"params": params}, tok[None])
        assert (rel(a, b) > 1e-3) == moves, layout


def test_a_full_layer_sees_past_the_window():
    tok = tokens()
    full, params = one_layer((0,))
    banded = lm(layout=(0,)).clone(window_layout=(1,))
    no_window = lm(layout=(0,)).clone(window=None)
    a = full.apply({"params": params}, tok[None])[0]
    assert rel(a, no_window.apply({"params": params}, tok[None])[0]) < 1e-6
    b = banded.apply({"params": params}, tok[None])[0]
    # the first WINDOW positions see the same keys either way
    assert rel(a[:WINDOW], b[:WINDOW]) < 1e-5
    assert rel(a[WINDOW:], b[WINDOW:]) > 1e-3


# ------------------- (f) the benchmark's check says no to what it should


@pytest.fixture(scope="module")
def checked_cell():
    """The benchmark's cell at its rehearsal sizes, seeded weights and
    tokens, and what the program hands the check."""
    manifest = harness.load_manifest()
    cell = harness.resolve(manifest, "st-21b-ep4-t8k", rehearse=True)
    step = harness.load_module(manifest, "steps", cell.config["step"])
    k_init, k_data = jax.random.split(harness.seed_key(4294967301))
    params = step.draw_params(cell, k_init)
    tok = jax.random.randint(k_data, (1, cell.traffic["seq"]), 0,
                             cell.config["vocab_size"])
    return cell, step, params, tok, step.program_forward(cell, params, tok)


def test_check_accepts_the_program(checked_cell):
    cell, step, params, tok, got = checked_cell
    record = step.forward_check(cell, params, tok, got)
    assert record["ok"], record
    assert min(record["routing_agree"]) == 1.0
    assert record["routes_held"] == record["routes_chosen_in_range"]


@pytest.mark.parametrize("control,limit", [
    ({"round_router_to": jnp.bfloat16}, "router"),
    ({"round_experts_to": jnp.float8_e4m3fn}, "experts")])
def test_check_refuses_one_precision_below(checked_cell, control, limit):
    """The reference with the router's operands in bfloat16 (float32 is
    stated) or the experts' in float8 (bfloat16 is stated), handed to the
    check as a program's forward pass, is NOT correct, by that
    mechanism's own limit."""
    cell, step, params, tok, _ = checked_cell
    record = step.forward_check(
        cell, params, tok, step.control_forward(cell, params, tok, **control))
    assert not record["ok"], record
    assert max(record[f"{limit}_rel_err"]) > record[f"{limit}_rtol"]
    if limit == "experts":    # rounded experts leave the router alone
        assert max(record["router_rel_err"]) <= record["router_rtol"]


def test_check_refuses_a_dropped_route(checked_cell):
    cell, step, params, tok, got = checked_cell
    out = np.array(got["layers"][1]["experts_out"])
    token = int(np.abs(out).sum(-1).argmax())
    out[token] = 0                        # that token's routes never ran
    layers = list(got["layers"])
    layers[1] = {**layers[1], "experts_out": out}
    record = step.forward_check(cell, params, tok, {**got, "layers": layers})
    assert not record["ok"]
    assert record["experts_worst_token_err"][1] > 0.5


def test_one_dropped_route_of_thousands_shows_in_its_token_only():
    """Why the check holds the worst token beside the norm over all."""
    manifest = harness.load_manifest()
    step = harness.load_module(manifest, "steps", "lm_experts_fused_xent")
    rng = np.random.default_rng(0)
    want = rng.standard_normal((4608, 64)).astype(np.float32)
    got = want * (1 + 1e-3 * rng.standard_normal(want.shape))
    overall, worst = step.token_errors(got, want)
    assert overall < 2e-3 and worst < 5e-3
    got[1234] = 0
    overall, worst = step.token_errors(got, want)
    assert overall < 0.02 and worst > 0.8
