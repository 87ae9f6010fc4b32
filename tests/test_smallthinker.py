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
from torchmpi_tpu.ops import moe  # noqa: E402
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
    def block(held=None):      # a windowed, rotated layer of lm()'s
        return Block(lm(held, layout=(1,)).layers()[0])

    x = jax.random.normal(jax.random.PRNGKey(3), (1, SEQ, E))
    whole = block().init(jax.random.PRNGKey(4), x)["params"]
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
        out = block((first, count)).apply(
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


# -------------------- (c') the live-row kernels against whole-buffer passes

MOVE_T, MOVE_BLOCK = 16, 8            # 48 routes, six blocks of eight rows
# case -> (live routes, whether they all go to ONE expert of the three held)
LIVE_CASES = {"none": (0, False), "one": (1, False),
              "one_short_of_a_block": (MOVE_BLOCK - 1, False),
              "a_block_exactly": (MOVE_BLOCK, False),
              "a_block_and_one": (MOVE_BLOCK + 1, False),
              "every_row": (MOVE_T * K, False),
              "one_expert_takes_all": (MOVE_T * K, True)}


def routing(case, seed=11):
    """Rank-major routes of which the case's number go to a held expert:
    ``order`` and ``n_live`` as ``held_experts`` builds them, and the
    inverse permutation the whole-buffer passes un-permute by."""
    n_live, one_expert = LIVE_CASES[case]
    rng = np.random.default_rng(seed)
    key = np.full(MOVE_T * K, 3)                    # 3: held elsewhere
    live = rng.permutation(MOVE_T * K)[:n_live]
    key[live] = 0 if one_expert else rng.integers(0, 3, n_live)
    order = np.argsort(key, kind="stable")
    return (jnp.asarray(order, jnp.int32),
            jnp.asarray(np.argsort(order), jnp.int32), jnp.int32(n_live))


def whole_buffer_dispatch(u, order, n_live):
    """The formulation the kernels replace: gather every row, mask."""
    rows = u[order % u.shape[0]]
    return jnp.where((jnp.arange(order.shape[0]) < n_live)[:, None], rows, 0)


def whole_buffer_combine(y, weight, inverse, n_live):
    """Mask, un-permute every row, the k weighted slices summed in order."""
    tokens = weight.shape[1]
    y = jnp.where((jnp.arange(y.shape[0]) < n_live)[:, None], y, 0)[inverse]
    weight = jnp.where(inverse.reshape(weight.shape) < n_live, weight, 0)
    return sum(y[j * tokens:(j + 1) * tokens].astype(jnp.float32)
               * weight[j][:, None] for j in range(weight.shape[0]))


def moved(dtype, seed=12):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (MOVE_T, E)).astype(dtype),
            jax.random.normal(ks[1], (MOVE_T * K, E)).astype(dtype),
            jax.nn.softmax(jax.random.normal(ks[2], (MOVE_T, K)), -1).T,
            jax.random.normal(ks[3], (MOVE_T, E)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_dispatch_writes_the_live_rows_of_the_sorted_buffer(case, dtype):
    order, _, n_live = routing(case)
    u = moved(dtype)[0]
    got = moe.rows_from_tokens(u, order, n_live, block=MOVE_BLOCK)
    want = whole_buffer_dispatch(u, order, n_live)
    assert got.dtype == dtype and got.shape == want.shape
    # a row is moved, not computed: the live prefix is the same bits
    assert np.array_equal(np.asarray(got[:int(n_live)], np.float32),
                          np.asarray(want[:int(n_live)], np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_combine_sums_the_live_rows_and_reads_no_other(case, dtype):
    order, inverse, n_live = routing(case)
    _, y, weight, _ = moved(dtype)
    want = whole_buffer_combine(y, weight, inverse, n_live)
    # NaN in every row that holds no route: nothing may read it
    y = y.at[int(n_live):].set(jnp.nan)
    got = moe.tokens_from_rows(y, order, n_live, MOVE_T,
                               weight=weight.reshape(-1), block=MOVE_BLOCK)
    assert got.dtype == jnp.float32
    if dtype == jnp.bfloat16:
        # the float32 buffer, rounded as it is read, is the bfloat16 one
        wide = moe.tokens_from_rows(
            y.astype(jnp.float32), order, n_live, MOVE_T,
            weight=weight.reshape(-1), round_to=dtype, block=MOVE_BLOCK)
        assert np.array_equal(np.asarray(wide), np.asarray(got))
    if int(n_live):
        assert rel(got, want) < 1e-6
    else:
        assert not np.asarray(got).any() and not np.asarray(want).any()
    # a token none of whose routes is live reads exactly zero
    dead = np.asarray((inverse.reshape(K, MOVE_T) >= n_live).all(0))
    assert not np.asarray(got)[dead].any()


@pytest.mark.parametrize("case", ["a_block_and_one", "every_row"])
def test_sums_too_large_to_be_resident_go_through_in_parts(case, monkeypatch):
    order, _, n_live = routing(case)
    _, y, weight, _ = moved(jnp.float32)

    def combine():
        return moe.tokens_from_rows(y, order, n_live, MOVE_T,
                                    weight=weight.reshape(-1),
                                    block=MOVE_BLOCK)

    assert moe.token_parts(MOVE_T, E) == 1
    whole = combine()
    monkeypatch.setattr(moe, "_RESIDENT_BYTES", MOVE_T * E * 4 // 2)
    assert moe.token_parts(MOVE_T, E) == 2
    assert np.array_equal(np.asarray(combine()), np.asarray(whole))


@pytest.mark.parametrize("case", ["one", "a_block_and_one", "every_row"])
def test_the_combines_transpose_rounds_as_the_casts_it_replaces(case):
    """bfloat16 rows against the float32 down product, as the benchmark's
    cell runs them: the scaled rows are rounded to bfloat16 and handed back
    in float32, the weights' cotangents use the product rounded likewise."""
    order, _, n_live = routing(case)
    n = int(n_live)
    g, y, weight, _ = moved(jnp.bfloat16)
    y = y.astype(jnp.float32) * 1.001          # no longer bfloat16 values
    dy, dots = moe.rows_from_tokens(g, order, n_live,
                                    weight=weight.reshape(-1), against=y,
                                    block=MOVE_BLOCK)
    assert dy.dtype == jnp.float32 and dots.shape == (MOVE_T * K,)
    rows = g[order % MOVE_T].astype(jnp.float32)
    want = (rows * weight.reshape(-1)[order][:, None]).astype(jnp.bfloat16)
    assert np.array_equal(np.asarray(dy[:n]),
                          np.asarray(want[:n].astype(jnp.float32)))
    rounded = y.astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(dots[:n]),
                               np.asarray((rows * rounded).sum(1)[:n]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_gradients_of_dispatch_and_combine_match_the_whole_buffer_passes(
        case, monkeypatch):
    """Through the layer's own custom_vjps, with blocks of eight rows so
    that the prefix ends inside, at the end of and past a block."""
    monkeypatch.setattr(moe, "_BLOCK", MOVE_BLOCK)
    order, inverse, n_live = routing(case)
    u, _, weight, cot = moved(jnp.float32)

    def between(rows):          # a row of the result from its own row only
        return jnp.tanh(rows) * 1.5 + rows

    def program(u, weight):
        rows = ep._dispatch(u, order, n_live)
        out = ep._combine(jnp.float32, between(rows), weight, order, n_live)
        return (out * cot).sum()

    def whole_buffer(u, weight):
        rows = whole_buffer_dispatch(u, order, n_live)
        return (whole_buffer_combine(between(rows), weight, inverse, n_live)
                * cot).sum()

    got, g_got = jax.value_and_grad(program, argnums=(0, 1))(u, weight)
    want, g_want = jax.value_and_grad(whole_buffer, argnums=(0, 1))(u, weight)
    assert abs(float(got) - float(want)) <= 1e-5 * max(abs(float(want)), 1e-6)
    for g, w in zip(g_got, g_want):
        if int(n_live):
            assert float(jnp.linalg.norm(w)) > 0 and rel(g, w) < 1e-5
        else:
            assert not np.asarray(g).any() and not np.asarray(w).any()


def test_a_row_type_the_kernels_cannot_move_is_refused():
    order, _, n_live = routing("one")
    with pytest.raises(TypeError, match="float32 or bfloat16"):
        moe.rows_from_tokens(jnp.zeros((MOVE_T, E), jnp.float16), order,
                             n_live)
    with pytest.raises(ValueError, match="divides the buffer"):
        moe.tokens_from_rows(jnp.zeros((MOVE_T * K, E)), order, n_live,
                             MOVE_T, block=7)


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
        (chosen,), (routes,), (rows,), (moved_rows,) = (
            layer["experts"], layer["routes_held"], layer["rows_computed"],
            layer["rows_moved"])
        assert chosen.shape == (SEQ, K)
        # the program chose the experts the reference chose
        assert np.array_equal(np.sort(chosen, -1),
                              np.sort(aux["experts"][i], -1))
        in_range = int(((chosen >= held[0])
                        & (chosen < held[0] + held[1])).sum())
        assert int(routes) == in_range == int(rows)
        # the dispatch wrote whole blocks: every live row and under one
        # block more
        block = moe.block_rows(SEQ * K)
        assert int(moved_rows) % block == 0
        assert in_range <= int(moved_rows) < in_range + block
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
