"""Expert-parallel MoE tests: the all-to-all dispatched layer equals a
per-token oracle applying the owning expert directly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

import torchmpi_tpu as mpi
from torchmpi_tpu.parallel import expert as ep

T, D, E_LOCAL = 16, 8, 2  # tokens/device, width, experts/device


def _setup(n_dev, seed=0):
    rng = np.random.RandomState(seed)
    E = n_dev * E_LOCAL
    gate_w = rng.randn(D, E).astype(np.float32)
    W = rng.randn(E, D, D).astype(np.float32) * 0.3  # one dense per expert
    X = rng.randn(n_dev, T, D).astype(np.float32)
    return gate_w, W, X


def _expert_fn(w_e, tokens):
    return jnp.tanh(tokens @ w_e)


def _oracle(gate_w, W, X, capacity_factor=2.0):
    """Per-source-device routing with per-(device, expert) capacity."""
    n_dev, T_, D_ = X.shape
    E = W.shape[0]
    capacity = max(1, int(capacity_factor * T_ / E))
    out = np.zeros_like(X)
    for d in range(n_dev):
        probs = np.asarray(jax.nn.softmax(jnp.asarray(X[d] @ gate_w), -1))
        expert_of = probs.argmax(-1)
        counts = {}
        for t in range(T_):
            e = int(expert_of[t])
            slot = counts.get(e, 0)
            counts[e] = slot + 1
            if slot < capacity:
                y = np.tanh(X[d, t] @ W[e]) * probs[t, e]
                out[d, t] = y
    return out


@pytest.mark.parametrize("capacity_factor", [2.0, 0.5])
def test_moe_matches_oracle(flat_runtime, capacity_factor):
    mesh = mpi.world_mesh()
    n_dev = 8
    gate_w, W, X = _setup(n_dev)
    expect = _oracle(gate_w, W, X, capacity_factor)

    def body(xd, gw, Wl):
        out = ep.moe_layer(xd[0], gw, _expert_fn, Wl,
                           ("dcn", "ici"), capacity_factor=capacity_factor)
        return out[None]

    spec_x = P(("dcn", "ici"))
    spec_W = P(("dcn", "ici"))
    got = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(spec_x, P(), spec_W),
        out_specs=spec_x, check_vma=False))(
        jax.device_put(X, NamedSharding(mesh, spec_x)),
        gate_w,
        jax.device_put(W, NamedSharding(mesh, spec_W)))
    np.testing.assert_allclose(np.asarray(got), expect, rtol=2e-5,
                               atol=2e-5)


def test_moe_grads_match_oracle(flat_runtime):
    """Gradients through dispatch (scatter-add), both all_to_alls, and the
    gate must equal the dense per-token oracle's gradients."""
    mesh = mpi.world_mesh()
    n_dev = 8
    gate_w, W, X = _setup(n_dev, seed=1)
    capacity = max(1, int(2.0 * T / (n_dev * E_LOCAL)))

    # Static validity mask per (device, token), from the fixed routing.
    valid = np.zeros((n_dev, T), bool)
    for d in range(n_dev):
        probs = np.asarray(jax.nn.softmax(jnp.asarray(X[d] @ gate_w), -1))
        eo = probs.argmax(-1)
        counts = {}
        for t in range(T):
            e = int(eo[t])
            s = counts.get(e, 0)
            counts[e] = s + 1
            valid[d, t] = s < capacity

    def oracle_loss(gw, Wfull):
        total = 0.0
        for d in range(n_dev):
            probs = jax.nn.softmax(jnp.asarray(X[d]) @ gw, -1)
            eo = jnp.argmax(probs, -1)
            gate = jnp.take_along_axis(probs, eo[:, None], axis=1)[:, 0]
            y = jax.vmap(lambda t, e: jnp.tanh(t @ Wfull[e]))(
                jnp.asarray(X[d]), eo)
            y = y * gate[:, None] * jnp.asarray(valid[d])[:, None]
            total = total + jnp.sum(y ** 2)
        return total

    g_gate_ref, g_W_ref = jax.grad(oracle_loss, argnums=(0, 1))(
        jnp.asarray(gate_w), jnp.asarray(W))

    def body(xd, gw, Wl):
        def loss(gw_, Wl_):
            out = ep.moe_layer(xd[0], gw_, _expert_fn, Wl_, ("dcn", "ici"))
            return jnp.sum(out ** 2)

        g1, g2 = jax.grad(loss, argnums=(0, 1))(gw, Wl)
        # gate grads are per-device partials of the global loss; sum them.
        from jax import lax
        return lax.psum(g1, ("dcn", "ici")), g2

    spec = P(("dcn", "ici"))
    g1, g2 = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(spec, P(), spec),
        out_specs=(P(), spec), check_vma=False))(
        jax.device_put(X, NamedSharding(mesh, spec)), gate_w,
        jax.device_put(W, NamedSharding(mesh, spec)))
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g_W_ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g_gate_ref),
                               rtol=2e-4, atol=2e-5)


def test_moe_transformer_matches_single_device(flat_runtime):
    """TransformerLM with an EP MoE MLP: the 8-way dispatched forward equals
    running the same global experts on each device's tokens locally."""
    from torchmpi_tpu.models import TransformerLM

    mesh = mpi.world_mesh()
    n_dev = 8
    Bt, Tt = 8, 8  # one batch row per device
    tokens = np.random.RandomState(0).randint(0, 64, size=(Bt, Tt)).astype(
        np.int32)

    moe_model = TransformerLM(vocab=64, embed=32, depth=1, num_heads=4,
                              head_dim=8, max_len=Tt, moe_axis=("dcn", "ici"),
                              moe_experts_per_device=1)
    # init inside shard_map (MoE slicing needs the axis in scope)
    spec = P(("dcn", "ici"))

    def init_fn(tok):
        return moe_model.init(jax.random.PRNGKey(0), tok)

    variables = jax.jit(shard_map(
        init_fn, mesh=mesh, in_specs=spec, out_specs=P(),
        check_vma=False))(
        jax.device_put(tokens, NamedSharding(mesh, spec)))

    def fwd(vs, tok):
        return moe_model.apply(vs, tok)

    got = jax.jit(shard_map(
        fwd, mesh=mesh, in_specs=(P(), spec), out_specs=spec,
        check_vma=False))(variables,
                          jax.device_put(tokens,
                                         NamedSharding(mesh, spec)))
    got = np.asarray(got)
    assert got.shape == (Bt, Tt, 64) and np.isfinite(got).all()

    # Oracle: same params, all 8 experts local (n_devices=1), applied to
    # each device's token row independently — identical routing, capacity,
    # and expert math, no cross-device exchange.
    oracle_model = TransformerLM(vocab=64, embed=32, depth=1, num_heads=4,
                                 head_dim=8, max_len=Tt, moe_axis="one",
                                 moe_experts_per_device=n_dev)
    from jax.sharding import Mesh as _Mesh
    one_mesh = _Mesh(np.asarray(jax.devices()[:1]), ("one",))

    for d in range(n_dev):
        ref = jax.jit(shard_map(
            lambda vs, tok: oracle_model.apply(vs, tok),
            mesh=one_mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False))(jax.device_get(variables),
                              tokens[d:d + 1])
        np.testing.assert_allclose(got[d:d + 1], np.asarray(ref),
                                   rtol=3e-4, atol=3e-4)


def _oracle_topk(gate_w, W, X, k, capacity_factor=2.0):
    """Per-source-device top-k routing oracle: routes fill capacity
    RANK-MAJOR (GShard priority — all rank-0 routes claim slots before
    any rank-1 route); combine weights renormalized over the selected
    experts."""
    n_dev, T_, D_ = X.shape
    E = W.shape[0]
    capacity = max(1, int(capacity_factor * T_ * k / E))
    out = np.zeros_like(X)
    for d in range(n_dev):
        probs = np.asarray(jax.nn.softmax(jnp.asarray(X[d] @ gate_w), -1))
        # match lax.top_k ordering (descending, ties by lower index)
        topk_e = np.asarray(
            jax.lax.top_k(jnp.asarray(probs), k)[1])
        counts = {}
        for j in range(k):
            for t in range(T_):
                sel_p = probs[t, topk_e[t]]
                wsum = max(sel_p.sum(), 1e-9)
                e = int(topk_e[t, j])
                slot = counts.get(e, 0)
                counts[e] = slot + 1
                if slot < capacity:
                    y = np.tanh(X[d, t] @ W[e])
                    out[d, t] += y * (sel_p[j] / wsum)
    return out


@pytest.mark.parametrize("capacity_factor", [2.0, 0.5])
def test_moe_top2_matches_oracle(flat_runtime, capacity_factor):
    mesh = mpi.world_mesh()
    n_dev = 8
    gate_w, W, X = _setup(n_dev, seed=3)
    expect = _oracle_topk(gate_w, W, X, k=2,
                          capacity_factor=capacity_factor)

    def body(xd, gw, Wl):
        out = ep.moe_layer(xd[0], gw, _expert_fn, Wl,
                           ("dcn", "ici"),
                           capacity_factor=capacity_factor, k=2)
        return out[None]

    spec_x = P(("dcn", "ici"))
    got = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(spec_x, P(), spec_x),
        out_specs=spec_x, check_vma=False))(
        jax.device_put(X, NamedSharding(mesh, spec_x)),
        gate_w,
        jax.device_put(W, NamedSharding(mesh, spec_x)))
    np.testing.assert_allclose(np.asarray(got), expect, rtol=2e-5,
                               atol=2e-5)


def test_moe_top2_grad_flows(flat_runtime):
    mesh = mpi.world_mesh()
    n_dev = 8
    gate_w, W, X = _setup(n_dev, seed=4)

    def body(xd, gw, Wl):
        out = ep.moe_layer(xd[0], gw, _expert_fn, Wl,
                           ("dcn", "ici"), k=2)
        from jax import lax as jlax
        return jlax.pmean(jnp.sum(out ** 2), ("dcn", "ici"))

    spec_x = P(("dcn", "ici"))

    def loss(X, gw, W):
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(spec_x, P(), spec_x),
            out_specs=P(), check_vma=False))(X, gw, W)

    g = jax.grad(loss, argnums=(1,))(
        jax.device_put(X, NamedSharding(mesh, spec_x)), gate_w,
        jax.device_put(W, NamedSharding(mesh, spec_x)))[0]
    assert np.isfinite(np.asarray(g)).all()
    assert float(np.abs(np.asarray(g)).sum()) > 0  # gate receives gradient


def test_moe_rejects_k_zero(flat_runtime):
    mesh = mpi.world_mesh()
    gate_w, W, X = _setup(8)

    def body(xd, gw, Wl):
        return ep.moe_layer(xd[0], gw, _expert_fn, Wl, ("dcn", "ici"),
                            k=0)[None]

    spec_x = P(("dcn", "ici"))
    with pytest.raises(ValueError, match="k >= 1"):
        jax.jit(shard_map(
            body, mesh=mesh, in_specs=(spec_x, P(), spec_x),
            out_specs=spec_x, check_vma=False))(
            jax.device_put(X, NamedSharding(mesh, spec_x)), gate_w,
            jax.device_put(W, NamedSharding(mesh, spec_x)))


def test_load_balance_loss_invariants():
    rng = np.random.RandomState(0)
    E = 8
    # Uniform router -> exactly 1.0.
    uniform = jnp.zeros((32, E), jnp.float32)
    expert_of = jnp.asarray(np.arange(32) % E)
    np.testing.assert_allclose(
        float(ep.load_balance_loss(uniform, expert_of, E)), 1.0, rtol=1e-6)
    # Collapsed routing (all tokens to expert 0, peaked probs) >> balanced.
    peaked = jnp.asarray(np.where(np.arange(E) == 0, 10.0, 0.0)[None]
                         .repeat(32, 0).astype(np.float32))
    collapsed = float(ep.load_balance_loss(
        peaked, jnp.zeros((32,), jnp.int32), E))
    assert collapsed > 4.0  # ~E when fully collapsed
    # [T, k] route shape accepted.
    two = jnp.asarray(rng.randint(0, E, size=(32, 2)))
    v = float(ep.load_balance_loss(uniform, two, E))
    assert np.isfinite(v)


def test_moe_layer_return_aux(flat_runtime):
    mesh = mpi.world_mesh()
    gate_w, W, X = _setup(8, seed=6)

    def body(xd, gw, Wl):
        out, aux = ep.moe_layer(xd[0], gw, _expert_fn, Wl, ("dcn", "ici"),
                                k=2, return_aux=True)
        return out[None], aux[None]

    spec_x = P(("dcn", "ici"))
    out, aux = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(spec_x, P(), spec_x),
        out_specs=(spec_x, P(("dcn", "ici"))), check_vma=False))(
        jax.device_put(X, NamedSharding(mesh, spec_x)), gate_w,
        jax.device_put(W, NamedSharding(mesh, spec_x)))
    aux = np.asarray(aux)
    assert aux.shape == (8,) and np.isfinite(aux).all() and (aux > 0).all()


# ---------------------------------------------------------------------------
# The held experts of a decode call, each touched expert read once
# (ops/moe.decode_experts, chosen by parallel/expert.experts_read_once): in
# interpret mode here, equal to the grouped path within float32
# accumulation's reordering.
# ---------------------------------------------------------------------------


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


# name: (rows, experts routed over, k, held first, held count, gated, act,
#        an edit of the router's logits that makes the case)
READ_ONCE_CASES = {
    "silu_all_held": (21, 8, 2, 0, 8, True, jax.nn.silu, None),
    "relu2_held_range": (21, 16, 3, 4, 8, False, _relu2, None),
    "swiglu_held_share": (21, 16, 2, 8, 4, True, jax.nn.silu, None),
    # held expert 2 (global 6) chosen by no row
    "expert_no_row_chose": (21, 16, 2, 4, 8, True, jax.nn.silu,
                            ("none", 6)),
    # held expert 1 (global 5) chosen by every row
    "expert_every_row_chose": (21, 16, 2, 4, 8, True, jax.nn.silu,
                               ("every", 5)),
    # row 0's routes all leave the held range
    "row_routes_all_leave": (21, 16, 3, 4, 8, False, _relu2, ("leave", 0)),
    "one_row": (1, 16, 3, 4, 8, True, jax.nn.silu, None),
    "68_rows": (68, 16, 4, 4, 8, False, _relu2, None),
    # the width in three tiles of 128: the down product summed over them
    "width_in_tiles": (21, 8, 2, 0, 8, True, jax.nn.silu, ("tiles", 128)),
}


@pytest.mark.parametrize("case", list(READ_ONCE_CASES))
def test_read_once_equals_the_grouped_path(case, monkeypatch):
    from torchmpi_tpu.ops import moe

    N, E, k, first, count, gated, act, edit = READ_ONCE_CASES[case]
    D, F = 32, 384 if edit and edit[0] == "tiles" else 48
    keys = jax.random.split(jax.random.PRNGKey(41), 5)
    u = jax.random.normal(keys[0], (N, D), jnp.float32)
    logits = jax.random.normal(keys[1], (N, E), jnp.float32)
    w_gate = (jax.random.normal(keys[2], (count, D, F)) / D ** 0.5
              if gated else None)
    w_up = jax.random.normal(keys[3], (count, D, F)) / D ** 0.5
    w_down = jax.random.normal(keys[4], (count, F, D)) / F ** 0.5
    if edit and edit[0] == "none":
        logits = logits.at[:, edit[1]].add(-20.0)
    elif edit and edit[0] == "every":
        logits = logits.at[:, edit[1]].add(20.0)
    elif edit and edit[0] == "leave":
        logits = logits.at[edit[1], first:first + count].add(-20.0)
    elif edit and edit[0] == "tiles":
        monkeypatch.setattr(moe, "_EXPERT_STEP_BYTES",
                            3 * D * edit[1] * 4)
        assert moe.expert_tile(F, D, 3, 4) == edit[1]

    want, want_stats = ep.held_experts(u, logits, k, first, w_gate, w_up,
                                       w_down, act=act)
    got, stats = ep.held_experts(u, logits, k, first, w_gate, w_up, w_down,
                                 act=act, read_once=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    experts = np.asarray(stats["experts"])
    np.testing.assert_array_equal(experts, np.asarray(want_stats["experts"]))
    held = (experts >= first) & (experts < first + count)
    touched = np.unique(experts[held]).size
    assert int(stats["routes_held"]) == int(want_stats["routes_held"]) == (
        held.sum())
    assert int(stats["rows_computed"]) == touched * N
    assert int(stats["rows_moved"]) == 0
    if edit and edit[0] == "none":
        assert edit[1] not in experts and 0 < touched < count
    elif edit and edit[0] == "every":
        assert (experts == edit[1]).any(axis=1).all()
    elif edit and edit[0] == "leave":
        assert not held[edit[1]].any() and held.any()
        assert not np.asarray(got)[edit[1]].any()


def test_read_once_rounds_each_experts_result_to_the_rows_type():
    # bfloat16 rows and weights, as on the chip: each expert's down product
    # is rounded to bfloat16 before it is weighted, the weighted sum is
    # float32 and the result bfloat16, as the grouped path's combine does;
    # the two differ by the rounding of sums taken in another order only
    N, E, k, D, F = 21, 8, 2, 64, 128
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    bf = jnp.bfloat16
    u = jax.random.normal(keys[0], (N, D)).astype(bf)
    logits = jax.random.normal(keys[1], (N, E), jnp.float32)
    w = [(jax.random.normal(kk, s) / s[1] ** 0.5).astype(bf)
         for kk, s in zip(keys[2:], [(E, D, F), (E, D, F), (E, F, D)])]
    want, _ = ep.held_experts(u, logits, k, 0, *w, act=jax.nn.silu)
    got, _ = ep.held_experts(u, logits, k, 0, *w, act=jax.nn.silu,
                             read_once=True)
    assert got.dtype == want.dtype == bf
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # within two bfloat16 steps of the result's magnitude
    np.testing.assert_allclose(got, want, rtol=2 ** -7,
                               atol=2 ** -7 * np.abs(want).max())
    assert np.mean(got == want) > 0.9


def test_touched_experts_compacts_the_held_ones_some_row_chose():
    experts = jnp.asarray([[5, 1], [9, 7], [5, 12]], jnp.int32)
    ids, n = ep.touched_experts(experts, 4, 6)        # held [4, 10)
    assert int(n) == 3
    assert np.asarray(ids).tolist() == [1, 3, 5, 5, 5, 5]
    ids, n = ep.touched_experts(experts, 20, 4)       # none held
    assert int(n) == 0 and np.asarray(ids).tolist() == [0, 0, 0, 0]


# (shape the expert layer sees, platform) -> the rule's answer
READ_ONCE_RULE = {
    "decode_on_the_chip": ((21, 1, 2048), "tpu", True),
    "decode_68_slots": ((68, 1, 4096), "tpu", True),
    "decode_at_the_ridge": ((240, 1, 2048), "tpu", True),
    "decode_past_the_ridge": ((241, 1, 2048), "tpu", False),
    "prefill": ((1, 64, 2048), "tpu", False),
    "verify": ((21, 5, 2048), "tpu", False),
    "train": ((1, 8192, 2560), "tpu", False),
    "decode_on_the_cpu": ((21, 1, 2048), "cpu", False),
}


@pytest.mark.parametrize("case", list(READ_ONCE_RULE))
def test_read_once_rule(case):
    shape, platform, want = READ_ONCE_RULE[case]
    assert ep.experts_read_once(shape, platform) is want


def test_read_once_rule_bound_is_the_chips_ridge():
    # 197 TFLOP/s over 819 GB/s: a bfloat16 weight's byte takes one
    # operation a row
    assert ep.READ_ONCE_ROWS == 240
    # the platform the kernels of the library are compiled for: here the
    # interpreter's, so the grouped path
    assert not ep.experts_read_once((21, 1, 2048))
