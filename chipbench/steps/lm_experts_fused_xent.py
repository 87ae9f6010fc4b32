"""``lm_fused_xent``'s train step for a ``TransformerLM`` whose feed-forward
is the held share of an expert layer (``ExpertFFN``): the same step
(``nn.data_parallel_step``, ``nn.synchronize_gradients``, the loss through
``fused_linear_cross_entropy``, the optimizer from the configuration) that
also hands back what the expert layers counted, and a check that knows the
model's reference.

**The check.**  Before the optimizer state exists, on the first
``check_tokens`` tokens of the first sequence (past the window, so the
banded and the full mask both bind), the program's forward pass (flash +
the expert layer + fused cross-entropy) is held to the plain reference's,
by the limits under ``tolerance`` in the configuration file.  End to end:

- ``loss_rtol``: the loss;
- ``prehead_rtol``: the final-norm activations the head reads,
  ``|program - reference| / |reference|`` over all of them.

End to end a bf16 rounding upstream moves a router logit enough to swap a
token's sixth and seventh expert, and a mean of 4607 losses hides what a
narrower product does to one layer.  So each layer's new mechanism is also
held to the reference ON THE PROGRAM'S OWN INPUTS, in EVERY layer and on
every token (flax's ``capture_intermediates`` gives what each norm and each
expert layer returned, the ``moe`` collection the router's logits and the
chosen experts):

- ``router_rtol``: the router's logits against the reference's float32
  product of the program's router input;
- ``routing_min_agree``: the share of tokens whose chosen experts are that
  product's top k;
- ``experts_rtol``: the expert layer's output against the reference's
  experts given the program's input and chosen experts, weighted by the
  reference's logits, in norm over all tokens;
- ``experts_token_rtol``: the same for the WORST single token, over the
  tokens' root-mean-square norm.  One dropped route is 1 / sqrt(routes) in
  the norm over all tokens, under any limit a bf16 product can meet; to its
  token it is the whole output;
- the counters: ``routes_held`` equals the routes the chosen experts send
  to the held range, and ``rows_computed`` is no less.

The forward pass under check is compiled with
``xla_allow_excess_precision`` off: XLA then keeps every rounding the
program states.  With the default a fusion may skip the bf16 rounding of
the residual sum before the norm, so the copy of the router's input that
the check captures and the one the router's product read differ by a bf16
rounding, which is what ``router_rtol`` exists to catch
(``tools/smallthinker_precision.py --excess-precision`` shows both).

Recorded beside them, and no limit: the share of all layers' top-k sets on
which the two END-TO-END passes agree.

``control_forward`` is what a program ONE PRECISION BELOW the file's would
hand the check (the reference with the router's or the experts' operands
rounded): ``forward_check`` has to say no to it
(``tools/smallthinker_precision.py`` on the chip at the published widths,
``tests/test_smallthinker.py`` at the rehearsal's).

**The counters.**  The check's go to the ``obs`` registry
(``parallel/expert.record_counters``), where ``readers/counter_ratio.py``
finds them.  Every step returns its own ``[layers, 2]`` (routes held, rows
computed) beside the loss; ``build``'s step keeps them on the device
(``STEP_COUNTS``) and ``counted`` fetches the last steps' after the window,
for ``readers/step_counts.py`` and the grouped products' roofline.
"""

import collections
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import torchmpi_tpu as mpi
from chipbench import harness
from torchmpi_tpu.ops.xent import fused_linear_cross_entropy
from torchmpi_tpu.parallel import expert

COUNTERS = ("routes_held", "rows_computed")
STRICT = {"xla_allow_excess_precision": False}
# one [layers, 2] device array a step of build's step, the newest kept
STEP_COUNTS = collections.deque(maxlen=64)


def draw_params(cell, key):
    """The model's parameters from ``key``: flax's draw, the embedding's
    rows at the configuration's ``embedding_std`` (flax's is 1 /
    sqrt(hidden); the file's ``assumed`` says why that will not do)."""
    twin = harness.build_model(cell).clone(attn_impl="local")
    p = twin.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    scale = cell.config["embedding_std"] * cell.config["hidden_size"] ** 0.5
    return {**p, "Embed_0": {"embedding": p["Embed_0"]["embedding"] * scale}}


def programs(cell, mesh):
    """``lm_fused_xent``'s programs with the parameters drawn as above and
    a step that returns, after the loss, what every expert layer counted:
    ``[layers, 2]`` int32, ``COUNTERS`` in order."""
    cfg = cell.config
    prog = harness.load_module(cell.manifest, "steps",
                               "lm_fused_xent").programs(cell, mesh)
    lm, tx = harness.build_model(cell), harness.build_optimizer(cell)

    def init_params(key):
        return draw_params(cell, key)

    def init(key):
        p = init_params(key)
        return p, tx.init(p)

    def counted_loss(p, tok):
        (h, head), sown = lm.apply({"params": p}, tok, return_prehead=True,
                                   mutable=["moe"])
        loss = fused_linear_cross_entropy(
            h[:, :-1].reshape(-1, h.shape[-1]).astype(jnp.bfloat16),
            head.astype(jnp.bfloat16), tok[:, 1:].reshape(-1)).mean()
        layers = [sown["moe"][f"Block_{i}"]["ExpertFFN_0"]
                  for i in range(cfg["num_hidden_layers"])]
        return loss, jnp.stack([jnp.stack([la[name][0] for name in COUNTERS])
                                for la in layers])

    def step(p, o, tok):
        (loss, counts), g = jax.value_and_grad(counted_loss,
                                               has_aux=True)(p, tok)
        g = mpi.nn.synchronize_gradients(g, mesh.axis_names)
        loss = mpi.collectives.allreduce_in_axis(loss, mesh.axis_names,
                                                 op="mean")
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss, counts

    prog.init_params, prog.init = init_params, init
    prog.step = mpi.nn.data_parallel_step(step, mesh=mesh, batch_argnums=(2,))
    return prog


def counted(steps):
    """Per layer, the mean of what the last ``steps`` steps of ``build``'s
    step counted: ``{"routes_held": [layers], "rows_computed": [layers]}``,
    or None before any step.  Fetches from the device: not for the
    window."""
    last = list(STEP_COUNTS)[-steps:]
    if not last:
        return None
    mean = np.stack([np.asarray(c) for c in last]).mean(0)
    return {name: mean[:, i].tolist() for i, name in enumerate(COUNTERS)}


def reference_kwargs(cfg, tokens):
    return dict(
        window_layout=cfg["sliding_window_layout"],
        rope_layout=cfg["rope_layout"], window=cfg["sliding_window_size"],
        rope_base=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        k=cfg["moe_num_active_primary_experts"],
        held=tuple(cfg["experts_held"]),
        token_block=min(cfg["reference"]["token_block"], tokens))


def rel_err(got, want):
    got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def token_errors(got, want):
    """[T, E] against [T, E] -> (the error in norm over all tokens, the
    worst single token's error over the tokens' root-mean-square norm)."""
    got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
    err = jnp.linalg.norm(got - want, axis=-1)
    size = jnp.linalg.norm(want, axis=-1)
    return (float(jnp.linalg.norm(err) / jnp.linalg.norm(size)),
            float(err.max() / jnp.sqrt(jnp.square(size).mean())))


def same_sets(a, b):
    """Per token: are the chosen experts the same set?  [..., T, k] ->
    [..., T]."""
    return (np.sort(np.asarray(a), -1) == np.sort(np.asarray(b), -1)).all(-1)


def routes_in_range(cfg, chosen):
    first, count = cfg["experts_held"]
    chosen = np.asarray(chosen)
    return int(((chosen >= first) & (chosen < first + count)).sum())


def program_forward(cell, params, tokens, compiler_options=STRICT):
    """The timed path's forward pass on ``tokens`` [1, T]: the loss, the
    final activations, and per layer what the router read and returned,
    what the expert layer read, chose, counted and returned."""
    lm = harness.build_model(cell)

    def program(p, tok):
        (h, head), kept = lm.apply(
            {"params": p}, tok, return_prehead=True,
            mutable=["moe", "intermediates"],
            capture_intermediates=lambda m, _: type(m).__name__ in (
                "RMSNorm", "ExpertFFN"))
        loss = fused_linear_cross_entropy(
            h[:, :-1].reshape(-1, h.shape[-1]).astype(jnp.bfloat16),
            head.astype(jnp.bfloat16), tok[:, 1:].reshape(-1)).mean()
        return loss, h[0], kept

    loss, prehead, kept = jax.jit(program).lower(params, tokens).compile(
        compiler_options=compiler_options)(params, tokens)
    layers = []
    for i in range(cell.config["num_hidden_layers"]):
        sown = kept["moe"][f"Block_{i}"]["ExpertFFN_0"]
        inter = {name: value["__call__"][0][0] for name, value in
                 kept["intermediates"][f"Block_{i}"].items()}
        layers.append({
            "router_in": inter["RMSNorm_0"], "experts_in": inter["RMSNorm_1"],
            "experts_out": inter["ExpertFFN_0"], "chosen": sown["experts"][0],
            "router_logits": sown["router_logits"][0],
            **{name: int(sown[name][0]) for name in COUNTERS}})
    return {"loss": float(loss), "prehead": prehead, "layers": layers,
            "moe": kept["moe"]}


def control_forward(cell, params, tokens, **rounding):
    """What a program one precision below the configuration's would hand
    the check: the reference's forward pass with ``round_router_to`` or
    ``round_experts_to`` set, in ``program_forward``'s form (the counters
    read what its routing did)."""
    cfg = cell.config
    ref = harness.load_module(cell.manifest, "reference",
                              cfg["reference"]["module"])
    loss, aux = jax.jit(lambda p, t: ref.loss(
        p, t, with_aux=True, **reference_kwargs(cfg, t.shape[0]),
        **rounding))(params, tokens[0])
    layers = [{**la, **dict.fromkeys(COUNTERS,
                                     routes_in_range(cfg, la["chosen"]))}
              for la in aux["layers"]]
    return {"loss": float(loss), "prehead": aux["prehead"], "layers": layers}


def reference_layers(cell, ref, params, got):
    """The reference's router and experts fed the PROGRAM's own inputs,
    layer by layer: (router logits [L, T, n_experts], experts' output
    [L, T, E])."""
    cfg = cell.config
    first = cfg["experts_held"][0]
    block = min(cfg["reference"]["token_block"], got[0]["chosen"].shape[0])

    @jax.jit
    def one(p, layer):
        r = ref.router_logits(layer["router_in"], p)
        # the program's choice weighted by the reference's logits, so that
        # a swapped expert is the routing limits' business alone
        mine = layer["chosen"]
        probs = jax.nn.softmax(jnp.take_along_axis(r, mine, 1), -1)
        return r, ref.experts_blocked(
            layer["experts_in"].astype(jnp.float32), probs, mine, p, first,
            block)

    keys = ("router_in", "experts_in", "chosen")
    outs = [one(params[f"Block_{i}"]["ExpertFFN_0"],
                {key: layer[key] for key in keys})
            for i, layer in enumerate(got)]
    return [r for r, _ in outs], [o for _, o in outs]


def forward_check(cell, params, tokens, got):
    """``got`` (``program_forward``'s, or a control's) against the
    reference's forward pass on ``tokens`` [1, T] -> the record of their
    comparison."""
    cfg, tol = cell.config, cell.config["tolerance"]
    ref = harness.load_module(cell.manifest, "reference",
                              cfg["reference"]["module"])
    ref_loss, aux = jax.jit(lambda p, t: ref.loss(
        p, t, with_aux=True, **reference_kwargs(cfg, t.shape[0])))(
            params, tokens[0])
    checked = harness.loss_check(
        f"forward pass on the first {tokens.shape[1]} tokens of the first "
        "sequence, flash + expert layer + fused xent vs plain reference",
        got["loss"], float(ref_loss), tol["loss_rtol"])
    layers = got["layers"]
    logits, outs = reference_layers(cell, ref, params, layers)
    k = cfg["moe_num_active_primary_experts"]
    mine = np.stack([np.asarray(la["chosen"]) for la in layers])
    errors = [token_errors(la["experts_out"], o)
              for la, o in zip(layers, outs)]
    routes = [la["routes_held"] for la in layers]
    rows = [la["rows_computed"] for la in layers]
    in_range = [routes_in_range(cfg, m) for m in mine]
    checked.update(
        prehead_rel_err=rel_err(got["prehead"], aux["prehead"]),
        prehead_rtol=tol["prehead_rtol"],
        router_rel_err=[rel_err(la["router_logits"], r)
                        for la, r in zip(layers, logits)],
        router_rtol=tol["router_rtol"],
        routing_agree=[float(same_sets(m, jax.lax.top_k(r, k)[1]).mean())
                       for m, r in zip(mine, logits)],
        routing_min_agree=tol["routing_min_agree"],
        experts_rel_err=[e for e, _ in errors],
        experts_rtol=tol["experts_rtol"],
        experts_worst_token_err=[w for _, w in errors],
        experts_token_rtol=tol["experts_token_rtol"],
        routes_held=routes, rows_computed=rows,
        routes_chosen_in_range=in_range,
        topk_sets_agreeing_end_to_end=float(
            same_sets(mine, aux["experts"]).mean()))
    checked["ok"] = bool(
        checked["ok"] and checked["prehead_rel_err"] <= tol["prehead_rtol"]
        and max(checked["router_rel_err"]) <= tol["router_rtol"]
        and min(checked["routing_agree"]) >= tol["routing_min_agree"]
        and max(checked["experts_rel_err"]) <= tol["experts_rtol"]
        and max(checked["experts_worst_token_err"])
        <= tol["experts_token_rtol"]
        and routes == in_range and all(c >= r for c, r in zip(rows, routes)))
    return checked


def build(cell, mesh, key):
    cfg = cell.config
    prog = programs(cell, mesh)
    k_init, k_data = jax.random.split(key)
    params = jax.jit(prog.init_params)(k_init)
    batches = jax.jit(prog.batches, out_shardings=NamedSharding(
        mesh, P(mesh.axis_names)))(k_data)

    # --- correctness, before the optimizer state takes its memory --------
    t_chk = min(cfg["reference"]["check_tokens"], cell.traffic["seq"])
    p1, tok1 = jax.device_put((params, batches[0][0]), jax.devices()[0])
    got = program_forward(cell, p1, tok1[:1, :t_chk])
    expert.record_counters(got["moe"])
    checked = forward_check(cell, p1, tok1[:1, :t_chk], got)
    del p1, tok1, got

    params = mpi.nn.synchronize_parameters(params, mesh=mesh, copy=False)
    opt = jax.jit(prog.init_opt,
                  out_shardings=NamedSharding(mesh, P()))(params)
    STEP_COUNTS.clear()

    def step(p, o, tok):
        p, o, loss, counts = prog.step(p, o, tok)
        STEP_COUNTS.append(counts)
        return p, o, loss

    return types.SimpleNamespace(
        step=step, state=(params, opt), batches=batches,
        check=lambda first_loss: checked,
        items_per_step=prog.items_per_step, sharded=batches[0][0],
        replicated=jax.tree.leaves(params)[0])
