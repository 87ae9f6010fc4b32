#!/usr/bin/env python
"""The quickest proof that torchmpi_tpu's main paths still start on a TPU.

Drives each main path once through the entry points a user would call, at
the full width of a model the repo supports (depth cut where said), with
random weights and data made from ``--seed``, and checks what comes out
against a plain reference that uses no ``torchmpi_tpu`` wrapper.

    python chip_smoke.py                 # one chip: the default phases
    python chip_smoke.py --chips 4       # four chips: the cross-chip phases only
    python chip_smoke.py --rehearse [--chips 4]
                                         # same phases, tiny widths, forced CPU

Default phases (one chip): ``init`` (1x1 world mesh + eager collectives),
``resnet50_dp`` (BASELINE config 3: ResNet-50 bf16, batch 128, 224x224
through ``recipes.make_bn_dp_train_step``), ``lm_flash_xent`` (bench.py
stage B2's flagship LM step: flash attention + fused linear+xent),
``serving`` (``serving.Server`` against offline ``generate``), ``downpour``
(BASELINE config 4 in small: ``csrc/ps.cpp`` built by
``utils/native.load_native``).  ``--chips 4`` runs ``collectives`` (every
verb, sync and async, per backend, against NumPy), ``resnet50_dp4`` (the
4-chip DP step against four shards run one after another on one device)
and ``serving4`` (``Server(replicas=4)`` on four devices), and nothing else.

One JSON object per phase goes to stdout (phase, seconds, first-call and
steady seconds apart, peak device bytes, what was checked); progress goes
to stderr.  The LAST stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``,
printed only when every phase passed.  The run fails (non-zero exit, no
``"ok"``) when jax finds no TPU (unless ``--rehearse``), when the device
count is not ``--chips``, when a phase raises or a comparison misses.  One
process holds the chip: nothing here starts a process that touches jax.
"""

import argparse
import faulthandler
import json
import math
import os
import re
import sys
import time
import types

DEFAULT_PHASES = ("init", "resnet50_dp", "lm_flash_xent", "serving",
                  "downpour")
FOUR_CHIP_PHASES = ("collectives", "resnet50_dp4", "serving4")

# Stated tolerances.  bf16 carries 8 mantissa bits (2^-9 ~ 2e-3 relative
# rounding per op); the losses are means over thousands of terms.
RESNET_LOSS_RTOL = 2e-2     # recipe step vs plain jit(value_and_grad)
RESNET_NORM_RTOL = 2e-3     # updated-parameter l2 norm, 4 chips vs by hand
FLASH_KERNEL_ATOL = 3.2e-2  # flash vs dense attention, elementwise: two
#                             bf16 ulps of an output in [2, 4)
FLASH_MODEL_ATOL = 1.25e-1  # pre-head activations after 8 bf16 layers,
#                             worst element: four bf16 ulps of a value in
#                             [4, 8) (measured 0.078) ...
FLASH_MODEL_MEAN_ATOL = 1e-2  # ... and their mean: 1% of unit scale
XENT_TOKEN_ATOL = 5e-3      # fused vs logsumexp loss, per token
LM_LOSS_RTOL = 1e-2         # flash+fused loss vs local+logsumexp loss
SERVING_LOGIT_ATOL = 5e-2   # a served token vs the reference's best logit
#                             (unit-scale logits; measured worst 0.009)


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="Run torchmpi_tpu's main paths once on the chip.")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: the default phases; 4: only the cross-chip "
                        "phases (needs exactly four devices)")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of every weight and every datum")
    p.add_argument("--rehearse", action="store_true",
                   help="same phases at tiny widths on --chips virtual "
                        "CPU devices (Pallas kernels interpreted)")
    p.add_argument("--phases", default=None,
                   help="comma list: run only these phases of the mode")
    return p.parse_args(argv)


def log(*a):
    print(time.strftime("[%H:%M:%S]"), *a, file=sys.stderr, flush=True)


def check(cond, msg):
    """A comparison that missed fails the run (not ``assert``: -O)."""
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def deadline(seconds):
    """No step may hang the chip: past ``seconds`` the process dumps every
    thread's stack and exits non-zero.  Re-armed before each step that
    compiles or talks across chips; the numbers are several times what
    the compiles took for a described chip."""
    faulthandler.dump_traceback_later(seconds, exit=True)


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self.t0


def widths(rehearse):
    """Every size the phases use: the real ones, or the rehearsal's."""
    if rehearse:
        return dict(
            rn_batch=8, rn_image=32, rn_steps=3,
            # ResNet-50's stem and bottleneck blocks: two stages of one
            # block, 8 filters.
            rn_cut=dict(stage_sizes=(1, 1), num_filters=8),
            lm=dict(vocab=256, embed=64, depth=2, num_heads=4,
                    head_dim=16, num_kv_heads=2, max_len=64, window=32),
            lm_batch=2, lm_steps=2,
            srv=dict(vocab=128, embed=64, depth=2, num_heads=4,
                     head_dim=16, num_kv_heads=2, max_len=64, window=16),
            srv_prompts=(8, 24), srv_answers=(6, 20), srv4_depth=2,
            ps_image=64, ps_batch=2, ps_rounds=2,
            coll_small=1024, coll_large=32 * 1024)
    flagship = dict(vocab=32768, embed=2048, depth=8, num_heads=16,
                    head_dim=128, num_kv_heads=4, max_len=2048, window=1024)
    return dict(
        rn_batch=128, rn_image=224, rn_steps=5, rn_cut={},
        lm=flagship, lm_batch=4, lm_steps=3,
        # The long prompt is longer than the window, so the sliding
        # window binds during prefill and decode.
        srv=flagship, srv_prompts=(32, 1100), srv_answers=(8, 24),
        srv4_depth=2,
        ps_image=64, ps_batch=32, ps_rounds=3,
        coll_small=1024,                 # 4 KiB f32 per rank
        coll_large=16 * 1024 * 1024)     # 64 MiB f32 per rank


# ---------------------------------------------------------------------------
# helpers shared by the phases (jax is imported by main() before any runs)
# ---------------------------------------------------------------------------


def peak_bytes(dev):
    stats = dev.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def first_and_steady(call, n):
    """Run ``call()`` n times, blocking on each result.  Returns the
    results, the first call's seconds (compile included) and the median
    of the later calls' seconds."""
    import jax
    import numpy as np

    outs, secs = [], []
    for _ in range(n):
        with Timer() as t:
            out = jax.block_until_ready(call())
        outs.append(out)
        secs.append(t.s)
    steady = float(np.median(secs[1:])) if n > 1 else None
    return outs, secs[0], steady


def resnet_setup(seed, batch, image, cut):
    """ResNet-50 bf16 (``cut`` shrinks it for the rehearsal), SGD+momentum,
    one synthetic batch, and the plain loss the recipe's step must agree
    with."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchmpi_tpu.models import ResNet50

    model = ResNet50(dtype=jnp.bfloat16).clone(**cut)
    # jit: one compile, not one per initializer op.
    variables = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, image, image, 3)), train=False))(
            jax.random.PRNGKey(seed))
    tx = optax.sgd(0.1, momentum=0.9)
    rng = np.random.RandomState(seed)
    images = rng.rand(batch, image, image, 3).astype(np.float32)
    labels = rng.randint(0, 1000, size=batch).astype(np.int32)

    def loss_fn(params, batch_stats, images, labels):
        logits, updated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, updated["batch_stats"]

    return (model, tx, variables["params"], variables["batch_stats"],
            images, labels, loss_fn)


def lm_build(spec, attn_impl):
    import jax.numpy as jnp

    from torchmpi_tpu.models import TransformerLM

    return TransformerLM(pos_emb="rope", dtype=jnp.bfloat16,
                         attn_impl=attn_impl, **spec)


def lm_params(model, seed):
    """Init through the "local"-attention twin: attention impls share one
    parameter tree, and init then traces no Pallas kernel."""
    import jax
    import jax.numpy as jnp

    twin = model.clone(attn_impl="local")
    return jax.jit(lambda key: twin.init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])(
            jax.random.PRNGKey(seed))


def serving_requests(w, seed, long_prompts=True):
    """Eight requests of mixed prompt and answer lengths."""
    import numpy as np

    from torchmpi_tpu import serving

    rng = np.random.RandomState(seed + 7)
    short, long_ = w["srv_prompts"]
    if not long_prompts:
        long_ = short
    few, many = w["srv_answers"]
    plens = [short, long_, short, short, long_, short, short, short]
    news = [few, many, many, few, few, many, few, many]
    return [serving.Request(
        f"r{i}", rng.randint(0, w["srv"]["vocab"], size=plens[i])
        .astype(np.int32), max_new=news[i], arrival_s=0.5 * i)
        for i in range(8)]


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_init(ctx):
    import numpy as np

    import torchmpi_tpu as mpi
    from torchmpi_tpu.ops import ring

    mesh = mpi.init()
    check(mesh.devices.shape == (1, 1), f"world mesh {mesh.devices.shape}")
    check((mpi.rank(), mpi.size(), mpi.device_count()) == (0, 1, 1),
          "rank/size/device_count")
    x = np.random.RandomState(ctx.seed).randn(1, 1024).astype(np.float32)
    check(np.array_equal(np.asarray(mpi.allreduce(x)), x), "allreduce")
    check(np.array_equal(np.asarray(mpi.broadcast(x)), x), "broadcast")
    check(np.array_equal(np.asarray(mpi.allgather(x)), x[None]),
          "allgather")
    # On the chip the kernels must lower for real; the interpreter is the
    # rehearsal's, and only the rehearsal's.
    interp = ring._interpret_mode()
    check(bool(interp) == ctx.rehearse, f"ring interpret mode {interp!r}")
    return {"mesh": list(mesh.devices.shape), "rank": 0, "size": 1,
            "device_count": 1, "round_trips": ["allreduce", "broadcast",
                                               "allgather"],
            "ring_interpret": bool(interp)}


def phase_resnet50_dp(ctx):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi

    w = ctx.w
    mesh = mpi.init()   # idempotent: the world mesh of phase init
    (model, tx, params, batch_stats, images, labels,
     loss_fn) = resnet_setup(ctx.seed, w["rn_batch"], w["rn_image"],
                            w["rn_cut"])

    # The reference: same model and loss, no torchmpi_tpu wrapper.
    (ref_loss, _), _ = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, batch_stats, images, labels)
    ref_loss = float(ref_loss)

    dp_step = mpi.recipes.make_bn_dp_train_step(model, tx, mesh=mesh)
    state = mpi.recipes.replicate_bn_state(params, tx.init(params),
                                           batch_stats, mesh=mesh)
    shard = NamedSharding(mesh, P(mesh.axis_names))
    im, lb = jax.device_put(images, shard), jax.device_put(labels, shard)
    # Does this backend's cost model count the step?  (bench.py's MFU.)
    cost = dp_step.jitted.lower(*state, im, lb).cost_analysis()
    box = {"state": state}

    def call():
        *box["state"], loss = dp_step(*box["state"], im, lb)
        return loss

    losses, first_s, steady_s = first_and_steady(call, w["rn_steps"])
    losses = [float(x) for x in losses]
    check(all(map(math.isfinite, losses)), f"losses {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(abs(losses[0] - ref_loss) <= RESNET_LOSS_RTOL * abs(ref_loss),
          f"first loss {losses[0]} vs plain jit {ref_loss}")
    return {"first_call_s": first_s, "step_s": steady_s,
            "batch": w["rn_batch"], "image": w["rn_image"],
            "losses": losses, "ref_first_loss": ref_loss,
            "loss_rtol": RESNET_LOSS_RTOL,
            "lowered_cost_analysis_flops":
                float(cost.get("flops", 0.0)) if cost else None}


def phase_lm_flash_xent(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu.ops.flash import flash_attention
    from torchmpi_tpu.ops.xent import fused_linear_cross_entropy
    from torchmpi_tpu.parallel.sequence import reference_attention

    w = ctx.w
    spec, B = w["lm"], w["lm_batch"]
    T, E = spec["max_len"], spec["embed"]
    mesh = mpi.init()   # idempotent: the world mesh of phase init
    lm = lm_build(spec, "flash")
    params = lm_params(lm, ctx.seed)
    rng = np.random.RandomState(ctx.seed + 3)
    tok = rng.randint(0, spec["vocab"], size=(B, T)).astype(np.int32)
    tx = optax.sgd(0.02)

    def prehead(model, p, tok):
        h, head = model.apply({"params": p}, tok, return_prehead=True)
        return (h[:, :-1].reshape(-1, E).astype(jnp.bfloat16),
                head.astype(jnp.bfloat16))

    def plain_xent(h, head, lab):
        logits = jnp.dot(h, head, preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, lab[:, None], axis=1)[:, 0]

    def step(p, o, tok):
        def loss_fn(p):
            h, head = prehead(lm, p, tok)
            return fused_linear_cross_entropy(
                h, head, tok[:, 1:].reshape(-1)).mean()

        loss, g = jax.value_and_grad(loss_fn)(p)
        g = mpi.nn.synchronize_gradients(g, mesh.axis_names)
        loss = mpi.collectives.allreduce_in_axis(loss, mesh.axis_names,
                                                 op="mean")
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    # --- comparisons at the same shapes, before the step donates ----------
    lab = jnp.asarray(tok[:, 1:].reshape(-1))
    tok_d = jnp.asarray(tok)
    h_flash, head = jax.jit(lambda p, t: prehead(lm, p, t))(params, tok_d)
    h_local, _ = jax.jit(lambda p, t: prehead(
        lm.clone(attn_impl="local"), p, t))(params, tok_d)
    diff = jnp.abs(h_flash.astype(jnp.float32) - h_local.astype(jnp.float32))
    model_err, model_mean_err = float(diff.max()), float(diff.mean())
    del diff
    fused = jax.jit(fused_linear_cross_entropy)(h_flash, head, lab)
    plain = jax.jit(plain_xent)(h_flash, head, lab)
    xent_err = float(jnp.max(jnp.abs(fused - plain)))
    ref_loss = float(jax.jit(plain_xent)(h_local, head, lab).mean())
    # The kernel alone, at the model's attention shapes.
    H, Hkv, D = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.bfloat16)
    kw = dict(causal=True, window=spec["window"])
    kern_err = float(jnp.max(jnp.abs(
        jax.jit(lambda q, k, v: flash_attention(q, k, v, **kw))(q, k, v)
        .astype(jnp.float32)
        - jax.jit(lambda q, k, v: reference_attention(q, k, v, **kw))(
            q, k, v).astype(jnp.float32))))
    del h_flash, h_local, fused, plain, q, k, v
    check(kern_err <= FLASH_KERNEL_ATOL, f"flash kernel err {kern_err}")
    check(model_err <= FLASH_MODEL_ATOL, f"flash model err {model_err}")
    check(model_mean_err <= FLASH_MODEL_MEAN_ATOL,
          f"flash model mean err {model_mean_err}")
    check(xent_err <= XENT_TOKEN_ATOL, f"fused xent err {xent_err}")

    # --- the train step through the library's wrappers --------------------
    lm_step = mpi.nn.data_parallel_step(step, mesh=mesh, batch_argnums=(2,))
    p = mpi.nn.synchronize_parameters(params, mesh=mesh)
    o = mpi.nn.synchronize_parameters(tx.init(params), mesh=mesh)
    del params
    tok_s = jax.device_put(tok, NamedSharding(mesh, P(mesh.axis_names)))
    with Timer() as t_compile:
        compiled = lm_step.jitted.lower(p, o, tok_s).compile()
    n_custom = compiled.as_text().count("tpu_custom_call")
    cost = compiled.cost_analysis()
    if ctx.on_tpu:
        # flash's forward and its one backward per layer are fused by
        # XLA into repeated calls; xent adds its forward and its one
        # backward.  At least one of each kind:
        check(n_custom >= 4, f"{n_custom} tpu_custom_call in the step")
    box = {"s": (p, o)}

    def call():
        *box["s"], loss = lm_step(*box["s"], tok_s)
        return loss

    losses, first_s, steady_s = first_and_steady(call, w["lm_steps"])
    losses = [float(x) for x in losses]
    check(all(map(math.isfinite, losses)), f"losses {losses}")
    check(abs(losses[0] - ref_loss) <= LM_LOSS_RTOL * abs(ref_loss),
          f"first loss {losses[0]} vs local+logsumexp {ref_loss}")
    return {"lower_and_compile_s": t_compile.s, "first_call_s": first_s,
            "step_s": steady_s, "batch": B, "seq": T, "embed": E,
            "depth": spec["depth"], "tpu_custom_calls": n_custom,
            "losses": losses, "ref_first_loss": ref_loss,
            "compiled_cost_analysis_flops":
                float(cost.get("flops", 0.0)) if cost else None,
            "flash_kernel_err": kern_err, "flash_model_err": model_err,
            "flash_model_mean_err": model_mean_err,
            "xent_token_err": xent_err,
            "tolerances": {"kernel": FLASH_KERNEL_ATOL,
                           "model": FLASH_MODEL_ATOL,
                           "model_mean": FLASH_MODEL_MEAN_ATOL,
                           "xent": XENT_TOKEN_ATOL, "loss": LM_LOSS_RTOL}}


def offline_tokens(model, params, req):
    import numpy as np

    from torchmpi_tpu.models import generate

    out = np.asarray(generate(model, params, req.prompt[None],
                              steps=req.max_new))
    return out[0, req.prompt.size:].tolist()


def argmax_deficits(model, params, reqs, alts):
    """Teacher-forced plain reference (dense attention, no cache, one
    forward over prompt + served tokens).  Per request: how far below the
    reference's best logit each served token sits, and the same for the
    token ``alts`` holds at that position (the two share the prefix up to
    their first difference)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    T = max(r.prompt.size + len(r.tokens) for r in reqs)
    seq = np.zeros((len(reqs), T), np.int32)   # right-padded: causal
    alt = np.zeros((len(reqs), T), np.int32)
    for i, (r, a) in enumerate(zip(reqs, alts)):
        n = r.prompt.size
        seq[i, :n] = alt[i, :n] = r.prompt
        seq[i, n:n + len(r.tokens)] = r.tokens
        alt[i, n:n + len(a)] = a

    @jax.jit
    def deficits(p, seq, alt):
        logits = model.clone(attn_impl="local").apply(
            {"params": p}, seq).astype(jnp.float32)[:, :-1]
        best = logits.max(-1)
        pick = lambda t: jnp.take_along_axis(  # noqa: E731
            logits, t[:, 1:, None], axis=-1)[..., 0]
        return best - pick(seq), best - pick(alt)

    d_seq, d_alt = (np.asarray(d) for d in deficits(params, seq, alt))
    out = []
    for i, r in enumerate(reqs):
        lo = r.prompt.size - 1
        out.append((d_seq[i, lo:lo + len(r.tokens)],
                    d_alt[i, lo:lo + len(r.tokens)]))
    return out


def phase_serving(ctx):
    from torchmpi_tpu import serving

    w = ctx.w
    model = lm_build(w["srv"], "flash")
    params = lm_params(model, ctx.seed)

    def serve(one_at_a_time):
        reqs = serving_requests(w, ctx.seed)
        if one_at_a_time:
            for i, r in enumerate(reqs):
                r.arrival_s = 1e6 * i
        server = serving.Server(model, params, replicas=1, slots=4)
        with Timer() as t:
            done = server.run_trace(reqs, unit_seconds=1.0)
        check(len(done) == len(reqs), f"{len(done)} of {len(reqs)} done")
        return reqs, server.last_stats["ticks"], t.s

    reqs, ticks, serve_s = serve(False)
    # (1) Co-scheduling changes no token: exact, on any platform.
    alone, _, alone_s = serve(True)
    for a, b in zip(reqs, alone):
        check(a.tokens == b.tokens, f"{a.rid}: served together {a.tokens}"
              f" vs alone {b.tokens}")
    # (2) Offline generate, the property examples/continuous_serving.py
    # asserts.  It is another compiled program ([1, 1] scan step vs the
    # pool's [S, 1] step): on the chip its bf16 logits differ by < 0.01,
    # so tokens may part at a near-tie of the argmax — and only there.
    with Timer() as t_off:
        offline = [offline_tokens(model, params, r) for r in reqs]
    equal = [r.tokens == off for r, off in zip(reqs, offline)]
    # (3) Every served token is the plain reference's argmax for its own
    # prefix, up to SERVING_LOGIT_ATOL; where offline generate parts from
    # the served stream, its token at that step is too (a tie, not a bug).
    with Timer() as t_ref:
        deficits = argmax_deficits(model, params, reqs, offline)
    worst, ties = 0.0, []
    for r, off, (d_srv, d_off) in zip(reqs, offline, deficits):
        worst = max(worst, float(d_srv.max()))
        check(d_srv.max() <= SERVING_LOGIT_ATOL,
              f"{r.rid}: a served token sits {d_srv.max()} below the "
              "reference's best logit")
        first = next((k for k, (a, b) in enumerate(zip(r.tokens, off))
                      if a != b), None)
        if first is not None:
            check(d_off[first] <= SERVING_LOGIT_ATOL,
                  f"{r.rid}: offline generate parts from the served stream"
                  f" at token {first} by {d_off[first]} logits: not a tie")
            ties.append({"rid": r.rid, "token": first,
                         "served_deficit": float(d_srv[first]),
                         "offline_deficit": float(d_off[first])})
    if not ctx.on_tpu:
        check(all(equal), f"off the chip offline generate is exact: {equal}")
    return {"serve_s": serve_s, "serve_alone_s": alone_s,
            "offline_s": t_off.s, "reference_s": t_ref.s,
            "requests": len(reqs),
            "tokens": sum(len(r.tokens) for r in reqs),
            "prompt_lens": sorted({r.prompt.size for r in reqs}),
            "answer_lens": sorted({r.max_new for r in reqs}),
            "model": {k: w["srv"][k] for k in ("embed", "depth", "window",
                                               "num_heads", "num_kv_heads")},
            "ticks": ticks, "together_equals_alone": True,
            "equal_to_offline_generate": f"{sum(equal)}/{len(equal)}",
            "ties_where_offline_parts": ties,
            "worst_served_deficit": worst,
            "logit_atol": SERVING_LOGIT_ATOL}


def phase_downpour(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import AlexNet

    w = ctx.w
    model = AlexNet(num_classes=10, dropout=0.0)
    size = w["ps_image"]
    params0 = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, size, size, 3)), train=False))(
            jax.random.PRNGKey(ctx.seed))
    rng = np.random.RandomState(ctx.seed + 11)
    lr = 0.01

    @jax.jit
    def update_of(p, images, labels):
        def loss_fn(p):
            logits = model.apply(p, images, train=False)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()

        return jax.tree.map(lambda g: -lr * g, jax.grad(loss_fn)(p))

    with Timer() as t_build:  # builds csrc/ps.cpp when build/ is stale
        ps = mpi.parameterserver.init(params0, num_shards=1)
    try:
        # What the server must hold: f32 adds in arrival order.
        want = jax.tree.map(lambda a: np.array(a, np.float32), params0)
        local = params0
        with Timer() as t_rounds:
            for _ in range(w["ps_rounds"]):
                images = jnp.asarray(rng.rand(w["ps_batch"], size, size, 3),
                                     jnp.float32)
                labels = jnp.asarray(rng.randint(0, 10, size=w["ps_batch"]),
                                     jnp.int32)
                upd = update_of(local, images, labels)   # device arrays
                ps.send(upd, rule="add").wait()
                want = jax.tree.map(
                    lambda a, u: a + np.asarray(u, np.float32), want, upd)
                local = jax.tree.map(jnp.asarray, ps.receive().wait())
        got = ps.receive().wait()
        n_floats = 0
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            check(np.array_equal(np.asarray(a), b), "PS center != sum of "
                  "the pushed updates")
            n_floats += b.size
        moved = any(not np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(params0)))
        check(moved, "the pushed updates were all zero")
        ops = ps.ops_served()
    finally:
        ps.shutdown()
    return {"build_and_connect_s": t_build.s, "rounds_s": t_rounds.s,
            "rounds": w["ps_rounds"], "shards": 1, "floats": n_floats,
            "ops_served": ops, "center_equals_sum_of_updates": True}


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------


def collective_cases(n, m, x):
    """(verb, keyword arguments, NumPy answer) for every eager verb, for
    rank-major ``x`` of shape [n, m].  Values are small integers, so f32
    sums are exact whatever the order a backend adds them in."""
    import numpy as np

    total = x.sum(axis=0)
    reduced = x.copy()
    reduced[1] = total
    sent = x.copy()
    sent[2] = x[0]
    return [
        ("allreduce", {}, np.broadcast_to(total, (n, m))),
        ("broadcast", {"root": 1}, np.broadcast_to(x[1], (n, m))),
        ("reduce", {"root": 1}, reduced),
        ("allgather", {}, np.broadcast_to(x, (n, n, m))),
        ("reduce_scatter", {}, total.reshape(n, m // n)),
        ("alltoall", {}, x.reshape(n, n, m // n).transpose(1, 0, 2)
         .reshape(n, m)),
        ("sendreceive", {"src": 0, "dst": 2}, sent),
    ]


def phase_collectives(ctx):
    import jax
    import numpy as np

    import torchmpi_tpu as mpi
    from torchmpi_tpu import planner, selector

    w = ctx.w
    n = 4
    rng = np.random.RandomState(ctx.seed + 5)
    rows = []

    def sweep(backend, mesh):
        for m in (w["coll_small"], w["coll_large"]):
            x = rng.randint(-8, 9, size=(n, m)).astype(np.float32)
            for verb, kw, want in collective_cases(n, m, x):
                if backend not in selector.available(verb):
                    continue   # no such implementation: would run "xla"
                deadline(300)
                # The plan is the library's own record of what it will
                # run: a request that degraded shows another backend.
                plan = mpi.collectives.plan_for(
                    verb, jax.ShapeDtypeStruct(x.shape, x.dtype), mesh, n,
                    backend, kw)
                check(plan.backend == backend,
                      f"{verb} {m}: asked {backend}, planned {plan.backend}")
                kernel = None
                if backend == "pallas":
                    text = plan.extra["executable"].lower(
                        jax.ShapeDtypeStruct(x.shape, x.dtype,
                                             sharding=planner.
                                             rank_major_sharding(mesh))
                    ).as_text()
                    kernel = "tpu_custom_call" in text
                    if ctx.on_tpu:
                        check(kernel, f"{verb} {m}: no tpu_custom_call in "
                              "the lowered pallas program")
                with Timer() as t_first:
                    out = np.asarray(getattr(mpi, verb)(
                        x, backend=backend, **kw))
                check(np.array_equal(out, want), f"{backend} {verb} {m}")
                with Timer() as t_async:
                    handle = getattr(mpi.async_, verb)(
                        x, backend=backend, **kw)
                    out = np.asarray(mpi.sync_handle(handle))
                check(np.array_equal(out, want),
                      f"{backend} async {verb} {m}")
                rows.append({"backend": backend, "verb": verb,
                             "bytes_per_rank": 4 * m,
                             "first_call_s": round(t_first.s, 4),
                             "async_call_s": round(t_async.s, 4),
                             "tpu_custom_call": kernel})
                log(f"collectives: {rows[-1]}")

    mesh = mpi.init(mpi.Config(custom_min_bytes=0))
    check(mesh.devices.shape == (1, 4), f"flat mesh {mesh.devices.shape}")
    check(mpi.device_count() == 4, "device_count")
    sweep("xla", mesh)
    mpi.stop()
    mesh = mpi.init(mpi.Config(dcn_size=2, custom_min_bytes=0))
    check(mesh.devices.shape == (2, 2), f"2x2 mesh {mesh.devices.shape}")
    sweep("hierarchical", mesh)
    mpi.stop()
    # Last: the ring kernels' semaphore protocol is the newest to links.
    mesh = mpi.init(mpi.Config(custom_min_bytes=0))
    sweep("pallas", mesh)
    mpi.stop()
    per = {}
    for r in rows:
        per.setdefault(r["backend"], []).append(r["verb"])
    return {"meshes": [[1, 4], [2, 2]],
            "sizes_bytes_per_rank": [4 * w["coll_small"],
                                     4 * w["coll_large"]],
            "verbs_by_backend": {b: sorted(set(v)) for b, v in per.items()},
            "sync_and_async": True, "rows": rows}


def phase_resnet50_dp4(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi

    w = ctx.w
    n = 4
    per_chip = w["rn_batch"] // n
    mesh = mpi.init(mpi.Config(custom_min_bytes=0))
    (model, tx, params, batch_stats, images, labels,
     loss_fn) = resnet_setup(ctx.seed, w["rn_batch"], w["rn_image"],
                            w["rn_cut"])

    # By hand, on one device: BatchNorm normalises per shard, so the four
    # shards are four forward/backward passes whose gradients and
    # statistics are averaged (one batch of 128 is another computation).
    vg = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
    with Timer() as t_ref:
        outs = [vg(params, batch_stats,
                   images[s * per_chip:(s + 1) * per_chip],
                   labels[s * per_chip:(s + 1) * per_chip])
                for s in range(n)]
        mean = lambda *xs: sum(xs) / n  # noqa: E731
        ref_loss = float(mean(*[o[0][0] for o in outs]))
        grads = jax.tree.map(mean, *[o[1] for o in outs])
        upd, _ = tx.update(grads, tx.init(params), params)
        ref_norm = float(optax.global_norm(optax.apply_updates(params, upd)))
        ref_stats = float(optax.global_norm(
            jax.tree.map(mean, *[o[0][1] for o in outs])))
    del outs, grads, upd

    shard = NamedSharding(mesh, P(mesh.axis_names))
    im, lb = jax.device_put(images, shard), jax.device_put(labels, shard)
    homes = {s.device for s in im.addressable_shards}
    check(len(homes) == n and all(
        s.data.shape[0] == per_chip for s in im.addressable_shards),
        "the batch is not one shard per device")
    runs = {}
    for backend in ("xla", "pallas"):
        deadline(600)
        dp_step = mpi.recipes.make_bn_dp_train_step(model, tx, mesh=mesh,
                                                    backend=backend)
        state = mpi.recipes.replicate_bn_state(params, tx.init(params),
                                               batch_stats, mesh=mesh)
        kernel = None
        if backend == "pallas":
            kernel = "tpu_custom_call" in dp_step.jitted.lower(
                *state, im, lb).as_text()
            if ctx.on_tpu:
                check(kernel, "no tpu_custom_call in the pallas DP step")
        with Timer() as t:
            p, _, stats, loss = jax.block_until_ready(
                dp_step(*state, im, lb))
        loss = float(loss)
        norm = float(optax.global_norm(p))
        stats_norm = float(optax.global_norm(stats))
        leaf = jax.tree.leaves(p)[0]
        check({s.device for s in leaf.addressable_shards} == homes and all(
            s.data.shape == leaf.shape for s in leaf.addressable_shards),
            "the parameters are not replicated on every device")
        check(abs(loss - ref_loss) <= RESNET_LOSS_RTOL * abs(ref_loss),
              f"{backend}: loss {loss} vs by hand {ref_loss}")
        check(abs(norm - ref_norm) <= RESNET_NORM_RTOL * ref_norm,
              f"{backend}: |params| {norm} vs by hand {ref_norm}")
        check(abs(stats_norm - ref_stats) <= RESNET_LOSS_RTOL * ref_stats,
              f"{backend}: |batch_stats| {stats_norm} vs {ref_stats}")
        runs[backend] = {"first_call_s": t.s, "loss": loss,
                         "param_norm": norm, "stats_norm": stats_norm,
                         "tpu_custom_call": kernel}
        del p, stats, state
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.local_devices()]
    if ctx.on_tpu:
        check(all(b and b > 0 for b in in_use), f"bytes_in_use {in_use}")
    mpi.stop()
    return {"reference_s": t_ref.s, "per_chip_batch": per_chip,
            "image": w["rn_image"], "ref_loss": ref_loss,
            "ref_param_norm": ref_norm, "ref_stats_norm": ref_stats,
            "loss_rtol": RESNET_LOSS_RTOL, "norm_rtol": RESNET_NORM_RTOL,
            "runs": runs, "bytes_in_use_per_device": in_use}


def phase_serving4(ctx):
    import jax

    import torchmpi_tpu as mpi
    from torchmpi_tpu import serving

    w = ctx.w
    mpi.init()
    # Every replica compiles its own programs, and the sampling program
    # alone (a sort over the vocabulary) takes the chip's compiler ~25 s:
    # depth is cut and one prompt length is served.  Phase ``serving``
    # covers the whole depth and the window on one chip.
    spec = dict(w["srv"], depth=w["srv4_depth"])
    model = lm_build(spec, "flash")
    params = lm_params(model, ctx.seed)

    def serve(replicas):
        reqs = serving_requests(w, ctx.seed, long_prompts=False)
        server = serving.Server(model, params, replicas=replicas, slots=2)
        with Timer() as t:
            done = server.run_trace(reqs, unit_seconds=1.0)
        check(len(done) == len(reqs), "requests lost")
        return server, reqs, t.s

    server, reqs, four_s = serve(4)
    engines = list(server.router.live())
    homes = []
    for e in engines:
        on = {d for leaf in jax.tree.leaves((e.params, e._cache))
              for d in leaf.devices()}
        check(len(on) == 1, f"{e.name} spans devices {on}")
        homes.append(on.pop())
    check(homes == list(jax.local_devices()[:4]),
          f"replicas live on {homes}")
    used = sorted({r.replica for r in reqs})
    _, ref, one_s = serve(1)
    for a, b in zip(reqs, ref):
        check(a.tokens == b.tokens, f"{a.rid}: 4 replicas {a.tokens} vs "
              f"1 replica {b.tokens}")
    mpi.stop()
    return {"four_replicas_s": four_s, "one_replica_s": one_s,
            "replica_devices": [str(d) for d in homes],
            "replicas_that_served": used, "requests": len(reqs),
            "model": {"embed": spec["embed"], "depth": spec["depth"],
                      "window": spec["window"]},
            "tokens_equal_one_replica": True}


PHASES = {
    "init": phase_init, "resnet50_dp": phase_resnet50_dp,
    "lm_flash_xent": phase_lm_flash_xent, "serving": phase_serving,
    "downpour": phase_downpour, "collectives": phase_collectives,
    "resnet50_dp4": phase_resnet50_dp4, "serving4": phase_serving4,
}


def main(argv=None):
    args = parse_args(argv)
    mode = DEFAULT_PHASES if args.chips == 1 else FOUR_CHIP_PHASES
    names = mode if args.phases is None else tuple(
        p for p in args.phases.split(",") if p)
    unknown = [p for p in names if p not in mode]
    if unknown:
        raise SystemExit(f"unknown phases for --chips {args.chips}: "
                         f"{unknown} (have {list(mode)})")
    if args.rehearse:
        # Before jax is imported: the rehearsal owns the platform.
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.chips}"
        ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.utils import compilecache

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        log(f"no TPU: jax found platform {dev.platform!r}.  This script "
            "proves the paths on the chip; --rehearse runs them on the CPU.")
        return 2
    if len(devices) != args.chips:
        log(f"--chips {args.chips} needs exactly {args.chips} device(s), "
            f"jax found {len(devices)}")
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache at {compilecache.enable_persistent_cache()}")

    ctx = types.SimpleNamespace(seed=args.seed, rehearse=args.rehearse,
                                on_tpu=on_tpu, w=widths(args.rehearse))
    for name in names:
        log(f"phase {name} ...")
        deadline(900)
        with Timer() as t:
            checked = PHASES[name](ctx)
        faulthandler.cancel_dump_traceback_later()
        print(json.dumps({"phase": name, "seconds": round(t.s, 3),
                          "peak_bytes_in_use": peak_bytes(dev),
                          "checked": checked}), flush=True)
    if mpi.is_initialized():
        mpi.stop()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
