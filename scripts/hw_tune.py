#!/usr/bin/env python
"""Single-chip headline-number tuning experiments (needs a TPU).

Three quick studies, each printing one line per config:
  1. ResNet-50 DP train step vs per-chip batch (is 64 leaving MXU idle?)
  2. bf16 matmul TFLOP/s vs N (is the 4096 probe under-reporting peak?)
  3. transformer-LM step local (dense) vs flash attention at stage-B shapes

Informs bench.py defaults; run standalone, one process per chip.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from torchmpi_tpu.utils.metrics import fence, timed


def study_matmul():
    for n in (4096, 8192, 16384):
        a = jnp.ones((n, n), jnp.bfloat16)
        b = jnp.ones((n, n), jnp.bfloat16)

        @jax.jit
        def chain(a, b, n=n):
            mm = a
            for _ in range(4):
                mm = (mm @ b) * (1.0 / n)  # stay finite, keep dependency
            return mm

        dt = timed(lambda: chain(a, b), 10) / 4  # per-matmul
        print(f"matmul N={n}: {dt*1e6:.0f} us/matmul, "
              f"{2*n**3/dt/1e12:.1f} TFLOP/s", flush=True)


def study_resnet(batches):
    import optax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import ResNet50

    mesh = mpi.init()
    model = ResNet50(dtype=jnp.bfloat16)
    init_dev = jax.local_devices(backend="cpu")[0]
    with jax.default_device(init_dev):
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 224, 224, 3)), train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)
    dp_step = mpi.recipes.make_bn_dp_train_step(model, tx, mesh=mesh)
    p, o, bs = mpi.recipes.replicate_bn_state(params, opt_state,
                                              batch_stats, mesh=mesh)
    for batch in batches:
        images = jnp.asarray(
            np.random.RandomState(0).rand(batch, 224, 224, 3), jnp.float32)
        labels = jnp.asarray(
            np.random.RandomState(1).randint(0, 1000, size=(batch,)))
        t0 = time.time()
        state = [p, o, bs]

        def step(state=state, images=images, labels=labels):
            state[0], state[1], state[2], loss = dp_step(
                state[0], state[1], state[2], images, labels)
            return loss

        loss = step()
        fence(loss)
        compile_s = time.time() - t0
        dt = timed(step, 10)
        print(f"resnet50 b={batch}: {dt*1e3:.1f} ms/step, "
              f"{batch/dt:.0f} img/s, mfu "
              f"{3*8.2e9*batch/dt/1e12/197:.3f} "
              f"(compile {compile_s:.0f}s)", flush=True)


def study_transformer():
    import optax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import TransformerLM

    mesh = mpi.init()
    for impl, T, B in (("local", 512, 8), ("flash", 512, 8),
                       ("local", 2048, 2), ("flash", 2048, 2)):
        lm = TransformerLM(vocab=8192, embed=512, depth=4, num_heads=8,
                           head_dim=64, max_len=T, dtype=jnp.bfloat16,
                           attn_impl=impl)
        tok = jnp.asarray(np.random.RandomState(2).randint(
            0, 8192, size=(B, T)), jnp.int32)
        # init on-device: the flash variant's pallas_call cannot trace on
        # the CPU backend outside interpret mode, and this model is small.
        v = lm.init(jax.random.PRNGKey(1), tok[:1])
        tx = optax.sgd(0.1)
        o = tx.init(v)

        def lm_step(v, o, tok, lm=lm, tx=tx):
            def loss_fn(v):
                logits = lm.apply(v, tok).astype(jnp.float32)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits[:, :-1], tok[:, 1:]).mean()

            loss, g = jax.value_and_grad(loss_fn)(v)
            u, o2 = tx.update(g, o, v)
            return optax.apply_updates(v, u), o2, loss

        jit_step = jax.jit(lm_step)
        state = {"v": v, "o": o}

        def step(state=state, jit_step=jit_step, tok=tok):
            state["v"], state["o"], loss = jit_step(state["v"], state["o"],
                                                    tok)
            return loss

        dt = timed(step, 10)
        print(f"lm {impl} T={T} B={B}: {dt*1e3:.2f} ms/step, "
              f"{B*T/dt:.0f} tokens/s", flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--study", choices=["matmul", "resnet", "lm", "all"],
                    default="all")
    ap.add_argument("--batches", type=int, nargs="*",
                    default=[64, 128, 256])
    args = ap.parse_args()
    if args.study in ("matmul", "all"):
        study_matmul()
    if args.study in ("lm", "all"):
        study_transformer()
    if args.study in ("resnet", "all"):
        study_resnet(args.batches)
