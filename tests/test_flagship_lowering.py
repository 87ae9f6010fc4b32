"""AOT Mosaic lowering of the stage-B2 flagship LM train step for TPU.

bench.py's stage B2 composes the whole flagship stack — Pallas flash
attention with GQA + sliding window + RoPE, and the fused linear+xent
head — at production dims (E=2048, L=8, T=2048, V=32k).  A Mosaic
rejection at those dims (unsupported op in the kernel plan) would
otherwise surface only on the chip.  ``jax.export`` with
``platforms=["tpu"]`` runs the pallas->Mosaic pipeline host-side;
``jax.eval_shape`` keeps the ~0.5 GB of parameters virtual.  The chip's
own compiler (fast-memory limits, tiling, HBM fit) is run on the same
kernels by tests/test_chip_compile.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchmpi_tpu.ops import ring


@pytest.fixture(autouse=True)
def _real_lowering():
    # Force real Mosaic lowering: auto mode would resolve to the CPU
    # interpreter on this host, which pins the pallas calls to the cpu
    # backend and breaks cross-platform export.
    ring.set_interpret(False)
    yield
    ring.set_interpret(None)


@pytest.mark.slow
def test_flagship_lm_train_step_lowers_for_tpu():
    import optax

    from torchmpi_tpu.models import TransformerLM
    from torchmpi_tpu.ops.xent import fused_linear_cross_entropy

    E2, L2, H2, HKV2, HD2, T2, V2, W2, B2 = (
        2048, 8, 16, 4, 128, 2048, 32768, 1024, 4)
    lm2 = TransformerLM(vocab=V2, embed=E2, depth=L2, num_heads=H2,
                        head_dim=HD2, num_kv_heads=HKV2, max_len=T2,
                        window=W2, pos_emb="rope", dtype=jnp.bfloat16,
                        attn_impl="flash")
    tok_s = jax.ShapeDtypeStruct((B2, T2), jnp.int32)
    var_s = jax.eval_shape(
        lambda t: lm2.init(jax.random.PRNGKey(0), t), tok_s)
    tx = optax.sgd(0.02)
    opt_s = jax.eval_shape(lambda v: tx.init(v), var_s)

    def step(v, o, tok):
        def loss_fn(v):
            h, head = lm2.apply(v, tok, return_prehead=True)
            per_tok = fused_linear_cross_entropy(
                h[:, :-1].reshape(-1, E2).astype(jnp.bfloat16),
                head.astype(jnp.bfloat16), tok[:, 1:].reshape(-1),
                interpret=False)
            return per_tok.mean()

        loss, g = jax.value_and_grad(loss_fn)(v)
        u, o = tx.update(g, o, v)
        return optax.apply_updates(v, u), o, loss

    exp = jax.export.export(jax.jit(step), platforms=["tpu"])(
        var_s, opt_s, tok_s)
    module = exp.mlir_module()
    # Both Pallas kernels (flash fwd+bwd, xent fwd+bwd) must have
    # survived Mosaic lowering into TPU custom calls.
    assert module.count("tpu_custom_call") >= 4, (
        module.count("tpu_custom_call"))


@pytest.mark.slow
def test_flagship_decode_scan_lowers_for_tpu():
    # The serving path at flagship dims: prefill + KV-cache scanned
    # decode with GQA cache (HKV heads) and RoPE — the graph
    # lm_generate-style serving compiles.  Dense
    # (non-pallas) attention decode: the decode path uses the cache
    # rule, not the flash kernel, so this checks the scan/cache
    # plumbing lowers for TPU at size.
    from torchmpi_tpu.models import TransformerLM
    from torchmpi_tpu.models.generate import _generate_scan

    lm = TransformerLM(vocab=32768, embed=2048, depth=8, num_heads=16,
                       head_dim=128, num_kv_heads=4, max_len=1024,
                       window=512, pos_emb="rope", dtype=jnp.bfloat16,
                       attn_impl="local", decode=True)
    prompt_s = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    params_s = jax.eval_shape(
        lambda t: lm.init(jax.random.PRNGKey(0), t)["params"], prompt_s)

    def decode(params, prompt):
        return _generate_scan(lm, params, prompt, 16, jnp.float32(0.0),
                              jax.random.PRNGKey(1), eos_id=7)

    exp = jax.export.export(jax.jit(decode), platforms=["tpu"])(
        params_s, prompt_s)
    assert exp.mlir_module_serialized  # lowered without rejection
