"""Pallas flash attention (ops/flash.py) vs the dense oracle.

Runs in interpret mode on the CPU mesh (tests/conftest.py); real Mosaic
lowering is covered by test_ring_lowering.py's AOT exports."""

import numpy as np
import pytest

import jax.numpy as jnp

from torchmpi_tpu.ops.flash import flash_attention
from torchmpi_tpu.parallel.sequence import (causal_window_mask,
                                            reference_attention)


def _oracle(q, k, v, *, causal=False, q_offset=0, kv_offset=0):
    """Dense attention with global-position causal masking; fully-masked
    rows produce zeros (the kernel's convention)."""
    B, Tq, H, D = q.shape
    Tkv = k.shape[1]
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) / np.sqrt(D)
    if causal:
        qpos = q_offset + np.arange(Tq)
        kpos = kv_offset + np.arange(Tkv)
        mask = (qpos[:, None] >= kpos[None, :])[None, None]
        s = np.where(mask, s, -np.inf)
    m = np.max(s, axis=-1, keepdims=True)
    p = np.exp(s - np.where(np.isfinite(m), m, 0.0))
    p = np.where(np.isfinite(s), p, 0.0)
    l = p.sum(axis=-1, keepdims=True)
    p = p / np.where(l > 0, l, 1.0)
    return np.einsum("bhqk,bkhd->bqhd", p, np.asarray(v, np.float64))


def _rand(shape, seed, dtype=np.float32):
    return np.random.RandomState(seed).randn(*shape).astype(dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(flat_runtime, causal):
    q = _rand((2, 32, 2, 8), 0)
    k = _rand((2, 32, 2, 8), 1)
    v = _rand((2, 32, 2, 8), 2)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = reference_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_block_defaults_come_from_config(flat_runtime):
    # Call-site omission resolves block sizes from Config (the autotuned
    # knobs); an exotic configured tiling must still be numerically
    # correct and actually take effect (exercised via the config path).
    import torchmpi_tpu as mpi

    q, k, v = (_rand((1, 48, 2, 8), s) for s in (3, 4, 5))
    mpi.set_config(flash_block_q=16, flash_block_k=16)
    try:
        out = flash_attention(q, k, v, causal=True)  # no block args
    finally:
        mpi.set_config(flash_block_q=128, flash_block_k=128)
    ref = reference_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_cross_attention_lengths(flat_runtime):
    """T_q != T_kv (decoder-style cross attention)."""
    q = _rand((1, 16, 2, 8), 3)
    k = _rand((1, 48, 2, 8), 4)
    v = _rand((1, 48, 2, 8), 5)
    out = flash_attention(q, k, v, block_q=8, block_k=16)
    np.testing.assert_allclose(np.asarray(out), _oracle(q, k, v),
                               rtol=2e-5, atol=2e-5)


def test_flash_ragged_padding(flat_runtime):
    """Sequence lengths not divisible by the block sizes: the kernel pads
    internally and masks padded keys out of the softmax."""
    q = _rand((1, 40, 1, 8), 6)
    k = _rand((1, 40, 1, 8), 7)
    v = _rand((1, 40, 1, 8), 8)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(q, k, v, causal=True), rtol=2e-5,
        atol=2e-5)


def test_flash_sharded_offsets(flat_runtime):
    """q_offset/kv_offset place local blocks at global positions — the
    ring-attention shard-diagonal case where q starts mid-sequence."""
    q = _rand((1, 16, 2, 8), 9)
    k = _rand((1, 16, 2, 8), 10)
    v = _rand((1, 16, 2, 8), 11)
    # q block is the SECOND shard (global 16..31), kv the first (0..15):
    # causal over global positions = full attention here.
    out = flash_attention(q, k, v, causal=True, q_offset=16, kv_offset=0,
                          block_q=8, block_k=8)
    np.testing.assert_allclose(
        np.asarray(out),
        _oracle(q, k, v, causal=True, q_offset=16, kv_offset=0),
        rtol=2e-5, atol=2e-5)


def test_flash_fully_masked_rows_are_zero(flat_runtime):
    """kv entirely in the future of every query -> zeros, no nan."""
    q = _rand((1, 8, 1, 8), 12)
    k = _rand((1, 8, 1, 8), 13)
    v = _rand((1, 8, 1, 8), 14)
    out = flash_attention(q, k, v, causal=True, q_offset=0, kv_offset=64,
                          block_q=8, block_k=8)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.zeros_like(np.asarray(out)))


def test_flash_bf16(flat_runtime):
    q = _rand((1, 32, 2, 8), 15).astype(jnp.bfloat16)
    k = _rand((1, 32, 2, 8), 16).astype(jnp.bfloat16)
    v = _rand((1, 32, 2, 8), 17).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = _oracle(np.asarray(q, np.float32), np.asarray(k, np.float32),
                  np.asarray(v, np.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=0.05, atol=0.05)


def test_transformer_flash_matches_local(flat_runtime):
    """TransformerLM(attn_impl="flash") forward == attn_impl="local" on the
    same params — the kernel drops into the model unchanged."""
    import jax

    from torchmpi_tpu.models import TransformerLM

    tokens = np.random.RandomState(0).randint(0, 256, size=(2, 64)).astype(
        np.int32)
    local_model = TransformerLM(attn_impl="local")
    variables = local_model.init(jax.random.PRNGKey(0), jnp.asarray(tokens))
    expect = local_model.apply(variables, jnp.asarray(tokens))
    flash_model = TransformerLM(attn_impl="flash")
    got = flash_model.apply(variables, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_blocks(flat_runtime, causal):
    """ring_attention(block_impl="flash") == dense oracle: the Pallas
    kernel's residual outputs feed the cross-shard combiner, with the kv
    owner's traced offset riding into the kernel through SMEM."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu.parallel import sequence as seq

    mesh = mpi.world_mesh()
    B, T, H, D = 2, 64, 2, 8
    rng = np.random.RandomState(21)
    q, k, v = (rng.randn(B, T, H, D).astype(np.float32) * 0.3
               for _ in range(3))
    expect = np.asarray(seq.reference_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal))

    def body(q, k, v):
        return seq.ring_attention(q, k, v, "ici", causal=causal,
                                  block_impl="flash", block_q=8, block_k=8)

    spec = P(None, ("dcn", "ici"))
    sh = NamedSharding(mesh, spec)
    got = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                            out_specs=spec, check_vma=False))(
        *(jax.device_put(x, sh) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(got), expect, rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_matches_reference(flat_runtime, causal):
    """custom-VJP gradients (Pallas backward kernels) == autodiff through
    the dense oracle, for q, k, and v."""
    import jax

    from torchmpi_tpu.ops.flash import flash_attention_grad

    rng = np.random.RandomState(30)
    q, k, v, w = (jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32) * 0.5
                  for _ in range(4))

    def loss_flash(q, k, v):
        return (flash_attention_grad(q, k, v, causal=causal, block_q=8,
                                     block_k=8) * w).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=causal) * w).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)


def test_flash_prescale_matches_reference(flat_runtime):
    """Config.flash_prescale folds the scale into q at the boundary;
    forward AND gradients must still match the dense oracle (q is
    rounded to its dtype after scaling, so tolerance is dtype-level,
    and in f32 the rounding is negligible)."""
    import jax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.ops.flash import flash_attention, \
        flash_attention_grad

    rng = np.random.RandomState(31)
    q, k, v, w = (jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32) * 0.5
                  for _ in range(4))
    expect_o = np.asarray(reference_attention(q, k, v, causal=True))

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) * w).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)

    mpi.stop()
    mpi.init(mpi.Config(flash_prescale=True))
    try:
        assert mpi.config().flash_prescale
        got_o = np.asarray(flash_attention(q, k, v, causal=True,
                                           block_q=8, block_k=8))
        np.testing.assert_allclose(got_o, expect_o, rtol=5e-5, atol=5e-5)

        def loss_flash(q, k, v):
            return (flash_attention_grad(q, k, v, causal=True, block_q=8,
                                         block_k=8) * w).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-5, atol=5e-5)
        # Window path (static offsets -> baked-closure VJP instance,
        # the fs/fwd_s/bwd_s wiring): forward AND gradients asserted.
        expect_w = np.asarray(reference_attention(q, k, v, causal=True,
                                                  window=16))

        def loss_win(q, k, v):
            return (flash_attention_grad(q, k, v, causal=True, window=16,
                                         block_q=8, block_k=8) * w).sum()

        def loss_win_ref(q, k, v):
            return (reference_attention(q, k, v, causal=True,
                                        window=16) * w).sum()

        got_w = np.asarray(flash_attention(q, k, v, causal=True,
                                           window=16, block_q=8,
                                           block_k=8))
        np.testing.assert_allclose(got_w, expect_w, rtol=5e-5, atol=5e-5)
        gw = jax.grad(loss_win, argnums=(0, 1, 2))(q, k, v)
        gw_ref = jax.grad(loss_win_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gw, gw_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-5, atol=5e-5)
    finally:
        mpi.stop()
        mpi.init()


@pytest.mark.parametrize("causal", [
    False,
    # causal=True is the heavier variant; the False leg keeps the
    # ring-grad path in tier-1 (budget, ISSUE 4 satellite)
    pytest.param(True, marks=pytest.mark.slow),
])
def test_ring_flash_grad_matches_dense_ring(flat_runtime, causal):
    """The ring-level custom VJP (backward ring: k/v/dk/dv rotate a full
    cycle) == autodiff through the dense-block ring.

    Runs on a 4-device sub-ring: the backward ring is BY FAR the
    suite's heaviest interpreted-Pallas workload (flash kernels per ring
    step, each crossing the interpreter's N-party barriers), and at 8
    parties it is where the flaky full-suite abort struck in two
    containers.  The rotating-accumulator VJP
    math is ring-size-independent; 8-device ring FORWARD coverage
    remains elsewhere in the suite."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu.parallel import sequence as seq

    world = mpi.world_mesh()
    B, T, H, D = 1, 32, 2, 8
    rng = np.random.RandomState(31)
    q, k, v, w = (rng.randn(B, T, H, D).astype(np.float32) * 0.5
                  for _ in range(4))

    with mpi.communicator("ring4",
                          devices=list(world.devices.flat[:4]),
                          shape={"ici": 4}) as mesh:
        spec = P(None, "ici")
        sh = NamedSharding(mesh, spec)

        def make_loss(block_impl):
            def body(q, k, v, w):
                o = seq.ring_attention(q, k, v, "ici", causal=causal,
                                       block_impl=block_impl, block_q=4,
                                       block_k=4)
                from jax import lax
                return lax.psum((o * w).sum(), "ici")

            def loss(q, k, v, w):
                return jax.jit(shard_map(
                    body, mesh=mesh, in_specs=(spec,) * 4,
                    out_specs=P(), check_vma=False))(q, k, v, w)

            return loss

        args = [jax.device_put(x, sh) for x in (q, k, v, w)]
        g_flash = jax.grad(make_loss("flash"), argnums=(0, 1, 2))(*args)
        g_dense = jax.grad(make_loss("dense"), argnums=(0, 1, 2))(*args)
    for a, b in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-5,
                                   atol=3e-5)


def test_flash_multiblock_online_softmax(flat_runtime):
    """Many k blocks exercise the cross-block rescale recurrence; spiky
    values make a naive (non-online) accumulation overflow visibly."""
    q = _rand((1, 16, 1, 8), 18) * 8.0
    k = _rand((1, 128, 1, 8), 19) * 8.0
    v = _rand((1, 128, 1, 8), 20)
    out = flash_attention(q, k, v, block_q=8, block_k=16)
    np.testing.assert_allclose(np.asarray(out), _oracle(q, k, v),
                               rtol=1e-4, atol=1e-4)


def test_flash_unaligned_seq_with_default_blocks(flat_runtime):
    """T between tile width and the (large) default blocks: the clamp
    rounds the block UP to a tile-aligned size covering T (never a raw
    min(block, T) that Mosaic may refuse), and pads internally.  Also
    covers the skip predicate with a final partially-valid k block."""
    from torchmpi_tpu.ops.flash import _clamp_block

    assert _clamp_block(512, 300) == 384  # tile-aligned cover, not 300
    assert _clamp_block(512, 8) == 128
    assert _clamp_block(256, 4096) == 256  # explicit aligned passthrough

    q = _rand((1, 300, 2, 8), 21)
    k = _rand((1, 300, 2, 8), 22)
    v = _rand((1, 300, 2, 8), 23)
    out = flash_attention(q, k, v, causal=True)  # default (512) blocks
    np.testing.assert_allclose(
        np.asarray(out), _oracle(q, k, v, causal=True), rtol=2e-5,
        atol=2e-5)


def test_flash_grad_unaligned_seq_with_default_blocks(flat_runtime):
    """Backward path through the same clamp: grads at T=300 with default
    blocks match autodiff through the dense oracle."""
    import jax

    from torchmpi_tpu.ops.flash import flash_attention_grad

    q = _rand((1, 300, 1, 8), 24)
    k = _rand((1, 300, 1, 8), 25)
    v = _rand((1, 300, 1, 8), 26)

    def floss(q, k, v):
        o = flash_attention_grad(q, k, v, causal=True)
        return jnp.sum(o ** 2)

    def dloss(q, k, v):
        o = reference_attention(q, k, v, causal=True)
        return jnp.sum(o ** 2)

    got = jax.grad(floss, argnums=(0, 1, 2))(jnp.asarray(q),
                                             jnp.asarray(k),
                                             jnp.asarray(v))
    want = jax.grad(dloss, argnums=(0, 1, 2))(jnp.asarray(q),
                                              jnp.asarray(k),
                                              jnp.asarray(v))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-5, atol=5e-5)


def test_flash_sliding_window_matches_oracle(flat_runtime):
    """window=W: each query sees itself + the W-1 keys before it.  The
    numpy oracle applies the same band mask; multi-block shapes exercise
    the out-of-window block skip."""
    q = _rand((1, 64, 2, 8), 27)
    k = _rand((1, 64, 2, 8), 28)
    v = _rand((1, 64, 2, 8), 29)

    def oracle_window(q, k, v, w):
        B, Tq, H, D = q.shape
        s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float64),
                      np.asarray(k, np.float64)) / np.sqrt(D)
        pos = np.arange(Tq)
        keep = (pos[:, None] >= pos[None, :]) & \
            (pos[:, None] - pos[None, :] < w)
        s = np.where(keep[None, None], s, -np.inf)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        return np.einsum("bhqk,bkhd->bqhd", p, np.asarray(v, np.float64))

    for w in (1, 8, 17, 64):
        out = flash_attention(q, k, v, causal=True, window=w,
                              block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out),
                                   oracle_window(q, k, v, w),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"window={w}")


def test_flash_sliding_window_grad_matches_dense(flat_runtime):
    """Backward through the windowed kernel == autodiff through the dense
    windowed oracle (reference_attention with window=)."""
    import jax

    from torchmpi_tpu.ops.flash import flash_attention_grad

    q, k, v = (_rand((1, 48, 1, 8), s) for s in (30, 31, 32))
    W = 12

    def floss(q, k, v):
        o = flash_attention_grad(q, k, v, causal=True, window=W,
                                 block_q=16, block_k=16)
        return jnp.sum(o ** 2)

    def dloss(q, k, v):
        o = reference_attention(q, k, v, causal=True, window=W)
        return jnp.sum(o ** 2)

    got = jax.grad(floss, argnums=(0, 1, 2))(jnp.asarray(q),
                                             jnp.asarray(k),
                                             jnp.asarray(v))
    want = jax.grad(dloss, argnums=(0, 1, 2))(jnp.asarray(q),
                                              jnp.asarray(k),
                                              jnp.asarray(v))
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                   rtol=5e-5, atol=5e-5)


def test_flash_window_offsets_ring_shard(flat_runtime):
    """Sliding window composes with TRACED global offsets (the ring-shard
    layout — jnp scalars force the full grid + runtime _block_live skip):
    a q shard starting at global 16 with window 8 must only see the last
    8 positions of the earlier kv shard."""
    q = _rand((1, 16, 1, 8), 33)
    k = _rand((1, 16, 1, 8), 34)
    v = _rand((1, 16, 1, 8), 35)
    W = 8
    out = flash_attention(q, k, v, causal=True, window=W,
                          q_offset=jnp.int32(16), kv_offset=jnp.int32(0),
                          block_q=8, block_k=8)
    # Dense oracle over global positions.
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) / np.sqrt(8)
    qpos = 16 + np.arange(16)
    kpos = np.arange(16)
    keep = (qpos[:, None] >= kpos[None, :]) & \
        (qpos[:, None] - kpos[None, :] < W)
    s = np.where(keep[None, None], s, -np.inf)
    with np.errstate(invalid="ignore"):
        p = np.exp(s - np.nan_to_num(s.max(axis=-1, keepdims=True),
                                     neginf=0.0))
        l = p.sum(axis=-1, keepdims=True)
        p = np.where(l > 0, p / np.where(l > 0, l, 1.0), 0.0)
    want = np.einsum("bhqk,bkhd->bqhd", p, np.asarray(v, np.float64))
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)


def test_flash_window_validation(flat_runtime):
    q = _rand((1, 16, 1, 8), 36)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=4)
    with pytest.raises(ValueError, match=">= 1"):
        flash_attention(q, q, q, causal=True, window=0)


def test_transformer_window_local_vs_flash(flat_runtime):
    """TransformerLM(window=) parity between the dense-masked local impl
    and the block-skipping flash kernel."""
    import jax

    from torchmpi_tpu.models import TransformerLM

    tok = np.random.RandomState(40).randint(0, 64, size=(2, 48))
    tok = jnp.asarray(tok, jnp.int32)
    outs = {}
    for impl in ("local", "flash"):
        lm = TransformerLM(vocab=64, embed=32, depth=2, num_heads=2,
                           head_dim=16, max_len=48, attn_impl=impl,
                           window=8)
        v = lm.init(jax.random.PRNGKey(0), tok)
        outs[impl] = lm.apply(v, tok)
    np.testing.assert_allclose(np.asarray(outs["flash"]),
                               np.asarray(outs["local"]),
                               rtol=2e-4, atol=2e-4)


def test_flash_banded_grid_grad_long_seq(flat_runtime):
    """T large enough that the banded O(T*window) grids engage for fwd,
    dq, AND dkv (n_band < n_blocks); gradients must still match autodiff
    through the dense windowed oracle."""
    import jax

    from torchmpi_tpu.ops.flash import flash_attention_grad

    q, k, v = (_rand((1, 96, 1, 8), s) for s in (41, 42, 43))
    W = 8  # blocks 16 -> n_band 3 < nk 6: banded everywhere

    def floss(q, k, v):
        o = flash_attention_grad(q, k, v, causal=True, window=W,
                                 block_q=16, block_k=16)
        return jnp.sum(o ** 2)

    def dloss(q, k, v):
        o = reference_attention(q, k, v, causal=True, window=W)
        return jnp.sum(o ** 2)

    got = jax.grad(floss, argnums=(0, 1, 2))(jnp.asarray(q),
                                             jnp.asarray(k),
                                             jnp.asarray(v))
    want = jax.grad(dloss, argnums=(0, 1, 2))(jnp.asarray(q),
                                              jnp.asarray(k),
                                              jnp.asarray(v))
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                   rtol=5e-5, atol=5e-5)


def test_flash_banded_vs_full_grid_identical(flat_runtime):
    """The banded grid (static offsets) and the full grid (traced
    offsets, runtime skip only) must produce bit-identical outputs."""
    import jax

    q, k, v = (_rand((1, 96, 2, 8), s) for s in (44, 45, 46))
    banded = flash_attention(q, k, v, causal=True, window=8,
                             block_q=16, block_k=16)  # static 0 offsets
    full = flash_attention(q, k, v, causal=True, window=8,
                           q_offset=jnp.int32(0), kv_offset=jnp.int32(0),
                           block_q=16, block_k=16)  # traced -> full grid
    np.testing.assert_array_equal(np.asarray(banded), np.asarray(full))


def _gqa_oracle(q, k, v, *, causal=True):
    g = q.shape[2] // k.shape[2]
    return _oracle(q, np.repeat(k, g, axis=2), np.repeat(v, g, axis=2),
                   causal=causal)


def test_flash_gqa_matches_repeat_kv_oracle(flat_runtime):
    """Grouped-query attention: 4 q heads over 2 (and 1) kv heads match
    the dense oracle with repeated kv."""
    q = _rand((2, 32, 4, 8), 50)
    for hkv in (2, 1):
        k = _rand((2, 32, hkv, 8), 51 + hkv)
        v = _rand((2, 32, hkv, 8), 53 + hkv)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        np.testing.assert_allclose(
            np.asarray(out), _gqa_oracle(q, k, v), rtol=2e-5, atol=2e-5,
            err_msg=f"hkv={hkv}")


def test_flash_gqa_grad_matches_repeat_kv_autodiff(flat_runtime):
    """GQA gradients: dk/dv are group-sums (autodiff's transpose of the
    head repeat); also composed with a sliding window."""
    import jax

    from torchmpi_tpu.ops.flash import flash_attention_grad

    q = _rand((1, 48, 4, 8), 55)
    k = _rand((1, 48, 2, 8), 56)
    v = _rand((1, 48, 2, 8), 57)

    for w in (None, 12):
        def floss(q, k, v, w=w):
            o = flash_attention_grad(q, k, v, causal=True, window=w,
                                     block_q=16, block_k=16)
            return jnp.sum(o ** 2)

        def dloss(q, k, v, w=w):
            g = q.shape[2] // k.shape[2]
            o = reference_attention(q, jnp.repeat(k, g, axis=2),
                                    jnp.repeat(v, g, axis=2),
                                    causal=True, window=w)
            return jnp.sum(o ** 2)

        got = jax.grad(floss, argnums=(0, 1, 2))(jnp.asarray(q),
                                                 jnp.asarray(k),
                                                 jnp.asarray(v))
        want = jax.grad(dloss, argnums=(0, 1, 2))(jnp.asarray(q),
                                                  jnp.asarray(k),
                                                  jnp.asarray(v))
        for name, g_, w_ in zip("q k v".split(), got, want):
            np.testing.assert_allclose(
                np.asarray(g_), np.asarray(w_), rtol=5e-5, atol=5e-5,
                err_msg=f"d{name} window={w}")


def test_flash_gqa_validation(flat_runtime):
    q = _rand((1, 16, 4, 8), 58)
    k = _rand((1, 16, 3, 8), 59)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, k, causal=True)


@pytest.mark.slow  # GQA+decode composition; plain flash-vs-local and
# decode equivalences each have faster tests (tier-1 budget)
def test_transformer_gqa_local_vs_flash_and_decode(flat_runtime):
    """TransformerLM(num_kv_heads=): local/flash training parity, and
    KV-cache decode (cache holds only the kv heads) matches the
    full-recompute oracle token-for-token."""
    import jax

    from torchmpi_tpu.models import TransformerLM
    from torchmpi_tpu.models.generate import generate

    tok = np.random.RandomState(60).randint(0, 64, size=(2, 24))
    tok = jnp.asarray(tok, jnp.int32)
    outs = {}
    for impl in ("local", "flash"):
        lm = TransformerLM(vocab=64, embed=32, depth=2, num_heads=4,
                           head_dim=8, max_len=48, attn_impl=impl,
                           num_kv_heads=2)
        v = lm.init(jax.random.PRNGKey(0), tok)
        outs[impl] = lm.apply(v, tok)
    np.testing.assert_allclose(np.asarray(outs["flash"]),
                               np.asarray(outs["local"]),
                               rtol=2e-4, atol=2e-4)

    # greedy decode == full-recompute argmax, with the Hkv-headed cache
    lm = TransformerLM(vocab=64, embed=32, depth=2, num_heads=4,
                       head_dim=8, max_len=48, num_kv_heads=2)
    params = lm.init(jax.random.PRNGKey(1), tok)["params"]
    got = generate(lm, params, tok[:, :8], steps=6, temperature=0.0)
    # oracle: iteratively recompute the full forward and take argmax
    cur = tok[:, :8]
    for _ in range(6):
        logits = lm.apply({"params": params}, cur)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(cur.dtype)
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(cur))


# --- the one-pass backward (flash.dkv writes dq too) ----------------------

def _dense_attention(q, k, v, *, q_offset=0, kv_offset=0, window=None):
    """Causal attention at global positions, dense, for autodiff; a row
    with every key masked reads zeros (the kernel's convention).  Returns
    the output and each row's log-sum-exp ([B, H, T_q], +1e30 on such a
    row: lse_from_residuals' convention)."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    keep = causal_window_mask(q_offset + jnp.arange(q.shape[1]),
                              kv_offset + jnp.arange(k.shape[1]), window)
    s = jnp.where(keep[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(s - m)
    l = e.sum(axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", e / jnp.where(l > 0, l, 1.0), v)
    lse = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)), 1e30)
    return o, lse[..., 0]


# (T_q, T_kv, q heads, kv heads, block, window, q offset, kv offset,
#  offsets traced, resident dq budget in bytes or None for the module's)
BWD_CASES = {
    "full_causal": (64, 64, 2, 2, 16, None, 0, 0, False, None),
    "window_banded_static": (128, 128, 1, 1, 16, 20, 0, 0, False, None),
    "traced_offsets_ring_shard": (32, 32, 2, 2, 16, 40, 64, 32, True, None),
    "gqa_24_over_2": (32, 32, 24, 2, 16, None, 0, 0, False, None),
    "seq_not_a_multiple_of_the_block": (40, 40, 2, 1, 16, None, 0, 0,
                                        False, None),
    # two q blocks of 16 x 8 float32, twice: four spans of the 128 rows,
    # the later ones banded from a static q offset that is not 0
    "q_spans_banded": (128, 128, 2, 1, 16, 20, 0, 0, False, 2048),
    "q_spans_traced_offsets": (64, 32, 1, 1, 16, None, 32, 0, True, 1024),
}


@pytest.mark.parametrize("case", sorted(BWD_CASES))
def test_flash_one_pass_backward_matches_dense_autodiff(flat_runtime, case):
    """dq, dk and dv of the ONE backward kernel == autodiff through the
    dense oracle, on every path the kernel's grid takes."""
    import jax

    from torchmpi_tpu.ops import flash

    Tq, Tkv, H, Hkv, blk, window, qo, ko, traced, dq_bytes = BWD_CASES[case]
    D = 8
    q, do = _rand((1, Tq, H, D), 70) * 0.5, _rand((1, Tq, H, D), 73)
    k, v = (_rand((1, Tkv, Hkv, D), s) * 0.5 for s in (71, 72))
    nq = -(-Tq // blk)
    if dq_bytes is None:
        dq_bytes = flash._DQ_RESIDENT_BYTES
        assert flash._q_span_blocks(nq, blk, D, dq_bytes) == nq
    else:
        assert flash._q_span_blocks(nq, blk, D, dq_bytes) < nq

    def dense(q, k, v):
        return _dense_attention(q, k, v, q_offset=qo, kv_offset=ko,
                                window=window)

    (o, lse), vjp = jax.vjp(dense, *map(jnp.asarray, (q, k, v)))
    want = vjp((jnp.asarray(do), jnp.zeros_like(lse)))
    dvec = jnp.einsum("bqhd,bqhd->bhq", do, o)

    def bwd(qo, ko):
        return flash._flash_bwd(
            q, k, v, do, lse, dvec, causal=True, scale=1.0 / np.sqrt(D),
            q_offset=qo, kv_offset=ko, block_q=blk, block_k=blk,
            window=window, interpret=None, dq_bytes=dq_bytes)

    got = jax.jit(bwd)(qo, ko) if traced else bwd(qo, ko)
    for name, g, w in zip("q k v".split(), got, want):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=5e-5,
                                   atol=5e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("window,scale", [(None, 1.0), (20, 1.0),
                                          (20, 8 ** -0.5)])
def test_flash_one_pass_backward_keeps_the_tile_order(flat_runtime, window,
                                                      scale):
    """Bit-equal to the two-kernel backward's sums, written out in plain
    jnp: float32 accumulators from zero, a q block's dq over its kv
    blocks in ascending j, a kv block's dk/dv over its q blocks in
    ascending i, dead tiles skipped, dk/dv per q head and group-summed
    after."""
    import jax

    from torchmpi_tpu.ops import flash

    T, H, Hkv, D, blk = 64, 4, 2, 8, 16
    q, do = _rand((1, T, H, D), 80) * 0.5, _rand((1, T, H, D), 83)
    k, v = (_rand((1, T, Hkv, D), s) * 0.5 for s in (81, 82))
    o, lse = _dense_attention(*map(jnp.asarray, (q, k, v)), window=window)
    dvec = jnp.einsum("bqhd,bqhd->bhq", do, o)
    got = flash.flash_attention_bwd(q, k, v, do, lse, dvec, causal=True,
                                    scale=scale, block_q=blk, block_k=blk,
                                    window=window)

    def dot(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   preferred_element_type=jnp.float32)

    @jax.jit  # one program a tile, as the kernel's body is one
    def tile(q_i, do_i, lse_i, dvec_i, k_j, v_j, valid, dq_i, dk_j, dv_j):
        s = dot(q_i, k_j, ((1,), (1,)))
        if scale != 1.0:
            s = s * scale
        p = jnp.exp(jnp.where(valid, s, flash.NEG_INF) - lse_i)
        ds = p * (dot(do_i, v_j, ((1,), (1,))) - dvec_i)
        dkq, dqk = dot(ds, q_i, ((0,), (0,))), dot(ds, k_j, ((1,), (0,)))
        return (dq_i + (scale * dqk if scale != 1.0 else dqk),
                dk_j + (scale * dkq if scale != 1.0 else dkq),
                dv_j + dot(p, do_i, ((0,), (0,))))

    keep = causal_window_mask(np.arange(T), np.arange(T), window)
    n = T // blk
    rows = [slice(i * blk, (i + 1) * blk) for i in range(n)]
    dq = np.zeros((1, T, H, D), np.float32)
    dkv = np.zeros((2, 1, T, H, D), np.float32)  # per q head
    for h in range(H):
        kh, vh = (jnp.asarray(x[0, :, h // (H // Hkv)]) for x in (k, v))
        for j in range(n):
            dk_j = dv_j = jnp.zeros((blk, D), jnp.float32)
            for i in range(n):
                if not keep[rows[i], rows[j]].any():
                    continue
                dq[0, rows[i], h], dk_j, dv_j = tile(
                    q[0, rows[i], h], do[0, rows[i], h],
                    lse[0, h, rows[i], None], dvec[0, h, rows[i], None],
                    kh[rows[j]], vh[rows[j]], keep[rows[i], rows[j]],
                    dq[0, rows[i], h], dk_j, dv_j)
            dkv[0, 0, rows[j], h], dkv[1, 0, rows[j], h] = dk_j, dv_j
    dk, dv = (jnp.asarray(x).reshape(1, T, Hkv, H // Hkv, D).sum(axis=3)
              for x in dkv)
    for name, g, w in zip("q k v".split(), got, (dq, dk, dv)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"d{name}")
