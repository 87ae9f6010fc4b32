"""Fused linear + softmax cross-entropy Pallas kernel.

The LM-head loss is the other memory hog of long-context training (after
attention): computing ``softmax_xent(x @ W, labels)`` materializes a
[tokens, vocab] logits matrix (plus its f32 softmax) in HBM.  This kernel
streams vocab blocks through VMEM with an online log-sum-exp — logits never
exist in memory — and the custom VJP's ONE backward kernel recomputes each
tile's probabilities once and feeds both ``dx`` and ``dW`` from them (three
products a tile: the logits again, ``g W^T``, ``x^T g``), so peak memory is
O(block) instead of O(tokens x vocab).

No reference analog (TorchMPI predates transformers; SURVEY.md §6.7) —
this serves the beyond-reference long-context stack next to ops/flash.py,
with the same grid-scratch accumulation idiom: the (m, l, t) running state
carries across the minor vocab-block grid dimension.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ring

from .flash import NEG_INF, _float0_zero

_LANES = 128
_STAT_LANES = 8


def _xent_fwd_kernel(labels_ref, x_ref, w_ref, loss_ref, lse_ref, m_scr,
                     l_scr, t_scr, *, block_n: int, block_v: int,
                     vocab: int, pad_vocab: bool):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        t_scr[:] = jnp.zeros_like(t_scr)

    z = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [block_n, block_v]
    col = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)
    if pad_vocab:
        # Statically skipped when vocab % block_v == 0 (the production
        # case): no padded w columns exist, so the select is the
        # identity — one fewer [block_n, block_v] VPU pass per block.
        # The iota stays either way (the label-hit compare needs col).
        z = jnp.where(col < vocab, z, NEG_INF)  # mask vocab padding

    m_prev = jnp.max(m_scr[:], axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.max(z, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_prev = jnp.max(l_scr[:], axis=1, keepdims=True)
    l_new = alpha * l_prev + jnp.sum(jnp.exp(z - m_new), axis=1,
                                     keepdims=True)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # The label's logit, accumulated when its column passes through.
    lab = labels_ref[:]  # [block_n, 1] int32
    hit = jnp.where(col == lab, z, 0.0)
    t_scr[:] = t_scr[:] + jnp.broadcast_to(
        jnp.sum(hit, axis=1, keepdims=True), t_scr.shape)

    @pl.when(j == nv - 1)
    def _finalize():
        lse = m_new + jnp.log(jnp.maximum(l_new, 1e-37))
        t = jnp.max(t_scr[:], axis=1, keepdims=True)
        loss_ref[:] = jnp.broadcast_to(lse - t, loss_ref.shape)
        lse_ref[:] = jnp.broadcast_to(lse, lse_ref.shape)


def _xent_bwd_kernel(labels_ref, x_ref, w_ref, lse_ref, dl_ref, dw_ref,
                     dx_hbm, dw_acc, dx_buf, rd_sem, wr_sem, *, block_n: int,
                     block_v: int, vocab: int, pad_vocab: bool, slots: int):
    """One (vocab block j, token block i) tile: ``g = (p - y) * dloss`` is
    recomputed from the saved lse ONCE and feeds both gradients,
    ``dW_j += x_i^T g`` and ``dx_i += g W_j^T``.

    Grid (nv, nn), tokens minor.  dW's f32 accumulator is VMEM scratch,
    resident across the minor sweep and written out once, in ``w``'s type,
    at its end.  dx's cannot be resident too (an output block is written
    back when its index changes, not accumulated), so it lives in HBM as
    f32 and each tile reads, adds to and rewrites its ``[block_n, E]`` rows
    by DMA through ``slots`` VMEM buffers: the read hides under the first
    two products, the write under the next tile's.  Step k uses slot
    ``k % slots`` and first waits for that slot's write of step
    ``k - slots``; with ``slots = min(2, nn)`` the rewrite of rows i (step
    ``k - nn``) has then landed before they are read again."""
    j, i = pl.program_id(0), pl.program_id(1)
    nn = pl.num_programs(1)
    k = j * nn + i
    slot = k % slots
    rows = dx_hbm.at[pl.ds(i * block_n, block_n)]
    read = pltpu.make_async_copy(rows, dx_buf.at[slot], rd_sem.at[slot])
    write = pltpu.make_async_copy(dx_buf.at[slot], rows, wr_sem.at[slot])

    @pl.when(k >= slots)
    def _slot_free():
        write.wait()

    @pl.when(j > 0)
    def _fetch():
        read.start()

    z = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    col = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)
    if pad_vocab:  # see _xent_fwd_kernel: identity when unpadded
        z = jnp.where(col < vocab, z, NEG_INF)
    lse = jnp.max(lse_ref[:], axis=1, keepdims=True)
    p = jnp.exp(z - lse)  # vocab-padding cols give 0
    y = (col == labels_ref[:]).astype(jnp.float32)
    dl = jnp.max(dl_ref[:], axis=1, keepdims=True)
    g = (p - y) * dl  # [block_n, block_v]

    @pl.when(i == 0)
    def _init_dw():
        dw_acc[:] = jnp.zeros_like(dw_acc)

    dw_acc[:] = dw_acc[:] + jax.lax.dot_general(
        x_ref[:], g.astype(x_ref.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == nn - 1)
    def _write_dw():
        dw_ref[:] = dw_acc[:].astype(dw_ref.dtype)

    # First vocab sweep: nothing to read yet (the HBM rows are
    # uninitialized), the sum starts from zero.
    @pl.when(j == 0)
    def _init_dx():
        dx_buf[slot] = jnp.zeros(dx_buf.shape[1:], dx_buf.dtype)

    @pl.when(j > 0)
    def _fetched():
        read.wait()

    dx_buf[slot] = dx_buf[slot] + jax.lax.dot_general(
        g.astype(w_ref.dtype), w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    write.start()

    @pl.when(k == pl.num_programs(0) * nn - 1)
    def _drain():  # one write is still out on every slot
        for s in range(slots):
            pltpu.make_async_copy(dx_buf.at[s], rows, wr_sem.at[s]).wait()


def _pad_rows(a, block, fill=0):
    pad = (-a.shape[0]) % block
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=fill)
    return a


def _stats(x, n_pad):
    """[N] -> [N_pad, _STAT_LANES] broadcast blocks."""
    x = jnp.pad(x, ((0, n_pad - x.shape[0]),))
    return jnp.broadcast_to(x[:, None], (x.shape[0], _STAT_LANES))


def _interp():
    return ring._interpret_mode()


# Mosaic's default scoped-VMEM budget is 16 MiB — tuned for small kernels,
# not for an LM-head block carrying an [E, block_v] f32 accumulator plus
# double-buffered bf16 operand blocks (at E=2048, block_v=512 the old dW
# pass needed ~17 MiB and the first real-silicon stage-B' run died on
# exactly that).  v5e/v5p have 128 MiB of physical VMEM; declare an honest
# larger scope and, for truly huge shapes, shrink the blocks until the
# estimate fits.
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_BUDGET = 88 * 1024 * 1024

# The backward's own tile where the caller names none.  Per tile it moves
# block_n * E * (itemsize + 8) bytes (x in, f32 dx rows in and out) under
# 6 * block_n * E * block_v flops: block_v alone sets HBM time against MXU
# time, and 1024 keeps it near 2/5 on a v5e for bf16 (512, the forward's
# default, would be 4/5).  _fit_blocks narrows it where E is too large.
_BWD_BLOCK_N = 256
_BWD_BLOCK_V = 1024


def _bwd_vmem_bytes(bn: int, bv: int, embed: int, ds: int) -> int:
    """Upper-bound scoped-VMEM estimate for the backward kernel:
    double-buffered input blocks, the [E, bv] f32 dW accumulator and its
    double-buffered output block, the two [bn, E] f32 dx buffers, one f32
    product of each shape before it is added in, and ~4 [bn, bv] f32
    temporaries for z/p/g/col.  dW's part scales with E*bv, dx's with
    bn*E: both must fit."""
    ins = 2 * (bn * embed + embed * bv) * ds
    temps = 4 * bn * bv * 4
    dw = embed * bv * (2 * 4 + 2 * ds)
    return ins + dw + 3 * bn * embed * 4 + temps


def _fit_blocks(bn: int, bv: int, embed: int, ds: int):
    """Shrink (block_n, block_v) until the backward estimate fits the
    scoped-VMEM budget.  Vocab blocks shrink first (the [E, bv] f32
    accumulator dominates); 128 is the lane-tile floor for both."""
    while _bwd_vmem_bytes(bn, bv, embed, ds) > _VMEM_BUDGET and bv > _LANES:
        bv = max(_LANES, bv // 2)
    while _bwd_vmem_bytes(bn, bv, embed, ds) > _VMEM_BUDGET and bn > _LANES:
        bn = max(_LANES, bn // 2)
    return bn, bv


def _kernel_params(interpret, major: str):
    """Compiler params for the device-local xent kernels: the interpret
    barrier skip (ring.local_kernel_params) under interpret; on real
    TPU lowering the raised scoped-VMEM limit plus grid semantics.  Both
    kernels run 2-D grids whose VMEM state carries across the minor dim.
    The forward's major dim is ``"parallel"`` (its scratch is
    re-initialized at each minor sweep's first step, so Mosaic may
    pipeline or split across it, see flash._flash_params); the
    backward's must be ``"arbitrary"``: the dx accumulator in HBM carries
    across it."""
    if interpret:
        return ring.local_kernel_params(interpret)
    return pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_LIMIT,
        dimension_semantics=(major, "arbitrary"))


def _fused_xent_fwd(x, w, labels, block_n: int, block_v: int, interpret):
    N, E = x.shape
    V = w.shape[1]
    block_n = min(block_n, N)
    block_v = min(block_v, V)
    xp = _pad_rows(x, block_n)
    labp = _pad_rows(labels.astype(jnp.int32)[:, None], block_n, fill=-1)
    pad_v = (-V) % block_v
    wp = jnp.pad(w, ((0, 0), (0, pad_v))) if pad_v else w
    Np, Vp = xp.shape[0], wp.shape[1]
    grid = (Np // block_n, Vp // block_v)
    kern = functools.partial(_xent_fwd_kernel, block_n=block_n,
                             block_v=block_v, vocab=V,
                             pad_vocab=pad_v > 0)
    loss, lse = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((Np, _STAT_LANES), jnp.float32),
                   jax.ShapeDtypeStruct((Np, _STAT_LANES), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, E), lambda i, j: (i, 0)),
            pl.BlockSpec((E, block_v), lambda i, j: (0, j)),
        ],
        out_specs=(pl.BlockSpec((block_n, _STAT_LANES),
                                lambda i, j: (i, 0)),) * 2,
        scratch_shapes=[pltpu.VMEM((block_n, _LANES), jnp.float32)] * 3,
        interpret=interpret,
        compiler_params=_kernel_params(interpret, "parallel"),
        metadata=ring.kernel_identity("xent.fwd"),
    )(labp, xp, wp)
    return loss[:N, 0], lse[:N, 0]


def fused_linear_cross_entropy(x, w, labels, *,
                               block_n: Optional[int] = None,
                               block_v: Optional[int] = None,
                               interpret=None):
    """Per-token ``softmax_xent(x @ w, labels)`` without materializing
    logits.

    ``x``: [N, E] activations; ``w``: [E, V] unembedding; ``labels``: [N]
    int.  Returns f32 loss [N].  Differentiable (custom VJP): the backward
    recomputes blockwise probabilities from the saved lse — peak memory is
    O(block_n * block_v + block_n * E + E * block_v) versus the naive
    O(N * V) logits + softmax.  E rides whole in VMEM: sized for LM heads
    (E up to a few thousand), not for E-sharded tensor parallelism — shard
    E outside and psum the partial logits instead if E is huge.

    ``block_n`` / ``block_v`` given: both kernels tile by them.  Left
    ``None``: the forward takes ``Config.xent_block_n`` / ``xent_block_v``
    (what ``benchmarks/autotune.py`` measures), the backward its own wider
    vocab block (``_BWD_BLOCK_V``); either way shrunk to the VMEM budget.
    """
    if interpret is None:
        interpret = _interp()
    from .. import runtime

    embed, ds = x.shape[1], jnp.dtype(x.dtype).itemsize
    fwd_blocks = _fit_blocks(*runtime.resolve_blocks(
        block_n, block_v, "xent_block_n", "xent_block_v"), embed, ds)
    bwd_blocks = _fit_blocks(block_n or _BWD_BLOCK_N,
                             block_v or _BWD_BLOCK_V, embed, ds)
    return _xent_vjp(fwd_blocks, bwd_blocks, interpret)(x, w, labels)


@functools.lru_cache(maxsize=None)
def _xent_vjp(fwd_blocks, bwd_blocks, interp_key):
    @jax.custom_vjp
    def f(x, w, labels):
        return _fused_xent_fwd(x, w, labels, *fwd_blocks, interp_key)[0]

    def fwd(x, w, labels):
        loss, lse = _fused_xent_fwd(x, w, labels, *fwd_blocks, interp_key)
        return loss, (x, w, labels, lse)

    def bwd(res, dloss):
        x, w, labels, lse = res
        N, E = x.shape
        V = w.shape[1]
        # Whole sublane tiles even where N is smaller than the block: the
        # dx rows move by DMA, which (unlike a block that spans its whole
        # array) Mosaic refuses at a row count off the f32 tiling of 8.
        bn = min(bwd_blocks[0], pl.cdiv(N, 8) * 8)
        bv = min(bwd_blocks[1], V)
        xp = _pad_rows(x, bn)
        labp = _pad_rows(labels.astype(jnp.int32)[:, None], bn, fill=-1)
        pad_v = (-V) % bv
        wp = jnp.pad(w, ((0, 0), (0, pad_v))) if pad_v else w
        Np, Vp = xp.shape[0], wp.shape[1]
        # Padded rows: label -1 never matches, and lse=+1e30 makes p == 0,
        # so they contribute nothing to dW (and their dx rows are sliced).
        lse_l = _stats(jnp.where(jnp.isfinite(lse), lse, 0.0), Np)
        lse_l = lse_l.at[N:].set(-NEG_INF) if Np > N else lse_l
        dl_l = _stats(dloss.astype(jnp.float32), Np)

        nn_, nv_ = Np // bn, Vp // bv
        slots = min(2, nn_)
        kern = functools.partial(_xent_bwd_kernel, block_n=bn, block_v=bv,
                                 vocab=V, pad_vocab=pad_v > 0, slots=slots)
        # The identity is xent.dw's: a grid with vocab major and tokens
        # minor and a resident dW accumulator, with dx riding along (and
        # chipbench's xent_bwd_ms_per_step.tok matches xent.dx / xent.dw).
        dw, dx = pl.pallas_call(
            kern,
            out_shape=(jax.ShapeDtypeStruct((E, Vp), w.dtype),
                       jax.ShapeDtypeStruct((Np, E), jnp.float32)),
            grid=(nv_, nn_),
            in_specs=[
                pl.BlockSpec((bn, 1), lambda j, i: (i, 0)),
                pl.BlockSpec((bn, E), lambda j, i: (i, 0)),
                pl.BlockSpec((E, bv), lambda j, i: (0, j)),
                pl.BlockSpec((bn, _STAT_LANES), lambda j, i: (i, 0)),
                pl.BlockSpec((bn, _STAT_LANES), lambda j, i: (i, 0)),
            ],
            out_specs=(pl.BlockSpec((E, bv), lambda j, i: (0, j)),
                       pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((E, bv), jnp.float32),
                            pltpu.VMEM((slots, bn, E), jnp.float32),
                            pltpu.SemaphoreType.DMA((slots,)),
                            pltpu.SemaphoreType.DMA((slots,))],
            interpret=interp_key,
            compiler_params=_kernel_params(interp_key, "arbitrary"),
            metadata=ring.kernel_identity("xent.dw"),
        )(labp, xp, wp, lse_l, dl_l)
        if pad_v:
            dw = dw[:, :V]
        return dx[:N].astype(x.dtype), dw, _float0_zero(labels)

    f.defvjp(fwd, bwd)
    return f
