"""Fused linear + softmax cross-entropy Pallas kernel.

The LM-head loss is the other memory hog of long-context training (after
attention): computing ``softmax_xent(x @ W, labels)`` materializes a
[tokens, vocab] logits matrix (plus its f32 softmax) in HBM.  This kernel
streams vocab blocks through VMEM with an online log-sum-exp — logits never
exist in memory — and the custom VJP recomputes probabilities blockwise for
``dx`` and ``dW``, so peak memory is O(block) instead of O(tokens x vocab).

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


def _xent_bwd_dx_kernel(labels_ref, x_ref, w_ref, lse_ref, dl_ref, dx_ref,
                        dx_acc, *, block_n: int, block_v: int,
                        vocab: int, pad_vocab: bool):
    """dx_i = dloss_i * sum_v (p_iv - y_iv) W_v^T, p recomputed from lse."""
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        dx_acc[:] = jnp.zeros_like(dx_acc)

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
    dx_acc[:] = dx_acc[:] + jax.lax.dot_general(
        g.astype(w_ref.dtype), w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nv - 1)
    def _finalize():
        dx_ref[:] = dx_acc[:].astype(dx_ref.dtype)


def _xent_bwd_dw_kernel(labels_ref, x_ref, w_ref, lse_ref, dl_ref, dw_ref,
                        dw_acc, *, block_n: int, block_v: int,
                        vocab: int, pad_vocab: bool):
    """dW_v = sum_i x_i^T (p_iv - y_iv) dloss_i.  Grid (nv, nn): the token
    dimension is minor so the dW accumulator carries across it."""
    i = pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        dw_acc[:] = jnp.zeros_like(dw_acc)

    j = pl.program_id(0)
    z = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    col = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)
    if pad_vocab:  # see _xent_fwd_kernel: identity when unpadded
        z = jnp.where(col < vocab, z, NEG_INF)
    lse = jnp.max(lse_ref[:], axis=1, keepdims=True)
    p = jnp.exp(z - lse)
    y = (col == labels_ref[:]).astype(jnp.float32)
    dl = jnp.max(dl_ref[:], axis=1, keepdims=True)
    g = (p - y) * dl
    dw_acc[:] = dw_acc[:] + jax.lax.dot_general(
        x_ref[:], g.astype(x_ref.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == nn - 1)
    def _finalize():
        dw_ref[:] = dw_acc[:].astype(dw_ref.dtype)


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
# not for an LM-head block carrying two [E, block_v] f32 accumulators plus
# double-buffered bf16 operand blocks (at E=2048, block_v=512 the dW pass
# needs ~17 MiB and the first real-silicon stage-B' run died on exactly
# that).  v5e/v5p have 128 MiB of physical VMEM; declare an honest larger
# scope and, for truly huge shapes, shrink the vocab block until the
# estimate fits.
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_BUDGET = 88 * 1024 * 1024


def _bwd_vmem_bytes(bn: int, bv: int, embed: int, ds: int) -> int:
    """Upper-bound scoped-VMEM estimate for the backward pass: the max
    of the dx and dW kernels' footprints (each: double-buffered input
    blocks, double-buffered f32 output + f32 accumulator scratch, and
    ~4 [bn, bv] f32 temporaries for z/p/g/col).  dW's out/accumulator
    scale with E*bv, dx's with bn*E — both must fit (code review r4:
    modelling only dW passes configs whose dx kernel overflows)."""
    ins = 2 * (bn * embed + embed * bv) * ds
    temps = 4 * bn * bv * 4
    dw = ins + 3 * embed * bv * 4 + temps
    dx = ins + 3 * bn * embed * 4 + temps
    return max(dw, dx)


def _fit_blocks(bn: int, bv: int, embed: int, ds: int):
    """Shrink (block_n, block_v) until the backward estimate fits the
    scoped-VMEM budget.  Vocab blocks shrink first (the [E, bv] f32
    accumulators dominate); 128 is the lane-tile floor for both."""
    while _bwd_vmem_bytes(bn, bv, embed, ds) > _VMEM_BUDGET and bv > _LANES:
        bv = max(_LANES, bv // 2)
    while _bwd_vmem_bytes(bn, bv, embed, ds) > _VMEM_BUDGET and bn > _LANES:
        bn = max(_LANES, bn // 2)
    return bn, bv


def _kernel_params(interpret):
    """Compiler params for the device-local xent kernels: the interpret
    barrier skip (ring.local_kernel_params) under interpret; on real
    TPU lowering the raised scoped-VMEM limit plus grid semantics — all
    three kernels run 2-D grids whose scratch carries only across the
    MINOR dim (re-initialized at its first step), so the major dim is
    parallel and Mosaic may pipeline across it (see
    flash._flash_params)."""
    if interpret:
        return ring.local_kernel_params(interpret)
    return pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_LIMIT,
        dimension_semantics=("parallel", "arbitrary"))


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
        compiler_params=_kernel_params(interpret),
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
    """
    if interpret is None:
        interpret = _interp()
    from .. import runtime

    block_n, block_v = runtime.resolve_blocks(
        block_n, block_v, "xent_block_n", "xent_block_v")
    block_n, block_v = _fit_blocks(block_n, block_v, x.shape[1],
                                   jnp.dtype(x.dtype).itemsize)
    f = _xent_vjp(x.shape[1], block_n, block_v, interpret)
    return f(x, w, labels)


@functools.lru_cache(maxsize=None)
def _xent_vjp(embed: int, block_n: int, block_v: int, interp_key):
    @jax.custom_vjp
    def f(x, w, labels):
        return _fused_xent_fwd(x, w, labels, block_n, block_v,
                               interp_key)[0]

    def fwd(x, w, labels):
        loss, lse = _fused_xent_fwd(x, w, labels, block_n, block_v,
                                    interp_key)
        return loss, (x, w, labels, lse)

    def bwd(res, dloss):
        x, w, labels, lse = res
        N, E = x.shape
        V = w.shape[1]
        bn = min(block_n, N)
        bv = min(block_v, V)
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
        dx_kern = functools.partial(_xent_bwd_dx_kernel, block_n=bn,
                                    block_v=bv, vocab=V,
                                    pad_vocab=pad_v > 0)
        dx = pl.pallas_call(
            dx_kern,
            out_shape=jax.ShapeDtypeStruct((Np, E), jnp.float32),
            grid=(nn_, nv_),
            in_specs=[
                pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((bn, E), lambda i, j: (i, 0)),
                pl.BlockSpec((E, bv), lambda i, j: (0, j)),
                pl.BlockSpec((bn, _STAT_LANES), lambda i, j: (i, 0)),
                pl.BlockSpec((bn, _STAT_LANES), lambda i, j: (i, 0)),
            ],
            out_specs=pl.BlockSpec((bn, E), lambda i, j: (i, 0)),
            scratch_shapes=[pltpu.VMEM((bn, E), jnp.float32)],
            interpret=interp_key,
            compiler_params=_kernel_params(interp_key),
            metadata=ring.kernel_identity("xent.dx"),
        )(labp, xp, wp, lse_l, dl_l)

        dw_kern = functools.partial(_xent_bwd_dw_kernel, block_n=bn,
                                    block_v=bv, vocab=V,
                                    pad_vocab=pad_v > 0)
        dw = pl.pallas_call(
            dw_kern,
            out_shape=jax.ShapeDtypeStruct((E, Vp), jnp.float32),
            grid=(nv_, nn_),
            in_specs=[
                pl.BlockSpec((bn, 1), lambda j, i: (i, 0)),
                pl.BlockSpec((bn, E), lambda j, i: (i, 0)),
                pl.BlockSpec((E, bv), lambda j, i: (0, j)),
                pl.BlockSpec((bn, _STAT_LANES), lambda j, i: (i, 0)),
                pl.BlockSpec((bn, _STAT_LANES), lambda j, i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((E, bv), lambda j, i: (0, j)),
            scratch_shapes=[pltpu.VMEM((E, bv), jnp.float32)],
            interpret=interp_key,
            compiler_params=_kernel_params(interp_key),
            metadata=ring.kernel_identity("xent.dw"),
        )(labp, xp, wp, lse_l, dl_l)
        if pad_v:
            dw = dw[:, :V]
        return (dx[:N].astype(x.dtype), dw.astype(w.dtype),
                _float0_zero(labels))

    f.defvjp(fwd, bwd)
    return f
