"""Pallas TPU flash attention: the hot-op kernel for the compute path.

The reference has no attention anywhere (pre-transformer, SURVEY.md §6.7);
this kernel serves the beyond-reference long-context stack
(parallel/sequence.py, models/transformer.py) the TPU-first way: blocked
online-softmax attention that never materializes the [T, T] score matrix,
streaming K/V blocks through VMEM while the accumulator lives in VMEM
scratch across grid steps.  MXU-friendly: both matmuls per block are
[block_q, D] x [D, block_k] and [block_q, block_k] x [block_k, D] with f32
accumulation (``preferred_element_type``), bf16-ready inputs.

Why scratch-across-grid works: the TPU grid is executed sequentially with
the last dimension minor, so the (m, l, acc) scratch carries the running
softmax state across the k-block dimension for one (batch, head, q-block)
triple, exactly the flash-attention recurrence.

What carries across which grid axis.  Forward, grid (B, H, q block, kv
block): (m, l, acc) across the minor kv axis only, so the three outer axes
are ``"parallel"``.  Backward, ONE kernel on the grid (B, H, kv block, q
block) that computes each live tile's s, p, dp and ds once and issues all
five products from them: dk/dv carry in scratch across the minor q axis
for one kv block; dq carries across BOTH minor axes for one (batch, head),
in an output block that holds every q row of the head and stays in VMEM
until the head is done.  Two grid steps of one head may add into the same
dq rows, so the two minor axes are ``"arbitrary"`` (sequential, in grid
order: that order is also what keeps every sum's order fixed); batch and
head stay ``"parallel"``.

``q_offset``/``kv_offset`` place the local q and kv blocks at global
sequence positions and may be TRACED scalars (they ride in SMEM), so the
same kernel computes ring attention's per-step blocks inside ``shard_map``
where the kv owner — hence its offset — depends on ``lax.axis_index``.
``return_residuals=True`` returns the un-normalized numerator plus the
(m, l) softmax statistics, the contract ring attention's cross-block
combiner needs (parallel/sequence.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ring

# Finite stand-in for -inf in masked scores: keeps exp() exactly 0 without
# producing (-inf) - (-inf) = nan in the running-max rescale.
NEG_INF = -1e30

# Lane width of the VMEM m/l scratch: rows are stored broadcast across a
# full 128-lane vector so every read/write is a full-tile op (same layout
# the TPU flash kernels in jax use); per-row values are recovered with a
# lane-reduce.
_LANES = 128

# Lane width of the (optional) m/l residual OUTPUTS: 8 lanes keep the HBM
# footprint at Tq*8 floats per (batch, head) instead of Tq*128 while still
# writing full rows of the f32 (8, 128)-tile layout.
_STAT_LANES = 8



# What carries across which grid axis (module docstring): the forward's
# scratch across the minor kv axis only; the backward's dk/dv across the
# minor q axis and its resident dq across both minor axes.
_FWD_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")
_BWD_SEMANTICS = ("parallel", "parallel", "arbitrary", "arbitrary")

# The backward keeps the float32 dq of one (batch, head) resident in VMEM
# (flash_attention_bwd): two pipeline buffers of T_q x D x 4 bytes, 8 MB at
# 8192 x 128, beside about 4 MB of [512, 512] float32 intermediates and
# 2-3 MB of blocks.  Of the chip's 128 MiB the buffers may take 64 (q
# spans beyond that, _q_span_blocks).  Under Mosaic's scoped default of
# 16 MiB the chip's compiler takes 8192 rows and refuses 16384 ("Ran out
# of memory in memory space vmem"), so the limit is raised as ops/xent.py
# and ops/moe.py raise theirs: 65,536 rows then compile.
_DQ_RESIDENT_BYTES = 64 * 1024 * 1024
_BWD_VMEM_LIMIT = 100 * 1024 * 1024


def _flash_params(interpret, semantics=_FWD_SEMANTICS,
                  vmem_limit_bytes=None):
    """Compiler params for the flash kernels.  Interpret: the device-
    local barrier skip (ring.local_kernel_params).  Real Mosaic
    lowering: ``semantics`` marks a grid dim ``"arbitrary"`` when kernel
    state carries across it and ``"parallel"`` when it does not, which
    lets Mosaic schedule/pipeline across those grid steps instead of
    assuming a serial carried dependency (the jax TPU flash kernels mark
    their grids the same way)."""
    if interpret:
        return ring.local_kernel_params(interpret)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem_limit_bytes)


def _resolve_blocks(block_a, block_b, field_a: str, field_b: str):
    """Config-default tiling resolution — see runtime.resolve_blocks
    (deferred import: ops must stay importable before the runtime)."""
    from .. import runtime

    return runtime.resolve_blocks(block_a, block_b, field_a, field_b)


def _prescale_enabled() -> bool:
    """``Config.flash_prescale`` (see config.py): fold the attention
    scale into q once instead of scaling every score block."""
    from .. import runtime

    return bool(runtime.effective_config().flash_prescale)


def _prescale_q(q, scale):
    """q' = q * scale in q's dtype — one [B, T, H, D] pass replacing a
    [block_q, block_k] pass per live block inside the kernel."""
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _block_live(qo_ref, ko_ref, i, j, block_q: int, block_k: int,
                kv_len: int, causal: bool, window: Optional[int] = None):
    """Scalar predicate: does block (i, j) have ANY valid score?  The
    block-granular complement of :func:`_valid_mask` — a block is dead
    when its first k position is past the last q row (causal), when its
    last k position is before the oldest key the block's FIRST q row may
    see (sliding ``window`` — the first q row reaches furthest back), or
    when it is past the kv length.  The
    kv-length clause is purely defensive — callers pad by less than one
    block, so the last k block always holds >=1 valid key and in-block
    padding exclusion is _valid_mask's job.  Offsets are traced SMEM
    scalars (ring attention), so this is a runtime predicate, not grid
    pruning; for causal self-attention it halves the compute, and with a
    window the live band is O(window/block_k) blocks per q block — the
    kernel's cost becomes O(T * window) regardless of T.  Forward and
    backward kernels MUST skip identically, so all of them call this one
    helper."""
    k_first = ko_ref[0] + j * block_k
    live = k_first < ko_ref[0] + kv_len
    if causal:
        q_first = qo_ref[0] + i * block_q
        live = jnp.logical_and(live, k_first <= q_first + (block_q - 1))
        if window is not None:
            # The OLDEST q row in the block (q_first) reaches furthest
            # back: it sees keys >= q_first - (window - 1).  A k block
            # whose last key is older than that serves no q row here.
            live = jnp.logical_and(
                live, k_first + (block_k - 1) >= q_first - (window - 1))
    return live


def _block_full(qo_ref, ko_ref, i, j, block_q: int, block_k: int,
                kv_len: int, causal: bool, window: Optional[int] = None):
    """Scalar predicate: does block (i, j) have NO masked score at all?
    The complement question to :func:`_block_live` — a block is FULL when
    every (q row, k col) pair is valid: the k block sits entirely inside
    the kv length, entirely in the causal past of the block's OLDEST q
    row (k_last <= q_first), and (sliding window) entirely inside the
    window of the block's NEWEST q row (q_last - k_first < window).

    Why it exists (VERDICT r4 #2): the per-block VPU work — two iotas,
    compares, logical-ands and a [block_q, block_k] select — costs more
    than the block's two MXU matmuls at production shapes, and for
    causal T=4096 at 512x512 blocks ~78% of live blocks are interior
    (mask all-true).  Splitting the update into a full path (no mask
    math) and a partial path keeps numerics bit-identical: on a full
    block the mask is the identity.  Forward and backward kernels share
    this ONE predicate so they specialize identically."""
    k_first = ko_ref[0] + j * block_k
    k_last = k_first + (block_k - 1)
    full = k_last < ko_ref[0] + kv_len
    if causal:
        q_first = qo_ref[0] + i * block_q
        full = jnp.logical_and(full, k_last <= q_first)
        if window is not None:
            q_last = q_first + (block_q - 1)
            full = jnp.logical_and(full, q_last - k_first < window)
    return full


def _gqa_group(h: int, h_kv: int) -> int:
    """Query-heads-per-kv-head (grouped-query attention).  1 == MHA;
    kv head for q head ``h`` is ``h // group`` (the jnp.repeat layout)."""
    if h_kv == h:
        return 1
    if h_kv < 1 or h % h_kv != 0:
        raise ValueError(f"num q heads {h} must be a multiple of kv "
                         f"heads {h_kv}")
    return h // h_kv


def _check_window(window: Optional[int], causal: bool) -> None:
    """Sliding windows are defined over causal order: ``window`` counts
    the query itself plus the ``window - 1`` keys before it."""
    if window is None:
        return
    if not causal:
        raise ValueError("window= requires causal=True (a sliding window "
                         "is defined over causal order)")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


def _clamp_block(block: int, t: int, align: int = 128) -> int:
    """Clamp a config-default block size to a sequence of length ``t``
    without producing tile-unaligned block shapes: a block larger than
    ``t`` becomes ``t`` rounded UP to ``align`` (the input is then padded
    to one full block), never a raw ``min(block, t)`` that Mosaic may
    refuse to tile (e.g. t=300).  Explicit caller-passed blocks <= t are
    respected as-is."""
    if block >= t:
        return -(-t // align) * align
    return block



def _kv_band_start(i, *, qo: int, ko: int, window: int, block_q: int,
                   block_k: int, nk: int, n_band: int):
    """First kv-block index of q-block ``i``'s live band (static offsets).

    The oldest key q-block i can see is ``qo + i*block_q - (window-1)``;
    clamped so the whole band [start, start + n_band) stays inside
    [0, nk) — edge bands cover extra blocks that _block_live then skips.
    MUST match the kv index_map exactly (the kernel recomputes the true
    block index from its band position with this same function)."""
    lo = qo + i * block_q - (window - 1) - ko
    return jnp.clip(jnp.floor_divide(lo, block_k), 0, max(nk - n_band, 0))


def _q_band_start(j, *, qo: int, ko: int, window: int, block_q: int,
                  block_k: int, nq: int, n_band: int):
    """First q-block index of kv-block ``j``'s live band (static offsets):
    the oldest query that can see this block is ``ko + j*block_k - qo``
    (causal).  Same clamp/edge contract as :func:`_kv_band_start`."""
    lo = ko + j * block_k - qo
    return jnp.clip(jnp.floor_divide(lo, block_q), 0, max(nq - n_band, 0))


def _band_setup(window, causal, q_offset, kv_offset, *, span_block: int,
                step_block: int, n_total: int, start_fn, **start_kw):
    """(band_start_fn | None, minor grid size): the ONE place the banded
    sliding-window grid is derived, so the kernel's recomputed block
    index and the index_map can never disagree.  ``span_block`` is the
    major dim's block size (its rows define the band's reach),
    ``step_block`` the minor dim's.  Returns (None, n_total) — full grid
    — unless a window is set, masking is causal, offsets are static
    Python ints, and the band is actually narrower than the full axis."""
    if (window is None or not causal or not isinstance(q_offset, int)
            or not isinstance(kv_offset, int)):
        return None, n_total
    n_band = min(n_total, (span_block + window - 2) // step_block + 2)
    if n_band >= n_total:
        return None, n_total
    fn = functools.partial(start_fn, qo=q_offset, ko=kv_offset,
                           window=window, n_band=n_band, **start_kw)
    return fn, n_band


def _banded_minor_map(band_fn, head_group: int = 1):
    """Minor-axis BlockSpec index_map: grid position ``minor`` offset by
    the band start of ``major`` (identity map when not banded).
    ``head_group`` > 1 is GQA: q head ``h`` reads kv head ``h // group``
    (consecutive q heads share a kv head, the jnp.repeat layout)."""
    g = head_group
    if band_fn is None:
        return lambda b, h, major, minor: (b, h // g, minor, 0)
    return lambda b, h, major, minor: (b, h // g, band_fn(major) + minor, 0)


def _valid_mask(qo_ref, ko_ref, i, j, block_q: int, block_k: int,
                kv_len: int, causal: bool, window: Optional[int] = None):
    """[block_q, block_k] score-validity mask: k-padding rows out, (for
    causal) global q position >= global k position, and (for sliding
    ``window``, causal-only) global q position - global k position <
    ``window`` — each q attends to itself and the ``window - 1`` keys
    before it.  Forward and backward kernels MUST mask identically — the
    backward recomputes p against the forward's lse — so all of them call
    this one helper."""
    kv_offset = ko_ref[0]
    k_global = kv_offset + j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = k_global < kv_offset + kv_len
    if causal:
        q_global = qo_ref[0] + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = jnp.logical_and(valid, q_global >= k_global)
        if window is not None:
            valid = jnp.logical_and(valid, q_global - k_global < window)
    return valid


def _flash_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  kv_len: int, residuals: bool,
                  window: Optional[int] = None, band_j0=None):
    if residuals:
        m_out_ref, l_out_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    jb = pl.program_id(3)  # band position when band_j0, else kv block
    nb = pl.num_programs(3)

    @pl.when(jb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    i = pl.program_id(2)
    # Banded grid (static offsets + window): the grid's minor dim spans
    # only the O(window/block_k) live band; recover the true kv-block
    # index with the SAME band-start function the index_map used.
    j = band_j0(i) + jb if band_j0 is not None else jb
    live = _block_live(qo_ref, ko_ref, i, j, block_q, block_k, kv_len,
                       causal, window)
    full = _block_full(qo_ref, ko_ref, i, j, block_q, block_k, kv_len,
                       causal, window)

    def _update(masked):
        q = q_ref[0, 0]  # [block_q, D]
        k = k_ref[0, 0]  # [block_k, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk]
        if scale != 1.0:  # statically elided under Config.flash_prescale
            s = s * scale

        if masked:
            s = jnp.where(_valid_mask(qo_ref, ko_ref, i, j, block_q,
                                      block_k, kv_len, causal, window),
                          s, NEG_INF)

        m_prev = jnp.max(m_ref[:], axis=1, keepdims=True)  # [block_q, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Fully-masked-so-far rows have m_new == NEG_INF; exponentiate
        # against 0 there so masked scores give p == 0, not
        # exp(-1e30 + 1e30) == 1.  (A FULL block always yields finite
        # m_new, but the rescale must still guard m_prev rows from
        # earlier fully-masked blocks, so the guard stays in both paths.)
        m_safe = jnp.where(m_new > 0.5 * NEG_INF, m_new, 0.0)
        alpha = jnp.exp(m_prev - m_safe)  # 0 when m_prev is NEG_INF (init)
        p = jnp.exp(s - m_safe)  # masked entries: exp(NEG_INF) == 0
        l_prev = jnp.max(l_ref[:], axis=1, keepdims=True)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    # Full blocks (the interior majority at production shapes) skip the
    # iota/compare/select mask math entirely — see _block_full.
    @pl.when(jnp.logical_and(live, full))
    def _update_full():
        _update(masked=False)

    @pl.when(jnp.logical_and(live, jnp.logical_not(full)))
    def _update_partial():
        _update(masked=True)

    @pl.when(jb == nb - 1)
    def _finalize():
        # Read the running state back from scratch (NOT the _update
        # locals): the final j block can itself be skipped, e.g. the
        # first q block of a causal layout never sees the last k block.
        m_fin = jnp.max(m_ref[:], axis=1, keepdims=True)  # [block_q, 1]
        l_fin = jnp.max(l_ref[:], axis=1, keepdims=True)
        if residuals:
            # Numerator + statistics for a cross-block combiner; rows whose
            # every key was masked carry m == NEG_INF, l == 0, acc == 0.
            o_ref[0, 0] = acc_ref[:].astype(o_ref.dtype)
            m_out_ref[0, 0] = jnp.broadcast_to(m_fin,
                                               (block_q, _STAT_LANES))
            l_out_ref[0, 0] = jnp.broadcast_to(l_fin,
                                               (block_q, _STAT_LANES))
        else:
            # Fully-masked rows (l == 0) read as zeros, matching the
            # parallel variants' convention in parallel/sequence.py.
            denom = jnp.where(l_fin > 0, l_fin, 1.0)
            o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def _flash_bwd_kernel(qo_ref, ko_ref, k_ref, v_ref, q_ref, do_ref, lse_ref,
                      d_ref, dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                      scale: float, causal: bool, block_q: int, block_k: int,
                      kv_len: int, window: Optional[int] = None,
                      band_i0=None):
    """The whole backward of one (q, kv) tile: p is recomputed from lse
    ONCE and feeds all three gradients,

        dv_j += p_ij^T dO_i
        dk_j += scale * ds_ij^T q_i      ds_ij = p_ij * (dO_i . v_j - D_i)
        dq_i += scale * ds_ij k_j

    five products a live tile.  Grid (B, H, nk, nq) — or (B, H, nk,
    n_band) on the banded window path: kv block major, q block minor.
    dk/dv carry in VMEM scratch across the minor q axis for one kv block.
    dq carries across BOTH minor axes: its output block is every q row of
    one (batch, head), resident in VMEM from the head's first grid step
    (zeroed there) to its last (written back once, when the block index
    moves on), and each tile adds into its q block's rows.  A q block
    meets its kv blocks in ascending j and a kv block its q blocks in
    ascending i, so all three sums run in the order of a kernel that
    computed one of them alone."""
    j = pl.program_id(2)
    ib = pl.program_id(3)  # band position when band_i0, else q block
    nb = pl.num_programs(3)

    @pl.when(jnp.logical_and(j == 0, ib == 0))
    def _init_head():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(ib == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    i = band_i0(j) + ib if band_i0 is not None else ib
    # For this kv block, q blocks entirely in its past (causal)
    # contribute p == 0 — skip all five matmuls.  (Padded keys inside a
    # live block are excluded by _valid_mask, not here.)
    live = _block_live(qo_ref, ko_ref, i, j, block_q, block_k, kv_len,
                       causal, window)
    full = _block_full(qo_ref, ko_ref, i, j, block_q, block_k, kv_len,
                       causal, window)

    def _update(masked):
        k = k_ref[0, 0]  # [block_k, D]
        v = v_ref[0, 0]
        q = q_ref[0, 0]  # [block_q, D]
        do = do_ref[0, 0]
        lse = jnp.max(lse_ref[0, 0], axis=1, keepdims=True)  # [block_q, 1]
        dvec = jnp.max(d_ref[0, 0], axis=1, keepdims=True)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if scale != 1.0:  # statically elided under Config.flash_prescale
            s = s * scale
        if masked:
            s = jnp.where(_valid_mask(qo_ref, ko_ref, i, j, block_q,
                                      block_k, kv_len, causal, window),
                          s, NEG_INF)
        p = jnp.exp(s - lse)  # masked / fully-masked rows (lse=+1e30): 0

        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [block_q, block_k]
        ds = p * (dp - dvec)
        dkq = jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] = dk_acc[:] + (scale * dkq if scale != 1.0 else dkq)
        dqk = jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        dq_ref[0, 0, rows, :] = dq_ref[0, 0, rows, :] + (
            scale * dqk if scale != 1.0 else dqk)

    @pl.when(jnp.logical_and(live, full))
    def _update_full():
        _update(masked=False)

    @pl.when(jnp.logical_and(live, jnp.logical_not(full)))
    def _update_partial():
        _update(masked=True)

    @pl.when(ib == nb - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None, q_offset=0, kv_offset=0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None,
                    return_residuals: bool = False, interpret=None):
    """Blocked flash attention on one device.

    ``q``: [B, T_q, H, D]; ``k``/``v``: [B, T_kv, H_kv, D] (the bqhd
    layout of parallel/sequence.py).  ``H_kv`` may be a divisor of ``H``
    (grouped-query attention): q head ``h`` attends against kv head
    ``h // (H // H_kv)`` — the ``jnp.repeat`` layout — with the kv blocks
    fetched once per group straight from the ``H_kv``-headed arrays, no
    repeated tensor ever materialized.  Returns [B, T_q, H, D] in ``q``'s
    dtype — or,
    with ``return_residuals=True``, the tuple ``(numerator, m, l)`` with
    ``numerator`` un-normalized (f32, [B, T_q, H, D]) and ``m``/``l`` the
    per-row softmax max/denominator shaped [B, H, T_q] (f32), the
    partial-block contract of ``parallel.sequence._attn_block`` with
    ``NEG_INF`` in place of -inf.

    ``q_offset``/``kv_offset`` are the global positions of ``q[:, 0]`` and
    ``k[:, 0]`` for causal masking (both 0 for plain self-attention); they
    may be traced int32 scalars, so sequence-sharded callers inside
    ``shard_map`` can pass axis-index-derived offsets.  Numerics match
    :func:`parallel.sequence.reference_attention` to dtype tolerance; the
    [T_q, T_kv] score matrix never exists in memory — VMEM residency is
    O(block_q * block_k + block_q * D) per (batch, head).

    ``window`` (causal only) restricts each query to itself plus the
    ``window - 1`` keys before it (Mistral-style sliding-window
    attention); fully-out-of-window k blocks are skipped at block
    granularity, so cost is O(T * window) instead of O(T^2) — on the
    traced-offset ring path whole out-of-window kv shards skip too.
    """
    B, Tq, H, D = q.shape
    Tkv, Hkv = k.shape[1], k.shape[2]
    if k.shape != (B, Tkv, Hkv, D) or v.shape != k.shape:
        raise ValueError(f"shape mismatch: q {q.shape} k {k.shape} "
                         f"v {v.shape}")
    group = _gqa_group(H, Hkv)
    _check_window(window, causal)
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    block_q, block_k = _resolve_blocks(block_q, block_k,
                                      "flash_block_q", "flash_block_k")
    if not return_residuals and scale != 1.0 and _prescale_enabled():
        # Plain-forward path of Config.flash_prescale: fold the scale
        # into q once here; the kernel's scale==1.0 guard then elides
        # the per-block multiply.  The residual (ring) path is excluded
        # — its callers compose flash_attention_bwd themselves at the
        # original scale.
        q = _prescale_q(q, scale)
        scale = 1.0

    block_q = _clamp_block(block_q, Tq)
    block_k = _clamp_block(block_k, Tkv)
    pad_q = (-Tq) % block_q
    pad_k = (-Tkv) % block_k
    qt = jnp.moveaxis(q, 2, 1)  # [B, H, Tq, D]
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Tqp = qt.shape[2]
    nq = Tqp // block_q
    nk = kt.shape[2] // block_k

    if interpret is None:
        interpret = ring._interpret_mode()

    # Banded grid (window + STATIC offsets — the single-device model
    # path): the minor grid dim spans only the live diagonal band, so
    # iteration count and k/v DMA traffic are O(T * window) instead of
    # O(T^2).  Traced offsets (ring shards) keep the full grid and rely
    # on the runtime _block_live skip.
    band_j0, grid_nk = _band_setup(
        window, causal, q_offset, kv_offset, span_block=block_q,
        step_block=block_k, n_total=nk, start_fn=_kv_band_start,
        block_q=block_q, block_k=block_k, nk=nk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_len=Tkv, residuals=return_residuals,
        window=window, band_j0=band_j0)
    qo = jnp.asarray(q_offset, jnp.int32).reshape(1)
    ko = jnp.asarray(kv_offset, jnp.int32).reshape(1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    o_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kv_map = _banded_minor_map(band_j0, group)
    out_shape = [jax.ShapeDtypeStruct(
        qt.shape, jnp.float32 if return_residuals else q.dtype)]
    out_specs = [o_spec]
    if return_residuals:
        stat = pl.BlockSpec((1, 1, block_q, _STAT_LANES),
                            lambda b, h, i, j: (b, h, i, 0))
        out_shape += [jax.ShapeDtypeStruct((B, H, Tqp, _STAT_LANES),
                                           jnp.float32)] * 2
        out_specs += [stat, stat]
    single = not return_residuals
    result = pl.pallas_call(
        kernel,
        out_shape=out_shape[0] if single else tuple(out_shape),
        grid=(B, H, nq, grid_nk),
        in_specs=[
            smem,
            smem,
            o_spec,
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
        ],
        out_specs=out_specs[0] if single else tuple(out_specs),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((block_q, D), jnp.float32),       # output accum
        ],
        interpret=interpret,
        compiler_params=_flash_params(interpret),
        metadata=ring.kernel_identity("flash.fwd"),
    )(qo, ko, qt, kt, vt)
    out = result if single else result[0]
    if pad_q:
        out = out[:, :, :Tq]
    out = jnp.moveaxis(out, 1, 2)
    if not return_residuals:
        return out
    m, l = result[1], result[2]
    return out, m[:, :, :Tq, 0], l[:, :, :Tq, 0]


def lse_from_residuals(m, l):
    """Log-sum-exp per row from the (m, l) residuals; fully-masked rows
    (l == 0) get +1e30 so the backward recompute ``exp(s - lse)`` is 0."""
    return jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-37)), -NEG_INF)


def _stat_lanes(x, Tqp):
    """[B, H, Tq] stats -> [B, H, Tqp, _STAT_LANES] blocks for the bwd
    kernels; padded q rows get lse=+1e30 (=> p == 0, contributing nothing)."""
    pad = Tqp - x.shape[2]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)),
                    constant_values=-NEG_INF)
    return jnp.broadcast_to(x[..., None], (*x.shape, _STAT_LANES))


def _q_span_blocks(nq: int, block_q: int, d: int, dq_bytes: int) -> int:
    """q blocks ONE backward call keeps resident: all ``nq`` when both
    pipeline buffers of their float32 dq fit ``dq_bytes``, else the even
    split into the fewest spans that do."""
    fit = max(1, dq_bytes // (2 * block_q * d * 4))
    spans = -(-nq // fit)
    return -(-nq // spans)


def flash_attention_bwd(q, k, v, do, lse, dvec, *, causal: bool,
                        scale: float, q_offset=0, kv_offset=0,
                        block_q: int = 128, block_k: int = 128,
                        window: Optional[int] = None, interpret=None):
    """Gradients (dq, dk, dv) in f32 for one (q-shard, kv-shard) pair.

    The flash-attention backward: softmax probabilities are recomputed
    blockwise from ``lse`` (never materializing [T_q, T_kv]), with
    ``dvec[b,h,i] = dO_i . O_i`` supplied by the caller (it is a cheap XLA
    rowsum).  Serves both the single-device VJP and each step of the ring
    backward in parallel/sequence.py, where the kv shard (and its offset)
    rotates.

    ONE ``pallas_call`` (:func:`_flash_bwd_kernel`, identity
    ``flash.dkv``) on the grid (B, H, kv block, q block): dk/dv carry
    across the minor q axis, dq across both minor axes for one head, so
    both are ``"arbitrary"``.  The q rows whose dq one call keeps in VMEM
    follow from the shape (:func:`_q_span_blocks`): every row up to about
    64k of 128 lanes, beyond that the same kernel over spans of the q
    axis with dk/dv summed, as the ring backward sums them across shards.
    """
    return _flash_bwd(q, k, v, do, lse, dvec, causal=causal, scale=scale,
                      q_offset=q_offset, kv_offset=kv_offset,
                      block_q=block_q, block_k=block_k, window=window,
                      interpret=interpret, dq_bytes=_DQ_RESIDENT_BYTES)


def _flash_bwd(q, k, v, do, lse, dvec, *, causal, scale, q_offset,
               kv_offset, block_q, block_k, window, interpret, dq_bytes):
    """:func:`flash_attention_bwd` with the resident dq's VMEM budget as
    an argument (tests force several q spans with a small one)."""
    B, Tq, H, D = q.shape
    Tkv, Hkv = k.shape[1], k.shape[2]
    group = _gqa_group(H, Hkv)
    _check_window(window, causal)
    block_q = _clamp_block(block_q, Tq)
    block_k = _clamp_block(block_k, Tkv)
    pad_q = (-Tq) % block_q
    pad_k = (-Tkv) % block_k
    qt = jnp.moveaxis(q, 2, 1)
    dot_ = jnp.moveaxis(do, 2, 1).astype(jnp.float32)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        dot_ = jnp.pad(dot_, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Tqp, Tkvp = qt.shape[2], kt.shape[2]
    nk = Tkvp // block_k
    lse_l = _stat_lanes(lse, Tqp)
    # dvec's padding value is irrelevant (padded rows have p == 0, so
    # ds == p * (dp - dvec) == 0); _stat_lanes' +1e30 never produces nan.
    d_l = _stat_lanes(dvec, Tqp)

    if interpret is None:
        interpret = ring._interpret_mode()

    ko = jnp.asarray(kv_offset, jnp.int32).reshape(1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    # GQA: k/v INPUTS are fetched at the group's kv head (h // group),
    # but the kernel emits PER-Q-HEAD dk/dv partials (out at full H) —
    # writing Hkv-headed outs directly would let each group member's
    # finalize overwrite the last (out blocks are written, not
    # accumulated).  The group-sum afterwards is exactly autodiff's
    # transpose of the jnp.repeat head broadcast.
    kb = pl.BlockSpec((1, 1, block_k, D),
                      lambda b, h, j, i: (b, h // group, j, 0))
    dkv_out = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, h, j, i: (b, h, j, 0))
    dkv_shape = jax.ShapeDtypeStruct((B, H, Tkvp, D), jnp.float32)

    def one_span(r0, r1):
        """The kernel over q rows [r0, r1): their dq, and their share of
        every dk/dv."""
        nq = (r1 - r0) // block_q
        qo_s = q_offset + r0
        # Banded grid for static offsets + window — see flash_attention.
        band_i0, grid_nq = _band_setup(
            window, causal, qo_s, kv_offset, span_block=block_k,
            step_block=block_q, n_total=nq, start_fn=_q_band_start,
            block_q=block_q, block_k=block_k, nq=nq)
        q_map = _banded_minor_map(band_i0)
        qb = pl.BlockSpec((1, 1, block_q, D), q_map)
        sb = pl.BlockSpec((1, 1, block_q, _STAT_LANES), q_map)
        # Every q row of the head, whatever (j, i): resident, see the
        # kernel.
        dq_out = pl.BlockSpec((1, 1, r1 - r0, D),
                              lambda b, h, j, i: (b, h, 0, 0))
        kernel = functools.partial(
            _flash_bwd_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, kv_len=Tkv, window=window, band_i0=band_i0)
        return pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct((B, H, r1 - r0, D), jnp.float32),
                       dkv_shape, dkv_shape),
            grid=(B, H, nk, grid_nq),
            in_specs=[smem, smem, kb, kb, qb, qb, sb, sb],
            out_specs=(dq_out, dkv_out, dkv_out),
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32)],
            interpret=interpret,
            compiler_params=_flash_params(interpret, _BWD_SEMANTICS,
                                          vmem_limit_bytes=_BWD_VMEM_LIMIT),
            metadata=ring.kernel_identity("flash.dkv"),
        )(jnp.asarray(qo_s, jnp.int32).reshape(1), ko, kt, vt,
          qt[:, :, r0:r1], dot_[:, :, r0:r1], lse_l[:, :, r0:r1],
          d_l[:, :, r0:r1])

    span = _q_span_blocks(Tqp // block_q, block_q, D, dq_bytes) * block_q
    parts = [one_span(r0, min(r0 + span, Tqp)) for r0 in range(0, Tqp, span)]
    dq, dk, dv = parts[0]
    if len(parts) > 1:
        dq = jnp.concatenate([p[0] for p in parts], axis=2)
        dk = sum((p[1] for p in parts[1:]), dk)
        dv = sum((p[2] for p in parts[1:]), dv)
    if group > 1:
        dk = dk.reshape(B, Hkv, group, Tkvp, D).sum(axis=2)
        dv = dv.reshape(B, Hkv, group, Tkvp, D).sum(axis=2)

    if pad_q:
        dq = dq[:, :, :Tq]
    if pad_k:
        dk = dk[:, :, :Tkv]
        dv = dv[:, :, :Tkv]
    return (jnp.moveaxis(dq, 1, 2), jnp.moveaxis(dk, 1, 2),
            jnp.moveaxis(dv, 1, 2))


def _float0_zero(x):
    import numpy as np

    return np.zeros(jnp.shape(x), jax.dtypes.float0)


@functools.lru_cache(maxsize=None)
def _flash_vjp(causal: bool, scale: float, block_q: int, block_k: int,
               interp_key, window: Optional[int] = None,
               static_offsets: Optional[tuple] = None,
               prescale: bool = False):
    """custom_vjp instance per static config.  ``interp_key`` is the
    resolved interpret setting (hashable: False or InterpretParams).

    ``static_offsets=(qo, ko)`` bakes Python-int offsets into the closure
    instead of passing them as (traced) arguments — required for the
    banded sliding-window grids, whose index maps need static offsets;
    the instance then takes only (q, k, v).

    ``prescale`` (Config.flash_prescale): q is scaled ONCE at the
    boundary (q' = dtype(q * scale)) and the kernels run scale=1 — the
    forward, the saved residual, and the backward's s-recompute all see
    the SAME q', so lse stays consistent by construction; the chain
    rule puts the scale back on dq (dL/dq = scale * dL/dq')."""

    kw = dict(causal=causal, scale=1.0 if prescale else scale,
              block_q=block_q, block_k=block_k,
              window=window, interpret=interp_key)

    def _maybe_prescale(q):
        return _prescale_q(q, scale) if prescale else q

    # ONE implementation of the VJP math, parameterized over how offsets
    # arrive (baked-in static ints vs traced trailing args).  ``q`` here
    # is ALWAYS the (possibly prescaled) kernel-side q; fwd returns it
    # so the residual saves exactly what the backward must recompute
    # against.
    def _fwd_core(q, k, v, qo, ko):
        q = _maybe_prescale(q)
        num, m, l = flash_attention(q, k, v, q_offset=qo, kv_offset=ko,
                                    return_residuals=True, **kw)
        denom = jnp.where(l > 0, l, 1.0)
        o = (num / jnp.moveaxis(denom, 1, 2)[..., None]).astype(q.dtype)
        return o, lse_from_residuals(m, l), q

    def _bwd_core(q, k, v, o, lse, do, qo, ko):
        dvec = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                          o.astype(jnp.float32))
        dq, dk, dv = flash_attention_bwd(q, k, v, do, lse, dvec,
                                         q_offset=qo, kv_offset=ko, **kw)
        if prescale:
            dq = dq * scale  # chain rule through q' = scale * q
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    if static_offsets is not None:
        qo_s, ko_s = static_offsets

        @jax.custom_vjp
        def fs(q, k, v):
            return flash_attention(_maybe_prescale(q), k, v,
                                   q_offset=qo_s, kv_offset=ko_s, **kw)

        def fwd_s(q, k, v):
            o, lse, q_used = _fwd_core(q, k, v, qo_s, ko_s)
            return o, (q_used, k, v, o, lse)

        def bwd_s(res, do):
            q_used, k, v, o, lse = res
            return _bwd_core(q_used, k, v, o, lse, do, qo_s, ko_s)

        fs.defvjp(fwd_s, bwd_s)
        return fs

    @jax.custom_vjp
    def f(q, k, v, qo, ko):
        return flash_attention(_maybe_prescale(q), k, v, q_offset=qo,
                               kv_offset=ko, **kw)

    def fwd(q, k, v, qo, ko):
        o, lse, q_used = _fwd_core(q, k, v, qo, ko)
        return o, (q_used, k, v, qo, ko, o, lse)

    def bwd(res, do):
        q_used, k, v, qo, ko, o, lse = res
        return (*_bwd_core(q_used, k, v, o, lse, do, qo, ko),
                _float0_zero(qo), _float0_zero(ko))

    f.defvjp(fwd, bwd)
    return f


def flash_attention_grad(q, k, v, *, causal: bool = False,
                         scale: Optional[float] = None, q_offset=0,
                         kv_offset=0, block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         window: Optional[int] = None,
                         interpret=None):
    """Differentiable flash attention (custom VJP with Pallas backward
    kernels).  Same forward semantics as :func:`flash_attention`; gradients
    flow to q/k/v (offsets are integer-like, zero-cotangent).  Pallas has
    no autodiff rule, so this wrapper is what training code should call —
    ``TransformerLM(attn_impl="flash")`` routes here.  Block sizes default
    from Config (``flash_block_q``/``flash_block_k``)."""
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    block_q, block_k = _resolve_blocks(block_q, block_k,
                                      "flash_block_q", "flash_block_k")
    if interpret is None:
        interpret = ring._interpret_mode()
    prescale = scale != 1.0 and _prescale_enabled()
    if (window is not None and isinstance(q_offset, int)
            and isinstance(kv_offset, int)
            and q_offset == 0 and kv_offset == 0):
        # Zero static offsets (the whole-sequence model path) bake into
        # the closure so the banded O(T*window) grids apply to training
        # too — traced offsets would defeat them.  Restricted to (0, 0)
        # to keep the lru-cached VJP instances bounded: distinct nonzero
        # int offsets (e.g. per-chunk prefill) would each mint a cache
        # entry + compile; those callers get the traced path instead.
        f = _flash_vjp(causal, float(scale), block_q, block_k, interpret,
                      window, static_offsets=(0, 0), prescale=prescale)
        return f(q, k, v)
    f = _flash_vjp(causal, float(scale), block_q, block_k, interpret,
                   window, prescale=prescale)
    return f(q, k, v, jnp.asarray(q_offset, jnp.int32),
             jnp.asarray(kv_offset, jnp.int32))
