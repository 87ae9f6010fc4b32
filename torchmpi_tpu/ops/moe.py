"""Row movement of the dropless expert layer, over the LIVE rows only, and
the held experts of a decode call (:func:`decode_experts`, at the end).

``parallel/expert.held_experts`` sorts a token's routes by expert into a
buffer of ``R = T * k`` rows, the worst case, of which a prefix of
``n_live`` rows holds a route to an expert held here (a quarter, when a
quarter of the experts is held).  ``order`` [R] says which route a sorted
row holds; the routes are rank-major, so route ``r`` is token ``r % T``'s.
The two kernels below are each other's transpose and touch that prefix
only; ``n_live`` is read on the device.

- :func:`rows_from_tokens` (``moe.gather``): ``out[s] = src[order[s] % T]``
  for the sorted rows ``s`` below ``n_live`` rounded up to a block.  Rows
  past that are NOT WRITTEN: what they hold is undefined.
- :func:`tokens_from_rows` (``moe.combine``): ``out[t] = sum of
  weight[order[s]] * src[s]`` over the sorted rows ``s < n_live`` of token
  ``t``, in float32.  Rows at or past ``n_live`` are never read into a sum.

How a row moves.  Mosaic refuses a DMA of one row of a tiled ``[N, D]``
buffer in HBM (a slice has to be a multiple of the 8-row tile, whatever
the type, and a bf16 row is half a packed sublane besides), so no row is
moved by DMA.  The side that is indexed at random is held in VMEM WHOLE,
in one buffer of 32-bit words, and a row is one dynamic-sublane load
(gather) or read-modify-write (combine) of it; the side in sorted order
streams through in blocks, and a block that starts at or past ``n_live``
is neither fetched, computed nor written (its block index is clamped to
the last live block's, which the pipeline then leaves alone).  A bfloat16
``[T, D]`` table becomes ``[T, D/2]`` uint32 with column ``c`` in the high
half and column ``c + D/2`` in the low half (one elementwise pass over the
T tokens, outside the kernel), so that unpacking a block is two
lane-aligned shifts.  float32 rows move as they are.

On a v5e at 8192 x 2560 bf16 tokens and 12,288 of 49,152 rows live (PR 27,
PERF.md): the gather 0.22 ms, the weighted gather with float32 buffers
0.48, the combine 0.35, where XLA's gather of every row and the passes
round it took 1.2 to 3.7.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ring

# Sorted rows a grid step of either kernel moves.  256 rows of 2560 bf16 are
# 1.3 MB a buffer, so the pipeline's write of one block hides behind the
# next block's row loads, and the rows moved past ``n_live`` (half a block
# on average) stay under 1% of a quarter-live buffer of 49,152.
_BLOCK = 256
# What the resident side may take of the chip's 128 MiB of VMEM: the token
# table of the benchmark's cell is 8192 x 2560 bf16 = 42 MB, the float32
# sums of the same tokens 84 MB, both in ONE buffer.  Sums that do not fit
# go through in parts, each a pass over the live rows (two parts of 42 MB
# took 0.80 ms a call on the chip where one of 84 MB takes 0.38, PR 27).
_RESIDENT_BYTES = 96 * 1024 * 1024
_VMEM_LIMIT = 110 * 1024 * 1024


def _params(interpret, grid_dims: int):
    if interpret:
        return ring.local_kernel_params(interpret)
    # "arbitrary" throughout: a dead step revisits the last live block, and
    # the combine's sums carry across the sorted chunks.
    return pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_LIMIT,
        dimension_semantics=("arbitrary",) * grid_dims)


def _interpreted() -> bool:
    """Whether to interpret, by ``ring``'s rule.  Under the interpreter it
    is Pallas's own, which discharges a kernel into plain jax operations:
    the TPU interpreter works through ordered callbacks, an effect that
    ``jax.checkpoint`` (which the expert layer is under) refuses."""
    return bool(ring._interpret_mode())


def block_rows(rows: int) -> int:
    """Sorted rows a grid step moves in a buffer of ``rows``: ``_BLOCK``
    where it divides the buffer, else the largest multiple of 8 under it
    that does, else the whole buffer."""
    for block in range(min(_BLOCK, rows) // 8 * 8, 0, -8):
        if rows % block == 0:
            return block
    return rows


def _check(src):
    if src.dtype not in (jnp.float32, jnp.bfloat16):
        raise TypeError(f"rows of float32 or bfloat16, not {src.dtype}")
    if src.dtype == jnp.bfloat16 and src.shape[1] % 2:
        raise ValueError(f"bfloat16 rows of even width, not {src.shape[1]}")


def _words(x):
    """``[N, D]`` rows as the uint32 words a row is moved by."""
    def bits(v):     # a bfloat16 widened to float32 sits in the high half
        return lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)

    if x.dtype == jnp.float32:
        return bits(x)
    half = x.shape[1] // 2
    return bits(x[:, :half]) | (bits(x[:, half:]) >> 16)


def _halves(words, dtype):
    """A block of words as float32 values: the row's halves (bfloat16, both
    exact) or the row itself (float32)."""
    def as_f32(w):
        return lax.bitcast_convert_type(w, jnp.float32)

    if dtype == jnp.float32:
        return [as_f32(words)]
    return [as_f32(words & jnp.uint32(0xFFFF0000)), as_f32(words << 16)]


def _each_row(n: int, body):
    """``body(r)`` for ``r`` in ``range(n)``, eight to a loop turn (Mosaic
    unrolls a loop whole or not at all)."""
    unroll = math.gcd(n, 8)

    def turn(g, carry):
        for q in range(unroll):
            body(g * unroll + q)
        return carry

    lax.fori_loop(0, n // unroll, turn, 0)


def _last_live(n_live, block):
    return jnp.maximum(pl.cdiv(n_live, block) - 1, 0)


def _scalars(order, n_live, weight):
    """What both kernels prefetch into SMEM: the route a sorted row holds,
    the live count and, where the rows are weighted, the weights by route."""
    scalars = [order.astype(jnp.int32),
               jnp.reshape(n_live, (1,)).astype(jnp.int32)]
    if weight is not None:
        scalars.append(weight.astype(jnp.float32))
    return scalars


# ------------------------------------------------------------------ gather


def _gather_kernel(order_ref, n_live_ref, *refs, block: int, tokens: int,
                   dtype, weighted: bool):
    if weighted:
        (weight_ref, table_ref, against_ref, out_ref, dots_ref, words_ref,
         scale_ref) = refs
    else:
        table_ref, out_ref, words_ref = refs
    base = pl.program_id(0) * block

    @pl.when(base < n_live_ref[0])
    def _():
        def move(r):
            route = order_ref[base + r]
            words_ref[pl.ds(r, 1), :] = table_ref[
                pl.ds(lax.rem(route, tokens), 1), :]
            if weighted:
                scale_ref[pl.ds(r, 1), :] = jnp.full(
                    (1, scale_ref.shape[1]), weight_ref[route])

        _each_row(block, move)
        parts = _halves(words_ref[...], dtype)
        half = parts[0].shape[1]
        if weighted:
            # ``against`` as the combine read it: rounded to the rows' type
            dots_ref[...] = sum(
                (p * against_ref[:, i * half:(i + 1) * half].astype(
                    dtype).astype(jnp.float32)).sum(axis=1, keepdims=True)
                for i, p in enumerate(parts))
            parts = [p * scale_ref[:, :1] for p in parts]
        for i, p in enumerate(parts):
            out_ref[:, i * half:(i + 1) * half] = p.astype(dtype).astype(
                out_ref.dtype)


def rows_from_tokens(src, order, n_live, *, weight=None, against=None,
                     block=None):
    """``out[s] = src[order[s] % T]`` for the sorted rows ``s`` of the live
    prefix: ``src`` [T, D]; ``order`` [R] int32, the route (rank-major:
    route ``r`` is token ``r % T``'s) that sorted row ``s`` holds;
    ``n_live`` an int32 scalar on the device.  Every block of
    ``block_rows(R)`` rows that starts below ``n_live`` is written whole;
    the blocks after it are not touched, and what ``out`` holds there is
    undefined.

    With ``weight`` [R] float32 by route and ``against`` [R, D] by sorted
    row (the transpose of a weighted combine of ``against``): returns
    ``(out, dots)`` with ``out[s] = weight[order[s]] * src[order[s] % T]``,
    the product in float32, rounded to ``src``'s type and handed back in
    ``against``'s; and ``dots[s] = <src[order[s] % T], against[s]>`` in
    float32, [R], ``against`` rounded to ``src``'s type first.  Both are
    defined over the same blocks.
    """
    _check(src)
    tokens, width = src.shape
    rows = order.shape[0]
    block = block or block_rows(rows)
    weighted = weight is not None
    if rows % block or (weighted != (against is not None)):
        raise ValueError("a block that divides the buffer, and weight and "
                         "against together")
    interpret = _interpreted()
    table = _words(src)

    def live_block(i, order_ref, n_live_ref, *_):
        return (jnp.minimum(i, _last_live(n_live_ref[0], block)), 0)

    row_block = pl.BlockSpec((block, width), live_block)
    prefetch = _scalars(order, n_live, weight)
    in_specs = [pl.BlockSpec(table.shape, lambda i, *_: (0, 0),
                             pipeline_mode=pl.Buffered(1))]
    operands = [table]
    out_shape = [jax.ShapeDtypeStruct((rows, width), src.dtype)]
    out_specs = [row_block]
    scratch = [pltpu.VMEM((block, table.shape[1]), jnp.uint32)]
    if weighted:
        in_specs.append(row_block)
        operands.append(against)
        out_shape = [jax.ShapeDtypeStruct((rows, width), against.dtype),
                     jax.ShapeDtypeStruct((rows, 1), jnp.float32)]
        out_specs.append(pl.BlockSpec((block, 1), live_block))
        scratch.append(pltpu.VMEM((block, ring._LANES), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block=block, tokens=tokens,
                          dtype=src.dtype, weighted=weighted),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(rows // block,),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        interpret=interpret,
        compiler_params=_params(interpret, 1),
        metadata=ring.kernel_identity("moe.gather"),
    )(*prefetch, *operands)
    return (out[0], out[1][:, 0]) if weighted else out[0]


# ----------------------------------------------------------------- combine


def _combine_kernel(order_ref, n_live_ref, *refs, block: int, tokens: int,
                    part: int, parts: int, dtype, weighted: bool):
    if weighted:
        weight_ref, src_ref, out_ref, rows_ref = refs
    else:
        src_ref, out_ref, rows_ref = refs
    first = pl.program_id(0) * part
    base = pl.program_id(1) * block

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(base < n_live_ref[0])
    def _():
        # the block's tail past n_live holds nothing: cut off, not scaled
        row = base + lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        rows_ref[...] = jnp.where(
            row < n_live_ref[0],
            src_ref[...].astype(dtype).astype(jnp.float32), 0)

        def add(r):
            route = order_ref[base + r]
            t = lax.rem(route, tokens) - first

            def into():
                x = rows_ref[pl.ds(r, 1), :]
                out_ref[pl.ds(t, 1), :] += (weight_ref[route] * x
                                            if weighted else x)

            if parts == 1:
                into()
            else:
                pl.when((t >= 0) & (t < part))(into)

        _each_row(block, add)


def token_parts(tokens: int, width: int) -> int:
    """Parts the combine's float32 sums go through, each resident whole
    beside the sorted rows' three float32 blocks (two in flight, one
    scratch): 6,144 wide those are 18 MB, which 2,560 wide was not worth
    counting."""
    parts = 1
    while ((tokens // parts + 3 * _BLOCK) * width * 4 > _RESIDENT_BYTES
           and tokens % (2 * parts) == 0 and tokens // (2 * parts) % 8 == 0):
        parts *= 2
    return parts


def tokens_from_rows(src, order, n_live, tokens: int, *, weight=None,
                     round_to=None, block=None):
    """``out[t] = sum_s weight[order[s]] * src[s]`` over the sorted rows
    ``s < n_live`` whose route ``order[s]`` is token ``t``'s (``order[s] %
    tokens == t``): ``src`` [R, D], ``order`` [R] int32, ``weight`` [R]
    float32 by route (None: every weight 1).  Returns [tokens, D] float32:
    every product and sum in float32, a token's rows added in sorted
    order, a token with no live row exactly 0.  ``round_to``: a float32
    ``src`` is rounded to that type as it is read (the cast of the buffer
    that a separate pass over ALL its rows would make).  Rows of ``src``
    at or past ``n_live`` are never read into a sum (NaN there changes
    nothing)."""
    _check(src)
    rows, width = src.shape
    block = block or block_rows(rows)
    if rows % block:
        raise ValueError("a block that divides the buffer")
    interpret = _interpreted()
    parts = token_parts(tokens, width)
    prefetch = _scalars(order, n_live, weight)

    def live_block(p, c, order_ref, n_live_ref, *_):
        return (jnp.minimum(c, _last_live(n_live_ref[0], block)), 0)

    return pl.pallas_call(
        functools.partial(_combine_kernel, block=block, tokens=tokens,
                          part=tokens // parts, parts=parts,
                          dtype=round_to or src.dtype,
                          weighted=weight is not None),
        out_shape=jax.ShapeDtypeStruct((tokens, width), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(parts, rows // block),
            in_specs=[pl.BlockSpec((block, width), live_block)],
            # the sums stay put through a part's chunks and leave once, at
            # its end: one buffer
            out_specs=pl.BlockSpec((tokens // parts, width),
                                   lambda p, c, *_: (p, 0),
                                   pipeline_mode=pl.Buffered(1)),
            scratch_shapes=[pltpu.VMEM((block, width), jnp.float32)]),
        interpret=interpret,
        compiler_params=_params(interpret, 2),
        metadata=ring.kernel_identity("moe.combine"),
    )(*prefetch, src)


# ------------------------------------------------------------ decode experts

# What one grid step of ``decode_experts`` may bring of an expert's weights:
# a whole expert of instella (3 x 2048 x 1408 bf16, 17.3 MB) or of nemotron's
# latent (2 x 1024 x 2688, 11.0 MB), a quarter of K-EXAONE's (3 x 6144 x 512,
# 18.9 MB).  Two such blocks are in flight; few steps a call keep the
# pipeline's per-step cost off the stream of weights.
_EXPERT_STEP_BYTES = 24 * 1024 * 1024


def expert_tile(width: int, door: int, mats: int, itemsize: int) -> int:
    """Columns of an expert's width ``F`` a grid step of
    :func:`decode_experts` takes: the largest multiple of 128 that divides
    ``width`` and keeps a step's ``mats`` matrices (each ``door`` x tile)
    within ``_EXPERT_STEP_BYTES``, else the whole width."""
    fits = [t for t in range(ring._LANES, width + 1, ring._LANES)
            if width % t == 0
            and mats * door * t * itemsize <= _EXPERT_STEP_BYTES]
    return max(fits) if fits else width


def _decode_kernel(ids_ref, n_ref, x_ref, experts_ref, weights_ref, *refs,
                   first: int, act, gated: bool, tiles: int):
    if gated:
        gate_ref, up_ref, down_ref, out_ref, acc_ref = refs
    else:
        up_ref, down_ref, out_ref, acc_ref = refs
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]

        def product(a, w_ref):
            return jnp.dot(a, w_ref[...].astype(x.dtype),
                           preferred_element_type=jnp.float32)

        hidden = (act(product(x, up_ref)) if not gated
                  else act(product(x, gate_ref)) * product(x, up_ref))
        part = product(hidden.astype(x.dtype), down_ref)
        if tiles == 1:
            acc_ref[...] = part
        else:
            @pl.when(j == 0)
            def _():
                acc_ref[...] = part

            @pl.when(j > 0)
            def _():
                acc_ref[...] += part

        @pl.when(j == tiles - 1)
        def _():
            # each row's weight for this expert: 0 where it did not choose it
            chose = experts_ref[...] == ids_ref[i] + first
            p = jnp.sum(jnp.where(chose, weights_ref[...], 0.0), axis=1,
                        keepdims=True)
            out_ref[...] += p * acc_ref[...].astype(x.dtype).astype(
                jnp.float32)


def decode_experts(x, experts, weights, ids, n, first: int, w_gate, w_up,
                   w_down, *, act):
    """The held experts' part of a top-k expert layer for a call of FEW
    rows, each touched expert's weights read once (``moe.decode_experts``):

        out[t] = sum_{e in ids[:n]} p[t, e] * round(f_e(x[t]))

    ``x`` [N, D]; ``experts`` and ``weights`` [N, k]: each row's chosen
    experts (by their global id) and their weights; ``ids`` [count] int32,
    the held experts (``0 <= id < count``, the weights' own index) that some
    row chose, ascending, ``n`` of them, the entries past ``n`` repeating
    the last; ``w_gate``, ``w_up`` [count, D, F], ``w_down`` [count, F, D]
    (``w_gate`` None: a non-gated expert, ``f_e(x) = w_down act(w_up x)``).
    ``p[t, e]`` is row t's weight for held expert ``first + e``, 0 where t
    did not choose it; ``round`` rounds the down product to ``x``'s type.
    Products in ``x``'s type with float32 accumulation, the gate product
    through ``act`` in float32, the weighted sum in float32: [N, D].

    The grid runs over (touched expert, tile of F): a step brings the tile
    of each matrix (:func:`expert_tile`) and computes every row against it,
    since at a few rows the products cost the weights' bytes and nothing
    else, and a row's sum reads its own row only.  The rows, their choices
    and the sums stay in VMEM through the call.  A step past ``n`` keeps
    the last step's blocks, so the pipeline fetches nothing more: an expert
    no row chose is never read (with ``n == 0`` the first held expert is
    fetched once and nothing computed)."""
    N, D = x.shape
    count, _, F = w_up.shape
    gated = w_gate is not None
    mats = [w for w in (w_gate, w_up, w_down) if w is not None]
    tile = expert_tile(F, D, len(mats), w_up.dtype.itemsize)
    tiles = F // tile
    # whole packed sublanes of rows; a padded row chooses no expert
    rows = -(-N // 16) * 16
    pad = ((0, rows - N), (0, 0))
    x = jnp.pad(x, pad)
    experts = jnp.pad(experts.astype(jnp.int32), pad, constant_values=-1)
    weights = jnp.pad(weights.astype(jnp.float32), pad)
    interpret = _interpreted()

    def step(i, j, ids_ref, n_ref):
        # past the touched: the last step's blocks again, no fetch
        return ids_ref[i], jnp.where(i < n_ref[0], j, tiles - 1)

    def in_block(i, j, *scalars):
        expert, t = step(i, j, *scalars)
        return expert, 0, t

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, j, *_: (0, 0),
                            pipeline_mode=pl.Buffered(1))

    in_tile = pl.BlockSpec((None, D, tile), in_block)
    out_tile = pl.BlockSpec((None, tile, D),
                            lambda i, j, *s: (*step(i, j, *s), 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, first=first, act=act, gated=gated,
                          tiles=tiles),
        out_shape=jax.ShapeDtypeStruct((rows, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(count, tiles),
            in_specs=[whole(x.shape), whole(experts.shape),
                      whole(weights.shape)]
            + [in_tile] * (len(mats) - 1) + [out_tile],
            out_specs=whole((rows, D)),
            scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32)]),
        interpret=interpret,
        compiler_params=_params(interpret, 2),
        metadata=ring.kernel_identity("moe.decode_experts"),
    )(ids.astype(jnp.int32), jnp.reshape(n, (1,)).astype(jnp.int32),
      x, experts, weights, *mats)
    return out[:N]
