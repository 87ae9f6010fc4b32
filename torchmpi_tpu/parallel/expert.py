"""Expert parallelism: all-to-all Mixture-of-Experts dispatch.

Not in the reference (SURVEY.md §3.3: EP out of its scope, like TP/PP/SP);
this completes the parallelism-strategy set on the same communicator tree.
Minimal, correct, capacity-based top-k MoE (k=1 Switch-style combine,
k>=2 GShard-style renormalized combine):

- every device holds ``experts_per_device`` experts (the expert dimension is
  sharded over ``axis_name``);
- tokens are routed by a gating projection, packed into per-expert capacity
  buffers (static shapes — XLA-friendly; overflow tokens drop, the standard
  capacity-factor trade), exchanged with ONE ``all_to_all``, processed by
  the local experts, and returned by the inverse ``all_to_all``;
- combine scales by the gate probability, so dropped tokens degrade
  gracefully to zero contribution (residual connections carry them).

The communication pattern (dispatch all-to-all, combine all-to-all) is the
EP analog of the reference's allreduce: one collective pair per MoE layer.

:func:`held_experts` is the layer a device runs when it is TOLD which
experts it holds (one chip's share of a larger deployment, or the part an
expert axis's exchange would wrap): it routes over all experts, drops no
route to a held one, and computes the held experts' part of the result;
in a call of few rows on the chip (:func:`experts_read_once`: a pooled
decode step) it reads each touched held expert's weights once, by
``ops/moe.decode_experts``.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import moe


def topk_dispatch(x, gate_logits, n_experts_global: int, capacity: int,
                  k: int, *, renormalize: bool = True, probs=None):
    """Pack tokens into per-expert capacity slots along their top-k routes.

    x: [T, D]; gate_logits: [T, E_global].  Slots fill RANK-MAJOR
    (GShard priority): every token's rank-0 choice claims a slot before
    any token's rank-1 choice does, so under overflow an expert drops
    tokens' secondary routes first — never a later token's primary route
    in favor of an earlier token's secondary one.  Combine weights: the
    top-k probabilities renormalized over the selected experts
    (``renormalize=True``, GShard) or raw (False — at k=1 that is
    Switch-style scaling by the top-1 probability).

    Returns (buffers [E_global, capacity, D], combine_w [T, k],
    expert_of [T, k], slot_of [T, k], valid [T, k]).
    """
    T, D = x.shape
    if probs is None:
        probs = jax.nn.softmax(gate_logits, axis=-1)
    topk_p, topk_e = lax.top_k(probs, k)  # [T, k]
    combine_w = (topk_p / jnp.maximum(
        topk_p.sum(axis=-1, keepdims=True), 1e-9)
        if renormalize else topk_p)
    # Rank-major route order: [k*T] with all rank-0 routes first, so the
    # running per-expert cumsum assigns slots to every primary route
    # before any secondary route competes for one.
    routes = topk_e.T.reshape(-1)
    onehot = jax.nn.one_hot(routes, n_experts_global, dtype=jnp.int32)
    pos_in_expert = jnp.cumsum(onehot, axis=0) - 1
    slot_flat = jnp.take_along_axis(pos_in_expert, routes[:, None],
                                    axis=1)[:, 0]
    slot_of = slot_flat.reshape(k, T).T  # back to [T, k]
    valid = slot_of < capacity
    buffers = jnp.zeros((n_experts_global, capacity, D), x.dtype)
    safe_slot = jnp.where(valid, slot_of, capacity - 1)
    x_routes = jnp.broadcast_to(x[:, None], (T, k, D))
    # scatter-ADD, not set: overflow routes (clamped to the last slot)
    # contribute zeros instead of clobbering the slot's real occupant.
    buffers = buffers.at[topk_e, safe_slot].add(
        jnp.where(valid[..., None], x_routes, 0.0))
    return buffers, combine_w, topk_e, slot_of, valid


def load_balance_loss(gate_logits, expert_of, n_experts: int, *,
                      probs=None):
    """Switch-transformer auxiliary load-balancing loss for one device's
    tokens: ``E * sum_e(f_e * P_e)`` with ``f_e`` the fraction of routes
    dispatched to expert e and ``P_e`` the mean router probability.
    Equals 1.0 under perfectly uniform routing; grows as routing
    collapses.  ``expert_of``: [T] or [T, k] selected experts (from
    :func:`topk_dispatch`).  Pass ``probs`` if the router softmax is
    already computed.  Scale (typ. 1e-2) and add to the task loss.
    """
    if probs is None:
        probs = jax.nn.softmax(gate_logits, axis=-1)
    P = probs.mean(axis=0)  # [E]
    if expert_of.ndim == 1:
        expert_of = expert_of[:, None]
    f = jax.nn.one_hot(expert_of.reshape(-1), n_experts).mean(axis=0)
    return n_experts * jnp.sum(f * P)


def moe_layer(x, gate_w, expert_fn: Callable, expert_params,
              axis_name: str, *, capacity_factor: float = 2.0, k: int = 1,
              return_aux: bool = False):
    """Top-k expert-parallel MoE layer, for use inside shard_map.

    x: [T, D] this device's tokens; gate_w: [D, E_global] replicated;
    expert_params: this device's experts, leaves shaped
    ``[experts_per_device, ...]``; ``expert_fn(params_e, tokens) -> tokens``
    applies ONE expert.  Returns [T, D].

    ``k=1`` keeps Switch-style combine (scale by the raw top-1
    probability); ``k>=2`` is GShard-style — contributions weighted by the
    top-k probabilities renormalized over the selected experts.  Capacity
    scales with k: ``capacity_factor * T * k / E`` slots per expert.
    ``return_aux=True`` additionally returns this device's
    :func:`load_balance_loss` (add it to the task loss, typ. scaled 1e-2,
    to keep routing from collapsing onto few experts).
    """
    if k < 1:
        raise ValueError(f"moe_layer needs k >= 1 experts per token, "
                         f"got {k}")
    n_dev = lax.axis_size(axis_name)
    T, D = x.shape
    e_local = jax.tree.leaves(expert_params)[0].shape[0]
    E = n_dev * e_local
    capacity = max(1, int(capacity_factor * T * k / E))

    gate_logits = x @ gate_w
    probs = jax.nn.softmax(gate_logits, axis=-1)  # shared with the aux loss
    buffers, gate, expert_of, slot_of, valid = topk_dispatch(
        x, gate_logits, E, capacity, k, renormalize=k > 1, probs=probs)

    # Dispatch: buffers [E, C, D] with E = n_dev * e_local, expert-major.
    # tiled all_to_all on axis 0 sends block d (rows d*e_local:(d+1)*e_local)
    # to device d; the receive concatenates source blocks in order, so
    # dispatched[s*e_local + j] = source s's buffer for my local expert j.
    dispatched = lax.all_to_all(buffers, axis_name, split_axis=0,
                                concat_axis=0, tiled=True)
    # Per-local-expert queues: [e_local, n_dev * C, D].
    queues = (dispatched.reshape(n_dev, e_local, capacity, D)
              .transpose(1, 0, 2, 3).reshape(e_local, n_dev * capacity, D))

    # Apply local experts (vmapped over the expert dim).
    processed = jax.vmap(expert_fn)(expert_params, queues)

    # Combine: inverse exchange — repack expert-major and all_to_all back,
    # landing in the original [E, C, D] layout on each source device.
    packed = (processed.reshape(e_local, n_dev, capacity, D)
              .transpose(1, 0, 2, 3).reshape(E, capacity, D))
    returned = lax.all_to_all(packed, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)

    # k routes per token: gather each route's processed row, weight, sum.
    out_routes = returned[expert_of, jnp.where(valid, slot_of, 0)]  # [T,k,D]
    out_routes = jnp.where(valid[..., None], out_routes, 0.0)
    out = (out_routes * gate[..., None]).sum(axis=1)
    if return_aux:
        return out, load_balance_loss(gate_logits, expert_of, E,
                                      probs=probs)
    return out


@jax.custom_vjp
def _dispatch(u, order, n_live):
    """``u[order[s] % T]`` for the sorted rows ``s`` of the live prefix
    (``ops/moe.rows_from_tokens``); its transpose sums a token's live rows
    (``ops/moe.tokens_from_rows``, every weight 1)."""
    return moe.rows_from_tokens(u, order, n_live)


def _dispatch_fwd(u, order, n_live):
    return _dispatch(u, order, n_live), (order, n_live, u.shape[0])


def _dispatch_bwd(res, g):
    order, n_live, tokens = res
    with jax.named_scope("dispatch"):
        du = moe.tokens_from_rows(g, order, n_live, tokens)
    return du.astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(dtype, y, weight, order, n_live):
    """``out[t] = sum_j weight[j, t] * y[s(j, t)]`` over token t's live
    routes, ``s`` their sorted rows, in float32: ``weight`` [k, T]
    rank-major like the routes, ``y`` the down product as it comes
    (float32), rounded to ``dtype`` row by row as it is read.  Its
    transpose gathers the cotangent's row of every live route, scaled by
    the route's weight and rounded to ``dtype``, and the weight's own
    cotangent beside it (``ops/moe.rows_from_tokens``)."""
    return moe.tokens_from_rows(y, order, n_live, weight.shape[1],
                                weight=weight.reshape(-1), round_to=dtype)


def _combine_fwd(dtype, y, weight, order, n_live):
    return (_combine(dtype, y, weight, order, n_live),
            (y, weight, order, n_live))


def _combine_bwd(dtype, res, g):
    y, weight, order, n_live = res
    with jax.named_scope("combine"):
        dy, dots = moe.rows_from_tokens(
            g.astype(dtype), order, n_live, weight=weight.reshape(-1),
            against=y)
        # by sorted row -> by route: sorting by ``order`` undoes the sort
        # (a gather of k * T scalars by the inverse permutation takes eight
        # times as long on a TPU).  Past n_live the dots are undefined.
        live = jnp.arange(order.shape[0]) < n_live
        _, d_weight = lax.sort((order, jnp.where(live, dots, 0)), num_keys=1)
    return dy, d_weight.reshape(weight.shape), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _held_part(act, u, probs, order, sizes, w_gate, w_up, w_down):
    """``sum_j probs[j, t] * f_e(u[t])`` over token t's live routes:
    ``order`` sorts the rank-major routes ``[k * T]`` by expert, the live
    ones (``sizes`` of them an expert held) first; ``act`` is the
    activation on the experts' gate product, or, without a ``w_gate``, on
    the up product itself."""
    n_live = sizes.sum()
    with jax.named_scope("dispatch"):
        rows = _dispatch(u, order, n_live)
    with jax.named_scope("experts"):
        def product(x, w):
            return lax.ragged_dot(x, w.astype(x.dtype), sizes,
                                  preferred_element_type=jnp.float32)

        hidden = (act(product(rows, w_up)) if w_gate is None
                  else act(product(rows, w_gate)) * product(rows, w_up))
        y = product(hidden.astype(u.dtype), w_down)
    with jax.named_scope("combine"):
        # the cast of ``y`` to the rows' type rides on the kernel, and its
        # transpose on the gather: no pass over the whole buffer for either
        return _combine(u.dtype, y, probs, order, n_live)


def softmax_gate(logits, k: int):
    """Top-k of the router's logits, weighted by the softmax over the k
    chosen (SmallThinker's gate).  ``logits`` [T, n_experts] float32 ->
    ``(experts [T, k] int32, weights [T, k] float32)``."""
    scores, experts = lax.top_k(logits.astype(jnp.float32), k)
    return experts, jax.nn.softmax(scores, axis=-1)


def sigmoid_gate(logits, k: int, bias, scale: float):
    """``deepseek_v3``'s gate without groups: scores are the logits'
    sigmoids, the chosen are the top-k of ``scores + bias`` (the bias only
    SELECTS), the weights the chosen scores over their sum, times
    ``scale``.  Same shapes as :func:`softmax_gate`."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = lax.top_k(scores + bias, k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * scale


#: What the worst case of one call's sorted buffer may take, a route a row of
#: float32 the width of a token (the down product's): 8192 tokens x 6 routes
#: x 2560 take 0.5 GB, a prompt of 16,384 tokens x 8 x 6144 would take 3.2 GB.
_BUFFER_BYTES = 1 << 30


def token_chunks(tokens: int, width: int, k: int) -> int:
    """Chunks of tokens :func:`held_experts` routes a call in: the fewest
    (a power of two dividing ``tokens``) that keep a chunk's worst-case
    buffer within ``_BUFFER_BYTES``.  One for every call but the longest
    prompts of the widest layers: the program there is what it was."""
    chunks = 1
    while (tokens // chunks * k * width * 4 > _BUFFER_BYTES
           and tokens % (2 * chunks) == 0):
        chunks *= 2
    return chunks


#: The most rows a call of the held experts may have and still be bound by
#: its weights' bytes on the chip: a bfloat16 weight (2 bytes) takes one
#: multiply-add (2 operations) a row, so R rows do R operations a byte, and a
#: v5e's ridge is its peaks' ratio, 197 TFLOP/s over 819 GB/s (Google
#: Cloud, "TPU v5e").  Not a setting: what the chip's peaks give.
READ_ONCE_ROWS = int(197e12 / 819e9)


def experts_read_once(shape, platform: Optional[str] = None) -> bool:
    """How an expert layer's call of this ``shape`` ``[B, T, E]`` computes
    its held experts: by ``ops/moe.decode_experts``, each touched expert's
    weights read once (True), or by :func:`held_experts`' sorted buffer and
    grouped products (False).  THE one rule: ``models.transformer.
    ExpertFFN`` decides by it at trace time (a profile shows the kernel as
    ``moe.decode_experts``).  Not a ``Config`` field or an environment
    variable: what the code can observe.

    - ``platform`` (None: what ``ops/ring._interpret_mode`` decides, as
      ``models.transformer.rows_write_in_place`` reads it): the interpreter
      runs the kernel correctly but slowly, so the CPU keeps the grouped
      products.
    - ``shape``: ONE token a row (the pooled decode step, ``[slots, 1, E]``;
      a prompt, a verify ``[S, K+1]`` and a training call are not), and no
      more rows than :data:`READ_ONCE_ROWS`, where computing every row
      against a touched expert costs its weights' bytes and nothing more.
    """
    if platform is None:
        from ..ops import ring

        platform = "cpu" if ring._interpret_mode() else "tpu"
    B, T = shape[0], shape[1]
    return platform == "tpu" and T == 1 and B <= READ_ONCE_ROWS


def touched_experts(experts, first: int, count: int):
    """The held experts ``[first, first + count)`` that some row of
    ``experts`` [T, k] chose, by their index among the held: ``(ids
    [count] int32, n)``, the ``n`` touched ascending and the entries past
    them repeating the last (0 when none is)."""
    held = (experts >= first) & (experts < first + count)
    chosen = jnp.zeros((count,), jnp.bool_).at[
        jnp.where(held, experts - first, count)].set(True, mode="drop")
    n = chosen.sum(dtype=jnp.int32)
    ids = jnp.nonzero(chosen, size=count, fill_value=0)[0].astype(jnp.int32)
    last = ids[jnp.maximum(n - 1, 0)]
    return jnp.where(jnp.arange(count) < n, ids, last), n


def held_experts(u, router_logits, k: int, first: int, w_gate, w_up,
                 w_down, *, gate=softmax_gate, act=jax.nn.relu,
                 read_once: bool = False):
    """The held experts' part of a top-k gated expert layer.

    u: [T, D] tokens; router_logits: [T, n_experts] over ALL experts;
    ``gate(router_logits, k)`` gives the chosen experts and their weights
    (:func:`softmax_gate`, or :func:`sigmoid_gate` with its bias and scale
    bound); ``w_gate``, ``w_up`` [count, D, F] and ``w_down`` [count, F, D]
    are the weights of the experts ``[first, first + count)``, the only
    ones that exist here.  Returns ``(out, stats)`` with

        out[t] = sum_{j: e_j held} p_j * w_down[e_j] (act(w_gate[e_j] u_t)
                                                      * (w_up[e_j] u_t))

    where ``e`` are token t's chosen experts and ``p`` their weights, the
    gate's over all k whether held or not (``act``: relu for ReGLU experts,
    silu for SwiGLU).  A NON-GATED expert has two matrices: with ``w_gate``
    None it is ``w_down[e] act(w_up[e] u_t)`` (``nemotron_h``: ``act`` the
    squared relu).  What the other experts would add is left out: under
    an expert axis this is what :func:`moe_layer`'s exchange would wrap,
    and nothing here stands in for it.

    Dropless: the routes are sorted by expert into a buffer of ``T * k``
    rows, the worst case, so no imbalance drops a route.  The routes to a
    held expert come first: ``n_live`` of them, counted on the device, and
    ALL the row movement follows that count.  The three grouped products
    (``lax.ragged_dot``, which the TPU compiler turns into a grouped-matmul
    kernel that skips the tiles past the last routed row) compute the live
    rows.  ``dispatch`` writes them (``moe.gather``: each live row from its
    token) and ``combine`` reads them (``moe.combine``: a token's live rows
    of the down product, rounded to ``u``'s type, times their weights,
    summed in float32).  Their transposes are the same two kernels the
    other way round: the dispatch's sums a token's live cotangent rows,
    the combine's gathers a live route's cotangent row times its weight,
    with the weight's own cotangent (a row's dot with the down product)
    riding on it.  Rows past ``n_live`` (rounded up to the block
    ``ops/moe.block_rows`` gives) are never written by either gather, and
    what they hold is UNDEFINED, NaN included.  Nothing carries them into
    a result: a row of the grouped products and of the gate between them
    depends on its own row alone, the weight gradients' contraction over
    rows stops at ``sizes`` (shown on the chip with NaN there, PERF.md),
    and both combines stop at ``n_live``.  With every expert held every
    row is live and the same code moves them all.  The buffer may be
    smaller than one block (a pooled decode step routes a row a slot):
    the block is then the buffer.  A call whose worst-case buffer would
    pass ``_BUFFER_BYTES`` (the longest prompts of the widest layers)
    routes its tokens in chunks (:func:`token_chunks`), each as above: a
    token's routes stay in its chunk, so its output is the same, and
    ``rows_moved`` sums each chunk's rounding to its block.

    The gate runs in float32, the products in ``u``'s type with float32
    accumulation.  The sort runs once, outside the part that the backward
    pass recomputes (its integers are the residuals); the buffers are
    recomputed, not kept: at the worst case they are ``n_experts / count``
    times what the routes need.

    With ``read_once`` (what :func:`experts_read_once` says of a call:
    few rows, on the chip) there is no buffer, no grouped product and no
    combine: ``ops/moe.decode_experts`` reads each held expert that some
    row chose once (:func:`touched_experts`), computes every row against
    it and adds each row's weighted, rounded result into its sum.  Same
    types, same rounding, every route to a held expert computed; the
    forward pass only (no transpose: a decode step is not differentiated).

    ``stats``: ``routes_held`` (routes to a held expert), ``rows_computed``
    (rows the grouped products are told to compute; with ``read_once`` the
    rows times the experts touched, which the kernel computes),
    ``rows_moved`` (rows of the buffer the dispatch wrote: ``n_live``
    rounded up to its block; 0 with ``read_once``, which moves none) and
    ``experts`` ([T, k] chosen experts), all computed on the device.
    """
    n_experts, count = router_logits.shape[-1], w_up.shape[0]
    if not (0 <= first and first + count <= n_experts and 1 <= k <= n_experts):
        raise ValueError(
            f"held experts [{first}, {first + count}) with k={k} do not "
            f"fit a router over {n_experts}")
    chunks = token_chunks(*u.shape, k)
    if chunks > 1:
        # A long prompt's tokens in chunks, each routed, sorted and
        # combined as above on its own: a token's routes stay in its chunk,
        # and the worst-case buffer is a chunk's.
        def one(chunk):
            return held_experts(*chunk, k, first, w_gate, w_up, w_down,
                                gate=gate, act=act)

        split = (u.reshape(chunks, -1, u.shape[-1]),
                 router_logits.reshape(chunks, -1, n_experts))
        out, stats = lax.map(one, split)
        return out.reshape(u.shape), {
            name: (v.reshape(-1, k) if name == "experts" else v.sum())
            for name, v in stats.items()}
    with jax.named_scope("route"):
        experts, weights = gate(router_logits, k)
        held = (experts >= first) & (experts < first + count)
        group = jnp.where(held, experts - first, count)
    if read_once:
        with jax.named_scope("dispatch"):
            ids, n = touched_experts(experts, first, count)
        with jax.named_scope("experts"):
            out = moe.decode_experts(u, experts, weights, ids, n, first,
                                     w_gate, w_up, w_down, act=act)
        return out.astype(u.dtype), {
            "routes_held": held.sum(dtype=jnp.int32),
            "rows_computed": n * u.shape[0], "rows_moved": jnp.int32(0),
            "experts": experts}
    with jax.named_scope("dispatch"):
        # Routes are laid out rank-major, [k, T] flat, so that the stable
        # sort keeps a rank's tokens in order within an expert.
        key = group.T.reshape(-1)
        order = jnp.argsort(key, stable=True)        # held first, by expert
        sizes = (key[:, None] == jnp.arange(count)).sum(0, dtype=jnp.int32)
    out = jax.checkpoint(_held_part, static_argnums=(0,))(
        act, u, weights.T, order, sizes, w_gate, w_up, w_down)
    rows = sizes.sum()
    block = moe.block_rows(key.shape[0])
    return out.astype(u.dtype), {
        "routes_held": held.sum(dtype=jnp.int32), "rows_computed": rows,
        "rows_moved": (rows + block - 1) // block * block,
        "experts": experts}


def decode_counts(moe_collection, live, held=None):
    """What a pooled decode step's expert layers did, from the ``moe``
    collection the step sowed (one row a slot): ``[layers, 3]`` int32, a
    layer (in the order of the modules' paths) the HELD experts that at
    least one LIVE row chose (their weights are what the step must read),
    the live rows' routes, and those of them that went to a held expert;
    ``live`` [rows] bool leaves the idle slots' rows out, ``held`` is the
    layers' ``(first, count)`` (None: every expert is held, and the third
    number is the second).  None without an expert layer."""
    from flax.traverse_util import flatten_dict

    flat = flatten_dict(moe_collection)
    rows = []
    for path in sorted(p for p in flat if p[-1] == "experts"):
        chosen, = flat[path]                     # [rows, k]: one call
        n_experts = flat[path[:-1] + ("router_logits",)][0].shape[-1]
        mine = live[:, None]
        routes = routes_held = live.sum(dtype=jnp.int32) * chosen.shape[-1]
        if held is not None:
            first, count = held
            mine = mine & (chosen >= first) & (chosen < first + count)
            routes_held = mine.sum(dtype=jnp.int32)
        touched = jnp.zeros((n_experts,), jnp.int32).at[
            jnp.where(mine, chosen, n_experts)].max(1, mode="drop")
        rows.append(jnp.stack([touched.sum(), routes, routes_held]))
    return jnp.stack(rows) if rows else None


def record_counters(moe_collection) -> None:
    """A model's sown ``moe`` collection (``apply(..., mutable=["moe"])``)
    into the ``obs`` registry: ``tm_moe_routes_held_total``,
    ``tm_moe_rows_computed_total`` and ``tm_moe_rows_moved_total``, one
    series a layer (label ``layer`` = the module's path).  Fetches the
    counters from the device: call it beside a step, not inside one."""
    from flax.traverse_util import flatten_dict

    from .. import obs

    for (*layer, name), sown in flatten_dict(moe_collection).items():
        if name in ("routes_held", "rows_computed", "rows_moved"):
            for value in sown:      # one entry a call of the module
                obs.registry().counter_inc(f"tm_moe_{name}_total",
                                           int(value), layer="/".join(layer))
