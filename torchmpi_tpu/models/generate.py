"""Autoregressive generation with a KV cache (serving path).

Beyond-reference (the reference predates LMs — SURVEY.md §6.7): greedy or
temperature sampling from a :class:`TransformerLM`, single-forward
PREFILL (the whole prompt fills the KV caches in one batched attention
pass) followed by a ``lax.scan`` DECODE in which each step feeds ONE
token through the model in ``decode=True`` mode, appending to per-layer
[B, max_len] key/value caches instead of recomputing the whole prefix —
O(T) work per token, ``steps`` model dispatches total, all inside one
jit: static shapes, no host round-trips.

Two entry points:

- :func:`generate` — single-device dense decode;
- :func:`generate_parallel` — the same fused scan run under ``shard_map``
  over a device mesh, so expert-parallel MoE models decode with their
  dispatch/combine all-to-all riding the mesh axis exactly as in
  training (tiny per-step capacity — the decode analog of capacity-based
  routing), and the batch can shard over a data axis.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _check_sampling(top_k, top_p):
    """Entry-boundary validation: out-of-range knobs would otherwise
    silently degenerate (top_p=0 masks EVERY logit and categorical then
    emits token 0 forever; top_k=0 indexes the minimum logit)."""
    if top_k is not None and int(top_k) < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < float(top_p) <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def _filter_logits(logits, temperature, top_k, top_p):
    """Restrict sampling support: ``top_k`` keeps the k highest logits,
    ``top_p`` keeps the smallest set whose probability mass (at the given
    temperature, over the top-k-filtered support) reaches p — both
    static, composable (k first, then p), and no-ops for greedy decoding
    (argmax ignores the filtered tail).  One vocab sort serves both
    filters; softmax monotonicity lets the nucleus cut be applied as a
    LOGIT threshold, so no unsorted-probs pass is needed.
    """
    if top_k is None and top_p is None:
        return logits
    V = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    if top_k is not None:
        k = min(int(top_k), V)
        kth = sorted_desc[:, k - 1][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
        sorted_desc = jnp.where(jnp.arange(V)[None, :] < k, sorted_desc,
                                -jnp.inf)
    if top_p is not None:
        sp = jax.nn.softmax(
            sorted_desc / jnp.maximum(temperature, 1e-6), axis=-1)
        cum = jnp.cumsum(sp, axis=-1)
        keep_sorted = (cum - sp) < top_p  # exclusive-cumsum nucleus rule
        # The first sorted entry always survives (cum - sp == 0 there),
        # so the threshold is finite and at least one token remains.
        thresh = jnp.min(jnp.where(keep_sorted, sorted_desc, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits >= thresh, logits, -jnp.inf)
    return logits


def _sample(logits, rng, temperature, top_k, top_p, dtype):
    """Filtered greedy/categorical sampling — the one implementation
    behind every serving path (dense scan, TP, PP), so the
    temperature-0 select and the filter interplay can never diverge
    between them."""
    logits = _filter_logits(logits.astype(jnp.float32), temperature,
                            top_k, top_p)
    return jnp.where(
        temperature > 0.0,
        jax.random.categorical(rng, logits / jnp.maximum(
            temperature, 1e-6)),
        jnp.argmax(logits, axis=-1)).astype(dtype)


def _sample_keys(seeds, idxs):
    """Per-row sampling keys for the serving path: row i's key is
    ``fold_in(PRNGKey(seeds[i]), idxs[i])`` where ``idx`` counts the
    tokens the request has emitted so far.  The key therefore depends
    only on (request seed, token index) — NOT on the slot the session
    landed in, the pool shape, or how many times it was re-routed — so
    a sampled stream is bitwise-reproducible given (seed, prompt) and a
    re-prefilled session continues exactly where the dead replica left
    off."""
    return jax.vmap(lambda s, i: jax.random.fold_in(
        jax.random.PRNGKey(s), i))(seeds, idxs)


def _filter_logits_rows(logits, temps, top_ks, top_ps):
    """Per-ROW dynamic :func:`_filter_logits`: each row carries its own
    (temperature, top_k, top_p) as array operands, so ONE compiled
    executable serves a slot pool mixing greedy and sampled requests.

    Sentinels make the knobs exact no-ops without branching:
    ``top_k <= 0`` means k = V (the k-th highest logit is the minimum,
    and the strict ``<`` mask drops nothing), and ``top_p >= 2.0``
    keeps every sorted entry (cumulative mass never reaches 2), so the
    nucleus threshold lands on the row minimum.  A greedy row filtered
    through both sentinels is bitwise the unfiltered row — asserted in
    tests — which is what keeps the serving path's greedy tokens
    identical to the pre-sampling engine.  Composition order matches
    the static filter: k first, then p over the k-filtered support."""
    V = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    k = jnp.where(top_ks <= 0, V, jnp.clip(top_ks, 1, V))     # [R]
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    logits = jnp.where(logits < kth, -jnp.inf, logits)
    sorted_desc = jnp.where(jnp.arange(V)[None, :] < k[:, None],
                            sorted_desc, -jnp.inf)
    sp = jax.nn.softmax(
        sorted_desc / jnp.maximum(temps, 1e-6)[:, None], axis=-1)
    cum = jnp.cumsum(sp, axis=-1)
    keep_sorted = (cum - sp) < top_ps[:, None]  # exclusive-cumsum rule
    thresh = jnp.min(jnp.where(keep_sorted, sorted_desc, jnp.inf),
                     axis=-1, keepdims=True)
    return jnp.where(logits >= thresh, logits, -jnp.inf)


def _sample_rows(logits, keys, temps, top_ks, top_ps, dtype):
    """Per-row filtered sampling over [R, V] logits with [R] knob
    arrays and [R] per-row keys (:func:`_sample_keys`): greedy rows
    (temp <= 0) take the argmax of the logits, sampled rows a
    categorical draw at their own temperature over their own support.
    The serving engines route every emitted token — prefill first-token,
    [S, 1] decode, [S, K+1] speculative verify — through this one
    function.

    The tail does what its rows ask, decided ON THE DEVICE from the
    operands (one executable, nothing for the host to choose): a
    ``lax.cond`` on whether any row samples.  None does: the argmax,
    and no sort, softmax, running sum or draw over the vocabulary.  A
    greedy row's token never depended on its ``top_k`` / ``top_p``: the
    k-filter keeps the maximum, the nucleus rule the first sorted entry,
    ties their order, so this is bitwise what the other branch gives
    such rows.  One does: :func:`_filter_logits_rows`, then the draw."""

    def argmax(logits):
        return jnp.argmax(logits.astype(jnp.float32), axis=-1)

    def filter_then_draw(logits):
        logits = _filter_logits_rows(logits.astype(jnp.float32), temps,
                                     top_ks, top_ps)
        drawn = jax.vmap(jax.random.categorical)(
            keys, logits / jnp.maximum(temps, 1e-6)[:, None])
        return jnp.where(temps > 0.0, drawn, jnp.argmax(logits, axis=-1))

    with jax.named_scope("sample_rows"):
        return lax.cond(jnp.any(temps > 0.0), filter_then_draw, argmax,
                        logits).astype(dtype)


def _generate_scan(model, params, prompt, steps, temperature, rng,
                   top_k=None, top_p=None, eos_id=None):
    """Single-forward prefill + scanned decode: traceable anywhere a
    model.apply is — directly under jit (dense path) or inside shard_map
    (parallel path, where the model's collective ops see the mesh axes).

    The whole prompt fills the KV caches in ONE forward (the decode-mode
    attention handles T > 1 with the start-offset causal mask), then the
    remaining tokens decode one at a time under ``lax.scan`` — the old
    Tp + steps - 1 sequential model calls become ``steps`` total, the
    standard serving prefill/decode split (the win is O(Tp) fewer
    dispatches AND one big MXU-friendly attention over the prompt
    instead of Tp tiny ones).
    """
    B, Tp = prompt.shape
    if steps <= 0:
        return prompt

    def sample(logits, rng):  # logits: [B, vocab]
        return _sample(logits, rng, temperature, top_k, top_p,
                       prompt.dtype)

    # Prefill: one pass over the full prompt creates AND fills the KV
    # caches (flax initializes missing mutable collections, so no
    # separate shape-tracing pass).  return_prehead avoids the
    # [B, Tp, vocab] logits matmul — only the last position's logits are
    # needed to sample the first generated token.
    (xs, head), updated = model.apply(
        {"params": params}, prompt, pos_offset=0, return_prehead=True,
        mutable=["cache"])
    rng, sub = jax.random.split(rng)
    first = sample(xs[:, -1] @ head, sub)

    if steps == 1:
        return jnp.concatenate([prompt, first[:, None]], axis=1)

    # EOS stopping: once a row emits eos_id every later position is
    # eos_id-padded (static shapes — the scan always runs `steps` ticks;
    # finished rows just stop changing).
    done0 = (first == eos_id) if eos_id is not None else None

    def step(carry, i):
        cache, tok_in, rng, done = carry
        logits, updated = model.apply(
            {"params": params, "cache": cache}, tok_in[:, None],
            pos_offset=i, mutable=["cache"])
        rng, sub = jax.random.split(rng)
        nxt = sample(logits[:, 0], sub)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.asarray(eos_id, nxt.dtype), nxt)
            done = done | (nxt == eos_id)
        return (updated["cache"], nxt, rng, done), nxt

    init = (updated["cache"], first, rng, done0)
    _, toks = lax.scan(step, init, Tp + jnp.arange(steps - 1))
    return jnp.concatenate([prompt, first[:, None], toks.T], axis=1)


@partial(jax.jit, static_argnums=(0, 3, 6, 7, 8))
def _generate_jit(model, params, prompt, steps, temperature, rng,
                  top_k=None, top_p=None, eos_id=None):
    return _generate_scan(model, params, prompt, steps, temperature, rng,
                          top_k=top_k, top_p=top_p, eos_id=eos_id)


def _check_prompt(model, prompt, steps):
    if prompt.ndim != 2:
        raise ValueError(f"prompt must be [batch, time], got "
                         f"{prompt.shape}")
    total = prompt.shape[1] + steps
    if total > model.max_len:
        raise ValueError(
            f"prompt + steps = {total} exceeds model.max_len "
            f"{model.max_len}")


def _beam_expand(lp, fin, ln, step_lp, eos_id, dtype):
    """One beam expansion given per-beam next-token log-probs — the
    trellis bookkeeping shared by the dense/EP/Ulysses beam
    (:func:`_beam_scan`) and the TP beam
    (:func:`.tp_generate.tp_beam_search`), so the finished-beam and
    parent-gather semantics can never diverge between them.

    ``lp/fin/ln``: [B, K] cumulative log-prob / finished flag /
    generated length; ``step_lp``: [B, K, V].  Returns
    ``(new_lp, new_tok, new_fin, new_ln, parent)``."""
    B, K, V = step_lp.shape
    if eos_id is not None:
        # Finished beams: the single finite continuation is eos at +0,
        # so their cumulative score survives top_k unchanged.
        pad_row = jnp.where(jnp.arange(V) == eos_id, 0.0, -jnp.inf)
        step_lp = jnp.where(fin[:, :, None], pad_row[None, None, :],
                            step_lp)
    total = lp[:, :, None] + step_lp             # [B, K, V]
    new_lp, flat = lax.top_k(total.reshape(B, K * V), K)
    parent, new_tok = flat // V, (flat % V).astype(dtype)
    par_fin = jnp.take_along_axis(fin, parent, 1)
    new_ln = jnp.take_along_axis(ln, parent, 1) + \
        jnp.where(par_fin, 0, 1)
    new_fin = par_fin
    if eos_id is not None:
        new_fin = par_fin | (new_tok == eos_id)
    return new_lp, new_tok, new_fin, new_ln, parent


def _beam_backtrack(prompt, top_tok, toks, parents, final_lp, final_len,
                    length_penalty):
    """Reconstruct the best hypothesis through the (token, parent)
    trellis, ranked by the (optionally length-normalized) score."""
    score = final_lp
    if length_penalty:
        score = final_lp / jnp.maximum(
            final_len.astype(jnp.float32), 1.0) ** length_penalty
    best = jnp.argmax(score, axis=-1)            # [B]

    def back(beam, y):
        tok_t, par_t = y
        t = jnp.take_along_axis(tok_t, beam[:, None], 1)[:, 0]
        return jnp.take_along_axis(par_t, beam[:, None], 1)[:, 0], t

    beam0, path = lax.scan(back, best, (toks, parents), reverse=True)
    first = jnp.take_along_axis(top_tok, beam0[:, None], 1)[:, 0]
    return jnp.concatenate([prompt, first[:, None], path.T], axis=1)


def _beam_scan(model, params, prompt, steps, K, eos_id=None,
               length_penalty=0.0):
    """KV-cache beam search: prefill once on B rows, tile the caches to
    B*K beam rows, then scan decode steps keeping the K best
    (cumulative-log-prob) hypotheses per batch row.  Beam reindexing
    gathers cache rows by parent; sequences are reconstructed by a
    reverse scan over the (token, parent) trellis — no history carried
    in the decode loop.

    With ``eos_id``, a beam that emits it is FINISHED: its only legal
    continuation is eos_id at zero added log-prob, so its score freezes
    while other beams keep expanding (the fixed-shape analog of removing
    it from the frontier), and the emitted suffix is eos-padded.  With
    ``length_penalty`` alpha > 0, final hypotheses are ranked by
    ``logprob / len**alpha`` where len counts generated tokens up to and
    including the first eos — plain cumulative log-prob otherwise."""
    B, Tp = prompt.shape
    if steps <= 0:
        return prompt

    (xs, head), updated = model.apply(
        {"params": params}, prompt, pos_offset=0, return_prehead=True,
        mutable=["cache"])
    lp0 = jax.nn.log_softmax((xs[:, -1] @ head).astype(jnp.float32), -1)
    V = lp0.shape[-1]
    top_lp, top_tok = lax.top_k(lp0, K)          # [B, K] initial beams
    top_tok = top_tok.astype(prompt.dtype)
    cache = jax.tree.map(
        lambda c: (jnp.repeat(c, K, axis=0)
                   if c.ndim >= 2 and c.shape[0] == B else c),
        updated["cache"])

    if steps == 1:
        best = top_tok[:, 0]  # top_k sorts descending: beam 0 is argmax
        return jnp.concatenate([prompt, best[:, None]], axis=1)

    fin0 = (top_tok == eos_id) if eos_id is not None else \
        jnp.zeros((B, K), bool)
    len0 = jnp.ones((B, K), jnp.int32)

    def step(carry, i):
        cache, lp, tok, fin, ln = carry          # lp/tok/fin/ln: [B, K]
        logits, updated = model.apply(
            {"params": params, "cache": cache}, tok.reshape(B * K, 1),
            pos_offset=i, mutable=["cache"])
        step_lp = jax.nn.log_softmax(
            logits[:, 0].astype(jnp.float32), -1).reshape(B, K, V)
        new_lp, new_tok, new_fin, new_ln, parent = _beam_expand(
            lp, fin, ln, step_lp, eos_id, prompt.dtype)
        reorder = (jnp.arange(B)[:, None] * K + parent).reshape(-1)
        cache = jax.tree.map(
            lambda c: (c[reorder]
                       if c.ndim >= 2 and c.shape[0] == B * K else c),
            updated["cache"])
        return (cache, new_lp, new_tok, new_fin, new_ln), (new_tok, parent)

    (_, final_lp, _, _, final_len), (toks, parents) = lax.scan(
        step, (cache, top_lp, top_tok, fin0, len0),
        Tp + jnp.arange(steps - 1))

    return _beam_backtrack(prompt, top_tok, toks, parents, final_lp,
                           final_len, length_penalty)


@partial(jax.jit, static_argnums=(0, 3, 4, 5, 6))
def _beam_jit(model, params, prompt, steps, beams, eos_id=None,
              length_penalty=0.0):
    return _beam_scan(model, params, prompt, steps, beams, eos_id=eos_id,
                      length_penalty=length_penalty)


def _check_beams(model, beams):
    if beams < 1:
        raise ValueError(f"beams must be >= 1, got {beams}")
    if getattr(model, "vocab", None) is not None and beams > model.vocab:
        raise ValueError(f"beams {beams} exceeds vocab {model.vocab}")


def beam_search(model, params, prompt, steps: int, *, beams: int,
                eos_id: Optional[int] = None,
                length_penalty: float = 0.0,
                rng=None) -> jax.Array:
    """Beam-search decoding over the KV cache: returns, per batch row,
    the highest-scoring continuation among ``beams`` hypotheses expanded
    per step — ``beams=1`` is exactly greedy :func:`generate`, and with
    ``beams >= vocab`` and ``steps == 2`` it is exhaustive (both
    tested).  With ``eos_id``, beams that emit it finish (frozen score,
    eos-padded suffix); ``length_penalty`` alpha ranks final hypotheses
    by ``logprob / len**alpha`` (0.0 = raw cumulative log-prob).  Same
    single-device dense scope as :func:`generate` — use
    :func:`beam_search_parallel` for expert-parallel / ulysses /
    batch-sharded models; ``rng`` is accepted for signature symmetry and
    unused (beam search is deterministic)."""
    _check_prompt(model, prompt, steps)
    _check_beams(model, beams)
    if getattr(model, "moe_axis", None) is not None:
        raise ValueError(
            "beam_search supports dense MLPs only — use "
            "beam_search_parallel(model, ..., mesh=...) for "
            "expert-parallel decode")
    if (getattr(model, "attn_impl", "local").startswith("ulysses")
            and getattr(model, "seq_axis", None) is not None):
        raise ValueError(
            "ulysses decode needs the mesh axis in scope — use "
            "beam_search_parallel(model, ..., mesh=...) for the "
            "head-sharded-cache serving path")
    del rng
    return _beam_jit(model.clone(decode=True), params,
                     jnp.asarray(prompt), steps, int(beams),
                     None if eos_id is None else int(eos_id),
                     float(length_penalty))


def beam_search_parallel(model, params, prompt, steps: int, *, beams: int,
                         mesh, batch_axis: Optional[str] = None,
                         eos_id: Optional[int] = None,
                         length_penalty: float = 0.0) -> jax.Array:
    """Beam search under ``shard_map`` over ``mesh`` — the beam analog of
    :func:`generate_parallel` (VERDICT r3 #7).

    The decode inherits the model's training-time parallelism: an
    expert-parallel model (``moe_axis``) routes each step's B*K beam
    rows through the same dispatch/combine all-to-all as training, and a
    ulysses model (``seq_axis``) serves from the head-sharded KV cache.
    The per-step beam reindexing is a parent-gather over cache rows;
    batch (and therefore beam) rows live whole on each ``batch_axis``
    shard, and the head/expert dimensions the other axes shard are
    untouched by the gather, so the reorder stays shard-local — no
    cross-device traffic beyond the model's own collectives.  With
    ``batch_axis`` the prompt's leading dim shards over that axis.
    ``eos_id`` / ``length_penalty`` as in :func:`beam_search`.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    _check_prompt(model, prompt, steps)
    _check_beams(model, beams)
    fn = _beam_parallel_fn(model.clone(decode=True), steps, int(beams),
                           mesh, batch_axis,
                           None if eos_id is None else int(eos_id),
                           float(length_penalty))
    b_spec = P(batch_axis) if batch_axis else P()
    prompt = jax.device_put(jnp.asarray(prompt),
                            NamedSharding(mesh, b_spec))
    return fn(params, prompt)


@lru_cache(maxsize=None)
def _beam_parallel_fn(dmodel, steps, beams, mesh, batch_axis, eos_id,
                      length_penalty):
    from jax.sharding import PartitionSpec as P

    b_spec = P(batch_axis) if batch_axis else P()

    def body(params, prompt):
        return _beam_scan(dmodel, params, prompt, steps, beams,
                          eos_id=eos_id, length_penalty=length_penalty)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), b_spec),
        out_specs=b_spec, check_vma=False))


def generate(model, params, prompt, steps: int, *,
             temperature: float = 0.0,
             top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             eos_id: Optional[int] = None,
             rng: Optional[jax.Array] = None) -> jax.Array:
    """Generate ``steps`` tokens after ``prompt`` ([B, T_prompt] int).

    ``model`` must be a TransformerLM-like flax module supporting
    ``decode=True`` (single-device attention); pass the TRAINING model —
    this wrapper rebinds it for decoding.  ``temperature=0`` is greedy;
    otherwise softmax sampling at the given temperature using ``rng``,
    optionally restricted to the ``top_k`` highest-logit tokens and/or
    the ``top_p`` nucleus (smallest set reaching that probability mass).
    With ``eos_id``, rows that emit it stop: every later position is
    eos_id (static shapes — the scan still runs ``steps`` ticks).
    Returns the full [B, T_prompt + steps] sequence.
    """
    _check_prompt(model, prompt, steps)
    _check_sampling(top_k, top_p)
    if getattr(model, "moe_axis", None) is not None:
        raise ValueError(
            "generate() supports dense MLPs only: moe_axis routing needs "
            "a shard_map mesh axis — use generate_parallel(model, ..., "
            "mesh=...) to decode an expert-parallel model")
    if (getattr(model, "attn_impl", "local").startswith("ulysses")
            and getattr(model, "seq_axis", None) is not None):
        raise ValueError(
            "ulysses decode needs the mesh axis in scope — use "
            "generate_parallel(model, ..., mesh=...) for the "
            "head-sharded-cache serving path")
    dmodel = model.clone(decode=True)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return _generate_jit(dmodel, params, jnp.asarray(prompt), steps,
                         jnp.float32(temperature), rng, top_k, top_p,
                         None if eos_id is None else int(eos_id))


def generate_parallel(model, params, prompt, steps: int, *, mesh,
                      batch_axis: Optional[str] = None,
                      temperature: float = 0.0,
                      top_k: Optional[int] = None,
                      top_p: Optional[float] = None,
                      eos_id: Optional[int] = None,
                      rng: Optional[jax.Array] = None) -> jax.Array:
    """Sharded generation: the fused prefill+decode scan under
    ``shard_map`` over ``mesh``.

    The decode inherits the model's training-time parallelism: an
    expert-parallel model (``moe_axis`` set) routes each step's tokens
    through the same dispatch/combine all-to-all as training, with the
    per-step expert capacity computed from the tiny decode token count
    (capacity-based routing degrades to near-capacity-1).  With
    ``batch_axis`` the batch dimension additionally shards over that
    mesh axis (the leading prompt dim must divide by its size); sampling
    rngs are folded per-shard so sharded batches don't sample in
    lockstep.  Params are taken replicated (P()).  Returns the full
    [B, T_prompt + steps] sequence, sharded over ``batch_axis`` if set.

    The reference has no serving story at all (SURVEY.md §1: 2016-era
    convnets); this extends the beyond-reference EP/DP training axes to
    inference so a model trained parallel can be sampled parallel.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    _check_prompt(model, prompt, steps)
    _check_sampling(top_k, top_p)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    fn = _parallel_fn(model.clone(decode=True), steps, mesh, batch_axis,
                      top_k, top_p,
                      None if eos_id is None else int(eos_id))
    b_spec = P(batch_axis) if batch_axis else P()
    prompt = jax.device_put(jnp.asarray(prompt),
                            NamedSharding(mesh, b_spec))
    return fn(params, prompt, jnp.float32(temperature), rng)


# ---------------------------------------------------------------------------
# Slot-indexed cache plumbing (the continuous-batching serving path,
# torchmpi_tpu/serving/ — docs/SERVING.md).  Four primitives over a
# POOL cache whose batch dimension is the slot dimension:
#
# - :func:`slot_prefill`    — one request's prompt onto a FRESH [1, L]
#   cache (the same single-forward prefill + last-position sampling as
#   :func:`_generate_scan`, so tokens can never diverge from ``generate``);
#   with ``true_len`` the prompt may be right-PADDED to a length bucket
#   — the logits are sliced at the true last position, so padded and
#   unpadded prefill emit bitwise-identical tokens while the compile
#   count drops from O(distinct lengths) to O(buckets);
# - :func:`slot_write`      — write that request's cache rows into pool
#   row ``slot`` (admission);
# - :func:`slot_decode_step` — ONE [S, 1] decode tick advancing every
#   active slot at its own depth (per-row ``pos_offset`` — see
#   ``SPAttention``); rows beyond a slot's filled prefix are masked, so
#   REUSING a retired slot needs no zeroing to stay bit-identical to a
#   fresh static-batch decode;
# - :func:`slot_verify_step` — the speculative-decoding verify: ONE
#   [S, K+1] forward scoring each slot's pending token plus its K draft
#   tokens at per-row depths, returning what the model samples at EVERY
#   position — the accept/reject scan over those samples is host-side
#   (serving/engine.py) and distribution-exact by construction.
#
# Ownership: a POOL handed to :func:`slot_write`, :func:`slot_decode_step`
# or :func:`slot_verify_step` is CONSUMED (``donate_argnums``: the program
# writes its positions into the pool's own buffers, where an input it did
# not own would first be copied whole, every step).  Use what comes back
# and hold no second reference: the arrays that went in are deleted.  A
# ROW is not consumed: ``slot_write`` leaves ``one_cache`` alone (the
# engine cuts prefix fragments from it after the write), and the row
# programs (:func:`slot_prefill`, :func:`slot_extend`,
# :func:`slot_cache_write`) hand back a new row, because the engine's zero
# row is one template shared by every admission.
#
# Sampling: each primitive takes a ``sampling`` operand tuple
# ``(seeds, idxs, temps, top_ks, top_ps)`` ([R] arrays) routed through
# :func:`_sample_rows` — greedy rows use the no-op sentinels (temp 0,
# top_k 0, top_p 2.0) and stay bitwise-deterministic, which is what
# keeps re-routing token-exact: a re-prefilled session re-derives the
# same per-token keys from (seed, token index).
# ---------------------------------------------------------------------------


def clamp_slot_positions(positions, limit, width=1):
    """THE cache-index clamp chokepoint: bound ``positions`` (scalar or
    [S]) to ``[0, limit - width]`` so a width-``width``
    ``dynamic_update_slice``/``dynamic_slice`` at each position provably
    stays inside a ``limit``-deep buffer.  For valid inputs (the only
    inputs correct callers produce — serving/engine.py clamps host-side)
    this is bitwise the identity; what it buys is the PROOF: an
    out-of-range start otherwise CLAMPS silently (corrupt last rows, no
    error — the PR 17 bug class), and the static analyzer's S1 rule can
    only certify a write whose index is visibly bounded.  Every cache
    write in ``transformer.SPAttention`` decode and the TP decode blocks
    routes through here; S2 flags per-row slot writes that don't (the
    trace record below is its evidence).
    """
    from .. import fusion

    limit, width = int(limit), int(width)
    fusion._emit_trace_record(
        {"kind": "slot_clamp", "limit": limit, "width": width})
    return jnp.clip(jnp.asarray(positions), 0, max(0, limit - width))


def _greedy_sampling(n):
    """Sentinel sampling arrays for n rows: greedy, filter no-ops."""
    return (jnp.zeros((n,), jnp.uint32), jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.int32),
            jnp.full((n,), 2.0, jnp.float32))


#: Cache leaves with NO token axis: one value a slot, whatever the slot's
#: depth (``transformer.Mamba2Mixer``'s recurrent state and the last inputs
#: of its convolution).  ``slot_write`` carries them as they are; the
#: engine books them a slot, not a token, and the fragment primitives
#: below, which cut every leaf along axis 1, are not for a model that has
#: them (``serving.ReplicaEngine`` refuses the prefix cache there).
STATE_LEAVES = ("ssm_state", "conv_state")


@partial(jax.jit, static_argnums=(0,))
def _slot_prefill_jit(dmodel, params, prompt, true_len, seeds, idxs,
                      temps, top_ks, top_ps):
    # A model with state leaves is told the true length: what a recurrent
    # state holds after the bucket's padding is not what it held after the
    # prompt (the other models' programs are what they were).
    told = ({"true_len": true_len}
            if getattr(dmodel, "layer_pattern", None) else {})
    (xs, head), updated = dmodel.apply(
        {"params": params}, prompt, pos_offset=0, return_prehead=True,
        mutable=["cache"], **told)
    # The TRUE last position, not -1: with bucketed prefill the prompt
    # is right-padded, and the pad positions' logits must never be
    # sampled.  (Causality makes the real positions' activations
    # independent of the padding, so the sliced logits are bitwise the
    # unpadded ones; the pad positions' k/v land in the cache but every
    # later query is depth-masked below them until the decode steps
    # overwrite them in order.  A recurrent state is the exception, told
    # above.)
    x_last = lax.dynamic_slice_in_dim(
        xs, clamp_slot_positions(true_len - 1, xs.shape[1]), 1,
        axis=1)[:, 0]
    first = _sample_rows(x_last @ head, _sample_keys(seeds, idxs),
                         temps, top_ks, top_ps, prompt.dtype)
    return updated["cache"], first


def slot_prefill(dmodel, params, prompt, *, true_len=None,
                 sampling=None):
    """Prefill one request ([1, Tp] prompt, possibly right-padded to a
    length bucket) on a fresh cache; returns ``(cache, first_token
    [1])``.  ``dmodel`` is the ``decode=True`` clone (one jit
    specialization per PADDED prompt length — ``true_len`` is a traced
    operand, so every length in a bucket shares the executable).
    ``sampling`` is the 5-tuple of [1] arrays; None means greedy."""
    prompt = jnp.asarray(prompt)
    if true_len is None:
        true_len = prompt.shape[1]
    if sampling is None:
        sampling = _greedy_sampling(prompt.shape[0])
    return _slot_prefill_jit(dmodel, params, prompt,
                             jnp.asarray(true_len, jnp.int32), *sampling)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _slot_step_jit(dmodel, params, cache, tokens, positions, seeds,
                   idxs, temps, top_ks, top_ps):
    from ..parallel.expert import decode_counts

    logits, updated = dmodel.apply(
        {"params": params, "cache": cache}, tokens[:, None],
        pos_offset=positions, mutable=["cache", "moe"])
    nxt = _sample_rows(logits[:, 0], _sample_keys(seeds, idxs), temps,
                       top_ks, top_ps, tokens.dtype)
    # a live slot writes at its prompt's length or later, an idle one at 0
    return (updated["cache"], nxt,
            decode_counts(updated.get("moe", {}), positions > 0,
                          getattr(dmodel, "experts_held", None)))


def slot_decode_step(dmodel, params, cache, tokens, positions,
                     sampling=None, counted: bool = False):
    """One decode tick over the whole slot pool; consumes the pool it is
    given (``cache`` is deleted: use the one that comes back).  ``tokens``
    [S] are each slot's pending token, ``positions`` [S] its absolute
    write index
    (inactive slots pass 0 and any token: their outputs are ignored,
    their cache rows are fully overwritten on the next admission, and the
    expert layers' counts leave them out).
    Returns ``(new_cache, next_tokens [S])``, and with ``counted`` a third
    value: what the step's expert layers did
    (``parallel.expert.decode_counts``: [layers, 3] int32, ready when the
    tokens are), None for a model without expert layers.  One compiled
    executable serves the entire trace — admission, retirement, and
    greedy/sampled mixes never retrace (the sampling knobs are [S]
    operands)."""
    tokens = jnp.asarray(tokens)
    if sampling is None:
        sampling = _greedy_sampling(tokens.shape[0])
    out = _slot_step_jit(dmodel, params, cache, tokens,
                         jnp.asarray(positions), *sampling)
    return out if counted else out[:2]


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _slot_verify_jit(dmodel, params, cache, tokens, positions, seeds,
                     idxs, temps, top_ks, top_ps):
    logits, updated = dmodel.apply(
        {"params": params, "cache": cache}, tokens,
        pos_offset=positions, mutable=["cache"])
    S, T, V = logits.shape
    # Position j of row s samples with key (seed_s, idx_s + j): exactly
    # the key the NON-speculative path would use for that token index,
    # which is what makes accept-until-mismatch emit a bitwise-identical
    # stream (each kept sample conditions on an accepted prefix, i.e.
    # the same context the sequential path would have fed).
    keys = _sample_keys(
        jnp.repeat(seeds, T),
        (idxs[:, None] + jnp.arange(T, dtype=jnp.int32)).reshape(-1))
    flat = _sample_rows(logits.reshape(S * T, V), keys,
                        jnp.repeat(temps, T), jnp.repeat(top_ks, T),
                        jnp.repeat(top_ps, T), tokens.dtype)
    return updated["cache"], flat.reshape(S, T)


def slot_verify_step(dmodel, params, cache, tokens, positions,
                     sampling=None):
    """The speculative-decoding verify forward; consumes the pool it is
    given (``cache`` is deleted: use the one that comes back).  ``tokens``
    [S, K+1] is each slot's pending token followed by its K draft tokens,
    ``positions`` [S] each slot's write index.  One forward writes all
    K+1 k/v entries at per-row depths and returns the model's sample at
    EVERY position ([S, K+1]) — sample j is the token the sequential
    decode would emit after the fed prefix ``tokens[:, :j+1]``, so the
    host-side scan "accept while draft matches, then take the model's
    corrected token" reproduces non-speculative decoding bit for bit.
    Rejected positions leave stale k/v behind; the next forward for
    that row starts at its accepted depth and re-writes them before any
    query can attend (same-forward cache update precedes attention),
    so no masking bookkeeping is needed."""
    tokens = jnp.asarray(tokens)
    if sampling is None:
        sampling = _greedy_sampling(tokens.shape[0])
    return _slot_verify_jit(dmodel, params, cache, tokens,
                            jnp.asarray(positions), *sampling)


@partial(jax.jit, donate_argnums=(0,))
def _slot_write_jit(pool_cache, one_cache, slot):
    pooled = [p for p in jax.tree.leaves(pool_cache)
              if getattr(p, "ndim", 0) >= 1]
    if pooled:
        slot = clamp_slot_positions(slot, pooled[0].shape[0])

    def put(p, o):
        if getattr(o, "ndim", 0) >= 1 and o.shape[0] == 1 \
                and p.ndim == o.ndim:
            return lax.dynamic_update_slice(
                p, o.astype(p.dtype), (slot,) + (0,) * (p.ndim - 1))
        return p  # scalar cache leaves (the unused idx counter)

    return jax.tree.map(put, pool_cache, one_cache)


def slot_write(pool_cache, one_cache, slot: int):
    """Write a :func:`slot_prefill` cache (leading dim 1) into row
    ``slot`` of the pool cache (leading dim = slot count); consumes the
    pool it is given (``pool_cache`` is deleted: use the one that comes
    back) and leaves ``one_cache`` as it was."""
    return _slot_write_jit(pool_cache, one_cache,
                           jnp.asarray(slot, jnp.int32))


# ---------------------------------------------------------------------------
# Prefix-cache fragment primitives (serving/prefix_cache.py).  A
# "fragment" is a width-W token-axis slice of a single-row cache — the
# k/v a shared prompt prefix produced.  Causality + absolute-position
# rope make a prefix's k/v depend ONLY on the prefix tokens, so a
# fragment sliced from one request's prefill is bitwise the fragment
# every later request sharing that prefix would have computed; writing
# it back and running :func:`slot_extend` over just the unshared suffix
# reproduces the full prefill bit for bit (the per-row depth mask hides
# everything beyond the assembled depth, exactly the argument that
# already covers slot reuse and bucketed-prefill padding).
#
# Both helpers are layout-generic pytree maps: a leaf participates iff
# it looks like a per-row cache plane — ``ndim >= 2`` with a leading
# row dim of 1 (token axis 1).  That covers the dense flax cache dict
# ([1, max_len, heads, dim] k/v) and the TP list-of-(k, v) pairs alike;
# the dense cache's scalar ``idx`` counter falls through untouched.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(2,))
def _slot_cache_slice_jit(row_cache, start, width):
    def cut(p):
        if getattr(p, "ndim", 0) >= 2 and p.shape[0] == 1:
            return lax.dynamic_slice_in_dim(
                p, clamp_slot_positions(start, p.shape[1], width),
                width, axis=1)
        return p
    return jax.tree.map(cut, row_cache)


def slot_cache_slice(row_cache, start: int, width: int):
    """Slice ``width`` token positions starting at ``start`` out of a
    single-row cache — the fragment a prefix-cache node stores."""
    return _slot_cache_slice_jit(row_cache,
                                 jnp.asarray(start, jnp.int32),
                                 int(width))


@jax.jit
def _slot_cache_write_jit(row_cache, frag, start):
    def put(p, f):
        if getattr(f, "ndim", 0) >= 2 and f.shape[0] == 1 \
                and p.ndim == f.ndim:
            pos = clamp_slot_positions(start, p.shape[1], f.shape[1])
            return lax.dynamic_update_slice(
                p, f.astype(p.dtype),
                (0, pos) + (0,) * (p.ndim - 2))
        return p
    return jax.tree.map(put, row_cache, frag)


def slot_cache_write(row_cache, frag, start: int):
    """Write a :func:`slot_cache_slice` fragment back into a single-row
    cache at token position ``start`` (cache-hit row assembly)."""
    return _slot_cache_write_jit(row_cache, frag,
                                 jnp.asarray(start, jnp.int32))


@partial(jax.jit, static_argnums=(0,))
def _slot_extend_jit(dmodel, params, row_cache, suffix, pos_offset,
                     true_len, seeds, idxs, temps, top_ks, top_ps):
    (xs, head), updated = dmodel.apply(
        {"params": params, "cache": row_cache}, suffix,
        pos_offset=pos_offset, return_prehead=True, mutable=["cache"])
    # true_len is SUFFIX-local: the true last position within the
    # (possibly right-padded) suffix block, same bucketing contract as
    # _slot_prefill_jit.
    x_last = lax.dynamic_slice_in_dim(
        xs, clamp_slot_positions(true_len - 1, xs.shape[1]), 1,
        axis=1)[:, 0]
    first = _sample_rows(x_last @ head, _sample_keys(seeds, idxs),
                         temps, top_ks, top_ps, suffix.dtype)
    return updated["cache"], first


def slot_extend(dmodel, params, row_cache, suffix, *, pos_offset,
                true_len=None, sampling=None):
    """Prefill only the unshared SUFFIX of a prompt over a single-row
    cache pre-assembled from prefix-cache fragments; returns
    ``(cache, first_token [1])``.

    ``suffix`` is [1, Ts] (right-padded to a bucket like
    :func:`slot_prefill`; ``true_len`` is the suffix's true length),
    ``pos_offset`` the [1] absolute depth of the assembled prefix.  The
    1-D per-row offset with T > 1 takes the same cache-masked attention
    branch the speculative verify forward uses: queries attend the
    assembled fragments plus the in-flight suffix and nothing deeper —
    exactly the positions a full prefill's causal mask admits — and the
    sampling key is ``(seed, idx)`` with idx = the prompt's global
    token count, so a cache hit leaves the ``fold_in`` schedule
    untouched and the emitted stream bitwise-identical to a miss (and
    to offline ``generate``)."""
    suffix = jnp.asarray(suffix)
    if true_len is None:
        true_len = suffix.shape[1]
    if sampling is None:
        sampling = _greedy_sampling(suffix.shape[0])
    return _slot_extend_jit(dmodel, params, row_cache, suffix,
                            jnp.asarray(pos_offset, jnp.int32),
                            jnp.asarray(true_len, jnp.int32), *sampling)


@lru_cache(maxsize=None)
def _parallel_fn(dmodel, steps, mesh, batch_axis, top_k=None, top_p=None,
                 eos_id=None):
    """Build (once per (model, steps, mesh, batch_axis, filters)) the
    jitted shard_map serving fn — a fresh closure per call would retrace
    and recompile the whole scan every invocation; temperature and rng
    stay operands so greedy/sampled calls share the executable."""
    from jax.sharding import PartitionSpec as P

    b_spec = P(batch_axis) if batch_axis else P()

    def body(params, prompt, temperature, rng):
        if batch_axis is not None:
            rng = jax.random.fold_in(rng, lax.axis_index(batch_axis))
        return _generate_scan(dmodel, params, prompt, steps,
                              temperature, rng, top_k=top_k, top_p=top_p,
                              eos_id=eos_id)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), b_spec, P(), P()),
        out_specs=b_spec, check_vma=False))
