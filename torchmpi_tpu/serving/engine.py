"""One replica's continuous-batching decode engine.

A :class:`ReplicaEngine` owns one slot-pooled KV cache (leading dim =
slot count) plus the per-slot session bookkeeping, and exposes the two
iteration-level operations the scheduler composes:

- :meth:`admit` — allocate a slot, prefill the request's prompt onto a
  fresh cache, write it into the pool row, emit the FIRST token (the
  TTFT event).  Admission happens at token boundaries: no batch
  formation, no waiting for peers.  With ``prefill_bucket`` set the
  prompt is right-padded to a pow-2 length bucket, so the prefill
  executable count is O(buckets) instead of O(distinct lengths) — the
  emitted token is bitwise the unpadded one (the logits are sliced at
  the true last position; causality keeps it independent of padding).
  New executables are counted (``stats["prefill_compiles"]`` +
  ``tm_serving_prefill_compiles_total``) on the bucketed AND unbucketed
  paths, so the recompile cost is visible either way.
- :meth:`step` — ONE ``[S, 1]`` decode tick advancing every in-flight
  slot at its own cache depth (``models.generate.slot_decode_step``),
  as a one-deep pipeline: the call dispatches the NEXT pooled step,
  whose input tokens are this step's, left on the device, and only
  then reads this step's tokens and books them, so the chip runs the
  next step while the host reads, books and prepares the one after.
  Each call returns one token per session of the step it read (the
  first call on an idle pool dispatches two steps).  Sequences that
  emit EOS or reach their token budget retire when that token is read
  and their slot frees for the next admission.  With
  ``spec_k`` > 0 the tick becomes draft-then-verify (synchronous: the
  draft needs the tokens on the host): a
  :mod:`.spec` proposer drafts K tokens per slot, ONE ``[S, K+1]``
  target forward (``slot_verify_step``) scores them all, and the
  accept loop emits tokens exactly while drafts match — the stream is
  **bitwise-identical** to the non-speculative tick at the same seed,
  it just lands up to K+1 tokens per forward.

Sampling is per-request (temperature / top-k / top-p / seed, resolved
against the Config defaults at admission) and bitwise-reproducible
given (seed, prompt): token ``i`` of a request draws from
``fold_in(PRNGKey(seed), i)`` regardless of slot, pool neighbors, or
re-routes — which is also what keeps a drained session token-exact
when it re-prefills elsewhere (greedy OR sampled).

Work accounting: ``stats`` counts executable invocations and
``units`` accumulates work units (prefill = 1, pooled forward = 1,
draft forwards at the proposer's ``unit_weight``) — the noise-immune
clock ``benchmarks/serving_bench.py`` compares schedules on.

The engine is time-free and telemetry-free on purpose (the
exceptions are properties of the engine's own programs: the
prefill-compile counter above, the padded prompt tokens
(``stats["prefill_tokens"]``), the gauges of what a cached token, a
slot's recurrent state and a slot's window rings cost (each layer's
kind of cache is the model's to say: ``TransformerLM.layers``), how many
of the programs that were handed the pool took it over
(``stats["pool_donated"]`` of ``stats["pool_calls"]``), which branch
of the sampling tail each pooled step's sessions ask for
(:data:`SAMPLE_BRANCHES`: ``stats["sample_argmax"]`` +
``stats["sample_draw"]`` = ``stats["steps"]``), how many live sessions
the pooled steps decoded (``stats["live_slot_steps"]``: over
``stats["steps"]``, the slots a step REQUIRES the state of), the ring
rows those sessions read (``stats["window_rows_live"]``), the pooled
steps dispatched while an earlier step was unread
(``stats["steps_ahead"]``) and the rows decoded for a session that had
already ended at the unread step (``stats["rows_past_end"]``), and, for
a model with expert layers, what the pooled step itself
counted: held experts touched, routes and routes held, read with the tokens): the
scheduler owns the clock, the SLO histograms, and the fault hooks, so
the engine stays a pure slot/cache mechanism that tests can drive tick
by tick.  Which kernel a program runs is its layers' decision, made at
trace time; a profile shows it (``flash.fwd``, ``kv.write_rows``,
``moe.decode_experts``), the engine does not count it.

On the profiler's clock the engine names its own phases (:data:`SPANS`:
``tm.serve.admit`` and ``tm.serve.step`` with their children, each a
``jax.profiler.TraceAnnotation``, a flag test when no profiler is
attached; docs/OBSERVABILITY.md, "What a profile shows").
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import runtime
from ..models.generate import slot_cache_slice, slot_cache_write, \
    slot_decode_step, slot_extend, slot_prefill, slot_verify_step, \
    slot_write
from ..utils.telemetry import emit
from .prefix_cache import PrefixCache
from .slots import SlotPool

#: The serving tier's spans on the profiler's clock, parents before
#: children: THE list the tests and docs/OBSERVABILITY.md are held to.
#: ``gate`` and ``tick`` are the scheduler's, the rest the engine's; a
#: span's stats are given when it opens (``rid``, ``slot``, ``step``, ...).
SPANS = (
    "tm.serve.gate", "tm.serve.tick",
    "tm.serve.admit", "tm.serve.admit.operands", "tm.serve.admit.prefill",
    "tm.serve.admit.slot_write", "tm.serve.admit.read",
    "tm.serve.admit.book",
    "tm.serve.step", "tm.serve.step.operands", "tm.serve.step.draft",
    "tm.serve.step.dispatch", "tm.serve.step.read", "tm.serve.step.book")
span = jax.profiler.TraceAnnotation

#: What a pooled step's rows ask of the sampling tail, by the branch
#: ``models.generate._sample_rows`` takes for them on the device: no row
#: samples (the argmax) | some row samples (the filter, then the draw).
SAMPLE_BRANCHES = ("sample_argmax", "sample_draw")


class RequestRejected(ValueError):
    """Raised by :meth:`ReplicaEngine.admit` for a request that can
    NEVER be served (its ``prompt + max_new`` exceeds the slot block,
    or its sampling knobs are invalid).  A dedicated type so the
    scheduler can reject exactly this case and keep serving — any
    other exception out of admission is a real bug and stays loud."""


def _gone(pool) -> bool:
    """Whether a program took ``pool`` over: its first pooled leaf is
    deleted (an attribute of the array; the device is not asked)."""
    return next(leaf for leaf in jax.tree.leaves(pool)
                if getattr(leaf, "ndim", 0) >= 1).is_deleted()


@dataclasses.dataclass
class Session:
    """One in-flight request on one slot."""

    request: Any            # scheduler.Request
    slot: int
    last_tok: int           # the last token read (or the prefill's)
    pos_next: int           # absolute cache index the next dispatch writes
    emitted: List[int] = dataclasses.field(default_factory=list)
    #: Pooled steps dispatched for this session whose tokens are unread.
    ahead: int = 0
    #: Resolved (temperature, top_k, top_p, seed); greedy rows carry
    #: the filter no-op sentinels (0.0, 0, 2.0).
    sampling: Tuple[float, int, float, int] = (0.0, 0, 2.0, 0)
    #: Tokens emitted by the LAST tick that advanced this session (1
    #: for admit/plain step, up to K+1 for a speculative tick) — the
    #: scheduler's token/ITL accounting reads it.
    last_emit: int = 1
    #: Prefix-cache nodes this session pinned at admission (empty when
    #: the cache is off or missed) — released at retirement so idle
    #: blocks become evictable again.
    prefix_chain: List[Any] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Unread:
    """A pooled step dispatched and not yet read: its [S] tokens and its
    expert counts, on the device, and the session that owned each row it
    decoded when it was dispatched."""

    tokens: Any
    counts: Any
    owners: Dict[int, Session]


class ReplicaEngine:
    """Slot-pooled decode engine for one model replica.

    ``slots``/``slot_tokens``/``sample``/``prefill_bucket``/``spec_k``
    default from the active :class:`~torchmpi_tpu.config.Config`
    (``serving_slots`` / ``serving_slot_tokens`` / ``serving_sample`` /
    ``serving_prefill_buckets`` / ``serving_spec_k``).  ``draft`` is a
    :mod:`.spec` proposer template (bound per engine); ``spec_k`` > 0
    with no draft binds an :class:`~.spec.NgramDraft`.  With ``device``
    set, params and the pool cache are committed to that device, so
    replicas of one host spread over its chips exactly like
    data-parallel shards.
    """

    def __init__(self, model, params, *, name: str = "replica0",
                 slots: Optional[int] = None,
                 slot_tokens: Optional[int] = None,
                 device=None, sample: Optional[float] = None,
                 prefill_bucket: Optional[int] = None,
                 spec_k: Optional[int] = None, draft=None,
                 prefix_cache: Optional[int] = None,
                 prefix_block: int = 8):
        cfg = runtime.effective_config()
        slots = int(slots if slots is not None else cfg.serving_slots)
        st = int(slot_tokens if slot_tokens is not None
                 else (cfg.serving_slot_tokens or 0))
        if st == 0:
            st = int(model.max_len)
        if getattr(model, "pos_emb", "learned") == "learned" \
                and st != model.max_len:
            raise ValueError(
                f"serving_slot_tokens={st} != model.max_len="
                f"{model.max_len}: a learned position table is sized by "
                f"max_len, so slot blocks can only be shrunk for "
                f"pos_emb='rope' models")
        if getattr(model, "moe_axis", None) is not None or \
                getattr(model, "seq_axis", None) is not None:
            raise ValueError(
                "ReplicaEngine serves dense single-device models; use "
                "serving.TPReplicaEngine (or Server.sharded) for a "
                "mesh-parallel replica")
        self.dmodel = model.clone(decode=True, max_len=st)
        self.params = (jax.device_put(params, device)
                       if device is not None else params)
        self._device = device
        self.vocab = int(model.vocab)
        self.param_count = sum(int(np.prod(p.shape))
                               for p in jax.tree.leaves(params))
        self._init_serving(cfg, name, slots, st, sample=sample,
                           prefill_bucket=prefill_bucket, spec_k=spec_k,
                           draft=draft, prefix_cache=prefix_cache,
                           prefix_block=prefix_block)
        # Zero pool cache from the decode model's cache spec — no
        # forward pass runs at construction.
        shapes = jax.eval_shape(
            lambda: self.dmodel.init(
                jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32),
                pos_offset=jnp.zeros((slots,), jnp.int32)))["cache"]
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             shapes)
        self._cache = (jax.device_put(cache, device)
                       if device is not None else cache)
        if device is not None:
            self._no_prev = jax.device_put(self._no_prev, device)
        from .. import obs
        from flax.traverse_util import flatten_dict

        # The pool books its leaves by what their layer caches, as the
        # model says (models.transformer.Layer.cache, layer n's leaves under
        # Block_n).  A layer with a token axis as long as the slot costs a
        # slot its DEPTH (per-head keys and values, or a latent and its
        # rotary key); a window layer's RING (keys and values of its last
        # ``window`` positions) and a recurrent layer's STATE cost every
        # slot the same, whatever its depth.
        layers = self.dmodel.layers()
        kind = {f"Block_{i}": layer.cache for i, layer in enumerate(layers)}
        leaves = {path: s for path, s in flatten_dict(shapes).items()
                  if len(s.shape) >= 2}       # not the unused idx counter
        kept = {k: sorted(p for p in leaves if kind[p[0]] == k)
                for k in ("tokens", "ring", "state")}
        state, ring = kept["state"], kept["ring"]

        def leaf_bytes(paths, axis):     # each leaf's from ``axis`` on
            return sum(int(np.prod(leaves[p].shape[axis:]))
                       * leaves[p].dtype.itemsize for p in paths)

        #: What a cached token costs, summed over the leaves with a token
        #: axis, and what a slot's state and rings cost, summed over theirs.
        self.cache_bytes_per_token = leaf_bytes(kept["tokens"], 2)
        self.state_bytes_per_slot = leaf_bytes(state, 1)
        self.ring_bytes_per_slot = leaf_bytes(ring, 1)
        #: The window of every ring layer, for ``stats["window_rows_live"]``.
        self._ring_windows = [layer.window for layer in layers
                              if layer.cache == "ring"]
        for gauge, value in (
                ("cache_bytes_per_token", self.cache_bytes_per_token),
                ("state_bytes_per_slot", self.state_bytes_per_slot),
                ("ring_bytes_per_slot", self.ring_bytes_per_slot)):
            obs.registry().gauge_set(f"tm_serving_{gauge}", value,
                                     replica=name)
        if (state or ring) and (self._prefix is not None
                                or self._spec_k > 0):
            # a state cannot be cut into fragments by token, nor un-updated
            # after a rejected draft: both need snapshots (ROADMAP B5); a
            # ring holds no token axis to cut fragments from either, and a
            # rejected draft's rows overwrite positions its window still
            # reads
            asked = ("the prefix cache (prefix_cache > 0: slot_cache_slice "
                     "and slot_extend)" if self._prefix is not None
                     else "speculation (spec_k > 0)")
            what = (f"a per-slot recurrent state ({'/'.join(state[0])}, "
                    f"{len(state)} such leaves)" if state else
                    f"a window layer's keys and values in a ring of "
                    f"{self._ring_windows[0]} rows ({'/'.join(ring[0])}, "
                    f"{len(ring)} such leaves)")
            raise ValueError(f"{name}: the model keeps {what}, which "
                             f"{asked} cannot serve yet: set it to 0")
        # The expert layers (by their router's path, the order in which
        # the decode step counts them), each with its three counters' handles.
        count = obs.registry().counter_handle
        self._expert_counters = [
            tuple(count(f"tm_moe_{what}_total", layer="/".join(path[:-1]))
                  for what in ("experts_touched", "decode_routes",
                               "decode_routes_held"))
            for path in sorted(flatten_dict(params))
            if path[-1] == "router"]
        self._expert_steps = count("tm_moe_decode_steps_total", replica=name)

    def _init_serving(self, cfg, name, slots, st, *, sample,
                      prefill_bucket, spec_k, draft,
                      prefix_cache=None, prefix_block=8):
        """Backend-independent serving state (shared with the
        mesh-parallel subclass, which does NOT run the dense
        ``__init__``)."""
        self.name = name
        cap = int(prefix_cache if prefix_cache is not None
                  else cfg.serving_prefix_cache)
        self.pool = SlotPool(slots, st, prefix_blocks=cap)
        if cap > 0:
            self._prefix = PrefixCache(
                self.pool, block_tokens=min(int(prefix_block), st))
        else:
            self._prefix = None
        #: Lazily built 1-row zero cache (the assembly canvas for
        #: prefix-cache hits) — jax arrays are immutable, so one
        #: template serves every admission.
        self._row_zero = None
        #: The window of each layer whose keys and values are a ring (the
        #: dense engine reads them off its model's layers).
        self._ring_windows: List[int] = []
        self.dead = False
        #: The open sessions, by slot: each one's final token is unread.
        self._sessions: Dict[int, Session] = {}
        #: Pooled steps dispatched and not yet read, oldest first (one
        #: between calls of :meth:`step`, none with a draft bound).
        self._unread: "collections.deque[_Unread]" = collections.deque()
        #: What the last :meth:`_backend_step` counted (its expert
        #: layers' counts, on the device; None without expert layers):
        #: beside it, so that the hook returns the step's tokens alone,
        #: the contract the benchmark's fault test wraps.
        self._step_counts = None
        #: What a step dispatched with no step unread merges from (every
        #: row's token is the host's), placed as a step's own tokens are
        #: (the dense engine commits it to its device), so that one
        #: executable serves both dispatches.
        self._no_prev = jnp.zeros((slots,), jnp.int32)
        self._sample_default = float(
            sample if sample is not None else cfg.serving_sample)
        self._bucket = int(prefill_bucket if prefill_bucket is not None
                           else cfg.serving_prefill_buckets)
        self._spec_k = int(spec_k if spec_k is not None
                           else cfg.serving_spec_k)
        if self._spec_k > 0:
            if draft is None:
                from .spec import NgramDraft

                draft = NgramDraft()
            self._draft = draft.bind(self)
        else:
            self._draft = None
        #: Padded prompt lengths this engine has prefilled — each new
        #: one is one jit specialization, i.e. one XLA compile.
        self._prefill_lens: set = set()
        #: Executable-invocation counters — the work-unit accounting
        #: benchmarks/serving_bench.py builds its noise-immune
        #: continuous-vs-static comparison on.  ``spec_drafted`` /
        #: ``spec_accepted`` give the live acceptance rate.
        self.stats = {"prefills": 0, "steps": 0, "prefill_compiles": 0,
                      "spec_steps": 0, "spec_drafted": 0,
                      "spec_accepted": 0, "prefill_tokens": 0,
                      "prefix_hits": 0, "prefix_misses": 0,
                      "pool_calls": 0, "pool_donated": 0,
                      "live_slot_steps": 0, "window_rows_live": 0,
                      "steps_ahead": 0, "rows_past_end": 0,
                      **dict.fromkeys(SAMPLE_BRANCHES, 0)}
        #: Work units spent (prefill/pooled forward = 1 each, draft
        #: forwards at the proposer's weight) — the scheduler's
        #: ``unit_seconds`` virtual clock advances by the delta.
        self.units = 0.0

    # -- introspection -----------------------------------------------------

    @property
    def active(self) -> int:
        """Open sessions: those whose final token is not read yet."""
        return len(self._sessions)

    def sessions(self) -> List[Session]:
        return list(self._sessions.values())

    def has_capacity(self) -> bool:
        return not self.dead and self.pool.free_count > 0

    # -- sampling / bucketing resolution -----------------------------------

    def _resolve_sampling(self, request) -> Tuple[float, int, float, int]:
        """Per-request knobs against the Config default, validated.
        Greedy requests are FORCED to the filter no-op sentinels
        (temp 0.0, top_k 0, top_p 2.0) so the greedy stream is bitwise
        the unfiltered argmax regardless of stray k/p values."""
        t = getattr(request, "temperature", None)
        t = self._sample_default if t is None else float(t)
        seed = int(getattr(request, "seed", 0) or 0)
        if t <= 0.0:
            return (0.0, 0, 2.0, seed)
        k = getattr(request, "top_k", None)
        k = 0 if k is None else int(k)
        p = getattr(request, "top_p", None)
        p = 2.0 if p is None else float(p)
        if k < 0:
            raise RequestRejected(
                f"request {request.rid!r}: top_k must be >= 0 "
                f"(0 = off), got {k}")
        if p != 2.0 and not 0.0 < p <= 1.0:
            raise RequestRejected(
                f"request {request.rid!r}: top_p must be in (0, 1], "
                f"got {p}")
        return (t, k, p, seed)

    def _pad_prompt(self, prompt: np.ndarray,
                    cap: Optional[int] = None) -> Tuple[np.ndarray, int]:
        """Right-pad to the pow-2 bucket (>= ``prefill_bucket``, capped
        at ``cap`` — default the slot block; a prefix-hit suffix caps
        at the room REMAINING above the assembled depth so the padded
        write provably stays inside the row).  Returns ``(padded,
        true_len)``."""
        true_len = prompt.shape[1]
        if self._bucket <= 0:
            return prompt, true_len
        bucket = max(self._bucket, 1 << max(0, true_len - 1).bit_length())
        bucket = min(bucket,
                     self.pool.slot_tokens if cap is None else cap)
        if bucket <= true_len:
            return prompt, true_len
        padded = np.zeros((1, bucket), prompt.dtype)
        padded[:, :true_len] = prompt
        return padded, true_len

    def _count_prefill_compile(self, key) -> None:
        """A prompt length this engine has not prefilled before is one
        new jit specialization — one XLA compile.  Counted on the
        bucketed and unbucketed paths alike, so the per-distinct-length
        recompile cost is visible BEFORE bucketing is turned on.
        ``key`` is the padded length for full prefill, or ``("ext",
        padded_suffix_len)`` for the prefix-hit extend forward (its own
        executable family)."""
        if key in self._prefill_lens:
            return
        self._prefill_lens.add(key)
        self.stats["prefill_compiles"] += 1
        emit("record_serving", "prefill_compiles", replica=self.name)

    def _sampling_arrays(self, sessions: Dict[int, Session]):
        """[S] operand arrays for the pooled forwards.  ``idxs`` is
        each session's global emitted-token index (pre-reroute tokens
        and those of its unread steps included) — the fold_in schedule
        that makes sampling a pure function of (seed, token index)."""
        S = self.pool.n_slots
        seeds = np.zeros((S,), np.uint32)
        idxs = np.zeros((S,), np.int32)
        temps = np.zeros((S,), np.float32)
        tks = np.zeros((S,), np.int32)
        tps = np.full((S,), 2.0, np.float32)
        for slot, sess in sessions.items():
            t, k, p, seed = sess.sampling
            seeds[slot] = np.uint32(seed)
            idxs[slot] = len(getattr(sess.request, "tokens", []) or []) \
                + len(sess.emitted) + sess.ahead
            temps[slot] = t
            tks[slot] = k
            tps[slot] = p
        return (jnp.asarray(seeds), jnp.asarray(idxs),
                jnp.asarray(temps), jnp.asarray(tks), jnp.asarray(tps))

    # -- backend hooks (overridden by the mesh-parallel subclass) ----------

    def _backend_prefill(self, prompt: np.ndarray, true_len: int,
                         sampling):
        # Module-global lookup on purpose: tests monkeypatch
        # ``engine.slot_prefill`` to inject prefill failures.
        return slot_prefill(self.dmodel, self.params,
                            jnp.asarray(prompt), true_len=true_len,
                            sampling=sampling)

    def _pooled(self, program):
        """Run one program that CONSUMES the pool (a decode step, a verify,
        a slot write): ``program(pool)`` hands back the new pool first, and
        what follows it is returned as it came, on the device, unread
        (:meth:`_read` fetches a step's or a verify's).  The engine holds
        the only reference, so the pool that went in is gone when the call
        returns (``stats["pool_donated"]`` counts that; a backend that
        declines the donation leaves it behind).  A program that raises before it is
        dispatched has consumed nothing and the engine serves on; one that
        raises after it took the pool leaves nothing to decode from, and
        the replica reads as dead."""
        pool, self._cache = self._cache, None
        try:
            out = program(pool)
        except BaseException:
            if _gone(pool):
                self.dead = True
            else:
                self._cache = pool
            raise
        self._cache = out[0]
        donated = int(_gone(pool))
        self.stats["pool_calls"] += 1
        self.stats["pool_donated"] += donated
        emit("record_serving", "pool_calls", replica=self.name)
        emit("record_serving", "pool_donated", donated, replica=self.name)
        return out[1:]

    def _read(self, out):
        """The one blocking read (``tm.serve.step.read``) of what a step or
        a verify handed back: the device runs the program under it.  A
        program that failed on the device fails here, and so does the pool
        it handed on to every program queued behind it: the replica reads
        as dead (its sessions are re-routed from the tokens read)."""
        with span("tm.serve.step.read"):
            try:
                return jax.device_get(out)
            except BaseException:
                self.dead = True
                raise

    def _backend_step(self, toks, pos: np.ndarray, sampling):
        """Dispatch one pooled step; -> its [S] tokens, on the device.
        ``toks`` is ``(prev, fresh)``: ``fresh`` [S] the host's tokens, a
        negative entry where the row takes ``prev``'s, the tokens of the
        step before, unread (the program merges them)."""
        prev, fresh = toks
        # the counts are ready when the tokens are: one read fetches both
        nxt, self._step_counts = self._pooled(
            lambda pool: slot_decode_step(
                self.dmodel, self.params, pool, fresh, pos,
                sampling=sampling, counted=True, prev=prev))
        return nxt

    def _backend_verify(self, toks: np.ndarray, pos: np.ndarray,
                        sampling):
        return self._pooled(
            lambda pool: slot_verify_step(
                self.dmodel, self.params, pool, toks, pos,
                sampling=sampling))[0]

    def _row_template(self):
        """Fresh single-row zero cache — the canvas prefix-cache
        fragments are assembled onto before the extend forward."""
        shapes = jax.eval_shape(
            lambda: self.dmodel.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                pos_offset=jnp.zeros((1,), jnp.int32)))["cache"]
        row = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                           shapes)
        return (jax.device_put(row, self._device)
                if self._device is not None else row)

    def _backend_extend(self, row_cache, suffix: np.ndarray, depth: int,
                        true_len: int, sampling):
        return slot_extend(self.dmodel, self.params, row_cache,
                           jnp.asarray(suffix),
                           pos_offset=np.asarray([depth], np.int32),
                           true_len=true_len, sampling=sampling)

    # -- iteration-level operations ----------------------------------------

    def admit(self, request) -> Optional[Tuple[Session, bool]]:
        """Prefill ``request`` into a free slot; returns ``(session,
        finished)`` — ``finished`` when the first token already ends the
        request (EOS, or max_new == 1; its slot is freed again before
        returning).  None when the pool is full (caller retries next
        tick).  Raises on a request that can NEVER fit a slot block."""
        if self.dead:
            raise RuntimeError(f"{self.name} is dead")
        sampling = self._resolve_sampling(request)
        base = np.asarray(request.prompt, np.int32).reshape(-1)
        prev = np.asarray(getattr(request, "tokens", []) or [], np.int32)
        # A re-routed session re-prefills from its emitted prefix: the
        # continuation equals what the dead replica would have produced
        # — greedy decode is deterministic, and sampled decode keys
        # each token on (seed, token index), both independent of which
        # replica/slot serves it.
        prompt = np.concatenate([base, prev]).reshape(1, -1)
        total = base.size + int(request.max_new)
        if not self.pool.fits(total):
            raise RequestRejected(
                f"request {request.rid!r}: prompt+max_new = {total} "
                f"exceeds the {self.pool.slot_tokens}-token slot block")
        slot = self.pool.alloc()
        if slot is None:
            return None
        with span("tm.serve.admit", rid=str(request.rid), slot=slot,
                  replica=self.name, prompt_tokens=int(prompt.shape[1])):
            return self._admit_into(slot, request, sampling, prompt,
                                    prev.size)

    def _admit_into(self, slot: int, request, sampling, prompt: np.ndarray,
                    idx: int) -> Tuple[Session, bool]:
        """:meth:`admit` from the moment the slot is known: five phases,
        each its own span, in this order."""
        booked = False      # past every fallible op: the slot is a session's
        try:
            with span("tm.serve.admit.operands"):
                self.stats["prefills"] += 1
                self.units += 1.0
                samp = tuple(jnp.asarray(np.asarray([v], d)) for v, d in
                             zip((sampling[3], idx, sampling[0],
                                  sampling[1], sampling[2]),
                                 (np.uint32, np.int32, np.float32, np.int32,
                                  np.float32)))
                chain = (self._prefix.match(prompt[0])
                         if self._prefix is not None else [])
                if chain:
                    # Cache hit: assemble the matched fragments onto a
                    # fresh row and run the forward over ONLY the unshared
                    # suffix.  The sampling operand (idx = the request's
                    # global token index) is untouched by the hit, so the
                    # fold_in schedule — and therefore every emitted token
                    # — is bitwise the miss path's.
                    B = self._prefix.block_tokens
                    depth = B * len(chain)
                    if self._row_zero is None:
                        self._row_zero = self._row_template()
                    row = self._row_zero
                    for i, node in enumerate(chain):
                        row = slot_cache_write(row, node.frag, i * B)
                    padded, true_len = self._pad_prompt(
                        prompt[:, depth:],
                        cap=self.pool.slot_tokens - depth)
                    self._count_prefill_compile(("ext", padded.shape[1]))
                else:
                    depth = 0
                    padded, true_len = self._pad_prompt(prompt)
                    self._count_prefill_compile(padded.shape[1])
                n_padded = int(padded.shape[1])
            with span("tm.serve.admit.prefill", padded_tokens=n_padded):
                if chain:
                    one_cache, first = self._backend_extend(
                        row, padded, depth, true_len, samp)
                else:
                    one_cache, first = self._backend_prefill(
                        padded, true_len, samp)
            if chain:
                self.stats["prefix_hits"] += 1
            elif self._prefix is not None:
                self.stats["prefix_misses"] += 1
            self.stats["prefill_tokens"] += n_padded
            with span("tm.serve.admit.slot_write"):
                self._pooled(
                    lambda pool: (slot_write(pool, one_cache, slot),))
            with span("tm.serve.admit.read"):
                # the blocking read: the device runs the prefill under it
                tok = int(np.asarray(first)[0])
            with span("tm.serve.admit.book"):
                full_chain: List[Any] = []
                if self._prefix is not None:
                    # Cache every full block of the TRUE prompt from the
                    # row we just computed (one_cache covers the assembled
                    # depth + the suffix, so slicing works for matched and
                    # new blocks alike; insert only materializes the new
                    # ones), then pin the whole chain for this session's
                    # lifetime — eviction can never touch a block a live
                    # slot was built from.
                    B = self._prefix.block_tokens
                    full_chain, n_new, n_evicted = self._prefix.insert(
                        prompt[0], prompt.shape[1],
                        lambda i: slot_cache_slice(one_cache, i * B, B))
                    self._prefix.pin(full_chain)
                    if chain:
                        emit("record_serving", "prefix_hits",
                             replica=self.name)
                        emit("record_serving", "prefix_tokens_saved", depth,
                             replica=self.name)
                        emit("record_serving", "prefix_bytes_saved",
                             sum(n.nbytes for n in chain),
                             replica=self.name)
                    else:
                        emit("record_serving", "prefix_misses",
                             replica=self.name)
                    if n_new:
                        emit("record_serving", "prefix_inserted", n_new,
                             replica=self.name)
                    if n_evicted:
                        emit("record_serving", "prefix_evicted", n_evicted,
                             replica=self.name)
                booked = True
                sess = Session(request=request, slot=slot, last_tok=tok,
                               pos_next=prompt.shape[1], emitted=[tok],
                               sampling=sampling, last_emit=1,
                               prefix_chain=full_chain)
                if self._finished(sess):
                    self.pool.free(slot)
                    self._retire_prefix(sess)
                    return sess, True
                self._sessions[slot] = sess
                if self._draft is not None:
                    self.units += self._draft.admit(slot, sess)
                return sess, False
        except BaseException:
            # A failed prefill must not leak the block: after `slots`
            # leaks the pool would be silently full forever.  (Prefix
            # pins are taken LAST, after every fallible op, so there is
            # never a pinned chain to unwind here.)
            if not booked:
                self.pool.free(slot)
            raise

    def step(self) -> Tuple[List[Session], List[Session]]:
        """One decode tick over every in-flight slot; returns
        ``(advanced, finished)``.  Finished sessions are already retired
        (slot freed) — their blocks are reusable in the same tick.

        Without a draft the tick is one-deep pipelined: it dispatches the
        next pooled step first, then reads the one dispatched before and
        returns ITS tokens, one a session that step decoded (a session
        admitted since that step went out first advances in the next
        call; the first call on an idle pool dispatches two steps, so a
        request of ``max_new`` tokens takes as many calls as the order
        of dispatch-then-read).  Each row's token goes to the session
        that owned the row when the step was dispatched.  Speculative when
        a draft is bound, in the synchronous order (up to K+1 tokens per
        session per tick, bitwise the plain stream)."""
        if self.dead:
            raise RuntimeError(f"{self.name} is dead")
        if not self._sessions:
            return [], []
        spec = self._draft is not None
        with span("tm.serve.step", step=self.stats["steps"],
                  live=len(self._sessions), replica=self.name,
                  spec=int(spec)):
            return self._spec_step() if spec else self._plain_step()

    def _count_step(self, sessions: Dict[int, Session]) -> None:
        """One more pooled step, under the branch of the sampling tail
        its rows ask for (:data:`SAMPLE_BRANCHES`): the program's own
        rule, any temperature above zero, on the host's copy of it; the
        live sessions it decodes (what ``tm.serve.step`` carries as
        ``live``); and the ring rows they read, ``min(depth + 1, window)``
        a live session and ring layer (``stats["window_rows_live"]``)."""
        self.stats["steps"] += 1
        self.stats["live_slot_steps"] += len(sessions)
        if self._ring_windows:
            rows = sum(min(s.pos_next + 1, w) for s in sessions.values()
                       for w in self._ring_windows)
            self.stats["window_rows_live"] += rows
            emit("record_serving", "window_rows_live", rows,
                 replica=self.name)
        branch = SAMPLE_BRANCHES[any(s.sampling[0] > 0.0
                                     for s in sessions.values())]
        self.stats[branch] += 1
        emit("record_serving", branch, replica=self.name)
        emit("record_serving", "live_slot_steps", len(sessions),
             replica=self.name)

    def _plain_step(self) -> Tuple[List[Session], List[Session]]:
        if not self._unread:
            # nothing queued on the chip: the step the call reads goes
            # out first, from the host's tokens
            self._dispatch_step()
        if any(self._budget_left(s) > 0 for s in self._sessions.values()):
            self._dispatch_step()
        return self._read_step()

    def _dispatch_step(self) -> None:
        """Operands and dispatch of one pooled step over every open session
        its budget still takes (one whose last token is in an unread step
        is left out: the host knows that from counts).  A row the unread
        step decoded for the same session takes that step's token on the
        device; any other takes the host's (an admission's first token)."""
        with span("tm.serve.step.operands"):
            live = {slot: sess for slot, sess in self._sessions.items()
                    if self._budget_left(sess) > 0}
            self._count_step(live)
            self.units += 1.0
            last = self._unread[-1] if self._unread else None
            S = self.pool.n_slots
            fresh = np.zeros((S,), np.int32)
            pos = np.zeros((S,), np.int32)
            for slot, sess in live.items():
                on_chip = last is not None and last.owners.get(slot) is sess
                fresh[slot] = -1 if on_chip else sess.last_tok
                pos[slot] = sess.pos_next
            samp = self._sampling_arrays(live)
        with span("tm.serve.step.dispatch"):
            self._step_counts = None
            tokens = self._backend_step(
                (self._no_prev if last is None else last.tokens, fresh),
                pos, samp)
        for sess in live.values():
            sess.pos_next += 1
            sess.ahead += 1
        if last is not None:
            self.stats["steps_ahead"] += 1
            emit("record_serving", "steps_ahead", replica=self.name)
        self._unread.append(_Unread(tokens, self._step_counts, live))

    def _read_step(self) -> Tuple[List[Session], List[Session]]:
        """Read the oldest unread step and book its tokens to the sessions
        that owned its rows when it went out.  A row whose session ended
        (EOS) at an earlier read is discarded, whoever holds its slot now
        (``stats["rows_past_end"]``)."""
        step = self._unread.popleft()
        nxt, counts = self._read((step.tokens, step.counts))
        if counts is not None:
            self._expert_steps()
            for handles, row in zip(self._expert_counters, counts.tolist()):
                for add, value in zip(handles, row):
                    add(value)
        with span("tm.serve.step.book"):
            advanced, finished = [], []
            for slot, sess in step.owners.items():
                if self._sessions.get(slot) is not sess:
                    self.stats["rows_past_end"] += 1
                    emit("record_serving", "rows_past_end",
                         replica=self.name)
                    continue
                sess.ahead -= 1
                sess.last_tok = int(nxt[slot])
                sess.emitted.append(sess.last_tok)
                sess.last_emit = 1
                advanced.append(sess)
                if self._finished(sess):
                    del self._sessions[slot]
                    self.pool.free(slot)
                    self._retire_prefix(sess)
                    finished.append(sess)
            return advanced, finished

    def _spec_step(self) -> Tuple[List[Session], List[Session]]:
        """Draft K, verify in ONE [S, K+1] forward, accept while the
        drafts match what the target samples.  Every kept sample
        conditions only on accepted tokens, so the emitted stream is
        bitwise the non-speculative one at the same (seed, prompt) —
        drafting moves SPEED, never content."""
        with span("tm.serve.step.operands"):
            sessions = dict(self._sessions)
            # The [S, K+1] verify writes K+1 cache positions per row at its
            # own offset; a row near the end of its slot block has less
            # room than that, and an out-of-range dynamic_update_slice
            # CLAMPS the start index — silent corruption.  Clamp K to the
            # tick's tightest room instead (>= 0: an in-flight session
            # always has 1 free position for its next token).
            room = min(self.pool.slot_tokens - s.pos_next
                       for s in sessions.values())
            K = min(self._spec_k, max(0, room - 1))
            # Sampling arrays BEFORE drafting: idxs must index the first
            # token this tick emits.
            samp = self._sampling_arrays(sessions)
        with span("tm.serve.step.draft", k=K):
            drafts, draft_units = self._draft.propose(sessions, K)
        with span("tm.serve.step.operands"):
            S = self.pool.n_slots
            toks = np.zeros((S, K + 1), np.int32)
            pos = np.zeros((S,), np.int32)
            for slot, sess in sessions.items():
                d = list(drafts.get(slot, []))[:K]
                toks[slot, 0] = sess.last_tok
                if d:
                    toks[slot, 1:1 + len(d)] = d
                pos[slot] = sess.pos_next
            self._count_step(sessions)
            self.stats["spec_steps"] += 1
            self.units += 1.0 + float(draft_units)
        with span("tm.serve.step.dispatch"):
            out = self._backend_verify(toks, pos, samp)
        out = self._read(out)
        with span("tm.serve.step.book"):
            advanced, finished = [], []
            tick_drafted = tick_accepted = 0
            for slot, sess in sessions.items():
                d = list(drafts.get(slot, []))[:K]
                row = out[slot]
                m = 0
                for j in range(len(d) + 1):
                    t = int(row[j])
                    sess.last_tok = t
                    sess.emitted.append(t)
                    m += 1
                    if self._finished(sess):
                        break
                    if j < len(d) and t != d[j]:
                        # Mismatch: t IS the corrected token (sampled from
                        # the accepted prefix); the remaining samples
                        # conditioned on the wrong draft and are dropped.
                        break
                sess.pos_next += m
                sess.last_emit = m
                tick_drafted += len(d)
                tick_accepted += sum(1 for j in range(min(m, len(d)))
                                     if int(row[j]) == d[j])
                advanced.append(sess)
                if self._finished(sess):
                    del self._sessions[slot]
                    self.pool.free(slot)
                    self._retire_prefix(sess)
                    self._draft.free(slot)
                    finished.append(sess)
                else:
                    self._draft.observe(slot, sess)
            self.stats["spec_drafted"] += tick_drafted
            self.stats["spec_accepted"] += tick_accepted
            if tick_drafted:
                emit("record_serving", "spec_drafted", tick_drafted,
                     replica=self.name)
            if tick_accepted:
                emit("record_serving", "spec_accepted", tick_accepted,
                     replica=self.name)
            return advanced, finished

    def drain(self) -> List[Session]:
        """Mark this replica dead and hand its in-flight sessions back
        for re-routing (their cache state is presumed lost with the
        replica — the scheduler re-prefills each from its emitted
        prefix on a healthy replica).  Draft state is discarded with
        the replica: nothing speculative survives the move; so is an
        unread step: its tokens are recomputed where the sessions go,
        bitwise the same (sampling keys on seed and token index)."""
        self.dead = True
        self._unread.clear()
        out = list(self._sessions.values())
        for sess in out:
            self.pool.free(sess.slot)
            self._retire_prefix(sess)
        self._sessions.clear()
        if self._draft is not None:
            self._draft.drain()
        return out

    # -- internals ---------------------------------------------------------

    def _retire_prefix(self, sess: Session) -> None:
        """Release the session's prefix-block pins (refcounts fall back
        toward 1 = idle/evictable; exactly zero leaks by construction —
        the ledger raises on a double release)."""
        if sess.prefix_chain:
            self._prefix.release(sess.prefix_chain)
            sess.prefix_chain = []

    @classmethod
    def _finished(cls, sess: Session) -> bool:
        req = sess.request
        if req.eos_id is not None and sess.last_tok == int(req.eos_id):
            return True
        return cls._budget_left(sess) + sess.ahead <= 0

    @staticmethod
    def _budget_left(sess: Session) -> int:
        """Tokens the session's budget still takes beyond those emitted
        (re-routes included) and those its unread steps hold."""
        req = sess.request
        done_before = len(req.tokens) if hasattr(req, "tokens") else 0
        return (int(req.max_new) - done_before - len(sess.emitted)
                - sess.ahead)
