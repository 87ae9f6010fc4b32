"""Admission queue + iteration-level scheduler (the continuous-batching
serving loop).

One :class:`Server` drives N :class:`~.engine.ReplicaEngine` replicas
through a :class:`~.router.Router` over a shared FIFO admission queue:

- every tick, newly-arrived requests are admitted into free slot blocks
  (prefill + first token — the TTFT event) and ONE ``[S, 1]`` decode
  step advances each replica's in-flight slots; finished sequences
  retire immediately and their blocks free for the next admission —
  iteration-level (in-flight/continuous) batching, vs. the static
  baseline that forms a full batch and runs everyone to the longest
  decode (``benchmarks/serving_bench.py`` measures the gap);
- the clock is virtual: each tick advances by the measured wall time of
  its work (or a fixed ``tick_seconds`` for deterministic tests/chaos
  runs), and arrivals from the trace are admitted when the clock
  passes their ``arrival_s`` — so Poisson traces replay identically
  while TTFT/inter-token latencies still reflect real compute cost;
- a replica step that raises a fault-layer error takes the resilience
  path instead of crashing the server: transient faults count against
  the health ledger (the replica's sessions stall a tick), and a hard
  failure — or a ledger verdict of ``raise`` — DRAINS the replica: its
  in-flight sessions re-enter the queue front and re-prefill from
  their emitted prefix on a healthy replica (token-exact: greedy is
  deterministic, and sampled decode keys token i on
  ``fold_in(PRNGKey(seed), i)`` — replica- and slot-independent).
  ``tm_serving_rerouted_total`` counts the moved sessions.

SLO observability rides the obs registry when telemetry is active
(``tm_serving_*`` — docs/OBSERVABILITY.md): TTFT and inter-token
latency histograms (microseconds) per replica, queue-depth and
slot-occupancy gauges per tick, request/token/completion counters.
``scripts/obs_tool.py slo`` turns the dumps into p50/p95/p99 tables.

On the profiler's clock, and always (a flag test when no profiler is
attached): ``tm.serve.gate`` a request entering the program and
``tm.serve.tick`` a scheduler tick, with the engine's ``tm.serve.admit``
/ ``tm.serve.step`` trees inside it (``engine.SPANS``;
docs/OBSERVABILITY.md, "What a profile shows").
"""

from __future__ import annotations

import dataclasses
import sys
import time
from collections import deque
from typing import List, Optional, Sequence

import jax
import numpy as np

from .. import runtime
from ..utils.telemetry import active as obs_active, emit
from .engine import ReplicaEngine, RequestRejected, Session, span
from .fleet import AdmissionController, AdmissionRejected, \
    FleetController
from .router import Router


@dataclasses.dataclass
class Request:
    """One serving request.  ``max_new`` bounds the generated tokens;
    ``eos_id`` retires the sequence early.  The server fills in the
    result fields (``tokens`` — the emitted ids, eos included when hit
    — and the SLO timestamps, seconds on the virtual clock)."""

    rid: str
    prompt: np.ndarray
    max_new: int
    eos_id: Optional[int] = None
    arrival_s: float = 0.0
    # -- decode diversity (None -> the Config default) --
    # Sampling is bitwise-reproducible given (seed, prompt): token i
    # draws from fold_in(PRNGKey(seed), i) regardless of slot, pool
    # neighbors, replica, or re-routes.  temperature <= 0 is greedy;
    # top_k 0 / top_p 1.0 disable that filter.
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: int = 0
    # -- results (server-owned) --
    tokens: List[int] = dataclasses.field(default_factory=list)
    ttft_s: Optional[float] = None
    finish_s: Optional[float] = None
    replica: Optional[str] = None
    reroutes: int = 0
    # Set instead of tokens when the request is unservable (e.g. it can
    # never fit a slot block): the server rejects IT and keeps serving
    # everyone else — one bad request must not abort the trace.
    error: Optional[str] = None
    # True when the ADMISSION GATE shed this request (SLO backpressure
    # or a serving.admit chaos drop) — ``error`` carries the typed
    # AdmissionRejected text.  Distinct from an unservable rejection:
    # a shed request is perfectly servable, the fleet just can't meet
    # its TTFT budget right now.
    shed: bool = False
    # Clock of the most recent emitted token — carries the inter-token
    # gap across a drain/re-admission so the re-route stall really
    # lands in the ITL histogram.
    last_token_s: Optional[float] = None

    def latency_s(self) -> Optional[float]:
        if self.finish_s is None:
            return None
        return self.finish_s - self.arrival_s


def _is_fault(e: BaseException) -> bool:
    """Is ``e`` a fault-layer error?  Checked via sys.modules: if the
    fault layer was never armed, the classes do not exist and no
    exception can be one (the restart.py discipline)."""
    mod = sys.modules.get("torchmpi_tpu.faults.inject")
    return mod is not None and isinstance(e, mod.FaultError)


class Server:
    """Continuous-batching server over ``replicas`` engine replicas of
    one ``(model, params)`` checkpoint.

    Replica count / slots / slot block size default from the active
    Config (``serving_replicas`` / ``serving_slots`` /
    ``serving_slot_tokens``).  Replica i lives on
    ``jax.local_devices()[i % n_local]`` (data-parallel spread on a
    multi-chip host); ``devices`` pins replica i to ``devices[i]``
    instead.
    """

    # Class-level defaults so a hand-assembled Server (tests build one
    # via ``Server.__new__`` around a pre-wired Router) runs the trace
    # loop with the gate and the autoscaler disarmed.
    _admission = None
    _fleet = None
    #: Ticks this server has run (``tm.serve.tick``'s ``tick`` stat).
    _n_ticks = 0

    def __init__(self, model, params, *, replicas: Optional[int] = None,
                 slots: Optional[int] = None,
                 slot_tokens: Optional[int] = None,
                 devices: Optional[Sequence] = None,
                 ledger=None, sample: Optional[float] = None,
                 prefill_bucket: Optional[int] = None,
                 spec_k: Optional[int] = None, draft=None,
                 engines: Optional[Sequence] = None,
                 prefix_cache: Optional[int] = None,
                 prefix_block: int = 8,
                 slo_ttft_us: Optional[float] = None,
                 autoscale: Optional[int] = None,
                 engine_factory=None,
                 scale_high_water: int = 4, scale_low_water: int = 0,
                 scale_sustain: int = 3):
        cfg = runtime.effective_config()
        if engines is None:
            n = int(replicas if replicas is not None
                    else cfg.serving_replicas)
            if n < 1:
                raise ValueError(f"need >= 1 replica, got {n}")
            if devices is None:
                # Spread over this host's chips, wrapping round when
                # there are more replicas than devices.
                local = jax.local_devices()
                devices = [local[i % len(local)] for i in range(n)]
            elif len(devices) < n:
                raise ValueError(
                    f"{n} replicas but only {len(devices)} devices")
            engines = [
                ReplicaEngine(model, params, name=f"replica{i}",
                              slots=slots, slot_tokens=slot_tokens,
                              device=devices[i], sample=sample,
                              prefill_bucket=prefill_bucket,
                              spec_k=spec_k, draft=draft,
                              prefix_cache=prefix_cache,
                              prefix_block=prefix_block)
                for i in range(n)]
            if engine_factory is None:
                # Default scale-up factory: a fresh dense replica with
                # the same knobs (no device pin — a scaled replica
                # lands wherever jax defaults it).
                def engine_factory(name, _m=model, _p=params):
                    return ReplicaEngine(
                        _m, _p, name=name, slots=slots,
                        slot_tokens=slot_tokens, sample=sample,
                        prefill_bucket=prefill_bucket, spec_k=spec_k,
                        draft=draft, prefix_cache=prefix_cache,
                        prefix_block=prefix_block)
        else:
            engines = list(engines)
        self.router = Router(engines, ledger=ledger)
        # SLO admission gate: live p95 TTFT vs the target, typed
        # AdmissionRejected shedding (fleet.py).  0 disarms.
        slo = float(slo_ttft_us if slo_ttft_us is not None
                    else cfg.serving_slo_ttft_us)
        self._admission = (AdmissionController(slo) if slo > 0
                           else None)
        # Queue-depth autoscaler: value = max replicas (0 disarms).
        amax = int(autoscale if autoscale is not None
                   else cfg.serving_autoscale)
        if amax > 0:
            if engine_factory is None:
                raise ValueError(
                    "autoscale needs an engine_factory when the server "
                    "is built from pre-made engines (it must be able "
                    "to construct a replica on scale-up)")
            self._fleet = FleetController(
                self.router, engine_factory=engine_factory,
                max_replicas=amax, min_replicas=len(engines),
                high_water=scale_high_water, low_water=scale_low_water,
                sustain=scale_sustain, drain=self._drain)
        else:
            self._fleet = None
        #: Filled by :meth:`run_trace`: ``ticks`` (work ticks run),
        #: ``busy_s`` (summed tick durations — the compute time
        #: throughput divides by), ``clock_s`` (final virtual clock,
        #: idle gaps included), ``tokens`` (total emitted).
        self.last_stats: dict = {}

    @classmethod
    def sharded(cls, params, *, tp: int, num_heads: int,
                slot_tokens: int, axis: str = "model",
                replicas: Optional[int] = None,
                devices: Optional[Sequence] = None, **kw) -> "Server":
        """A server whose every replica is a TP mesh slice: carve
        ``replicas`` disjoint ``tp``-device meshes from ``devices``
        (default ``jax.devices()``) and serve one
        :class:`~.tp_engine.TPReplicaEngine` per slice.  ``params`` is
        a full ``tp_generate.init_tp_lm`` tree (placed per mesh).
        Defaults to as many replicas as the device pool can hold."""
        import jax
        from jax.sharding import Mesh

        from .tp_engine import TPReplicaEngine

        if tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        devices = list(devices if devices is not None else jax.devices())
        n = int(replicas) if replicas is not None else len(devices) // tp
        if n < 1 or n * tp > len(devices):
            raise ValueError(
                f"{n} replicas x {tp} devices need {n * tp} devices, "
                f"have {len(devices)}")
        engines = [
            TPReplicaEngine(
                params,
                mesh=Mesh(np.asarray(devices[i * tp:(i + 1) * tp]),
                          (axis,)),
                axis=axis, num_heads=num_heads, name=f"tp{i}",
                slot_tokens=slot_tokens, **kw)
            for i in range(n)]
        return cls(None, None, engines=engines)

    def _total_units(self) -> float:
        """Summed work units across ALL replicas (dead included —
        their spent work stays spent): prefills + pooled forwards at
        1.0, draft forwards at the proposer's weight.  The
        ``unit_seconds`` clock advances by the per-tick delta."""
        return sum(e.units for e in self.router.replicas)

    # -- the serving loop --------------------------------------------------

    def run_trace(self, requests: Sequence[Request], *,
                  tick_seconds: Optional[float] = None,
                  unit_seconds: Optional[float] = None,
                  max_ticks: int = 1_000_000) -> List[Request]:
        """Serve a whole arrival trace to completion; returns the
        requests in completion order (every one finished — the server
        refuses to lose work: with all replicas dead it raises).

        The virtual clock, per tick:

        - default (both None): each tick's measured wall time —
          latencies reflect real compute cost;
        - ``tick_seconds``: a fixed step per tick (deterministic tests
          / chaos runs);
        - ``unit_seconds``: the tick's WORK UNITS (prefills admitted +
          replica steps run, i.e. invocations of the two compiled
          executables) times this — deterministic like
          ``tick_seconds`` but load-faithful, since a tick that
          admitted three requests costs three prefills of clock.  The
          noise-immune schedule ``benchmarks/serving_bench.py``
          compares continuous vs static on.
        """
        if tick_seconds is not None and unit_seconds is not None:
            raise ValueError(
                "tick_seconds and unit_seconds are exclusive clock "
                "modes")
        arrivals = deque(sorted(requests, key=lambda r: r.arrival_s))
        pending: deque = deque()
        completed: List[Request] = []
        clock = busy = 0.0
        n_ticks = n_tokens = 0
        units_prev = self._total_units()
        for _tick in range(max_ticks):
            if not (arrivals or pending
                    or any(e.active for e in self.router.live())):
                self.last_stats = {"ticks": n_ticks, "busy_s": busy,
                                   "clock_s": clock,
                                   "tokens": n_tokens}
                return completed
            t0 = time.monotonic()
            while arrivals and arrivals[0].arrival_s <= clock:
                req = arrivals.popleft()
                shed = self._gate(req, len(pending))
                if shed is not None:
                    # Typed backpressure, not a timeout: the request
                    # completes immediately as shed with the evidence
                    # in .error, and the fleet's admitted latency
                    # budget stays intact.
                    req.error = shed
                    req.shed = True
                    req.finish_s = clock
                    completed.append(req)
                    continue
                pending.append(req)
            newly_admitted, stepped, finished, steps_run, rejected = \
                self._tick(pending)
            for req in rejected:
                req.finish_s = clock
                completed.append(req)
            worked = bool(newly_admitted or stepped or finished
                          or rejected)
            if not worked and not pending and arrivals and \
                    not any(e.active for e in self.router.live()):
                # Idle gap — nothing queued OR in flight: jump straight
                # to the next arrival instead of spinning the virtual
                # clock through empty ticks.  (In-flight sessions
                # stalled by a transient replica fault must NOT jump:
                # their tick still costs clock and samples gauges.)
                clock = max(clock, arrivals[0].arrival_s)
                continue
            if not worked and pending and not self.router.live():
                raise RuntimeError(
                    "all replicas dead with requests still queued")
            if unit_seconds is not None:
                # The delta of the engines' own unit ledgers, not a
                # recount here: speculative ticks bill 1 verify +
                # K x draft-weight, prefills 1 each — whatever the
                # engines actually ran is what the clock charges.
                units_now = self._total_units()
                n_units = units_now - units_prev
                units_prev = units_now
                elapsed = max(1.0, n_units) * unit_seconds
            elif tick_seconds is not None:
                elapsed = tick_seconds
            else:
                elapsed = max(time.monotonic() - t0, 1e-9)
            clock += elapsed
            busy += elapsed
            n_ticks += 1
            n_tokens += len(newly_admitted) + \
                sum(s.last_emit for s in stepped)
            self._record_tick(pending, newly_admitted, stepped,
                              finished, completed, clock, elapsed)
            if self._fleet is not None:
                event = self._fleet.tick(len(pending), pending)
                if event is not None:
                    emit("record_serving", event)
        raise RuntimeError(f"trace did not drain in {max_ticks} ticks")

    # -- one tick ----------------------------------------------------------

    def _tick(self, pending: deque):
        n, self._n_ticks = self._n_ticks, self._n_ticks + 1
        with span("tm.serve.tick", tick=n, pending=len(pending)):
            return self._tick_body(pending)

    def _tick_body(self, pending: deque):
        admitted: List[Session] = []
        finished: List[Session] = []
        stepped: List[Session] = []
        rejected: List[Request] = []
        steps_run = 0
        # Admission at the token boundary: fill free slot blocks from
        # the queue front, spread by router health/load.
        while pending:
            eng = self.router.pick()
            if eng is None:
                break
            req = pending.popleft()
            try:
                res = eng.admit(req)
            except RequestRejected as e:
                # Unservable request (can never fit a slot block):
                # reject IT and keep serving — one bad request must not
                # abort everyone else's trace.  Only this typed
                # rejection is absorbed; any other admission exception
                # is a real bug and stays loud.
                req.error = str(e)
                rejected.append(req)
                emit("record_serving", "rejected", replica=eng.name)
                continue
            if res is None:  # raced a full pool; retry next tick
                pending.appendleft(req)
                break
            sess, done = res
            req.replica = eng.name
            admitted.append(sess)
            if done:
                finished.append(sess)
        # One decode step per replica with in-flight slots.
        for eng in list(self.router.live()):
            if not eng.active:
                continue
            try:
                self._fire(eng.name)
                advanced, fin = eng.step()
                steps_run += 1
            except BaseException as e:  # noqa: BLE001 — resilience path
                if not self._handle_failure(eng, e, pending):
                    raise
                continue
            self.router.record(eng, True)
            stepped.extend(advanced)
            finished.extend(fin)
        return admitted, stepped, finished, steps_run, rejected

    @staticmethod
    def _fire(name: str) -> None:
        """The ``serving.replica`` chaos site: one arrival per replica
        step when the fault layer is armed (one string compare when
        off — the import discipline of every other site)."""
        if runtime.effective_config().faults == "off":
            return
        from .. import faults

        faults.fire("serving.replica", peer=name)

    def _gate(self, req: Request, depth: int) -> Optional[str]:
        """The admission gate, run once per arrival BEFORE it queues:
        the ``serving.admit`` chaos site (any fault verdict at the door
        is a shed — a dropped admission RPC and an SLO rejection look
        identical to the client), then the SLO admission controller.
        Returns the shed reason, or None to admit into the queue."""
        with span("tm.serve.gate", rid=str(req.rid)):
            if runtime.effective_config().faults != "off":
                from .. import faults

                try:
                    faults.fire("serving.admit", peer=req.rid)
                except BaseException as e:  # noqa: BLE001 — shed, not crash
                    if not _is_fault(e):
                        raise
                    emit("record_serving", "shed")
                    return (f"request {req.rid!r} shed (fault at "
                            f"serving.admit): {e}")
            if self._admission is not None:
                try:
                    self._admission.check(req.rid, depth)
                except AdmissionRejected as e:
                    emit("record_serving", "shed")
                    return str(e)
                emit("record_serving", "admitted")
            return None

    def _handle_failure(self, eng: ReplicaEngine, e: BaseException,
                        pending: deque) -> bool:
        """Route a failed replica step; returns False to re-raise (not
        a fault-layer error — a model bug must stay loud)."""
        if not _is_fault(e):
            return False
        if getattr(e, "transient", False):
            verdict = self.router.record(eng, False)
        else:
            # Hard failure: the replica is gone now.
            self.router.mark_dead(eng)
            verdict = "raise"
        if verdict == "raise":
            self._drain(eng, pending)
        return True

    def _drain(self, eng: ReplicaEngine, pending: deque) -> None:
        """Dead replica: move its in-flight sessions to the queue FRONT
        (they already waited once) for re-prefill elsewhere."""
        sessions = eng.drain()
        eng.dead = True
        if sessions:
            emit("record_serving", "rerouted", len(sessions),
                 replica=eng.name)
        for sess in reversed(sessions):
            req = sess.request
            req.tokens.extend(sess.emitted)
            req.reroutes += 1
            pending.appendleft(req)

    # -- telemetry + result bookkeeping ------------------------------------

    def _record_tick(self, pending, admitted, stepped, finished,
                     completed, clock: float, elapsed: float) -> None:
        on = obs_active()
        for sess in admitted:
            req = sess.request
            if req.ttft_s is None:
                req.ttft_s = clock - req.arrival_s
                if self._admission is not None:
                    # Feed the SLO gate's rolling window regardless of
                    # telemetry — admission control must work with obs
                    # off.
                    self._admission.observe(req.ttft_s)
                emit("record_serving", "requests", replica=req.replica)
                emit("record_serving_latency", "ttft", req.ttft_s,
                     replica=req.replica)
            elif on:
                # Re-admission after a re-route: the WHOLE stall since
                # the session's last token (drain + queue wait +
                # re-prefill) is one long inter-token latency, not a
                # second TTFT — that is the SLO impact of the kill.
                since = (req.last_token_s if req.last_token_s is not None
                         else clock - elapsed)
                emit("record_serving_latency", "itl", clock - since,
                     replica=req.replica)
            req.last_token_s = clock
        for sess in finished:
            req = sess.request
            req.tokens.extend(sess.emitted)
            sess.emitted = []
            req.finish_s = clock
            completed.append(req)
            emit("record_serving", "completed", replica=req.replica)
        if not on:
            return
        for sess in stepped:
            req = sess.request
            # Gap since the request's LAST token, not this tick's
            # elapsed: equal for an unstalled session (its previous
            # token landed exactly one tick ago), but a session stalled
            # N ticks by transient replica faults — or re-admitted
            # after a drain this same tick (then the admission already
            # carried the stall and last_token_s is this clock) —
            # reports its true inter-token latency.  A speculative tick
            # that landed m tokens records m observations of gap/m:
            # the histogram keeps counting per TOKEN, and the spec win
            # shows up as the smaller per-token gap it is.
            since = (req.last_token_s if req.last_token_s is not None
                     else clock - elapsed)
            m = max(1, sess.last_emit)
            for _ in range(m):
                emit("record_serving_latency", "itl", (clock - since) / m,
                     replica=req.replica)
            req.last_token_s = clock
        n_tok = len(admitted) + sum(s.last_emit for s in stepped)
        if n_tok:
            by_rep: dict = {}
            for sess in admitted:
                by_rep[sess.request.replica] = \
                    by_rep.get(sess.request.replica, 0) + 1
            for sess in stepped:
                by_rep[sess.request.replica] = \
                    by_rep.get(sess.request.replica, 0) + sess.last_emit
            for rep, n in by_rep.items():
                emit("record_serving", "tokens", n, replica=rep)
        emit("record_serving_depth", len(pending))
        for eng in self.router.live():
            emit("record_serving_occupancy", eng.pool.occupancy_pct(),
                 replica=eng.name)
        # Tick boundary: the serving-side attribution window edge
        # (obs_tool attribute; docs/OBSERVABILITY.md).
        emit("record_step", "serving_tick")
