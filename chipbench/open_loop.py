"""An open loop on the wall clock: the schedule, the feeder, the loop around
``serving.Server`` and the reduction from token stamps to metrics.

The traffic generator is general and reads a data file (``traffic/<mix>.json``
with ``"loop": "open"``): a rate, a distribution of prompt lengths, one of
answer lengths, one of gaps between arrivals.  **The population is fixed**:
the N requests of a stretch of time take the N quantiles of each distribution
(lengths and gaps), and ``--seed`` ORDERS them and draws the token ids: every
seed offers the same work and the same burstiness in another order.  A file
with a ``round`` offers that many quantiles round after round, each round in
a new order (every few seconds the same work: what a rate, which counts the
part of the work that fell inside the window, needs to repeat).

The loop is the benchmark's and not the program's because ``Server.run_trace``
replays a list under a virtual clock (the sum of tick durations, idle gaps
jumped over): a request is handed to the server when ``clock()`` passes its
due time, whether or not earlier ones have finished, and the loop then calls
the scheduler's own tick (``Server._gate``, ``Server._tick``: admissions into
free slots, one pooled decode step).  Every token is stamped when the tick
that produced it returns: that is when a caller of this server can see it.

Nothing here imports jax or the program.
"""

from __future__ import annotations

import collections
import contextlib
import math
import statistics
import threading
import time
import types

import numpy as np

from chipbench import harness

# ------------------------------------------------------------- the schedule


def _norm_ppf(p):
    return statistics.NormalDist().inv_cdf(p)


def quantiles(spec, n):
    """The n mid-quantiles ((i + 1/2) / n) of the distribution ``spec``."""
    ps = [(i + 0.5) / n for i in range(n)]
    kind = spec["dist"]
    if kind == "lognormal":
        xs = [spec["median"] * math.exp(spec["sigma"] * _norm_ppf(p))
              for p in ps]
    elif kind == "exponential":
        xs = [-math.log1p(-p) for p in ps]         # mean 1: scaled below
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in spec or "max" in spec:
        xs = [min(max(x, spec.get("min", x)), spec.get("max", x))
              for x in xs]
    return xs


def population(traffic, seconds):
    """The fixed population of a stretch of ``seconds``: its ``n`` requests
    come in rounds of ``size``, each round the ``size`` quantiles of the
    prompt lengths, of the answer lengths and of the gaps (sorted; the seed
    only orders them).  Without a ``round`` in the file the whole stretch
    is one round; with one, every ``round`` arrivals offer the same work,
    the last round a part of it.  ``gap_scale`` makes the expected gaps
    sum to ``seconds``."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    size = min(n, int(traffic.get("round", n)))
    gaps = quantiles(traffic["gaps"], size)
    return types.SimpleNamespace(
        n=n, size=size,
        prompts=[int(round(x)) for x in quantiles(traffic["prompt_tokens"],
                                                  size)],
        answers=[int(round(x)) for x in quantiles(traffic["answer_tokens"],
                                                  size)],
        gaps=gaps, gap_scale=seconds * size / (n * sum(gaps)))


def rng_for(seed, stream):
    """A numpy generator from any whole number (the driver's seeds pass
    2**31) and a stream number."""
    seed = int(seed)
    return np.random.default_rng(
        [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, stream])


def schedule(traffic, seed, *, seconds, vocab, extra_s=0.0):
    """Every request of one run, sorted by due time (seconds; the window
    opens at 0).  Three stretches, each a population of its own: the warm-up
    before the window (``warmup_s``, due times below 0), the window, and
    ``extra_s`` beyond it (the traced stretch).  A request is a namespace
    ``rid, due, prompt (int32 ids), max_new, phase``."""
    out = []
    stretches = (("warm", -float(traffic.get("warmup_s", 0.0)), 0.0),
                 ("window", 0.0, float(seconds)),
                 ("extra", float(seconds), float(seconds) + extra_s))
    for stream, (phase, lo, hi) in enumerate(stretches):
        if hi <= lo:
            continue
        pop = population(traffic, hi - lo)
        rng = rng_for(seed, stream)                      # the token ids
        order = rng_for(seed, stream + 16)

        def dealt(values):      # round after round, each in a new order
            values = np.asarray(values)
            picks = [order.permutation(pop.size)[:min(pop.size, pop.n - at)]
                     for at in range(0, pop.n, pop.size)]
            return values[np.concatenate(picks)]

        prompts, answers = dealt(pop.prompts), dealt(pop.answers)
        gaps = dealt(pop.gaps) * pop.gap_scale
        gaps *= (hi - lo) / gaps.sum()       # a part round: exactly hi - lo
        # the first request falls half its gap in, so that a stretch's
        # arrivals neither start nor end on its edge
        due = lo + np.cumsum(gaps) - 0.5 * gaps
        for i in range(pop.n):
            out.append(types.SimpleNamespace(
                rid=f"{phase}{i}", phase=phase, due=float(due[i]),
                prompt=rng.integers(0, vocab, size=int(prompts[i]),
                                    dtype=np.int32),
                max_new=int(answers[i])))
    return sorted(out, key=lambda r: r.due)


# --------------------------------------------------------------- the feeder


class Feeder:
    """Hands each request over when ``clock()`` passes ``t0 + due``, into
    ``inbox``, stamping ``handed`` (seconds after ``t0``).  ``threaded``: a
    thread of its own, so that a long tick of the server does not make the
    generator late; otherwise ``poll()`` hands over what is due (tests, with
    an injected clock)."""

    def __init__(self, requests, t0, *, clock=time.monotonic,
                 sleep=time.sleep, threaded=True):
        self.todo = collections.deque(requests)
        self.inbox = collections.deque()
        self.t0, self.clock, self.sleep = t0, clock, sleep
        self.thread = (threading.Thread(target=self._run, daemon=True)
                       if threaded else None)
        self.stopped = False

    def _hand(self, now):
        while self.todo and self.todo[0].due <= now - self.t0:
            r = self.todo.popleft()
            r.handed = now - self.t0
            self.inbox.append(r)

    def _run(self):
        while self.todo and not self.stopped:
            wait = self.t0 + self.todo[0].due - self.clock()
            if wait > 0:
                self.sleep(min(wait, 0.05))
            self._hand(self.clock())

    def start(self):
        if self.thread:
            self.thread.start()

    def poll(self):
        if not self.thread:
            self._hand(self.clock())

    def stop(self):
        self.stopped = True
        if self.thread and self.thread.is_alive():
            self.thread.join()

    @property
    def exhausted(self):
        return not self.todo


# ----------------------------------------------------------------- the loop


class Spans:
    """Host spans ``name -> [(start, end)]`` in seconds after ``t0``; with
    ``annotate`` (``jax.profiler.TraceAnnotation``) each also goes into the
    profiler's trace, on the device events' clock."""

    def __init__(self, clock, t0, annotate=None):
        self.clock, self.t0 = clock, t0
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.spans = collections.defaultdict(list)

    @contextlib.contextmanager
    def span(self, name):
        t = self.clock()
        with self.annotate(name):
            yield
        self.spans[name].append((t - self.t0, self.clock() - self.t0))

    def wrap(self, name, fn, before=None):
        def wrapped(*a, **kw):
            if before is not None:
                before(*a, **kw)
            with self.span(name):
                return fn(*a, **kw)
        return wrapped


def make_request(serving, r):
    """The program's request for one of the schedule's."""
    return serving.Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                           eos_id=None, arrival_s=r.due)


def serve(server, serving, requests, *, until, drain_s=0.0, marks=(),
          clock=time.monotonic, sleep=time.sleep, threaded=True,
          annotate=None, t0=None, window=None):
    """Drive ``server`` with ``requests`` (sorted by ``due``) on the wall
    clock.  Arrivals are handed over until ``until`` seconds after ``t0``;
    then the loop runs on until every handed request has finished or
    ``drain_s`` more seconds have passed (0: it stops at once).  ``window``
    is the model's attention window (what a decode step attends is capped
    by it).  ``marks`` is
    a list of ``(seconds, callable)``: each is called once, between two
    ticks, when the clock has passed its time.

    Returns the records ``rid -> namespace(due, handed, admit, stamps,
    tokens, finished, error, shed, prompt_len, max_new, phase)`` (times in
    seconds after ``t0``), the host spans and the per-step samples."""
    t0 = clock() if t0 is None else t0
    spans = Spans(clock, t0, annotate)
    engines = list(server.router.live())
    steps = {"live": [], "cache_tokens": [], "attended": [], "at": []}
    cap = window or math.inf
    recs = {}

    def on_admit(req):
        recs[req.rid].admit = clock() - t0

    def on_step(eng):
        def before():
            ss = eng.sessions()
            steps["live"].append(len(ss))
            steps["cache_tokens"].append(sum(s.pos_next for s in ss))
            steps["attended"].append(sum(min(s.pos_next, cap) for s in ss))
            steps["at"].append(clock() - t0)
        return before

    for eng in engines:
        eng.admit = spans.wrap("admit", eng.admit, before=on_admit)
        eng.step = spans.wrap("step", eng.step, before=on_step(eng))

    feeder = Feeder([r for r in requests if r.due <= until], t0,
                    clock=clock, sleep=sleep, threaded=threaded)
    pending = collections.deque()
    marks = sorted(marks, key=lambda m: m[0])
    fired = 0
    open_ = 0           # handed over and neither finished nor failed
    drain_from = None   # when the arrivals and the marks were done
    feeder.start()
    try:
        while True:
            now = clock() - t0
            while fired < len(marks) and marks[fired][0] <= now:
                marks[fired][1]()
                fired += 1
                now = clock() - t0      # a mark may take seconds
            feeder.poll()
            while feeder.inbox:
                r = feeder.inbox.popleft()
                rec = recs[r.rid] = types.SimpleNamespace(
                    rid=r.rid, phase=r.phase, due=r.due, handed=r.handed,
                    admit=None, stamps=[], tokens=[], finished=None,
                    error=None, shed=False, prompt_len=int(r.prompt.size),
                    max_new=r.max_new, prompt=r.prompt)
                req = make_request(serving, r)
                why = server._gate(req, len(pending))
                if why is not None:
                    rec.error, rec.shed = why, True
                    continue
                pending.append(req)
                open_ += 1
            past = now >= until and feeder.exhausted and fired == len(marks)
            if past and drain_from is None:
                drain_from = now    # a mark may take seconds (the profiler)
            if past and (open_ == 0 or now >= drain_from + drain_s):
                break
            if not pending and not any(e.active for e in engines):
                # nothing to serve: wait for the next arrival or mark
                wake = min([drain_from + drain_s if past else until]
                           + [m[0] for m in marks[fired:fired + 1]])
                with spans.span("wait_arrival"):
                    while not feeder.inbox and clock() - t0 < wake:
                        sleep(0.0005)
                        feeder.poll()
                continue
            with spans.span("tick"):
                admitted, stepped, finished, _, rejected = server._tick(
                    pending)
            with spans.span("stamp"):
                stamp = clock() - t0
                for sess in admitted:
                    recs[sess.request.rid].stamps.append(stamp)
                for sess in stepped:
                    recs[sess.request.rid].stamps.extend(
                        [stamp] * max(1, sess.last_emit))
                for sess in finished:
                    rec = recs[sess.request.rid]
                    rec.tokens = (list(sess.request.tokens)
                                  + [int(t_) for t_ in sess.emitted])
                    rec.finished = stamp
                    open_ -= 1
                for req in rejected:
                    recs[req.rid].error = req.error
                    open_ -= 1
    finally:
        feeder.stop()
        for eng in engines:
            del eng.admit, eng.step     # the instance's wrappers
    return types.SimpleNamespace(
        t0=t0, records=recs, spans=dict(spans.spans), steps=steps,
        ended=clock() - t0, unhanded=len(feeder.todo))


# ------------------------------------------------------------ the reduction


def percentile(values, q):
    """``harness.percentile`` (linear interpolation) where a value may be
    infinite (a failed request's latency): infinite as soon as the rank
    reaches one."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    if pos == lo or math.isinf(xs[lo]):
        return xs[lo]
    return math.inf if math.isinf(xs[lo + 1]) else harness.percentile(xs, q)


def in_window(stamp, seconds):
    return 0.0 <= stamp < seconds


def reduce(records, *, seconds, count, spans=None, steps=None, slots=None,
           slot_tokens=None):
    """From records to the numbers of one window ``[0, seconds)``.

    ``count`` is the traffic file's: ``"due"``: attempted = requests due
    inside the window, failed = those shed, refused, errored or unfinished
    when the loop ended; ``"admitted"``: attempted = requests admitted to a
    slot inside the window (the backlog is the design), failed = those of
    them that errored.  A failed request misses any latency: its TTFT
    counts as infinite.  TTFT = first stamp - DUE time.  A gap = the
    difference of two successive stamps of one request.  Output tokens =
    stamps inside the window, whoever's.
    """
    recs = list(records.values())
    if count == "due":
        mine = [r for r in recs if in_window(r.due, seconds)]
        failed = [r for r in mine if r.error or r.finished is None]
    elif count == "admitted":
        mine = [r for r in recs
                if in_window(r.handed if r.admit is None else r.admit,
                             seconds) and (r.admit is not None or r.error)]
        failed = [r for r in mine if r.error]
    else:
        raise ValueError(f"unknown count {count!r}")
    bad = {id(r) for r in failed}
    ttft = [1e3 * (r.stamps[0] - r.due) if r.stamps and id(r) not in bad
            else math.inf for r in mine]
    gaps = [1e3 * (b - a) for r in mine
            for a, b in zip(r.stamps, r.stamps[1:])]
    out_tokens = sum(in_window(s, seconds) for r in recs for s in r.stamps)
    late = [1e3 * (r.handed - r.due) for r in mine]
    waits = [1e3 * (r.admit - r.due) for r in mine if r.admit is not None]
    short = [r for r in mine if r.finished is not None
             and len(r.tokens) != r.max_new]
    out = {
        "attempted": len(mine), "failed": len(failed),
        "offered_tokens_per_s": sum(
            r.max_new for r in recs if in_window(r.due, seconds)) / seconds,
        "requests_finished": sum(r.finished is not None for r in mine),
        "short_answers": len(short),
        "out_tokens": out_tokens,
        "out_tokens_per_s": out_tokens / seconds,
        # every token the window got through: a prompt counts when its
        # first token is stamped, an output token when it is
        "served_tokens_per_s": (out_tokens + sum(
            r.prompt_len for r in recs
            if r.stamps and in_window(r.stamps[0], seconds))) / seconds,
        "prompt_tokens_admitted": sum(
            r.prompt_len for r in recs
            if r.admit is not None and in_window(r.admit, seconds)),
        "ttft_samples": len(ttft), "gap_samples": len(gaps),
    }
    out["itl_ms_mean"] = sum(gaps) / len(gaps) if gaps else None
    for name, values, qs in (("ttft_ms", ttft, (50, 90)),
                             ("itl_ms", gaps, (50, 95, 99)),
                             ("generator_late_ms", late, (50, 95)),
                             ("queue_wait_ms", waits, (50, 90))):
        for q in qs:
            out[f"{name}_p{q}"] = (percentile(values, q)
                                   if values else None)
    if steps is not None and steps["at"]:
        at = [i for i, t in enumerate(steps["at"]) if in_window(t, seconds)]
        out["decode_steps"] = len(at)
        if at and slots:
            live = sum(steps["live"][i] for i in at)
            used = sum(steps["cache_tokens"][i] for i in at)
            out["live_tokens_per_step"] = used / len(at)
            out["live_slots_per_step"] = live / len(at)
            out["batch_occupancy_pct"] = 100.0 * live / (len(at) * slots)
            out["cache_tokens_used_over_reserved"] = used / (
                len(at) * slots * slot_tokens)
    if spans is not None:
        def inside(name):
            return sum(max(0.0, min(e, seconds) - max(s, 0.0))
                       for s, e in spans.get(name, ()))
        out["prefill_share_pct"] = 100.0 * inside("admit") / seconds
        out["step_share_pct"] = 100.0 * inside("step") / seconds
        out["host_share_pct"] = 100.0 * (
            seconds - inside("admit") - inside("step")) / seconds
    return out
