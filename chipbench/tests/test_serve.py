"""The served cells' own tests: the schedule, the reduction from stamps to
metrics, the wall-clock loop against an injected clock, the operation and
byte counts against hand counts, and, at the rehearsal's sizes on the CPU,
the comparison that decides ``correct``: the control one precision below
comes out not correct, and so does a run whose timed path alters a token
where it is produced.

    python -m pytest chipbench/tests/test_serve.py -q
"""

import collections
import json
import math
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import flops, flops_serve, harness, open_loop  # noqa: E402

MANIFEST = harness.load_manifest()
CELLS = ("sc2-3b-serve-r80", "sc2-3b-serve-sat")


# ------------------------------------------------------------ the schedule


@pytest.mark.parametrize("cell", CELLS)
def test_schedule_same_seed_same_bytes_other_seed_same_population(cell):
    c = harness.resolve(MANIFEST, cell)
    kw = dict(seconds=30, vocab=c.config["vocab_size"], extra_s=3.0)

    def blob(reqs):
        return [(r.rid, r.due, r.max_new, r.prompt.tobytes()) for r in reqs]

    a = open_loop.schedule(c.traffic, 2**31 + 17, **kw)
    b = open_loop.schedule(c.traffic, 2**31 + 17, **kw)
    other = open_loop.schedule(c.traffic, 5, **kw)
    assert blob(a) == blob(b) and blob(a) != blob(other)
    # ONE ordering rule, the seed's: another seed, the same population at
    # other moments in another order
    assert "order_seed" not in c.traffic
    assert [r.due for r in other] != [r.due for r in a]
    assert [r.prompt.size for r in other] != [r.prompt.size for r in a]
    assert [r.due for r in a] == sorted(r.due for r in a)
    for phase, lo, hi in (("warm", -c.traffic["warmup_s"], 0),
                          ("window", 0, 30), ("extra", 30, 33)):
        mine, theirs = ([r for r in s if r.phase == phase]
                        for s in (a, other))
        n = round(c.traffic["rate_per_s"] * (hi - lo))
        size = min(n, c.traffic.get("round", n))
        assert len(mine) == len(theirs) == n
        assert all(lo < r.due < hi for r in mine)
        pop = open_loop.population(c.traffic, hi - lo)
        assert (pop.n, pop.size) == (n, size)

        def by_arrival(reqs):       # rid numbers a stretch's arrivals
            return sorted(reqs, key=lambda r: int(r.rid[len(phase):]))

        def gaps(reqs):     # gap i lies around due i: due = sum - gap / 2
            out, at = [], lo
            for r in by_arrival(reqs):
                out.append(2 * (r.due - at))
                at += out[-1]
            return out

        assert sum(gaps(mine)) == pytest.approx(hi - lo)
        # every FULL round offers the same work, whatever the seed: the
        # round's quantiles of prompts, of answers and of gaps
        for reqs in (mine, theirs):
            ordered, g = by_arrival(reqs), gaps(reqs)
            scale = sum(g[:size]) / sum(pop.gaps)
            for at in range(0, n - size + 1, size):
                one = ordered[at:at + size]
                assert sorted(r.prompt.size for r in one) == pop.prompts
                assert sorted(r.max_new for r in one) == pop.answers
                assert sorted(g[at:at + size]) == pytest.approx(
                    [x * scale for x in pop.gaps])
            part = ordered[n - n % size:] if n % size else []
            assert set(r.max_new for r in part) <= set(pop.answers)
    win = [r for r in a if r.phase == "window"]
    p, ans = c.traffic["prompt_tokens"], c.traffic["answer_tokens"]
    assert ("round" in c.traffic) == (cell == CELLS[1])     # both rules run
    assert p["min"] <= min(r.prompt.size for r in win)
    assert max(r.prompt.size for r in win) == p["max"]
    assert ans["min"] <= min(r.max_new for r in win)
    assert max(r.max_new for r in win) <= ans["max"]
    assert all(0 <= r.prompt.min() and r.prompt.max()
               < c.config["vocab_size"] for r in win)
    # a slot holds the longest prompt with the longest answer
    assert p["max"] + ans["max"] <= c.config["serving"]["slot_tokens"]


def test_quantiles_are_the_distributions():
    q = open_loop.quantiles({"dist": "lognormal", "median": 512,
                             "sigma": 1.0, "min": 64, "max": 4096}, 1001)
    assert q[500] == pytest.approx(512) and q[0] == 64 and q[-1] == 4096
    e = open_loop.quantiles({"dist": "exponential"}, 1000)
    assert sum(e) / len(e) == pytest.approx(1.0, rel=0.01)
    with pytest.raises(ValueError):
        open_loop.quantiles({"dist": "nothing"}, 3)


# ----------------------------------------------------------- the reduction


def rec(rid, due, handed, admit, stamps, max_new=None, error=None,
        finished=True, prompt_len=10, phase="window"):
    return types.SimpleNamespace(
        rid=rid, phase=phase, due=due, handed=handed, admit=admit,
        stamps=stamps, tokens=list(range(len(stamps))),
        finished=stamps[-1] if finished and stamps else None, error=error,
        shed=False, prompt_len=prompt_len,
        max_new=len(stamps) if max_new is None else max_new)


HAND = {
    # on time: first token 0.5 s after it was DUE, gaps 0.1 and 0.3
    "a": rec("a", 1.0, 1.0, 1.2, [1.5, 1.6, 1.9]),
    # the generator was 0.2 s late: TTFT still counts from the due time
    "b": rec("b", 2.0, 2.2, 2.3, [3.0, 3.1]),
    # due inside the window, served after it closed: its latency counts,
    # its tokens are not the window's
    "c": rec("c", 9.0, 9.0, 10.5, [11.0, 11.2]),
    # unfinished when the drain ended: failed, misses any latency
    "d": rec("d", 5.0, 5.0, 5.5, [6.0], max_new=4, finished=False),
    # refused by the engine: failed
    "e": rec("e", 6.0, 6.0, None, [], error="too long", finished=False),
    # the warm-up's: due before the window; its tokens inside count as
    # output, nothing else of it counts
    "w": rec("w", -1.0, -1.0, -0.9, [-0.5, 0.5, 0.7], phase="warm"),
}


def test_reduction_on_hand_made_stamps():
    got = open_loop.reduce(HAND, seconds=10.0, count="due")
    assert (got["attempted"], got["failed"]) == (5, 2)
    assert got["requests_finished"] == 3 and got["short_answers"] == 0
    # stamps inside [0, 10): a 3, b 2, d 1, w 2
    assert got["out_tokens"] == 8 and got["out_tokens_per_s"] == 0.8
    # a prompt (10 tokens each) counts when its first token is stamped in
    # the window: a, b, d; not c (served after it), not w (before it)
    assert got["served_tokens_per_s"] == pytest.approx((8 + 30) / 10.0)
    # offered: the answers of the requests DUE in the window, a 3, b 2,
    # c 2, d 4, e 0
    assert got["offered_tokens_per_s"] == pytest.approx(1.1)
    # TTFT in ms: a 500, b 1000 (from DUE, not from the hand-over), c 2000,
    # d and e infinite
    assert got["ttft_ms_p50"] == pytest.approx(2000.0)
    assert got["ttft_ms_p90"] == math.inf
    assert got["ttft_samples"] == 5
    # gaps of the window's requests: a 100, 300; b 100; c 200
    assert got["gap_samples"] == 4
    assert got["itl_ms_p50"] == pytest.approx(150.0)
    assert got["itl_ms_p95"] == pytest.approx(285.0)
    assert got["itl_ms_p99"] == pytest.approx(297.0)
    assert got["generator_late_ms_p95"] == pytest.approx(160.0)
    # due -> admitted: a 200, b 300, c 1500, d 500
    assert got["queue_wait_ms_p50"] == pytest.approx(400.0)
    ok = {k: v for k, v in HAND.items() if k in "abc"}
    fine = open_loop.reduce(ok, seconds=10.0, count="due")
    assert fine["failed"] == 0
    assert fine["ttft_ms_p90"] == pytest.approx(1800.0)
    assert fine["ttft_ms_p50"] == pytest.approx(1000.0)


def test_reduction_counts_admissions_where_the_backlog_is_the_design():
    got = open_loop.reduce(HAND, seconds=10.0, count="admitted")
    # admitted inside the window: a, b, d; refused inside it: e; c was
    # admitted after it closed and is neither attempted nor failed
    assert (got["attempted"], got["failed"]) == (4, 1)
    assert got["out_tokens"] == 8
    with pytest.raises(ValueError):
        open_loop.reduce(HAND, seconds=10.0, count="nothing")


def test_reduction_of_spans_and_step_samples():
    spans = {"admit": [(-1.0, 0.5), (2.0, 3.0)], "step": [(3.0, 5.0),
                                                          (9.5, 10.5)]}
    steps = {"at": [-0.5, 1.0, 2.0, 11.0], "live": [1, 2, 4, 4],
             "cache_tokens": [10, 100, 300, 400], "attended": [0] * 4}
    got = open_loop.reduce({}, seconds=10.0, count="due", spans=spans,
                           steps=steps, slots=4, slot_tokens=100)
    assert got["prefill_share_pct"] == pytest.approx(15.0)
    assert got["step_share_pct"] == pytest.approx(25.0)
    assert got["host_share_pct"] == pytest.approx(60.0)
    assert got["decode_steps"] == 2
    assert got["batch_occupancy_pct"] == pytest.approx(75.0)
    assert got["cache_tokens_used_over_reserved"] == pytest.approx(0.5)
    assert got["live_tokens_per_step"] == pytest.approx(200.0)


# ------------------------------------------------ the loop, a clock injected


class FakeEngine:
    """Two slots; an admission takes 0.3 s of the fake clock and emits the
    first token, a step 0.1 s and one token a session."""

    def __init__(self, clock):
        self.clock, self.live, self.name = clock, {}, "fake0"

    @property
    def active(self):
        return len(self.live)

    def sessions(self):
        return list(self.live.values())

    def admit(self, req):
        self.clock.t += 0.3
        sess = types.SimpleNamespace(request=req, emitted=[7], last_emit=1,
                                     pos_next=len(req.prompt))
        self.live[req.rid] = sess
        return sess, False

    def step(self):
        self.clock.t += 0.1
        out, done = [], []
        for rid, sess in list(self.live.items()):
            sess.emitted.append(7)
            sess.pos_next += 1
            out.append(sess)
            if len(sess.emitted) >= sess.request.max_new:
                done.append(self.live.pop(rid))
        return out, done


class FakeServer:
    def __init__(self, clock):
        self.engine = FakeEngine(clock)
        self.router = types.SimpleNamespace(live=lambda: [self.engine])
        self.ticks = 0

    def _gate(self, req, depth):
        return "shed" if req.rid == "shed" else None

    def _tick(self, pending):
        self.ticks += 1
        admitted, rejected = [], []
        while pending and self.engine.active < 2:
            req = pending.popleft()
            if req.rid == "bad":
                req.error = "rejected"
                rejected.append(req)
                continue
            admitted.append(self.engine.admit(req)[0])
        stepped, finished = self.engine.step() if self.engine.active \
            else ([], [])
        return admitted, stepped, finished, 1, rejected


class FakeClock:
    def __init__(self):
        self.t = 100.0
        self.slept = 0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s
        self.slept += 1


def request(rid, due, max_new, phase="window"):
    return types.SimpleNamespace(rid=rid, phase=phase, due=due,
                                 prompt=np.zeros(5, np.int32),
                                 max_new=max_new)


def test_loop_on_an_injected_clock_waits_out_gaps_and_stamps_every_token():
    clock = FakeClock()
    serving = types.SimpleNamespace(
        Request=lambda **kw: types.SimpleNamespace(tokens=[], error=None,
                                                   **kw))
    reqs = [request("a", 0.0, 3), request("b", 0.05, 2),
            request("c", 0.06, 2),          # waits for a slot
            request("shed", 0.07, 2), request("bad", 0.08, 2),
            request("late", 5.0, 2)]        # after an idle gap
    fired = []
    server = FakeServer(clock)
    out = open_loop.serve(
        server, serving, reqs, until=6.0, drain_s=10.0, t0=clock(),
        clock=clock, sleep=clock.sleep, threaded=False,
        marks=[(1.0, lambda: fired.append(clock() - 100.0))])
    r = out.records
    # a: admitted at once (0.3), stepped (0.1): two tokens at 0.4; b and c
    # were due during that tick and are handed over after it, late
    assert r["a"].stamps == pytest.approx([0.4, 0.4, 0.8])
    assert r["a"].finished == pytest.approx(0.8) and len(r["a"].tokens) == 3
    assert r["b"].handed == pytest.approx(0.4)
    assert r["b"].stamps == pytest.approx([0.8, 0.8])
    # c got a's or b's slot only when one had finished
    assert r["c"].admit == pytest.approx(0.8) and len(r["c"].stamps) == 2
    assert r["shed"].shed and r["shed"].error and not r["shed"].stamps
    assert r["bad"].error == "rejected" and r["bad"].finished is None
    # the idle gap is waited out on the clock, not jumped: the late request
    # is handed over when it is due; the mark fired between two ticks, when
    # the tick that was running at 1.0 had returned
    assert r["late"].handed == pytest.approx(5.0, abs=1e-3)
    assert r["late"].stamps[0] == pytest.approx(5.4, abs=1e-3)
    assert fired == [pytest.approx(1.2, abs=1e-3)] and clock.slept > 100
    waited = sum(e - s for s, e in out.spans["wait_arrival"])
    assert waited == pytest.approx(    # the gap, and the window's idle end
        5.0 - r["c"].finished + 6.0 - r["late"].finished, abs=1e-2)
    assert len(out.spans["tick"]) == server.ticks
    assert len(out.spans["admit"]) == 4 and out.steps["live"][0] == 1
    # every token of every request has a stamp, and no stamp goes back
    for x in r.values():
        assert len(x.stamps) == len(x.tokens) or x.error
        assert x.stamps == sorted(x.stamps)
    # the engine's own methods are back
    assert "admit" not in vars(server.engine)
    got = open_loop.reduce(r, seconds=6.0, count="due", spans=out.spans,
                           steps=out.steps, slots=2, slot_tokens=10)
    assert (got["attempted"], got["failed"]) == (6, 2)
    assert got["out_tokens"] == 9


def test_loop_without_a_drain_stops_at_the_end_of_the_window():
    clock = FakeClock()
    serving = types.SimpleNamespace(
        Request=lambda **kw: types.SimpleNamespace(tokens=[], error=None,
                                                   **kw))
    reqs = [request(f"r{i}", 0.1 * i, 50) for i in range(20)]
    out = open_loop.serve(FakeServer(clock), serving, reqs, until=2.0,
                          drain_s=0.0, t0=clock(), clock=clock,
                          sleep=clock.sleep, threaded=False)
    assert out.ended < 2.5
    got = open_loop.reduce(out.records, seconds=2.0, count="admitted")
    assert got["attempted"] == 2 and got["failed"] == 0     # two slots


def test_a_slow_mark_does_not_eat_the_drain():
    """The profiler takes seconds to stop: the drain counts from when the
    marks are done, so the window's requests still finish."""
    clock = FakeClock()
    serving = types.SimpleNamespace(
        Request=lambda **kw: types.SimpleNamespace(tokens=[], error=None,
                                                   **kw))
    reqs = [request("long", 1.9, 30)]           # 0.3 + 29 * 0.1 s of work
    out = open_loop.serve(
        FakeServer(clock), serving, reqs, until=2.0, drain_s=4.0,
        t0=clock(), clock=clock, sleep=clock.sleep, threaded=False,
        marks=[(2.0, lambda: clock.sleep(5.0))])
    assert out.records["long"].finished is not None
    assert 7.0 < out.ended < 12.0


def test_threaded_feeder_hands_over_on_time():
    import time

    reqs = [request(f"r{i}", 0.02 * i, 1) for i in range(10)]
    t0 = time.monotonic()
    f = open_loop.Feeder(reqs, t0)
    f.start()
    time.sleep(0.3)
    f.stop()
    assert f.exhausted and len(f.inbox) == 10
    late = [r.handed - r.due for r in f.inbox]
    assert min(late) >= 0 and max(late) < 0.05


# ------------------------------------------------------------ counts, peaks

SC2 = harness.resolve(MANIFEST, "sc2-3b-serve-r80").config


def sizes():
    return {k: SC2[k] for k in SC2["flops"]["sizes"]}


def test_serving_flops_and_bytes_against_hand_counts():
    layer, head = 95944704, 3072 * 49152
    # a prompt of 1000 tokens: the layers at every position, the head at
    # one, 1000 * 1001 / 2 causal pairs of 4 * 24 * 128 operations a layer
    assert flops_serve.prefill_flops(1000, **sizes()) == pytest.approx(
        2 * 30 * layer * 1000 + 2 * head + 4 * 24 * 128 * 500500 * 30)
    # the mean prompt of the issue: 800 tokens is about 4.7 TFLOP
    assert flops_serve.prefill_flops(800, **sizes()) == pytest.approx(
        4.72e12, rel=0.01)
    # past the window a decoded token attends 4096 keys
    at = lambda c: flops_serve.decode_flops(c, **sizes())  # noqa: E731
    assert at(100) == pytest.approx(
        2 * (30 * layer + head) + 4 * 24 * 128 * 100 * 30)
    assert at(4500) == at(4096) > at(4095)
    # a step with 10,000 live tokens: 3.03 B weights in bfloat16 and
    # 2 * 2 * 128 * 30 float32 numbers a token
    need = flops_serve.decode_step_bytes(10000, **sizes())
    assert need == pytest.approx(2 * (30 * layer + head) + 4 * 15360 * 10000)
    assert need / 819e9 == pytest.approx(8.15e-3, rel=0.01)
    assert flops_serve.decode_step_bytes(
        10000, cache_bytes=2, **sizes()) < need


def test_mfu_and_roofline_readers_against_hand_counts(monkeypatch):
    cell = harness.resolve(MANIFEST, "sc2-3b-serve-sat")
    records = {"a": rec("a", 0.0, 0.0, 0.1, [1.0, 2.0, 31.0],
                        prompt_len=1000)}
    ctx = {"cell": cell, "kind": "TPU v5 lite", "platform": "tpu",
           "records": records, "seconds": 30.0}
    mfu = harness.load_module(MANIFEST, "readers", "serve_mfu")
    need = (flops_serve.prefill_flops(1000, **sizes())
            + flops_serve.decode_flops(1001, **sizes()))   # 31.0 is outside
    assert mfu.read(ctx) == pytest.approx(100 * need / 30 / 197e12)
    assert mfu.read({**ctx, "platform": "cpu"}) is None
    roof = harness.load_module(MANIFEST, "readers", "serve_decode_roofline")
    ms = harness.load_module(MANIFEST, "readers", "serve_module_ms")
    monkeypatch.setattr(ms, "device_s", lambda ctx, module: 0.4)
    ctx["traced"] = {"steps": 10, "live_tokens_per_step": 10000,
                     "prefill_padded_ktok": 8.0}
    spec = harness.load_json(MANIFEST, "layer_metrics",
                             "decode_hbm_roofline_pct.srv")
    least_ms = 1e3 * flops_serve.decode_step_bytes(10000, **sizes()) / 819e9
    assert roof.read(ctx, **spec["args"]) == pytest.approx(
        100 * least_ms / 40.0)
    assert ms.read(ctx, "x", "prefill_padded_ktok") == pytest.approx(50.0)
    assert roof.read({**ctx, "traced": {}}, **spec["args"]) is None
    assert ms.read({**ctx, "traced": {}}, "x", "steps") is None
    stat = harness.load_module(MANIFEST, "readers", "serve_stat")
    assert stat.read({"serve": {"k": 3.5}}, "k") == 3.5
    assert stat.read({}, "k") is None
    assert flops.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_manifest_entries_of_the_served_cells():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    # below the knee the tails are judged, above it the tokens a second
    assert e2e["out_tokens_per_s_chip"]["workloads"] == [CELLS[1]]
    assert e2e["itl_ms_p99"]["workloads"] == [CELLS[0]]
    # the first token is recorded and judged by nobody (PERF.md section 2)
    assert not any(n.startswith("ttft") for n in e2e)
    assert not set(CELLS) & set(e2e["step_ms_p90"]["workloads"])
    for cell, suffix in zip(CELLS, (".lat", ".srv")):
        c = harness.resolve(MANIFEST, cell)
        assert c.config["reduced"] == [] and c.chips == 1
        assert c.config["num_hidden_layers"] == 30
        names = {m["name"] for m in c.per_layer}
        assert all(n.endswith(suffix) for n in names)
        mine = {m["name"] for m in c.end_to_end}
        for m in c.per_layer:       # a metric moves what its cell reports
            assert m["moves"] in mine
            harness.find(MANIFEST, "readers", m["reader"] + ".py")
    sat = {m["name"] for m in harness.resolve(MANIFEST, CELLS[1]).per_layer}
    assert {"mfu_pct.srv", "decode_hbm_roofline_pct.srv",
            "device_idle_pct.srv"} <= sat
    sweep = harness.load_json(MANIFEST, "traffic", "open-r80")["knee"]
    r80, sat = (harness.load_json(MANIFEST, "traffic", t)["rate_per_s"]
                for t in ("open-r80", "open-sat"))
    assert r80 == pytest.approx(0.8 * sweep["knee_per_s"], rel=0.01)
    assert sat == pytest.approx(1.25 * sweep["knee_per_s"], rel=0.01)
    assert len(sweep["rows"]) >= 4
    assert all(row["orders"] >= 3 for row in sweep["rows"])


# ------------------------------- what decides `correct`, at rehearsal sizes


@pytest.fixture(scope="module")
def rehearsed():
    """One rehearsal window served in this process on the CPU."""
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("the rehearsal's sizes are for the CPU")
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    cell = harness.resolve(MANIFEST, "sc2-3b-serve-r80", rehearse=True)
    runner = harness.load_module(MANIFEST, "runners", "serve_open_loop")
    return cell, runner


def test_warm_up_reaches_the_buckets_the_engine_runs_and_no_others(rehearsed):
    """``bucket_of`` is a copy of the engine's padding rule: held against
    the engine's own, and a window after the warm-up counts no new prefill
    length."""
    import numpy as np

    from chipbench import open_loop as ol

    cell, runner = rehearsed
    srv = cell.config["serving"]
    schedule = ol.schedule(cell.traffic, 77, seconds=0.5,
                           vocab=cell.config["vocab_size"])
    lengths = [r.prompt.size for r in schedule]
    ready = runner.setup(cell, 77, lengths)
    for n in sorted(set(lengths)) + [1, srv["prefill_bucket"],
                                     srv["slot_tokens"] - 2]:
        padded, true_len = ready.engine._pad_prompt(
            np.zeros((1, n), np.int32))
        assert (padded.shape[1], true_len) == (runner.bucket_of(n, srv), n)
    warmed = runner.warm_lengths(lengths, srv)
    assert len(warmed) == len({runner.bucket_of(n, srv) for n in lengths})
    assert ready.engine.stats["prefill_compiles"] == len(warmed)
    s = runner.window(cell, ready, schedule, 0.5)
    assert s.counters["prefill_compiles"] == 0 and s.stats["failed"] == 0
    assert s.counters["prefill_tokens"] == sum(
        runner.bucket_of(r.prompt_len, srv) for r in s.records.values()
        if r.admit is not None)


def args(seed, seconds=1.0, trace=0):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 4294967311])
def test_control_one_precision_below_comes_out_not_correct(rehearsed, seed):
    from chipbench import served_check

    cell, runner = rehearsed
    s = runner.served(cell, seed, 1.0)
    limit = cell.config["tolerance"]["logit_gap"]
    picked = served_check.sample(s.records, seed, 1000)
    assert picked[0].prompt_len + len(picked[0].tokens) == max(
        r.prompt_len + len(r.tokens) for r in picked)
    program = served_check.gaps(cell, s.params, picked)
    control = served_check.gaps(cell, s.params, picked, control=True)
    assert program["served_tokens"] > 150
    assert program["widest_gap"] <= limit < control["widest_gap"]
    assert control["widest_gap"] > 3 * program["widest_gap"]
    # the run itself is judged on its sample and comes out correct
    _, compared, correct = runner.judge(cell, seed, s)
    assert correct and compared["logit_gap"][0] <= limit


def test_lowered_weights_are_float8_e4m3fn_values(rehearsed):
    """The control's rounding is written in arithmetic (the chip's compiler
    removes a narrowing cast that is widened again at once): on the CPU it
    equals the cast through the type, bit for bit."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import transformer_lm_served as ref

    for seed, shape, std in ((0, (512, 384), 0.02), (1, (100, 77), 1.0),
                             (2, (64, 64), 1e-3)):
        w = (jax.random.normal(jax.random.PRNGKey(seed), shape)
             * std).astype(jnp.bfloat16)
        x = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x)) / 448.0
        cast = ((x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * scale).astype(jnp.bfloat16)
        assert np.array_equal(np.asarray(ref._fp8(w).astype(jnp.float32)),
                              np.asarray(cast.astype(jnp.float32)))
    tree = {"Dense_0": {"kernel": w, "bias": w[0]}, "head": w}
    low = ref.lowered(tree)
    assert not np.array_equal(np.asarray(low["head"], np.float32),
                              np.asarray(w, np.float32))
    assert np.array_equal(np.asarray(low["Dense_0"]["bias"], np.float32),
                          np.asarray(w[0], np.float32))


def test_a_token_altered_where_it_is_produced_comes_out_not_correct(
        rehearsed, monkeypatch):
    """The rest of a run with the timed path broken underneath: the pooled
    decode step hands back another token for one slot in five."""
    from torchmpi_tpu.serving import engine

    cell, runner = rehearsed
    plain = engine.ReplicaEngine._backend_step
    calls = collections.Counter()

    def altered(self, toks, pos, sampling):
        out = np.array(plain(self, toks, pos, sampling))
        calls["steps"] += 1
        if calls["steps"] % 5 == 0:
            out[:] = (out + 1) % cell.config["vocab_size"]
        return out

    monkeypatch.setattr(engine.ReplicaEngine, "_backend_step", altered)
    result = runner.run(cell, args(23))
    assert result["correct"] is False
    assert result["compared"]["logit_gap"][0] > result["compared"][
        "logit_gap"][1]
    assert list(result)[-1] == "compared"
    json.dumps(result)
    monkeypatch.undo()
    good = runner.run(cell, args(23))
    assert good["correct"] is True and good["failed"] == 0


def test_a_compile_inside_the_window_or_a_short_answer_is_not_correct(
        rehearsed):
    cell, runner = rehearsed
    s = runner.served(cell, 31, 0.5)
    assert runner.judge(cell, 31, s)[2] is True
    assert runner.judge(cell, 31, s, compile_events=["x"])[2] is False
    r = next(x for x in s.records.values() if x.phase == "window")
    s.stats["short_answers"] = 1
    assert runner.judge(cell, 31, s)[2] is False
    s.stats["short_answers"] = 0
    s.stats["failed"] = max(1, s.stats["attempted"] // 50)
    assert runner.judge(cell, 31, s)[2] is False
    s.stats["failed"] = 0
    r.tokens[0] = cell.config["vocab_size"]
    assert runner.judge(cell, 31, s)[2] is False


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_what_the_cpu_can(cell):
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", "4294967301", "--seconds", "0.3",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True
    want = {m["name"] for m in
            harness.metrics_of(MANIFEST, "per_layer", cell)}
    assert set(out["metrics"]) <= want
    suffix = ".lat" if cell == CELLS[0] else ".srv"
    assert {"batch_occupancy_pct" + suffix, "prefill_share_pct" + suffix,
            "generator_late_ms_p95" + suffix} <= set(out["metrics"])
    # no chip: no share of a peak or of a roofline, no device time
    assert not any("mfu" in k or "roofline" in k or "idle" in k
                   or k.startswith(("decode_step", "prefill_ms"))
                   for k in out["metrics"])
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert list(out)[-1] == "compared"
    assert "compared logit_gap:" in p.stderr
