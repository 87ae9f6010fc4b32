"""Continuous-batching serving layer (torchmpi_tpu/serving/, ISSUE 9;
docs/SERVING.md).

Covers: slot-pool lifecycle invariants, iteration-level scheduling
emitting per-request tokens BIT-IDENTICAL to the offline
``models.generate.generate`` path (admission at token boundaries, EOS
retirement, slot reuse without zeroing), health-routed multi-replica
dispatch with a deterministic fault-plan replica kill (drain +
re-route, sessions still token-exact, ``tm_serving_rerouted_total``),
the ``tm_serving_*`` SLO telemetry + ``obs_tool slo`` rendering, and
the off-by-default import discipline (a non-serving session never has
``torchmpi_tpu.serving`` in ``sys.modules`` — subprocess-checked like
analysis/obs/faults).
"""

import collections
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import torchmpi_tpu as mpi
from torchmpi_tpu import serving
from torchmpi_tpu.models import TransformerLM, generate
from torchmpi_tpu.serving.engine import SAMPLE_BRANCHES, SPANS
from torchmpi_tpu.serving.slots import SlotPool

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB = 41


@pytest.fixture(scope="module")
def lm():
    """One tiny RoPE LM shared by the module (rope: slot blocks may be
    smaller than max_len, and the jit caches are keyed by the decode
    clone, so every test reuses the same executables)."""
    model = TransformerLM(vocab=VOCAB, embed=32, depth=2, num_heads=4,
                          head_dim=8, max_len=64, pos_emb="rope")
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _prompts(n, tp=5, seed=0):
    return np.random.RandomState(seed).randint(
        0, VOCAB, size=(n, tp)).astype(np.int32)


def _offline(model, params, prompt, steps, eos_id=None):
    """The static offline oracle: ``generate`` on a [1, Tp] batch."""
    out = np.asarray(generate(model, params, prompt.reshape(1, -1),
                              steps=steps, eos_id=eos_id))
    return out[0, prompt.size:]


# ---------------------------------------------------------------------------
# Slot pool invariants
# ---------------------------------------------------------------------------


def test_slot_pool_lifecycle():
    pool = SlotPool(3, slot_tokens=16)
    assert pool.fits(16) and not pool.fits(17) and not pool.fits(0)
    got = [pool.alloc() for _ in range(3)]
    assert sorted(got) == [0, 1, 2]
    assert pool.alloc() is None  # exhausted, not an error
    assert pool.in_use == 3 and pool.occupancy_pct() == 100.0
    pool.free(1)
    assert pool.alloc() == 1  # LIFO reuse: the freed block comes back
    pool.free(2)
    with pytest.raises(ValueError, match="not allocated"):
        pool.free(2)  # double free
    with pytest.raises(ValueError, match="not allocated"):
        pool.free(7)  # never allocated
    with pytest.raises(ValueError):
        SlotPool(0, 16)
    with pytest.raises(ValueError):
        SlotPool(2, 0)


# ---------------------------------------------------------------------------
# Default replica placement: one device each, wrapping round
# ---------------------------------------------------------------------------


def _homes(srv):
    """The single device each replica's params AND cache live on."""
    homes = []
    for e in srv.router.replicas:
        on = {d for leaf in jax.tree.leaves((e.params, e._cache))
              for d in leaf.devices()}
        assert len(on) == 1, (e.name, on)
        homes.append(on.pop())
    return homes


@pytest.mark.parametrize("replicas", [1, 3, 8, 11])
def test_default_replicas_spread_over_local_devices(lm, replicas):
    model, params = lm
    local = jax.local_devices()  # the conftest's eight CPU devices
    srv = serving.Server(model, params, replicas=replicas, slots=1,
                         slot_tokens=16)
    homes = _homes(srv)
    assert homes == [local[i % len(local)] for i in range(replicas)]
    assert len(set(homes)) == min(replicas, len(local))


def test_callers_own_devices_are_honoured(lm):
    model, params = lm
    local = jax.local_devices()
    srv = serving.Server(model, params, replicas=2, slots=1,
                         slot_tokens=16, devices=[local[5], local[2]])
    assert _homes(srv) == [local[5], local[2]]
    with pytest.raises(ValueError, match="only 1 devices"):
        serving.Server(model, params, replicas=2, slots=1, slot_tokens=16,
                       devices=local[:1])


# ---------------------------------------------------------------------------
# Continuous batching == offline generate, token for token
# ---------------------------------------------------------------------------


def test_continuous_matches_offline(lm):
    model, params = lm
    prompts = _prompts(6)
    # Three DISTINCT lengths keep the offline oracle at three scan
    # compiles (steps is a static argnum) while still mixing decode
    # lengths enough that retirement interleaves with admission.
    lens = [4, 12, 4, 8, 12, 8]
    reqs = [serving.Request(f"r{i}", prompts[i], max_new=lens[i],
                            arrival_s=0.002 * i) for i in range(6)]
    srv = serving.Server(model, params, replicas=1, slots=3,
                         slot_tokens=32)
    done = srv.run_trace(reqs, tick_seconds=0.001)
    assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
    for i, req in enumerate(reqs):
        exp = _offline(model, params, prompts[i], lens[i])
        assert req.tokens == exp.tolist(), (i, req.tokens, exp)
        assert req.ttft_s is not None and req.ttft_s >= 0
        assert req.finish_s is not None and req.latency_s() >= req.ttft_s
    # 6 requests through 3 slot blocks: admission really was
    # iteration-level (a static batcher would have needed 6 slots or
    # two sequential batches — completion ISN'T in arrival order).
    assert srv.router.replicas[0].pool.in_use == 0


def test_eos_retirement_frees_slot_and_reuse_is_bitwise(lm):
    model, params = lm
    engine = serving.ReplicaEngine(model, params, slots=1,
                                   slot_tokens=32)
    pa, pb = _prompts(2, seed=3)
    # EOS chosen as a token request A actually emits mid-stream, so the
    # retirement path (not the budget path) frees the slot.
    free_run = _offline(model, params, pa, 8)
    eos = next(int(t) for t in free_run[1:] if t != free_run[0])
    exp_a = _offline(model, params, pa, 8, eos_id=eos)

    ra = serving.Request("a", pa, max_new=8, eos_id=eos)
    sess_a, done = engine.admit(ra)
    assert sess_a.slot == 0 and not done
    emitted = list(sess_a.emitted)
    while not engine.pool.in_use == 0:
        _, finished = engine.step()
        if finished:
            emitted = finished[0].emitted
    # EOS retired the session early and freed the block.
    assert emitted[-1] == eos and len(emitted) < 8
    assert emitted == exp_a.tolist()[:len(emitted)]
    assert engine.pool.free_count == 1

    # Reuse the SAME block (no zeroing) for an unrelated request: its
    # tokens must equal a fresh static-batch decode bit for bit.
    rb = serving.Request("b", pb, max_new=9)
    sess_b, done = engine.admit(rb)
    assert sess_b.slot == 0 and not done  # the reused block
    toks = list(sess_b.emitted)
    while engine.pool.in_use:
        _, finished = engine.step()
        if finished:
            toks = finished[0].emitted
    exp_b = _offline(model, params, pb, 9)
    assert toks == exp_b.tolist()


def test_request_that_cannot_fit_a_block_is_rejected(lm):
    model, params = lm
    engine = serving.ReplicaEngine(model, params, slots=2,
                                   slot_tokens=16)
    req = serving.Request("big", _prompts(1)[0], max_new=12)  # 5+12 > 16
    with pytest.raises(ValueError, match="slot block"):
        engine.admit(req)
    # Server level: the bad request is rejected with .error set and
    # everyone else still serves — one unservable request must not
    # abort the trace.
    prompts = _prompts(3, seed=11)
    reqs = [serving.Request("ok0", prompts[0], max_new=4),
            serving.Request("big", prompts[1], max_new=99),
            serving.Request("ok1", prompts[2], max_new=4)]
    srv = serving.Server(model, params, replicas=1, slots=2,
                         slot_tokens=32)
    done = srv.run_trace(reqs, tick_seconds=0.001)
    assert len(done) == 3
    bad = next(r for r in done if r.rid == "big")
    assert bad.error and "slot block" in bad.error and not bad.tokens
    for rid, i in (("ok0", 0), ("ok1", 2)):
        good = next(r for r in done if r.rid == rid)
        assert good.error is None
        assert good.tokens == _offline(model, params, prompts[i],
                                       4).tolist()


def test_failed_prefill_does_not_leak_slot(lm, monkeypatch):
    import torchmpi_tpu.serving.engine as eng_mod

    model, params = lm
    engine = serving.ReplicaEngine(model, params, slots=1,
                                   slot_tokens=32)
    monkeypatch.setattr(
        eng_mod, "slot_prefill",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("exploded")))
    with pytest.raises(RuntimeError, match="exploded"):
        engine.admit(serving.Request("x", _prompts(1)[0], max_new=4))
    # The block came back: after `slots` such failures the pool would
    # otherwise be silently full forever.
    assert engine.pool.free_count == 1


def test_learned_pos_requires_full_size_blocks():
    # Constructor-time validation only: no prefill/step runs, so dummy
    # params suffice (the pool cache comes from eval_shape — abstract).
    model = TransformerLM(vocab=VOCAB, embed=16, depth=1, num_heads=2,
                          head_dim=8, max_len=32, pos_emb="learned")
    with pytest.raises(ValueError, match="rope"):
        serving.ReplicaEngine(model, {}, slots=2, slot_tokens=16)
    # Full-size blocks are fine for learned tables.
    serving.ReplicaEngine(model, {}, slots=2, slot_tokens=32)


# ---------------------------------------------------------------------------
# Health-routed multi-replica dispatch + deterministic replica kill
# ---------------------------------------------------------------------------


def _write_kill_plan(path, after=6):
    plan = {"version": 1, "seed": 3, "note": "serving chaos",
            "rules": [{"site": "serving.replica", "kind": "fail",
                       "prob": 1.0, "after": after, "max_hits": 1}]}
    path.write_text(json.dumps(plan))
    return str(path)


def test_replica_kill_drains_and_reroutes(lm, tmp_path):
    model, params = lm
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1,
                        faults=_write_kill_plan(tmp_path / "plan.json"),
                        obs="metrics", obs_dir=str(tmp_path / "obs")))
    try:
        from torchmpi_tpu import faults, obs

        obs.reset()
        faults.ledger().clear()
        prompts = _prompts(10, seed=5)
        # Same three distinct lengths as the offline-match test: the
        # oracle scan executables are already compiled.
        lens = [4, 12, 4, 8, 12, 8, 4, 12, 8, 4]
        reqs = [serving.Request(f"k{i}", prompts[i], max_new=lens[i],
                                arrival_s=0.01 * i) for i in range(10)]
        srv = serving.Server(model, params, replicas=2, slots=3,
                             slot_tokens=32)
        done = srv.run_trace(reqs, tick_seconds=0.01)
        assert len(done) == 10  # the run COMPLETES despite the kill
        dead = [e.name for e in srv.router.replicas if e.dead]
        assert len(dead) == 1  # exactly the planned hard failure
        rerouted = obs.registry().counter_total(
            "tm_serving_rerouted_total")
        assert rerouted > 0
        assert sum(r.reroutes for r in reqs) == rerouted
        # Every request — including the re-routed ones — still matches
        # the offline oracle token for token (greedy re-prefill from
        # the emitted prefix is exact).
        for i, req in enumerate(reqs):
            exp = _offline(model, params, prompts[i], lens[i])
            assert req.tokens == exp.tolist(), (i, req.reroutes)
        # SLO histograms landed for BOTH replicas.
        snap = obs.registry().snapshot()
        ttft = [r for r in snap if r["name"] == "tm_serving_ttft_us"]
        assert ttft and sum(r["count"] for r in ttft) == 10
    finally:
        # stop() keeps the fault layer armed (init with faults="off"
        # disarms stale state); later tests must not inherit it.
        from torchmpi_tpu import faults

        faults.reset()
        mpi.stop()


def test_router_prefers_healthy_replicas(lm):
    from torchmpi_tpu.faults.health import HealthLedger

    model, params = lm
    e0 = serving.ReplicaEngine(model, params, name="r0", slots=2,
                               slot_tokens=16)
    e1 = serving.ReplicaEngine(model, params, name="r1", slots=2,
                               slot_tokens=16)
    # Explicit ledger: the suspect/dead thresholds under test must not
    # depend on whether an earlier test left the fault layer armed.
    router = serving.Router([e0, e1],
                            ledger=HealthLedger(suspect_after=1,
                                                dead_after=3))
    assert router.pick() in (e0, e1)
    router.record(e1, False)  # r1 suspect
    assert router.decide(e1) == "degrade"
    assert router.pick() is e0  # healthy wins while it has capacity
    # Dead replicas never admit; drained state shows through decide().
    router.mark_dead(e1)
    assert router.decide(e1) == "raise"
    assert router.pick() is e0
    with pytest.raises(ValueError, match="unique"):
        serving.Router([e0, e0])


def test_healed_replica_readmitted(lm):
    """The recovery half of health routing (ISSUE 11 satellite): a
    drained replica whose ledger returns to healthy — one recorded
    success, the HealthLedger contract — rejoins the dispatch rotation
    and actually serves again."""
    from torchmpi_tpu.faults.health import HealthLedger

    model, params = lm
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1))
    try:
        e0 = serving.ReplicaEngine(model, params, name="r0", slots=2,
                                   slot_tokens=16)
        e1 = serving.ReplicaEngine(model, params, name="r1", slots=2,
                                   slot_tokens=16)
        router = serving.Router([e0, e1],
                                ledger=HealthLedger(suspect_after=2,
                                                    dead_after=3))
        router.mark_dead(e1)
        e1.drain()  # the scheduler's kill path: sessions out, dead on
        assert e1.dead and router.decide(e1) == "raise"
        assert router.pick() is e0
        assert router.live() == [e0]
        # A failure on a dead replica must NOT readmit it.
        assert router.record(e1, ok=False) == "raise"
        assert e1.dead
        # One success resets the ledger -> healthy -> readmitted.
        assert router.record(e1, ok=True) == "ok"
        assert not e1.dead
        assert router.live() == [e0, e1]
        # And it really serves: two concurrent sessions spread across
        # both replicas by least-loaded routing.
        srv = serving.Server.__new__(serving.Server)
        srv.router = router
        srv.last_stats = {}
        prompts = _prompts(2, seed=9)
        reqs = [serving.Request(f"h{i}", prompts[i], max_new=4)
                for i in range(2)]
        done = srv.run_trace(reqs, tick_seconds=0.01)
        assert len(done) == 2
        assert {r.replica for r in reqs} == {"r0", "r1"}
        for i, req in enumerate(reqs):
            assert req.tokens == _offline(model, params, prompts[i],
                                          4).tolist()
    finally:
        mpi.stop()


# ---------------------------------------------------------------------------
# SLO telemetry + obs_tool slo
# ---------------------------------------------------------------------------


def _load_obs_tool():
    spec = importlib.util.spec_from_file_location(
        "_obs_tool_under_test",
        os.path.join(_REPO, "scripts", "obs_tool.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_slo_metrics_and_obs_tool_slo(lm, tmp_path, capsys):
    model, params = lm
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1, obs="metrics",
                        obs_dir=str(tmp_path)))
    try:
        from torchmpi_tpu import obs

        obs.reset()
        prompts = _prompts(6, seed=7)
        reqs = [serving.Request(f"s{i}", prompts[i], max_new=4 + i,
                                arrival_s=0.001 * i) for i in range(6)]
        srv = serving.Server(model, params, replicas=1, slots=4,
                             slot_tokens=32)
        srv.run_trace(reqs)
        reg = obs.registry()
        assert reg.counter_total("tm_serving_requests_total") == 6
        assert reg.counter_total("tm_serving_completed_total") == 6
        assert reg.counter_total("tm_serving_tokens_total") == \
            sum(len(r.tokens) for r in reqs)
        snap = reg.snapshot()
        names = {r["name"] for r in snap}
        assert {"tm_serving_ttft_us", "tm_serving_itl_us",
                "tm_serving_queue_depth",
                "tm_serving_slot_occupancy_pct"} <= names
        paths = obs.dump(str(tmp_path))
        tool = _load_obs_tool()
        rc = tool.main(["slo", paths[0]])
        out = capsys.readouterr().out
        assert rc == 0
        assert "TTFT" in out and "inter-token" in out and "p99" in out
        assert "replica0" in out
        # The prefill-compile counter surfaces in the SLO table too
        # (admissions here span one distinct prompt length = 1 compile).
        assert "prefill_compiles" in out
        # And a non-serving dump exits nonzero (CI greps depend on it).
        empty = tmp_path / "empty.jsonl"
        empty.write_text(json.dumps(
            {"kind": "meta", "stream": "metrics", "host": "x"}) + "\n")
        assert tool.main(["slo", str(empty)]) == 2
    finally:
        mpi.stop()


# ---------------------------------------------------------------------------
# Sampled decode: reproducible, layout-independent, greedy untouched
# ---------------------------------------------------------------------------


def _sampled_reqs(prompts, max_new=6, seed0=100):
    return [serving.Request(f"p{i}", prompts[i], max_new=max_new,
                            temperature=0.9, top_k=12, top_p=0.9,
                            seed=seed0 + i)
            for i in range(len(prompts))]


def test_sampled_decode_reproducible_across_layouts(lm):
    """Sampling keys token i on fold_in(PRNGKey(seed), i) — never on
    the slot, pool neighbors, or replica — so the same (seed, prompt)
    emits the same stream under ANY replica layout."""
    model, params = lm
    prompts = _prompts(6, seed=21)
    streams = []
    for replicas in (1, 2, 1):
        reqs = _sampled_reqs(prompts)
        srv = serving.Server(model, params, replicas=replicas, slots=3,
                             slot_tokens=32)
        done = srv.run_trace(reqs, tick_seconds=0.001)
        assert len(done) == 6
        streams.append({r.rid: list(r.tokens) for r in reqs})
    assert streams[0] == streams[1] == streams[2]
    # And sampling is actually sampling: some stream differs from the
    # greedy oracle.
    greedy = {f"p{i}": _offline(model, params, prompts[i], 6).tolist()
              for i in range(6)}
    assert any(streams[0][k] != greedy[k] for k in greedy)


def test_greedy_ignores_stray_filter_knobs(lm):
    """temperature <= 0 forces the filter no-op sentinels: a greedy
    request with leftover top_k/top_p still emits bitwise the
    unfiltered argmax stream (pre-sampling engine behavior)."""
    model, params = lm
    prompts = _prompts(3, seed=23)
    reqs = [serving.Request(f"g{i}", prompts[i], max_new=6,
                            temperature=0.0, top_k=3, top_p=0.5,
                            seed=9) for i in range(3)]
    srv = serving.Server(model, params, replicas=1, slots=3,
                         slot_tokens=32)
    srv.run_trace(reqs, tick_seconds=0.001)
    for i, req in enumerate(reqs):
        assert req.tokens == _offline(model, params, prompts[i],
                                      6).tolist()


def test_invalid_sampling_rejected(lm):
    model, params = lm
    engine = serving.ReplicaEngine(model, params, slots=1,
                                   slot_tokens=32)
    with pytest.raises(serving.RequestRejected, match="top_p"):
        engine.admit(serving.Request("bad", _prompts(1)[0], max_new=4,
                                     temperature=0.5, top_p=0.0))
    with pytest.raises(serving.RequestRejected, match="top_k"):
        engine.admit(serving.Request("bad", _prompts(1)[0], max_new=4,
                                     temperature=0.5, top_k=-2))
    assert engine.pool.free_count == 1  # nothing leaked


# ---------------------------------------------------------------------------
# The sampling tail does what a pool's rows ask: the engine counts which
# branch of generate._sample_rows each pooled step's sessions ask for
# ---------------------------------------------------------------------------

_SAMPLED = {"temperature": dict(temperature=0.9, seed=31),
            "filtered": dict(temperature=0.9, top_k=12, top_p=0.9, seed=31)}


def _branch_counts(engine):
    return [engine.stats[b] for b in SAMPLE_BRANCHES]


@pytest.mark.parametrize("kind", ["plain", "bucketed", "spec_ngram"])
def test_a_greedy_trace_counts_every_step_under_the_argmax(lm, kind):
    model, params = lm
    reqs, prompts, eng = _pool_trace(lm, kind)
    assert _branch_counts(eng) == [eng.stats["steps"], 0]
    assert eng.stats["steps"] > 0
    for i, req in enumerate(reqs):
        assert req.tokens == _offline(model, params, prompts[i], 7).tolist()


@pytest.mark.parametrize("knobs", sorted(_SAMPLED))
def test_a_sampled_request_moves_the_steps_it_is_live_for(lm, knobs):
    """Two greedy sessions decode throughout; one sampled request is
    admitted after two steps and retires four steps later: exactly the
    steps it is live for leave ``sample_argmax``, the greedy streams stay
    the offline ones, and the sampled stream is what the request emits
    alone in a pool (where every step draws)."""
    model, params = lm
    prompts = _prompts(3, seed=27)
    sampled = dict(max_new=5, **_SAMPLED[knobs])
    alone = serving.ReplicaEngine(model, params, slots=1, slot_tokens=32)
    want = _drive(alone, serving.Request("s", prompts[2], **sampled))
    assert _branch_counts(alone) == [0, 4] and alone.stats["steps"] == 4

    engine = serving.ReplicaEngine(model, params, slots=3, slot_tokens=32)
    greedy = [engine.admit(serving.Request(f"g{i}", prompts[i],
                                           max_new=12))[0]
              for i in range(2)]
    # the first call on the idle pool dispatches two steps, the second one
    # more: three greedy steps, the third unread when "s" is admitted
    engine.step(), engine.step()
    assert _branch_counts(engine) == [3, 0]
    sess, _ = engine.admit(serving.Request("s", prompts[2], **sampled))
    finished = []
    while sess not in finished:
        finished = engine.step()[1]
    # its four steps, and the greedy one dispatched beside its last read
    assert _branch_counts(engine) == [4, 4]
    while engine.active:
        engine.step()
    assert _branch_counts(engine) == [11 - 4, 4]
    assert engine.stats["steps"] == 11
    assert sess.emitted == want
    assert want != _offline(model, params, prompts[2], 5).tolist()
    for i, g in enumerate(greedy):
        assert g.emitted == _offline(model, params, prompts[i], 12).tolist()


def test_branch_counters_are_mirrored_in_the_registry(lm, tmp_path):
    model, params = lm
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1, obs="metrics", obs_dir=str(tmp_path)))
    try:
        from torchmpi_tpu import obs

        obs.reset()
        prompts = _prompts(3, seed=28)
        reqs = [serving.Request("g", prompts[0], max_new=9),
                serving.Request("d", prompts[1], max_new=3,
                                **_SAMPLED["temperature"]),
                serving.Request("f", prompts[2], max_new=6, arrival_s=0.004,
                                **_SAMPLED["filtered"])]
        eng = _run_server(model, params, reqs)
        reg = obs.registry()
        counts = _branch_counts(eng)
        assert all(counts) and sum(counts) == eng.stats["steps"]
        for branch, n in zip(SAMPLE_BRANCHES, counts):
            assert reg.counter(f"tm_serving_{branch}_total",
                               replica=eng.name) == n
    finally:
        mpi.stop()


# ---------------------------------------------------------------------------
# Speculative decoding: bitwise the plain stream, cheaper per token
# ---------------------------------------------------------------------------


def _run_server(model, params, reqs, **kw):
    srv = serving.Server(model, params, replicas=1, slots=3,
                         slot_tokens=32, **kw)
    done = srv.run_trace(reqs, tick_seconds=0.001)
    assert len(done) == len(reqs)
    return srv.router.replicas[0]


def test_spec_ngram_bitwise_and_cheaper(lm):
    """Draft-K/verify-once with the ngram proposer: the stream is
    bitwise the non-speculative one (greedy AND sampled), and the
    work-unit bill is strictly lower whenever drafts land (the ngram
    drafts are free)."""
    model, params = lm
    prompts = _prompts(6, seed=31)

    def reqs():
        out = [serving.Request(f"n{i}", prompts[i], max_new=12)
               for i in range(4)]
        out += [serving.Request(f"n{i}", prompts[i], max_new=12,
                                temperature=0.8, top_k=10, seed=50 + i)
                for i in range(4, 6)]
        return out

    plain_reqs, spec_reqs = reqs(), reqs()
    plain_eng = _run_server(model, params, plain_reqs)
    spec_eng = _run_server(model, params, spec_reqs, spec_k=4)
    assert {r.rid: r.tokens for r in plain_reqs} == \
        {r.rid: r.tokens for r in spec_reqs}
    assert spec_eng.stats["spec_steps"] > 0
    assert spec_eng.stats["spec_drafted"] > 0
    assert 0 < spec_eng.stats["spec_accepted"] <= \
        spec_eng.stats["spec_drafted"]
    # Accepted drafts land extra tokens per forward: fewer units total.
    assert spec_eng.units < plain_eng.units


def test_spec_fills_slot_block_exactly(lm):
    """Regression: the [S, K+1] verify must clamp K when a row is
    within K positions of its slot block end — an out-of-range cache
    write CLAMPS its start index and silently corrupts the row.  A
    request whose prompt+max_new fills the block exactly walks decode
    into that corner."""
    model, params = lm
    prompts = _prompts(2, seed=33)
    reqs = [serving.Request(f"e{i}", prompts[i], max_new=11)
            for i in range(2)]  # 5 + 11 == 16 == slot_tokens
    srv = serving.Server(model, params, replicas=1, slots=2,
                         slot_tokens=16, spec_k=4)
    done = srv.run_trace(reqs, tick_seconds=0.001)
    assert len(done) == 2
    for i, req in enumerate(reqs):
        assert req.tokens == _offline(model, params, prompts[i],
                                      11).tolist()


def test_spec_model_draft_bitwise(lm):
    """A small draft LM proposes over its own pool cache (catch-up
    protocol included); the stream stays bitwise plain decode, and the
    per-slot draft state is freed with the sessions."""
    model, params = lm
    draft_model = TransformerLM(vocab=VOCAB, embed=16, depth=1,
                                num_heads=2, head_dim=8, max_len=32,
                                pos_emb="rope")
    draft_params = draft_model.init(jax.random.PRNGKey(7),
                                    jnp.zeros((1, 4),
                                              jnp.int32))["params"]
    draft = serving.ModelDraft(draft_model, draft_params)
    prompts = _prompts(4, seed=37)

    def reqs():
        out = [serving.Request(f"m{i}", prompts[i], max_new=8)
               for i in range(2)]
        out += [serving.Request(f"m{i}", prompts[i], max_new=8,
                                temperature=0.7, top_p=0.9, seed=60 + i)
                for i in range(2, 4)]
        return out

    plain_reqs, spec_reqs = reqs(), reqs()
    _run_server(model, params, plain_reqs)
    eng = _run_server(model, params, spec_reqs, spec_k=3, draft=draft)
    assert {r.rid: r.tokens for r in plain_reqs} == \
        {r.rid: r.tokens for r in spec_reqs}
    assert eng.stats["spec_steps"] > 0
    # Draft forwards are priced by the param ratio, not free.
    assert 0 < eng._draft.unit_weight < 1
    assert eng.units > eng.stats["prefills"] + eng.stats["steps"]
    # Every session retired -> every per-slot draft pointer freed.
    assert eng._draft.active_slots() == []


# ---------------------------------------------------------------------------
# Bucketed prefill: O(buckets) compiles, streams unchanged
# ---------------------------------------------------------------------------


def test_bucketed_prefill_compile_count_and_bitwise(lm):
    model, params = lm
    rng = np.random.RandomState(41)
    plens = [3, 5, 9, 3, 5, 9]
    prompts = [rng.randint(0, VOCAB, size=(L,)).astype(np.int32)
               for L in plens]

    def reqs():
        return [serving.Request(f"b{i}", prompts[i], max_new=4)
                for i in range(6)]

    plain_reqs, buck_reqs = reqs(), reqs()
    plain_eng = _run_server(model, params, plain_reqs)
    buck_eng = _run_server(model, params, buck_reqs, prefill_bucket=8)
    # Pre-bucketing the counter already tracks one compile per DISTINCT
    # prompt length (satellite: the recompile cost is visible before
    # bucketing is on); bucketing collapses {3,5}->8 and {9}->16.
    assert plain_eng.stats["prefill_compiles"] == 3
    assert buck_eng.stats["prefill_compiles"] == 2
    # Padding never changes tokens: causal attention + the true-length
    # logit slice make the first token independent of the pad tail.
    assert {r.rid: r.tokens for r in plain_reqs} == \
        {r.rid: r.tokens for r in buck_reqs}
    for i, req in enumerate(plain_reqs):
        assert req.tokens == _offline(model, params, prompts[i],
                                      4).tolist()


# ---------------------------------------------------------------------------
# TP-sharded replicas: a mesh slice behind the same serving API
# ---------------------------------------------------------------------------


def test_tp_sharded_server_matches_tp_oracle(tmp_path):
    """``Server.sharded`` carves disjoint TP meshes per replica; every
    stream must equal the offline ``tp_generate`` oracle — and spec +
    bucketed prefill compose with the sharded backend bitwise."""
    import importlib

    tpg = importlib.import_module("torchmpi_tpu.models.tp_generate")
    from jax.sharding import Mesh

    V = 64  # divisible by the 2-way model axis
    tparams = tpg.init_tp_lm(jax.random.PRNGKey(5), vocab=V, embed=32,
                             depth=2, num_heads=4, head_dim=8)
    rng = np.random.RandomState(13)
    prompts = rng.randint(0, V, size=(6, 5)).astype(np.int32)
    lens = [4, 8, 4, 8, 4, 8]
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    oracle = {}
    for i in range(6):
        out = np.asarray(tpg.tp_generate(
            tparams, prompts[i].reshape(1, -1), steps=lens[i],
            mesh=mesh, axis="model", num_heads=4))
        oracle[f"t{i}"] = out[0, 5:].tolist()

    reqs = [serving.Request(f"t{i}", prompts[i], max_new=lens[i],
                            arrival_s=0.001 * i) for i in range(6)]
    srv = serving.Server.sharded(tparams, tp=2, num_heads=4,
                                 slot_tokens=32, replicas=2, slots=2)
    done = []
    spans = _traced(tmp_path, lambda: done.extend(
        srv.run_trace(reqs, tick_seconds=0.001)))
    assert len(done) == 6
    assert {r.replica for r in reqs} == {"tp0", "tp1"}
    for i, req in enumerate(reqs):
        assert req.tokens == oracle[req.rid], i
    # a mesh-slice replica names its phases as the dense one does: the
    # dispatch and the read sit in the one seam both backends go through
    assert {s.name for s in spans} == set(SPANS) - {"tm.serve.step.draft"}
    step_spans = _named(spans, "tm.serve.step")
    assert {s.stats["replica"] for s in step_spans} == {"tp0", "tp1"}
    for s in step_spans:
        _steps_dispatched(_children(spans, s))
    admits = _named(spans, "tm.serve.admit")
    assert sorted(a.stats["rid"] for a in admits) == sorted(oracle)
    for a in admits:
        kids = _children(spans, a)
        assert [k.name for k in kids] == ADMIT_CHILDREN
        assert kids[1].stats == {"padded_tokens": 5}

    # Speculation + bucketing over the SAME sharded stack: bitwise.
    reqs2 = [serving.Request(f"t{i}", prompts[i], max_new=lens[i])
             for i in range(6)]
    srv2 = serving.Server.sharded(tparams, tp=2, num_heads=4,
                                  slot_tokens=32, replicas=1, slots=2,
                                  spec_k=3, prefill_bucket=8)
    done2 = srv2.run_trace(reqs2, tick_seconds=0.001)
    assert len(done2) == 6
    for req in reqs2:
        assert req.tokens == oracle[req.rid]
    eng = srv2.router.replicas[0]
    assert eng.stats["spec_steps"] > 0
    assert eng.stats["prefill_compiles"] == 1  # one 8-bucket
    # the sharded pool is consumed by every step, verify and slot write
    for e in srv.router.replicas + [eng]:
        assert e.stats["pool_donated"] == e.stats["pool_calls"] > 0

    # The planner keys one decision plan per (replica, mesh) topology.
    from torchmpi_tpu import planner

    p1 = planner.plan_serving_replica("tp0", mesh, ("model",))
    if p1 is not None:  # planner may be disabled in this session
        assert p1 is planner.plan_serving_replica("tp0", mesh,
                                                  ("model",))
        assert p1.extra["devices"] == 2
        assert p1.extra["axes"] == ("model",)


def test_tp_engine_takes_the_branch_its_rows_ask_for():
    """The branch under ``shard_map`` (the predicate replicated): a greedy
    stream beside a sampled one is the offline oracle's, the sampled one
    is what it is alone in a pool, and the steps are counted by branch."""
    import importlib

    tpg = importlib.import_module("torchmpi_tpu.models.tp_generate")
    from jax.sharding import Mesh

    V = 64
    tparams = tpg.init_tp_lm(jax.random.PRNGKey(5), vocab=V, embed=32,
                             depth=2, num_heads=4, head_dim=8)
    prompts = np.random.RandomState(14).randint(
        0, V, size=(2, 5)).astype(np.int32)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    oracle = np.asarray(tpg.tp_generate(
        tparams, prompts[0].reshape(1, -1), steps=9, mesh=mesh,
        axis="model", num_heads=4))[0, 5:].tolist()

    def engine(slots):
        return serving.TPReplicaEngine(tparams, mesh=mesh, num_heads=4,
                                       slots=slots, slot_tokens=32)

    sampled = dict(max_new=4, **_SAMPLED["filtered"])
    alone = engine(1)
    want = _drive(alone, serving.Request("s", prompts[1], **sampled))
    assert _branch_counts(alone) == [0, 3]

    eng = engine(2)
    greedy, _ = eng.admit(serving.Request("g", prompts[0], max_new=9))
    eng.step()
    got = _drive(eng, serving.Request("s", prompts[1], **sampled))
    while eng.active:
        eng.step()
    assert _branch_counts(eng) == [5, 3]
    assert got == want and greedy.emitted == oracle


def test_tp_engine_requires_explicit_slot_tokens():
    import importlib

    tpg = importlib.import_module("torchmpi_tpu.models.tp_generate")
    from jax.sharding import Mesh

    from torchmpi_tpu.serving.tp_engine import TPReplicaEngine

    tparams = tpg.init_tp_lm(jax.random.PRNGKey(5), vocab=64, embed=32,
                             depth=2, num_heads=4, head_dim=8)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    with pytest.raises(ValueError, match="slot_tokens"):
        TPReplicaEngine(tparams, mesh=mesh, num_heads=4, slots=2,
                        slot_tokens=0)


# ---------------------------------------------------------------------------
# Chaos: a replica killed MID-SPECULATION drains cleanly
# ---------------------------------------------------------------------------


def test_mid_speculation_kill_discards_draft_state(lm, tmp_path):
    """Satellite: a hard replica kill mid-speculation must drain +
    re-route with ALL draft state discarded — nothing speculative
    survives the move, and the re-routed streams stay token-exact
    because verify only ever emitted target-sampled tokens."""
    model, params = lm
    draft_model = TransformerLM(vocab=VOCAB, embed=16, depth=1,
                                num_heads=2, head_dim=8, max_len=32,
                                pos_emb="rope")
    draft_params = draft_model.init(jax.random.PRNGKey(8),
                                    jnp.zeros((1, 4),
                                              jnp.int32))["params"]
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1,
                        faults=_write_kill_plan(tmp_path / "plan.json",
                                                after=4),
                        obs="metrics", obs_dir=str(tmp_path / "obs")))
    try:
        from torchmpi_tpu import faults, obs

        obs.reset()
        faults.ledger().clear()
        prompts = _prompts(8, seed=43)
        lens = [8, 12, 8, 12, 8, 12, 8, 12]
        reqs = [serving.Request(f"c{i}", prompts[i], max_new=lens[i],
                                arrival_s=0.01 * i) for i in range(8)]
        srv = serving.Server(
            model, params, replicas=2, slots=3, slot_tokens=32,
            spec_k=3,
            draft=serving.ModelDraft(draft_model, draft_params))
        done = srv.run_trace(reqs, tick_seconds=0.01)
        assert len(done) == 8
        dead = [e for e in srv.router.replicas if e.dead]
        assert len(dead) == 1
        reg = obs.registry()
        rerouted = reg.counter_total("tm_serving_rerouted_total")
        assert rerouted > 0
        assert sum(r.reroutes for r in reqs) == rerouted
        # The kill really interrupted speculation on the dead replica…
        assert dead[0].stats["spec_steps"] > 0
        # …and its draft state went with it: drained clean.
        assert dead[0]._draft.active_slots() == []
        assert dead[0].pool.in_use == 0
        # The survivor's draft state also fully retired with the trace.
        live = next(e for e in srv.router.replicas if not e.dead)
        assert live._draft.active_slots() == []
        # Token-exact across the re-route, same as the plain chaos path.
        for i, req in enumerate(reqs):
            exp = _offline(model, params, prompts[i], lens[i])
            assert req.tokens == exp.tolist(), (i, req.reroutes)
        # Speculation telemetry reached the registry.
        drafted = reg.counter_total("tm_serving_spec_drafted_total")
        accepted = reg.counter_total("tm_serving_spec_accepted_total")
        assert drafted > 0 and 0 <= accepted <= drafted
        assert reg.counter_total(
            "tm_serving_prefill_compiles_total") > 0
    finally:
        from torchmpi_tpu import faults

        faults.reset()
        mpi.stop()


# ---------------------------------------------------------------------------
# Off-by-default: a non-serving session never imports the package
# ---------------------------------------------------------------------------


# (The off-mode never-imports subprocess probe formerly here is
# superseded by the static H1 import-discipline rule —
# torchmpi_tpu/analysis/hostcheck.py, tests/test_hostcheck.py;
# runtime anchors live in test_obs.py / test_faults.py.)


# ---------------------------------------------------------------------------
# The prefill through the flash forward kernel: the engine's side
# (models/transformer.prefill_runs_flash; tests/test_generate.py has the
# layer's side).  On the CPU the dense form runs unless a test calls
# conftest's chip_rule.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gqa_lm():
    # 4 q / 2 kv heads and a window smaller than the prompts; sizes no
    # other test of this process uses (programs are keyed by the model)
    model = TransformerLM(vocab=43, embed=32, depth=2, num_heads=4,
                          num_kv_heads=2, head_dim=8, max_len=96,
                          window=12, pos_emb="rope")
    params = jax.jit(model.init)(jax.random.PRNGKey(2),
                                 jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _kernel_reqs():
    rng = np.random.RandomState(47)
    shared = rng.randint(0, 43, size=(16,))
    prompts = [np.concatenate([shared, rng.randint(0, 43, size=(n,))])
               .astype(np.int32) for n in (3, 21, 5, 40)]
    return [serving.Request(f"k{i}", p, max_new=5, arrival_s=0.001 * i)
            for i, p in enumerate(prompts)]


def _serve(model, params, **kw):
    reqs = _kernel_reqs()
    srv = serving.Server(model, params, replicas=1, slots=2,
                         slot_tokens=96, prefill_bucket=32, **kw)
    assert len(srv.run_trace(reqs, tick_seconds=0.001)) == len(reqs)
    return {r.rid: r.tokens for r in reqs}, srv.router.replicas[0]


def _kernels(program, *args):
    """The ``tm_kernel`` of every kernel call in the trace of ``program`` (a
    jitted ``models.generate._slot_*_jit``) over ``args``, sorted: each call
    site of a shared inner jaxpr counts for itself (the layers share one
    trace of the prompt's kernel)."""
    def found(jaxpr):
        names = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["metadata"]["tm_kernel"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                names += found(sub)
        return names

    return sorted(found(program.trace(*args).jaxpr.jaxpr))


def _prefill_kernels(eng, bucket, depth=0):
    """The kernels of the program ``eng`` prefills a prompt padded to
    ``bucket`` with; with ``depth``, of a prefix hit's extend of a suffix
    that long over a row assembled ``depth`` deep."""
    from torchmpi_tpu.models.generate import (
        _greedy_sampling, _slot_extend_jit, _slot_prefill_jit)

    prompt, n = jnp.zeros((1, bucket), jnp.int32), jnp.int32(bucket)
    if depth:
        return _kernels(_slot_extend_jit, eng.dmodel, eng.params,
                        eng._row_template(), prompt,
                        jnp.asarray([depth], jnp.int32), n,
                        *_greedy_sampling(1))
    return _kernels(_slot_prefill_jit, eng.dmodel, eng.params, prompt, n,
                    *_greedy_sampling(1))


def _pooled_kernels(eng, spec_k=0):
    """The kernels of the pooled program ``eng`` dispatches over its pool:
    the step, or with drafts the ``[slots, spec_k + 1]`` verify."""
    from torchmpi_tpu.models.generate import (
        _greedy_sampling, _slot_step_jit, _slot_verify_jit)

    S = eng.pool.n_slots
    rows = jnp.zeros((S,), jnp.int32)
    head = (eng.dmodel, eng.params, eng._cache)
    if spec_k:
        return _kernels(_slot_verify_jit, *head,
                        jnp.zeros((S, spec_k + 1), jnp.int32), rows,
                        *_greedy_sampling(S))
    return _kernels(_slot_step_jit, *head, rows, rows, *_greedy_sampling(S),
                    rows)


def test_kernel_prefill_serves_the_dense_tokens_and_is_counted(
        gqa_lm, chip_rule):
    # The prompt's attention through the flash forward kernel, interpreted
    # here, as on the chip (models/transformer.prefill_runs_flash): the
    # served tokens are the dense form's, and the programs the engine
    # prefilled with hold the kernel, one call a layer, at each bucket
    model, params = gqa_lm
    dense_toks, dense_eng = _serve(model, params)
    # padded: 19 -> 32, 37 -> 64, 21 -> 32, 56 -> 64
    assert dense_eng.stats["prefill_tokens"] == 192
    assert _prefill_kernels(dense_eng, 32) == []        # the CPU: dense

    chip_rule()
    toks, eng = _serve(model, params)
    assert toks == dense_toks
    assert eng.stats["prefill_tokens"] == 192
    for bucket in (32, 64):
        assert _prefill_kernels(eng, bucket) == ["flash.fwd"] * 2


_ROW_WRITE_LMS = {}


def _row_write_lm(head_dim):
    # gqa_lm's sizes at another head width (programs are keyed by the model)
    if head_dim not in _ROW_WRITE_LMS:
        model = TransformerLM(vocab=43, embed=32, depth=2, num_heads=4,
                              num_kv_heads=2, head_dim=head_dim, max_len=96,
                              window=12, pos_emb="rope")
        params = jax.jit(model.init)(jax.random.PRNGKey(2),
                                     jnp.zeros((1, 4), jnp.int32))["params"]
        _ROW_WRITE_LMS[head_dim] = model, params
    return _ROW_WRITE_LMS[head_dim]


@pytest.mark.parametrize("head_dim", [128, 8])
@pytest.mark.parametrize("spec_k", [0, 4])
def test_kernel_row_write_serves_the_same_tokens_and_is_counted(
        rows_rule, spec_k, head_dim):
    # The pooled step's (and, with drafts, the verify's) per-row cache write
    # through ops/cache_rows's kernel, interpreted here, as on the chip
    # (models/transformer.rows_write_in_place): the served tokens are the
    # vmap form's bit for bit, and the pooled program the engine dispatched
    # holds the kernel for each key and value leaf of each layer.  A head
    # of 8 is no whole row of lanes: the rule keeps vmap's form there, and
    # the program holds no kernel.  The window of 12 makes each layer's
    # keys and values a ring of 12 rows (written at the position mod 12),
    # which speculation cannot serve: with drafts the same model attends
    # over the whole slot.
    model, params = _row_write_lm(head_dim)
    if spec_k:
        model = model.clone(window=None)
    xla_toks, xla_eng = _serve(model, params, spec_k=spec_k)
    assert _pooled_kernels(xla_eng, spec_k) == []       # the CPU: vmap

    rows_rule()
    toks, eng = _serve(model, params, spec_k=spec_k)
    assert toks == xla_toks
    assert eng.stats["pool_calls"] > eng.stats["prefills"]
    want = ["kv.write_rows"] * 4 if head_dim == 128 else []
    assert _pooled_kernels(eng, spec_k) == want


_EXPERT_LMS = {}


def _expert_lm(held):
    # gqa_lm's attention beside an expert layer of 8 SwiGLU experts, top-2,
    # of which ``held`` exist here (programs are keyed by the model)
    if held not in _EXPERT_LMS:
        model = TransformerLM(vocab=43, embed=32, depth=2, num_heads=4,
                              num_kv_heads=2, head_dim=8, max_len=96,
                              pos_emb="rope", n_experts=8, moe_k=2,
                              experts_held=held, expert_width=24,
                              expert_act="silu", dtype=jnp.float32)
        params = jax.jit(model.init)(jax.random.PRNGKey(5),
                                     jnp.zeros((1, 4), jnp.int32))["params"]
        _EXPERT_LMS[held] = model, params
    return _EXPERT_LMS[held]


@pytest.mark.parametrize("held", [None, (2, 4)])
@pytest.mark.parametrize("spec_k", [0, 4])
def test_kernel_experts_serve_the_same_tokens_and_are_counted(
        experts_rule, tmp_path, spec_k, held):
    # The pooled step's expert layers through ops/moe.decode_experts,
    # interpreted here, as on the chip (parallel/expert.experts_read_once):
    # the served tokens are the grouped path's, and the pooled step the
    # engine dispatched holds the kernel once an expert layer.  Where the
    # rule does not hold (the CPU's answer; a draft's verify, [slots, K+1]
    # tokens a call) the program holds none (the grouped path's own
    # kernels, moe.gather and moe.combine, instead).
    from torchmpi_tpu import obs

    model, params = _expert_lm(held)
    grouped_toks, grouped_eng = _serve(model, params, spec_k=spec_k)
    read_once = "moe.decode_experts"
    assert read_once not in _pooled_kernels(grouped_eng, spec_k)  # the CPU

    experts_rule()
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1, obs="metrics",
                        obs_dir=str(tmp_path / "obs")))
    try:
        obs.reset()
        toks, eng = _serve(model, params, spec_k=spec_k)
        assert toks == grouped_toks
        steps = eng.stats["steps"]
        assert steps > 0
        assert _pooled_kernels(eng, spec_k).count(read_once) == (
            0 if spec_k else 2)
        assert obs.registry().counter_total(
            "tm_moe_decode_steps_total") == (0 if spec_k else steps)
    finally:
        mpi.stop()


def test_prefix_hit_extend_stays_dense_and_uncounted(gqa_lm, chip_rule):
    # A prefix hit's extend (a suffix at a per-row depth, T > 1) attends
    # against the CACHE: dense on every platform, its program holds no
    # kernel, and the tokens are the full kernel prefill's.  (Without the
    # window: a ring has no token axis to cut prefix fragments from.)
    model, params = gqa_lm
    model = model.clone(window=None)
    chip_rule()
    miss_toks, _ = _serve(model, params)
    toks, eng = _serve(model, params, prefix_cache=16, prefix_block=8)
    assert toks == miss_toks
    assert eng.stats["prefix_hits"] == 3
    assert eng.stats["prefill_tokens"] > 32
    # the one miss's prefill (19 tokens in a bucket of 32) takes the kernel,
    # an extend of 16 shared tokens' suffix does not
    assert _prefill_kernels(eng, 32) == ["flash.fwd"] * 2
    assert _prefill_kernels(eng, 32, depth=16) == []


# ---------------------------------------------------------------------------
# The pool is donated (PR 34): every program that takes it takes it over,
# the engine holds the one reference, and a failure leaves it serving or dead
# ---------------------------------------------------------------------------


def _alive(tree):
    return not any(leaf.is_deleted() for leaf in jax.tree.leaves(tree))


def _model_draft():
    """A small same-vocabulary LM as the speculative proposer."""
    draft_model = TransformerLM(vocab=VOCAB, embed=16, depth=1,
                                num_heads=2, head_dim=8, max_len=32,
                                pos_emb="rope")
    draft_params = draft_model.init(
        jax.random.PRNGKey(9), jnp.zeros((1, 4), jnp.int32))["params"]
    return serving.ModelDraft(draft_model, draft_params)


def _pool_trace(lm, kind):
    """One finished trace of ``kind`` -> (requests, their prompts, engine)."""
    model, params = lm
    prompts = _prompts(6, seed=61)
    kw = {}
    if kind == "bucketed":
        kw = dict(prefill_bucket=8)
    elif kind == "spec_ngram":
        kw = dict(spec_k=3)
    elif kind == "spec_model":
        kw = dict(spec_k=2, draft=_model_draft())
    elif kind == "prefix":
        prompts = np.concatenate(
            [np.tile(_prompts(1, tp=16, seed=62), (6, 1)), prompts], axis=1)
        kw = dict(prefix_cache=16, prefix_block=8)
    reqs = [serving.Request(f"d{i}", prompts[i], max_new=7,
                            arrival_s=0.001 * i) for i in range(6)]
    return reqs, prompts, _run_server(model, params, reqs, **kw)


@pytest.mark.parametrize(
    "kind", ["plain", "bucketed", "spec_ngram", "spec_model", "prefix"])
def test_every_pooled_call_takes_the_pool_over(lm, kind):
    model, params = lm
    reqs, prompts, eng = _pool_trace(lm, kind)
    # the served tokens are what they were before the pool was donated
    for i, req in enumerate(reqs):
        assert req.tokens == _offline(model, params, prompts[i], 7).tolist()
    # steps, verifies and slot writes: each handed the pool over, each got
    # it back, and the one left is alive
    assert eng.stats["pool_calls"] == \
        eng.stats["prefills"] + eng.stats["steps"] > 0
    assert eng.stats["pool_donated"] == eng.stats["pool_calls"]
    assert _alive(eng._cache)
    if kind == "prefix":
        # ONE zero row served every hit's assembly and is still there
        assert eng.stats["prefix_hits"] == 5 and _alive(eng._row_zero)
    if kind == "spec_model":   # the draft's own pool, rebound the same way
        assert _alive(eng._draft._cache)


def test_pool_counters_are_mirrored_in_the_registry(lm, tmp_path):
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1, obs="metrics", obs_dir=str(tmp_path)))
    try:
        from torchmpi_tpu import obs

        obs.reset()
        _, _, eng = _pool_trace(lm, "spec_ngram")
        reg = obs.registry()
        assert reg.counter("tm_serving_pool_calls_total",
                           replica=eng.name) == eng.stats["pool_calls"] > 0
        assert reg.counter("tm_serving_pool_donated_total",
                           replica=eng.name) == eng.stats["pool_donated"]
    finally:
        mpi.stop()


def test_a_backend_that_declines_the_donation_reads_zero(lm, monkeypatch):
    # the share is a measurement, not a constant: with the step's program
    # jitted WITHOUT donation the pool that went in is still there
    import importlib

    gen = importlib.import_module("torchmpi_tpu.models.generate")
    model, params = lm
    monkeypatch.setattr(gen, "_slot_step_jit", jax.jit(
        gen._slot_step_jit.__wrapped__, static_argnums=(0,)))
    engine = serving.ReplicaEngine(model, params, slots=2, slot_tokens=32)
    prompt = _prompts(1, seed=63)[0]
    req = serving.Request("x", prompt, max_new=5)
    engine.admit(req)
    while engine.active:
        engine.step()
    assert engine.stats["pool_calls"] == 5           # 1 write, 4 steps
    assert engine.stats["pool_donated"] == 1         # the write alone


def _drive(engine, req):
    """Admit ``req`` and step the engine dry -> the request's tokens."""
    sess, done = engine.admit(req)
    while not done and engine.active:
        _, finished = engine.step()
        done = sess in finished
    return sess.emitted


def test_prefill_that_raises_leaves_the_engine_serving(lm, monkeypatch):
    import torchmpi_tpu.serving.engine as eng_mod

    model, params = lm
    prompts = _prompts(3, seed=64)
    engine = serving.ReplicaEngine(model, params, slots=2, slot_tokens=32)
    first, _ = engine.admit(serving.Request("a", prompts[0], max_new=6))
    engine.step()
    calls = engine.stats["pool_calls"]
    with monkeypatch.context() as m:
        m.setattr(eng_mod, "slot_prefill",
                  lambda *a, **k: (_ for _ in ()).throw(
                      RuntimeError("exploded")))
        with pytest.raises(RuntimeError, match="exploded"):
            engine.admit(serving.Request("b", prompts[1], max_new=6))
    # the prefill failed BEFORE the pool was handed to anything
    assert not engine.dead and engine.pool.free_count == 1
    assert engine.stats["pool_calls"] == calls
    assert _drive(engine, serving.Request("c", prompts[2], max_new=6)) == \
        _offline(model, params, prompts[2], 6).tolist()
    assert first.emitted == _offline(model, params, prompts[0], 6).tolist()


@pytest.mark.parametrize("program", ["slot_decode_step", "slot_write"])
@pytest.mark.parametrize("when", ["at_dispatch", "after_it_took_the_pool"])
def test_pooled_program_that_raises(lm, monkeypatch, program, when):
    # A program that fails at dispatch has consumed nothing: the engine
    # keeps its pool and serves on.  One that fails after it took the pool
    # leaves nothing to decode from: the replica reads as dead, it does not
    # decode from a deleted buffer.  Either way an admission's slot is freed.
    import torchmpi_tpu.serving.engine as eng_mod

    model, params = lm
    prompts = _prompts(2, seed=65)
    engine = serving.ReplicaEngine(model, params, slots=2, slot_tokens=32)
    first, _ = engine.admit(serving.Request("a", prompts[0], max_new=6))
    calls = engine.stats["pool_calls"]

    def fails(*args, **kwargs):
        pool = args[0] if program == "slot_write" else args[2]
        if when == "after_it_took_the_pool":
            for leaf in jax.tree.leaves(pool):
                leaf.delete()
        raise RuntimeError("exploded")

    with monkeypatch.context() as m:
        m.setattr(eng_mod, program, fails)
        with pytest.raises(RuntimeError, match="exploded"):
            if program == "slot_write":
                engine.admit(serving.Request("b", prompts[1], max_new=6))
            else:
                engine.step()
    assert engine.stats["pool_calls"] == calls      # a finished call counts
    assert engine.pool.free_count == 1              # "a" holds the other
    if when == "at_dispatch":
        assert not engine.dead
        while engine.active:
            engine.step()
        assert first.emitted == _offline(model, params, prompts[0],
                                         6).tolist()
        assert _drive(engine, serving.Request("b", prompts[1], max_new=6)) \
            == _offline(model, params, prompts[1], 6).tolist()
    else:
        assert engine.dead and not engine.has_capacity()
        with pytest.raises(RuntimeError, match="is dead"):
            engine.step()
        with pytest.raises(RuntimeError, match="is dead"):
            engine.admit(serving.Request("c", prompts[1], max_new=6))
        assert [s.request.rid for s in engine.drain()] == ["a"]


# ---------------------------------------------------------------------------
# The tier's own spans on the profiler's clock (``tm.serve.*``)
# ---------------------------------------------------------------------------

ADMIT_CHILDREN = [s for s in SPANS if s.startswith("tm.serve.admit.")]
Span = collections.namedtuple("Span", "name start end stats")


def _traced(trace_dir, body):
    """Run ``body`` under ``jax.profiler`` -> the host plane's
    ``tm.serve.*`` events in order of their start (a parent before its
    first child), as ``[Span]``."""
    import glob

    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    spans = [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                  dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("tm.serve.")]
    assert {s.name for s in spans} <= set(SPANS)
    return sorted(spans, key=lambda s: (s.start, -s.end))


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _inside(spans, parent):
    """The spans that lie inside ``parent``, itself left out."""
    return [s for s in spans if s is not parent
            and parent.start <= s.start and s.end <= parent.end]


def _children(spans, parent):
    """Those inside ``parent`` that no other span inside it holds."""
    inside = _inside(spans, parent)
    return [s for s in inside
            if not any(s in _inside(inside, other) for other in inside)]


def _in_order(spans):
    return all(a.end <= b.start for a, b in zip(spans, spans[1:]))


def _steps_dispatched(kids):
    """A plain step call's phases, in order: the operands and the dispatch
    of each pooled step it dispatched (none, one, or two on an idle pool),
    then the read of the oldest unread one and its book -> how many it
    dispatched."""
    names = [k.name for k in kids]
    n = (len(names) - 2) // 2
    assert n <= 2 and names == (
        ["tm.serve.step.operands", "tm.serve.step.dispatch"] * n
        + ["tm.serve.step.read", "tm.serve.step.book"]), names
    assert _in_order(kids)
    return n


def _span_server(lm, **kw):
    """A one-replica server whose programs are compiled: what it is traced
    serving afterwards is the steady loop."""
    model, params = lm
    kw = {"slots": 2, "slot_tokens": 32, "prefill_bucket": 8, **kw}
    srv = serving.Server(model, params, replicas=1, **kw)
    warm = [serving.Request(f"warm{i}", p, max_new=3)
            for i, p in enumerate(_prompts(2, seed=70))]
    srv.run_trace(warm, tick_seconds=0.001)
    return srv, srv.router.replicas[0]


def test_a_served_trace_writes_the_span_tree(lm, tmp_path):
    model, params = lm
    srv, eng = _span_server(lm)
    prompts = _prompts(5, seed=71)
    lens = [4, 1, 6, 3, 5]          # "s1" is done at its admission
    reqs = [serving.Request(f"s{i}", prompts[i], max_new=lens[i],
                            arrival_s=0.002 * i) for i in range(5)]
    ticks, steps = srv._n_ticks, eng.stats["steps"]
    spans = _traced(tmp_path, lambda: srv.run_trace(reqs,
                                                    tick_seconds=0.001))
    for i, req in enumerate(reqs):      # the tokens are what they were
        assert req.tokens == _offline(model, params, prompts[i],
                                      lens[i]).tolist()
    # one tick a call of _tick, counted by the server from its first on
    tick_spans = _named(spans, "tm.serve.tick")
    assert [t.stats["tick"] for t in tick_spans] == list(
        range(ticks, srv._n_ticks))
    assert len(tick_spans) > 5 and _in_order(tick_spans)
    assert tick_spans[0].stats["pending"] == 1      # "s0" was queued
    # one admission a request, with its rid, the slot it got, the replica
    admits = _named(spans, "tm.serve.admit")
    assert [a.stats["rid"] for a in admits] == [f"s{i}" for i in range(5)]
    assert all(a.stats["slot"] in (0, 1) and a.stats["replica"] == eng.name
               and a.stats["prompt_tokens"] == 5 for a in admits)
    padded, _ = eng._pad_prompt(prompts[:1])
    for a in admits:
        kids = _children(spans, a)
        assert [k.name for k in kids] == ADMIT_CHILDREN and _in_order(kids)
        assert _inside(spans, a) == kids
        assert kids[1].stats == {"padded_tokens": padded.shape[1]}
    # one step a call of step(), with the pooled steps the engine counted
    # before it and its open sessions; each dispatches what it counts (two
    # the first, on the idle pool) and reads one
    step_spans = _named(spans, "tm.serve.step")
    dispatched = []
    for s in step_spans:
        kids = _children(spans, s)
        dispatched.append(_steps_dispatched(kids))
        assert _inside(spans, s) == kids
    assert dispatched[0] == 2
    assert [s.stats["step"] for s in step_spans] == [
        steps + sum(dispatched[:i]) for i in range(len(step_spans))]
    assert steps + sum(dispatched) == eng.stats["steps"]
    assert all(s.stats["live"] in (1, 2) and s.stats["spec"] == 0
               and s.stats["replica"] == eng.name for s in step_spans)
    assert max(s.stats["live"] for s in step_spans) == 2
    # a tick holds its admissions and then its one step, nothing else
    for t in tick_spans:
        kids = _children(spans, t)
        assert [k.name for k in kids] == \
            ["tm.serve.admit"] * (len(kids) - 1) + ["tm.serve.step"] \
            or {k.name for k in kids} <= {"tm.serve.admit"}
    assert sum(len(_children(spans, t)) for t in tick_spans) == \
        len(admits) + len(step_spans)
    # a request's path: its gate, then the admission of the same rid
    gates = _named(spans, "tm.serve.gate")
    assert [g.stats["rid"] for g in gates] == [f"s{i}" for i in range(5)]
    for g, a in zip(gates, admits):
        assert g.end <= a.start and not _inside(spans, g)
    assert {s.name for s in spans} == set(SPANS) - {"tm.serve.step.draft"}


def test_a_tick_with_no_live_session_writes_no_step_span(lm, tmp_path):
    srv, eng = _span_server(lm)
    reqs = [serving.Request(f"o{i}", p, max_new=1)
            for i, p in enumerate(_prompts(3, seed=72))]
    steps = eng.stats["steps"]
    spans = _traced(tmp_path, lambda: srv.run_trace(reqs,
                                                    tick_seconds=0.001))
    assert [len(r.tokens) for r in reqs] == [1, 1, 1]
    assert eng.stats["steps"] == steps
    assert len(_named(spans, "tm.serve.admit")) == 3
    assert not _named(spans, "tm.serve.step")
    assert not [s for s in spans if s.name.startswith("tm.serve.step.")]


def test_a_request_that_gets_no_slot_opens_no_admit_span(lm, tmp_path):
    model, params = lm
    engine = serving.ReplicaEngine(model, params, slots=1, slot_tokens=16)
    prompts = _prompts(3, seed=73)
    out = {}

    def body():
        out["first"] = engine.admit(
            serving.Request("fits", prompts[0], max_new=4))
        # the pool is full: raced, the caller retries next tick
        out["raced"] = engine.admit(
            serving.Request("raced", prompts[1], max_new=4))
        with pytest.raises(serving.RequestRejected):
            engine.admit(serving.Request("big", prompts[2], max_new=12))

    spans = _traced(tmp_path, body)
    assert out["first"] is not None and out["raced"] is None
    assert [a.stats["rid"] for a in _named(spans, "tm.serve.admit")] == [
        "fits"]
    assert {s.name for s in spans} == {"tm.serve.admit", *ADMIT_CHILDREN}


def test_a_prefill_that_raises_closes_its_spans(lm, monkeypatch, tmp_path):
    import torchmpi_tpu.serving.engine as eng_mod

    model, params = lm
    prompts = _prompts(2, seed=74)
    engine = serving.ReplicaEngine(model, params, slots=1, slot_tokens=32)
    out = {}

    def body():
        with monkeypatch.context() as m:
            m.setattr(eng_mod, "slot_prefill",
                      lambda *a, **k: (_ for _ in ()).throw(
                          RuntimeError("exploded")))
            with pytest.raises(RuntimeError, match="exploded"):
                engine.admit(serving.Request("bad", prompts[0], max_new=4))
        out["free"] = engine.pool.free_count
        out["tokens"] = _drive(engine, serving.Request("good", prompts[1],
                                                       max_new=4))

    spans = _traced(tmp_path, body)
    # the slot came back and the engine serves on
    assert out["free"] == 1 and not engine.dead
    assert out["tokens"] == _offline(model, params, prompts[1], 4).tolist()
    bad, good = _named(spans, "tm.serve.admit")
    assert (bad.stats["rid"], good.stats["rid"]) == ("bad", "good")
    # the failed admission's spans are closed where it failed ...
    assert [k.name for k in _inside(spans, bad)] == ADMIT_CHILDREN[:2]
    assert bad.end <= good.start
    # ... and the next one writes all five
    assert [k.name for k in _children(spans, good)] == ADMIT_CHILDREN


@pytest.mark.parametrize("draft", ["ngram", "model"])
def test_a_speculative_step_writes_spec_and_its_draft_span(lm, tmp_path,
                                                           draft):
    model, params = lm
    kw = {"draft": _model_draft()} if draft == "model" else {}
    srv, eng = _span_server(lm, spec_k=3, **kw)
    prompts = _prompts(3, seed=75)
    reqs = [serving.Request(f"k{i}", prompts[i], max_new=9)
            for i in range(3)]
    spans = _traced(tmp_path, lambda: srv.run_trace(reqs,
                                                    tick_seconds=0.001))
    for i, req in enumerate(reqs):
        assert req.tokens == _offline(model, params, prompts[i], 9).tolist()
    step_spans = _named(spans, "tm.serve.step")
    assert step_spans and all(s.stats["spec"] == 1 for s in step_spans)
    for s in step_spans:
        kids = _children(spans, s)
        assert [k.name for k in kids] == [
            f"tm.serve.step.{k}" for k in (
                "operands", "draft", "operands", "dispatch", "read",
                "book")] and _in_order(kids)
        assert 0 <= kids[1].stats["k"] <= 3
    assert max(_named(spans, "tm.serve.step.draft"),
               key=lambda d: d.stats["k"]).stats["k"] == 3
    assert {s.name for s in spans} == set(SPANS)


def test_the_span_names_are_one_list_held_by_the_code_and_the_docs():
    """``engine.SPANS`` is THE list: every ``tm.serve.*`` literal under
    ``serving/`` and every one in docs/OBSERVABILITY.md is on it, each at
    least once (a parent before its children)."""
    import glob
    import re

    token = re.compile(r"tm\.serve(?:\.[a-z_]+)+")
    code = set()
    for path in glob.glob(os.path.join(_REPO, "torchmpi_tpu", "serving",
                                       "*.py")):
        with open(path) as f:
            code |= set(token.findall(f.read()))
    with open(os.path.join(_REPO, "docs", "OBSERVABILITY.md")) as f:
        documented = set(token.findall(f.read()))
    assert code == documented == set(SPANS)
    assert len(set(SPANS)) == len(SPANS) == 14
    for i, name in enumerate(SPANS):
        parent = name.rsplit(".", 1)[0]
        assert parent == "tm.serve" or parent in SPANS[:i]
