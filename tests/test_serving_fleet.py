"""Serving fleet at scale (ISSUE 20): radix prefix-sharing KV cache +
SLO-driven admission and autoscaling (torchmpi_tpu/serving/
{prefix_cache,fleet}.py; docs/SERVING.md).

Covers: the refcounted block ledger on :class:`SlotPool` (alloc / pin /
release edges, capacity, monotonic never-reissued ids), the radix
:class:`PrefixCache` (block-aligned longest match, LRU eviction that
never touches a held block or an interior node, best-effort insert),
bitwise token streams with the cache on — greedy equal to the offline
``generate`` oracle and sampled equal to the cache-off serving stream
(the fold_in schedule is untouched), INCLUDING across a mid-stream
replica kill re-route — the typed :class:`AdmissionRejected` shed path
with its ``tm_serving_{shed,admitted}_total`` counters and ``obs_tool
slo`` fleet line, the ``serving.admit`` chaos site (drop => shed, lint
flags corrupt at the payload-free door), and the
:class:`FleetController` scale-up/scale-down loop (drain + retire,
retired replicas never auto-readmitted, streams token-exact across the
scale events).
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import torchmpi_tpu as mpi
from torchmpi_tpu import serving
from torchmpi_tpu.models import TransformerLM, generate
from torchmpi_tpu.serving import fleet
from torchmpi_tpu.serving.prefix_cache import PrefixCache
from torchmpi_tpu.serving.slots import SlotPool

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB = 41


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab=VOCAB, embed=32, depth=2, num_heads=4,
                          head_dim=8, max_len=64, pos_emb="rope")
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _offline(model, params, prompt, steps):
    out = np.asarray(generate(model, params,
                              np.asarray(prompt).reshape(1, -1),
                              steps=steps))
    return out[0, len(prompt):].tolist()


def _shared_prefix_reqs(n=6, shared_len=17, seed=0, max_new=6):
    """n requests opening with the same shared_len tokens, alternating
    greedy / sampled (per-request seeds).  Tails differ in CONTENT but
    share one length, so the whole set costs a single extend compile
    (shape-keyed executables, same reason the bench buckets prefill)."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, VOCAB, size=shared_len)
    reqs = []
    for i in range(n):
        tail = rng.integers(1, VOCAB, size=3)
        prompt = np.concatenate([shared, tail]).astype(np.int32)
        reqs.append(serving.Request(
            f"q{i}", prompt, max_new=max_new, arrival_s=0.0005 * i,
            temperature=0.8 if i % 2 else None,
            top_k=12 if i % 2 else None, seed=7 + i))
    return reqs


def _clone(reqs):
    return [serving.Request(r.rid, r.prompt, r.max_new,
                            arrival_s=r.arrival_s,
                            temperature=r.temperature, top_k=r.top_k,
                            top_p=r.top_p, seed=r.seed)
            for r in reqs]


def _run(model, params, reqs, **kw):
    srv = serving.Server(model, params, replicas=1, slots=4,
                         slot_tokens=64, **kw)
    out = _clone(reqs)
    done = srv.run_trace(out, tick_seconds=0.001)
    assert len(done) == len(out)
    return {r.rid: r.tokens for r in out}, srv


# ---------------------------------------------------------------------------
# SlotPool block ledger: the refcount protocol
# ---------------------------------------------------------------------------


def test_block_ledger_refcount_protocol():
    pool = SlotPool(2, 16, prefix_blocks=3)
    a = pool.block_alloc()
    b = pool.block_alloc()
    assert a != b and pool.blocks_in_use == 2
    assert pool.block_refcount(a) == 1  # born with the tree's own ref
    assert pool.block_ref(a) == 2       # a live slot pins it
    assert pool.block_ref(a) == 3       # a second slot shares it
    assert pool.block_deref(a) == 2
    assert pool.block_deref(a) == 1     # back to idle, still cached
    assert pool.block_deref(a) == 0     # eviction: entry is gone
    assert pool.block_refcount(a) == 0 and pool.blocks_in_use == 1
    with pytest.raises(ValueError, match="not live"):
        pool.block_deref(a)  # double-deref past zero
    with pytest.raises(ValueError, match="not live"):
        pool.block_ref(99)   # never allocated
    pool.block_deref(b)
    assert pool.blocks_in_use == 0


def test_block_ledger_capacity_and_monotonic_ids():
    pool = SlotPool(1, 8, prefix_blocks=2)
    a, b = pool.block_alloc(), pool.block_alloc()
    assert pool.block_alloc() is None  # capacity, not an error
    pool.block_deref(a)
    c = pool.block_alloc()
    assert c not in (a, b)  # ids are never reissued (ABA hazard)
    with pytest.raises(ValueError, match="not live"):
        pool.block_ref(a)   # the stale id fails loudly
    assert SlotPool(1, 8).prefix_blocks == 0  # ledger off by default
    assert SlotPool(1, 8).block_alloc() is None
    with pytest.raises(ValueError):
        SlotPool(1, 8, prefix_blocks=-1)


# ---------------------------------------------------------------------------
# PrefixCache: radix match / insert / LRU eviction (pure bookkeeping)
# ---------------------------------------------------------------------------


def _frag(i):
    return {"k": np.full((1, 4, 2), i, np.float32),
            "v": np.full((1, 4, 2), -i, np.float32)}


def test_prefix_cache_match_insert_lru():
    pool = SlotPool(1, 16, prefix_blocks=8)
    pc = PrefixCache(pool, block_tokens=4)
    toks = list(range(10))
    chain, n_new, n_evicted = pc.insert(toks, 10, _frag)
    # 10 tokens at B=4 -> 2 full blocks; the tail 2 stay uncached.
    assert len(chain) == 2 and n_new == 2 and n_evicted == 0
    assert pc.n_nodes == 2 == pool.blocks_in_use
    assert chain[1].parent is chain[0] and chain[0].parent is None

    # Longest block-aligned match — capped so >= 1 suffix token remains.
    assert len(pc.match(toks)) == 2
    assert len(pc.match(toks[:8])) == 1  # 8 tokens: 1 block + 1 spare
    assert len(pc.match(toks[:4] + [99, 98, 97, 96, 95])) == 1  # fork
    assert pc.match([99, 98, 97]) == []  # miss counted
    assert pc.stats["hits"] == 3 and pc.stats["misses"] == 1
    assert pc.stats["tokens_saved"] == 2 * 4 + 4 + 4
    assert pc.stats["bytes_saved"] > 0

    # Re-insert reuses the nodes — no new blocks, same ledger.
    chain2, n_new2, _ = pc.insert(toks, 10, _frag)
    assert n_new2 == 0 and [n.bid for n in chain2] == \
        [n.bid for n in chain]
    assert pool.blocks_in_use == 2


def test_prefix_cache_eviction_skips_held_and_interior():
    pool = SlotPool(1, 16, prefix_blocks=2)
    pc = PrefixCache(pool, block_tokens=4)
    (a_chain, _, _) = pc.insert([1] * 5, 5, _frag)   # 1 block
    (b_chain, _, _) = pc.insert([2] * 5, 5, _frag)   # ledger now full
    pc.match([1] * 5)  # touch A: B becomes the LRU leaf

    # C's insert must evict B (LRU idle leaf), never touched A.
    (c_chain, n_new, n_evicted) = pc.insert([3] * 5, 5, _frag)
    assert n_new == 1 and n_evicted == 1
    assert pc.match([2] * 5) == []      # B is gone
    assert len(pc.match([1] * 5)) == 1  # A survived

    # A held block (live-slot pin) is never evicted even as LRU.
    pc.pin(c_chain)
    pc.match([1] * 5)  # touch A again: C is LRU but held
    (d_chain, n_new, n_evicted) = pc.insert([4] * 5, 5, _frag)
    assert n_evicted == 1 and pc.match([1] * 5) == []  # A evicted
    assert len(pc.match([3] * 5)) == 1  # held C survived
    pc.release(c_chain)

    # Everything pinned: insert degrades to best-effort (no eviction,
    # partial chain), it never raises and never steals a held block.
    pc.pin(d_chain)
    pc.pin(pc.match([3] * 5))
    (e_chain, n_new, n_evicted) = pc.insert([5] * 5, 5, _frag)
    assert e_chain == [] and n_new == 0 and n_evicted == 0

    # Interior nodes are not evictable: a two-block chain with an idle
    # head but a HELD tail keeps the head (orphan prevention).
    pool2 = SlotPool(1, 16, prefix_blocks=2)
    pc2 = PrefixCache(pool2, block_tokens=4)
    (deep, _, _) = pc2.insert([7] * 9, 9, _frag)  # 2 blocks: head+leaf
    pc2.pin(deep[1:])  # hold only the LEAF
    (f_chain, _, n_evicted) = pc2.insert([8] * 5, 5, _frag)
    assert f_chain == [] and n_evicted == 0  # head is interior, safe
    assert len(pc2.match([7] * 9)) == 2


def test_prefix_cache_validation():
    with pytest.raises(ValueError, match="prefix_blocks"):
        PrefixCache(SlotPool(1, 16))  # no ledger configured
    with pytest.raises(ValueError, match="block_tokens"):
        PrefixCache(SlotPool(1, 16, prefix_blocks=2), block_tokens=0)
    with pytest.raises(ValueError, match="cannot exceed"):
        PrefixCache(SlotPool(1, 8, prefix_blocks=2), block_tokens=9)


# ---------------------------------------------------------------------------
# AdmissionController / FleetController: pure decision logic
# ---------------------------------------------------------------------------


def test_admission_controller_typed_shed():
    ac = fleet.AdmissionController(1000.0, window=8, min_samples=2)
    assert ac.armed
    ac.check("warm", 0)  # below min_samples: stays open
    ac.observe(0.0005)
    ac.observe(0.0006)
    ac.check("ok", 1)    # p95 600us < 1000us
    ac.observe(0.002)    # 2000us dominates the window p95
    with pytest.raises(fleet.AdmissionRejected) as ei:
        ac.check("r9", 3)
    e = ei.value
    assert e.rid == "r9" and e.reason == "slo"
    assert e.queue_depth == 3 and e.target_us == 1000.0
    assert e.p95_ttft_us >= 2000.0
    assert "p95 TTFT" in str(e) and "target 1000us" in str(e)
    assert ac.shed == 1 and ac.admitted == 2
    # Disarmed (slo <= 0) never sheds — the PR 17 behavior.
    off = fleet.AdmissionController(0.0)
    assert not off.armed
    for _ in range(20):
        off.observe(10.0)
        off.check("x", 50)
    assert off.shed == 0


def test_fleet_controller_validation_and_streaks():
    class StubRouter:
        def __init__(self):
            self.replicas = []

        def live(self):
            return [r for r in self.replicas if not r.dead]

        def add(self, r):
            self.replicas.append(r)

        def retire(self, r):
            r.dead = r.retired = True

    class StubEngine:
        def __init__(self, name):
            self.name = name
            self.dead = False
            self.active = 0

    with pytest.raises(ValueError, match="max_replicas"):
        fleet.FleetController(StubRouter(), engine_factory=StubEngine,
                              max_replicas=0)
    with pytest.raises(ValueError, match="min_replicas"):
        fleet.FleetController(StubRouter(), engine_factory=StubEngine,
                              max_replicas=2, min_replicas=3)
    with pytest.raises(ValueError, match="high_water"):
        fleet.FleetController(StubRouter(), engine_factory=StubEngine,
                              max_replicas=2, high_water=1, low_water=1)

    router = StubRouter()
    router.add(StubEngine("r0"))
    drained = []
    fc = fleet.FleetController(
        router, engine_factory=StubEngine, max_replicas=2,
        high_water=4, low_water=0, sustain=2,
        drain=lambda eng, pending: drained.append(eng.name))
    assert fc.tick(9, []) is None           # 1 hot tick: not sustained
    assert fc.tick(2, []) is None           # streak broken
    assert fc.tick(9, []) is None
    assert fc.tick(9, []) == "scale_up"     # sustained: acts
    assert [r.name for r in router.live()] == ["r0", "scale1"]
    assert fc.tick(9, []) is None           # at max_replicas: holds
    assert fc.tick(0, []) is None
    assert fc.tick(0, []) == "scale_down"   # drains then retires
    assert drained == ["r0"]                # least-loaded victim
    assert router.replicas[0].retired
    assert fc.tick(0, []) is None           # at min_replicas: holds
    assert fc.events == ["scale_up", "scale_down"]


# ---------------------------------------------------------------------------
# Prefix cache end to end: bitwise, shared pins, no leaks
# ---------------------------------------------------------------------------


def test_prefix_hit_bitwise_and_prefill_win(lm):
    """Cache on vs off: greedy streams equal the offline ``generate``
    oracle, sampled streams equal the cache-off serving stream (the
    fold_in schedule is untouched), hits land, prefilled tokens drop,
    and the ledger comes back all-idle."""
    model, params = lm
    reqs = _shared_prefix_reqs()
    off_toks, off_srv = _run(model, params, reqs)
    on_toks, on_srv = _run(model, params, reqs, prefix_cache=16,
                           prefix_block=8)
    assert on_toks == off_toks
    for r in reqs:
        if r.temperature is None:
            assert on_toks[r.rid] == _offline(model, params, r.prompt,
                                              r.max_new)
    eng = on_srv.router.replicas[0]
    assert eng.stats["prefix_hits"] > 0
    assert eng.stats["prefill_tokens"] < \
        off_srv.router.replicas[0].stats["prefill_tokens"]
    assert eng.pool.blocks_in_use == eng._prefix.n_nodes
    for node in eng._prefix._nodes:
        assert eng.pool.block_refcount(node.bid) == 1  # no leaked pins


def test_shared_blocks_pinned_during_decode_released_after(lm):
    """Copy-on-extend accounting: two in-flight sessions sharing a
    prefix hold the same blocks (refcount 3 = tree + both), the shared
    fragments are never mutated by either session's decode, and
    retirement returns every block to exactly the tree's own reference
    — across slot reuse, with no drift."""
    model, params = lm
    eng = serving.ReplicaEngine(model, params, slots=2, slot_tokens=64,
                                prefix_cache=8, prefix_block=8)
    rng = np.random.default_rng(3)
    shared = rng.integers(1, VOCAB, size=16)
    pa = np.concatenate([shared, rng.integers(1, VOCAB, size=3)])
    pb = np.concatenate([shared, rng.integers(1, VOCAB, size=4)])

    sess_a, done = eng.admit(serving.Request("a", pa, max_new=8))
    assert not done
    shared_chain = sess_a.prefix_chain[:2]  # the 16 shared tokens
    assert len(shared_chain) == 2
    frag_before = [np.asarray(jax.tree_util.tree_leaves(n.frag)[0])
                   for n in shared_chain]
    sess_b, done = eng.admit(serving.Request("b", pb, max_new=8))
    assert not done and eng.stats["prefix_hits"] == 1
    for node in shared_chain:
        assert eng.pool.block_refcount(node.bid) == 3  # tree + a + b

    while eng.active:
        eng.step()
    for node in shared_chain:
        assert eng.pool.block_refcount(node.bid) == 1  # both released
    for before, node in zip(frag_before, shared_chain):
        after = np.asarray(jax.tree_util.tree_leaves(node.frag)[0])
        assert np.array_equal(before, after)  # copy-on-extend: intact

    # Slot reuse: a second wave re-pins the SAME blocks and still
    # returns them — the ledger never drifts.
    eng.admit(serving.Request("c", pa, max_new=4))
    for node in shared_chain:
        assert eng.pool.block_refcount(node.bid) == 2
    while eng.active:
        eng.step()
    for node in shared_chain:
        assert eng.pool.block_refcount(node.bid) == 1
    assert eng.pool.in_use == 0


def test_prefix_cache_survives_replica_kill_bitwise(lm, tmp_path):
    """THE acceptance edge: a mid-trace replica hard-kill with the
    prefix cache ON — the re-routed sessions (greedy AND sampled) must
    finish bitwise-identical to the no-fault cache-off reference."""
    model, params = lm
    reqs = _shared_prefix_reqs(n=8, max_new=8)
    ref_toks, _ = _run(model, params, reqs)  # no faults, cache off

    plan = {"version": 1, "seed": 3, "note": "prefix kill",
            "rules": [{"site": "serving.replica", "kind": "fail",
                       "prob": 1.0, "after": 6, "max_hits": 1}]}
    plan_path = tmp_path / "plan.json"
    plan_path.write_text(json.dumps(plan))
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1, faults=str(plan_path)))
    try:
        from torchmpi_tpu import faults

        faults.ledger().clear()
        run = _clone(reqs)
        srv = serving.Server(model, params, replicas=2, slots=3,
                             slot_tokens=64, prefix_cache=16,
                             prefix_block=8)
        done = srv.run_trace(run, tick_seconds=0.005)
        assert len(done) == len(run)
        assert sum(1 for e in srv.router.replicas if e.dead) == 1
        assert sum(r.reroutes for r in run) > 0
        assert {r.rid: r.tokens for r in run} == ref_toks
        for eng in srv.router.replicas:
            if eng._prefix is None:
                continue
            for node in eng._prefix._nodes:  # drain released its pins
                assert eng.pool.block_refcount(node.bid) == 1
    finally:
        from torchmpi_tpu import faults

        faults.reset()
        mpi.stop()


@pytest.mark.slow
def test_tp_prefix_bitwise():
    """The SAME radix tree drives the TP list-of-(k, v) cache layout:
    sharded streams with the cache on equal the cache-off ones."""
    import importlib

    tpg = importlib.import_module("torchmpi_tpu.models.tp_generate")
    V = 64
    tparams = tpg.init_tp_lm(jax.random.PRNGKey(5), vocab=V, embed=32,
                             depth=2, num_heads=4, head_dim=8)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, V, size=17)
    reqs = []
    for i in range(6):
        tail = rng.integers(1, V, size=3 + i)
        reqs.append(serving.Request(
            f"q{i}", np.concatenate([shared, tail]).astype(np.int32),
            max_new=6, arrival_s=0.0,
            temperature=0.8 if i % 2 else None,
            top_k=12 if i % 2 else None, seed=7 + i))

    def run(**kw):
        srv = serving.Server.sharded(tparams, tp=2, num_heads=4,
                                     slot_tokens=64, replicas=1,
                                     slots=4, **kw)
        out = _clone(reqs)
        done = srv.run_trace(out, tick_seconds=0.001)
        assert len(done) == len(out)
        return {r.rid: r.tokens for r in out}, srv.router.replicas[0]

    off_toks, _ = run()
    on_toks, eng = run(prefix_cache=16, prefix_block=8)
    assert on_toks == off_toks
    assert eng.stats["prefix_hits"] > 0
    for node in eng._prefix._nodes:
        assert eng.pool.block_refcount(node.bid) == 1
    # tp_slot_decode consumes the cache it is given: the pool every step,
    # and in the extend the row ASSEMBLED for the hit, never the one zero
    # template behind every assembly
    assert eng.stats["pool_donated"] == eng.stats["pool_calls"] > 0
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(eng._row_zero))


# ---------------------------------------------------------------------------
# Admission gate: SLO shed, chaos drop at the door, counters, obs_tool
# ---------------------------------------------------------------------------


def _load_obs_tool():
    spec = importlib.util.spec_from_file_location(
        "_obs_tool_under_test",
        os.path.join(_REPO, "scripts", "obs_tool.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_slo_shed_counters_and_obs_tool_fleet_line(lm, tmp_path,
                                                   capsys):
    model, params = lm
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1, obs="metrics",
                        obs_dir=str(tmp_path)))
    try:
        from torchmpi_tpu import obs

        obs.reset()
        rng = np.random.default_rng(1)
        # arrivals spread until the gate has its samples: a request holds
        # its slot for the tick its last token is read in (the pipelined
        # step), four units of clock for four tokens
        reqs = [serving.Request(
            f"r{i}", rng.integers(1, VOCAB, size=8).astype(np.int32),
            max_new=4, arrival_s=i * 1.0) for i in range(40)]
        srv = serving.Server(model, params, replicas=1, slots=2,
                             slot_tokens=32, slo_ttft_us=1.0)
        done = srv.run_trace(reqs, unit_seconds=1.0)
        shed = [r for r in done if r.shed]
        served = [r for r in done if not r.shed]
        assert len(done) == 40 and shed and served
        for r in shed:
            assert "slo" in r.error and r.tokens == []
        reg = obs.registry()
        assert reg.counter_total("tm_serving_shed_total") == len(shed)
        assert reg.counter_total("tm_serving_admitted_total") == \
            len(served)
        paths = obs.dump(str(tmp_path))
        tool = _load_obs_tool()
        assert tool.main(["slo", paths[0]]) == 0
        out = capsys.readouterr().out
        assert "fleet:" in out and "shed=" in out
        assert "queue_depth" in out
    finally:
        mpi.stop()


def test_serving_admit_drop_fault_sheds(lm, tmp_path):
    """A chaos drop at the admission door is a SHED — typed reason on
    the request, counted, and the rest of the trace still completes
    bitwise."""
    model, params = lm
    plan = {"version": 1, "seed": 2, "note": "admit drop",
            "rules": [{"site": "serving.admit", "kind": "drop",
                       "prob": 1.0, "after": 2, "max_hits": 2}]}
    plan_path = tmp_path / "plan.json"
    plan_path.write_text(json.dumps(plan))
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1, faults=str(plan_path),
                        obs="metrics", obs_dir=str(tmp_path / "obs")))
    try:
        from torchmpi_tpu import faults, obs

        obs.reset()
        faults.ledger().clear()
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, VOCAB, size=6).astype(np.int32)
                   for _ in range(6)]
        reqs = [serving.Request(f"d{i}", prompts[i], max_new=4,
                                arrival_s=0.002 * i) for i in range(6)]
        srv = serving.Server(model, params, replicas=1, slots=3,
                             slot_tokens=32)
        done = srv.run_trace(reqs, tick_seconds=0.001)
        assert len(done) == 6
        shed = [r for r in done if r.shed]
        assert [r.rid for r in shed] == ["d2", "d3"]  # after=2, 2 hits
        for r in shed:
            assert "serving.admit" in r.error
        assert obs.registry().counter_total(
            "tm_serving_shed_total") == 2
        for r in done:
            if not r.shed:
                assert r.tokens == _offline(
                    model, params, r.prompt, r.max_new)
    finally:
        from torchmpi_tpu import faults

        faults.reset()
        mpi.stop()


def test_chaos_lint_flags_corrupt_at_admit(tmp_path):
    """``serving.admit`` is payload-free (nothing to corrupt at the
    door): the generic plan lint must flag corrupt/corrupt_silent rules
    there, and accept drop/fail."""
    from torchmpi_tpu.faults import inject

    assert "serving.admit" in inject.SITES
    assert "serving.admit" not in inject.PAYLOAD_SITES
    bad = inject.FaultPlan.from_json(
        {"version": 1, "seed": 0,
         "rules": [{"site": "serving.admit", "kind": "corrupt"}]})
    problems = inject.lint_plan(bad)
    assert any("no payload" in p for p in problems)
    good = inject.FaultPlan.from_json(
        {"version": 1, "seed": 0,
         "rules": [{"site": "serving.admit", "kind": "drop"}]})
    assert inject.lint_plan(good) == []

    # Same verdicts through the chaos_tool CLI (what CI runs).
    spec = importlib.util.spec_from_file_location(
        "_chaos_tool_under_test",
        os.path.join(_REPO, "scripts", "chaos_tool.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    bad_path = tmp_path / "bad.json"
    bad_path.write_text(json.dumps(
        {"version": 1, "seed": 0,
         "rules": [{"site": "serving.admit", "kind": "corrupt"}]}))
    assert tool.main(["lint", str(bad_path)]) == 1


# ---------------------------------------------------------------------------
# FleetController end to end: scale events, token-exact, no readmit
# ---------------------------------------------------------------------------


def test_autoscale_up_down_streams_exact_retired_stays_out(lm):
    model, params = lm
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, VOCAB, size=6).astype(np.int32)
               for _ in range(20)]
    reqs = [serving.Request(f"s{i}", prompts[i], max_new=6,
                            arrival_s=0.0005 * i) for i in range(20)]
    oracle = {f"s{i}": _offline(model, params, prompts[i], 6)
              for i in range(20)}

    def factory(name, _m=model, _p=params):
        return serving.ReplicaEngine(_m, _p, name=name, slots=2,
                                     slot_tokens=32)

    srv = serving.Server(model, params, replicas=1, slots=2,
                         slot_tokens=32, autoscale=3,
                         engine_factory=factory, scale_high_water=2,
                         scale_low_water=0, scale_sustain=2)
    done = srv.run_trace(reqs, tick_seconds=0.001)
    assert len(done) == 20
    assert "scale_up" in srv._fleet.events
    assert any(r.replica.startswith("scale") for r in reqs)
    for r in reqs:  # token-exact across every scale event + reroute
        assert r.tokens == oracle[r.rid], r.rid

    retired = [e for e in srv.router.replicas
               if getattr(e, "retired", False)]
    if "scale_down" in srv._fleet.events:
        assert retired  # the victim was drained, then retired
    for eng in retired:
        srv.router.readmit(eng)  # healed-ledger path must refuse it
        assert eng.dead and eng.retired
        assert eng not in srv.router.live()

    # Pre-built engines can't autoscale without a factory: loud error.
    with pytest.raises(ValueError, match="engine_factory"):
        serving.Server(model, params, replicas=1, slots=2,
                       slot_tokens=32, autoscale=2,
                       engines=[factory("pre0")])


# ---------------------------------------------------------------------------
# Config / runtime plumbing
# ---------------------------------------------------------------------------


def test_serving_fleet_config_fields_validate():
    mpi.init()
    cfg0 = mpi.runtime.effective_config()
    try:
        mpi.set_config(serving_prefix_cache=4, serving_autoscale=2,
                       serving_slo_ttft_us=1500.0)
        cfg = mpi.runtime.effective_config()
        assert cfg.serving_prefix_cache == 4
        assert cfg.serving_autoscale == 2
        assert cfg.serving_slo_ttft_us == 1500.0
        for bad in (dict(serving_prefix_cache=-1),
                    dict(serving_autoscale=-2),
                    dict(serving_slo_ttft_us=-0.5)):
            with pytest.raises(ValueError):
                mpi.set_config(**bad)
    finally:
        mpi.set_config(
            serving_prefix_cache=cfg0.serving_prefix_cache,
            serving_autoscale=cfg0.serving_autoscale,
            serving_slo_ttft_us=cfg0.serving_slo_ttft_us)
