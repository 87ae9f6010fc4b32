"""The served engine's one-deep decode pipeline (serving/engine.py;
docs/SERVING.md, "The one-deep pipeline").

Without a draft, ``ReplicaEngine.step()`` dispatches the next pooled step,
whose input tokens are the unread step's on the device, before it reads
that step and books its tokens to the sessions that owned its rows when it
went out.  Covered here: the streams stay the offline ones (greedy) and the
synchronous primitives' (sampled) over admissions mid-stream, budget and EOS
retirements and a slot an admission reuses before the lagged read; who gets
a row's token; ``steps_ahead`` / ``rows_past_end``; one executable for the
first and every later dispatch; a failed lagged read re-routes with the same
continuation; a bound draft keeps the synchronous order.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import torchmpi_tpu as mpi
from torchmpi_tpu import serving
from torchmpi_tpu.models import TransformerLM, generate
from torchmpi_tpu.models.generate import slot_decode_step, slot_prefill, \
    slot_write

VOCAB = 37
SLOT_TOKENS = 32


@pytest.fixture(scope="module")
def lm():
    # sizes no other test module uses: the jitted slot_* programs are keyed
    # by the model, so the executable counts below are this module's own
    model = TransformerLM(vocab=VOCAB, embed=24, depth=2, num_heads=3,
                          head_dim=8, max_len=64, pos_emb="rope")
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _prompts(n, tp=6, seed=0):
    return np.random.RandomState(seed).randint(
        0, VOCAB, size=(n, tp)).astype(np.int32)


def _offline(model, params, prompt, steps, eos_id=None):
    """The static offline oracle (greedy): ``generate`` on [1, Tp]."""
    out = np.asarray(generate(model, params, prompt.reshape(1, -1),
                              steps=steps, eos_id=eos_id))
    return out[0, prompt.size:].tolist()


def _synchronous(model, params, req):
    """The request served alone through the slot primitives in the order
    dispatch-then-read, one step at a time: prefill, the write into a
    one-slot pool, then one pooled step a token with the sampling key of
    (seed, token index) -- the engine's contract, without its pipeline."""
    dmodel = model.clone(decode=True, max_len=SLOT_TOKENS)
    t = float(req.temperature or 0.0)
    knobs = (t, int(req.top_k or 0), float(req.top_p or 2.0)) if t > 0 \
        else (0.0, 0, 2.0)

    def sampling(i):
        return (jnp.asarray([req.seed], jnp.uint32),
                jnp.asarray([i], jnp.int32),
                jnp.asarray([knobs[0]], jnp.float32),
                jnp.asarray([knobs[1]], jnp.int32),
                jnp.asarray([knobs[2]], jnp.float32))

    one, first = slot_prefill(dmodel, params, req.prompt.reshape(1, -1),
                              sampling=sampling(0))
    pool = jax.tree.map(
        lambda s: jnp.zeros(s.shape if s.ndim else (), s.dtype), one)
    pool = slot_write(pool, one, 0)
    toks, pos = [int(np.asarray(first)[0])], req.prompt.size
    while len(toks) < req.max_new and toks[-1] != req.eos_id:
        pool, nxt = slot_decode_step(
            dmodel, params, pool, np.asarray([toks[-1]], np.int32),
            np.asarray([pos], np.int32), sampling=sampling(len(toks)))
        toks.append(int(np.asarray(nxt)[0]))
        pos += 1
    return toks


def _engine(lm, **kw):
    model, params = lm
    kw = {"slots": 2, "slot_tokens": SLOT_TOKENS, **kw}
    return serving.ReplicaEngine(model, params, **kw)


def _eos_of(run):
    """A token the free run emits first at index 2 or later (1 if none)."""
    for j in (2, 3, 4, 5, 6, 7, 1):
        if run[j] not in run[:j]:
            return run[j]
    raise AssertionError(run)


def test_streams_across_admissions_retirements_and_a_reused_row(lm):
    """Two slots.  ``a`` (greedy) ends on EOS while the next step already
    holds its row; ``r`` takes that slot before that step is read, and the
    row's token is booked to neither.  ``r`` then retires on its budget and
    ``s`` takes the slot while ``b`` still decodes.  ``b`` and ``s``
    sample.  Every stream is the offline one (greedy) or the synchronous
    primitives' (sampled)."""
    model, params = lm
    pa, pb, pr, ps = _prompts(4, seed=5)
    eos = _eos_of(_offline(model, params, pa, 12))
    a_req = serving.Request("a", pa, max_new=12, eos_id=eos)
    b_req = serving.Request("b", pb, max_new=26, temperature=0.8, top_k=9,
                            seed=21)
    r_req = serving.Request("r", pr, max_new=6)
    s_req = serving.Request("s", ps, max_new=4, temperature=0.7, top_p=0.9,
                            seed=22)
    want = {"a": _offline(model, params, pa, 12, eos_id=eos),
            "r": _offline(model, params, pr, 6)}
    want["a"] = want["a"][:want["a"].index(eos) + 1]
    for req in (b_req, s_req):
        want[req.rid] = _synchronous(model, params, req)

    eng = _engine(lm)
    got = {}

    def step():
        advanced, finished = eng.step()
        got.update((sess.request.rid, list(sess.emitted))
                   for sess in finished)
        return advanced, finished

    a, _ = eng.admit(a_req)
    b, _ = eng.admit(b_req)
    finished = []
    while a not in finished:
        finished = step()[1]
    # a ended on EOS at this read; the step dispatched before it holds its row
    assert got["a"][-1] == eos and eng._unread[-1].owners.get(a.slot) is a
    r, _ = eng.admit(r_req)
    assert r.slot == a.slot                     # before the lagged read
    advanced, _ = step()                        # the lagged read
    assert a not in advanced and r not in advanced and b in advanced
    assert eng.stats["rows_past_end"] == 1
    advanced, _ = step()                        # r's first step
    assert r in advanced and r.emitted == want["r"][:2]
    while r not in finished:                    # r retires on its budget
        finished = step()[1]
    s, _ = eng.admit(s_req)
    assert s.slot == r.slot and "b" not in got
    while eng.active:
        step()
    assert got == want
    assert eng.stats["rows_past_end"] == 1 and eng.pool.in_use == 0


def test_calls_return_the_read_steps_tokens_and_count_the_steps_ahead(
        lm, tmp_path):
    """On an idle pool a request of ``max_new`` tokens takes ``max_new - 1``
    calls, one token each: the first call dispatches two steps, the last
    none.  ``steps_ahead`` counts the dispatches made with a step unread,
    mirrored as ``tm_serving_steps_ahead_total`` (``rows_past_end`` and its
    counter stay 0 without EOS)."""
    model, params = lm
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1, obs="metrics", obs_dir=str(tmp_path)))
    try:
        from torchmpi_tpu import obs

        obs.reset()
        eng = _engine(lm)
        prompt = _prompts(1, seed=6)[0]
        sess, _ = eng.admit(serving.Request("x", prompt, max_new=5))
        per_call = []
        while eng.active:
            steps = eng.stats["steps"]
            advanced, finished = eng.step()
            per_call.append((eng.stats["steps"] - steps, len(advanced),
                             len(finished), len(eng._unread)))
        assert per_call == [(2, 1, 0, 1), (1, 1, 0, 1), (1, 1, 0, 1),
                            (0, 1, 1, 0)]
        assert sess.emitted == _offline(model, params, prompt, 5)
        assert (eng.stats["steps"], eng.stats["steps_ahead"]) == (4, 3)
        # the pool ran dry: the next request primes it again
        eng.admit(serving.Request("y", prompt, max_new=3))
        while eng.active:
            eng.step()
        assert (eng.stats["steps"], eng.stats["steps_ahead"]) == (6, 4)
        assert eng.stats["rows_past_end"] == 0
        reg = obs.registry()
        assert reg.counter("tm_serving_steps_ahead_total",
                           replica=eng.name) == 4
        assert reg.counter("tm_serving_rows_past_end_total",
                           replica=eng.name) == 0
    finally:
        mpi.stop()


def test_an_eos_row_past_the_end_is_counted_and_mirrored(lm, tmp_path):
    model, params = lm
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1, obs="metrics", obs_dir=str(tmp_path)))
    try:
        from torchmpi_tpu import obs

        obs.reset()
        prompt = _prompts(1, seed=7)[0]
        eos = _eos_of(_offline(model, params, prompt, 10))
        eng = _engine(lm, slots=1)
        sess, _ = eng.admit(serving.Request("e", prompt, max_new=10,
                                            eos_id=eos))
        while eng.active:
            eng.step()
        assert sess.emitted[-1] == eos
        # the step dispatched beside the EOS read is still unread: its row
        # is counted when a later call reads it
        assert eng.stats["rows_past_end"] == 0 and len(eng._unread) == 1
        nxt, _ = eng.admit(serving.Request("n", prompt, max_new=3))
        assert eng.step() == ([], [])
        assert eng.stats["rows_past_end"] == 1
        while eng.active:
            eng.step()
        assert nxt.emitted == _offline(model, params, prompt, 3)
        assert obs.registry().counter("tm_serving_rows_past_end_total",
                                      replica=eng.name) == 1
    finally:
        mpi.stop()


@pytest.mark.parametrize("on_device", [True, False])
def test_one_executable_serves_the_first_and_every_later_dispatch(
        lm, on_device):
    """A step dispatched with no step unread and one dispatched behind it
    hand the program the same operands' types and placements (the former
    merges from a zero vector placed as a step's tokens are): one compiled
    program for both, as the served cells' check of compiles in the window
    asks."""
    import importlib

    gen = importlib.import_module("torchmpi_tpu.models.generate")
    model, params = lm
    device = jax.local_devices()[1] if on_device else None
    eng = _engine(lm, slots=3, device=device)
    prompt = _prompts(1, seed=8)[0]
    eng.admit(serving.Request("p", prompt, max_new=2))
    while eng.active:           # one call: a step dispatched with none unread
        eng.step()
    compiled = gen._slot_step_jit._cache_size()
    assert eng.stats["steps_ahead"] == 0
    eng.admit(serving.Request("q", prompt, max_new=6))
    while eng.active:
        eng.step()
    assert eng.stats["steps_ahead"] > 0
    assert gen._slot_step_jit._cache_size() == compiled


def test_a_failed_lagged_read_reroutes_with_the_same_continuation(
        lm, monkeypatch):
    """A step that fails on the device fails at the read one call later
    (here the read raises a hard fault-layer error, as the chaos sites do):
    the replica reads as dead, its unread steps are dropped, and its
    sessions continue on the other replica from the tokens read -- the
    streams the offline and synchronous ones, bit for bit."""
    from torchmpi_tpu.faults.inject import FaultError

    model, params = lm
    prompts = _prompts(4, seed=9)
    reqs = [serving.Request(f"f{i}", prompts[i], max_new=9 + i,
                            arrival_s=0.001 * i) for i in range(3)]
    reqs.append(serving.Request("f3", prompts[3], max_new=10,
                                temperature=0.9, seed=33, arrival_s=0.003))
    want = {r.rid: (_offline(model, params, r.prompt, r.max_new)
                    if r.temperature is None
                    else _synchronous(model, params, r)) for r in reqs}
    reads = {"n": 0}
    device_get = jax.device_get

    def fails_once(out):
        reads["n"] += 1
        if reads["n"] == 7:
            raise FaultError("the step failed on the device")
        return device_get(out)

    monkeypatch.setattr(jax, "device_get", fails_once)
    srv = serving.Server(model, params, replicas=2, slots=2,
                         slot_tokens=SLOT_TOKENS)
    done = srv.run_trace(reqs, tick_seconds=0.001)
    assert len(done) == 4 and reads["n"] > 7
    dead = [e for e in srv.router.replicas if e.dead]
    assert len(dead) == 1 and not dead[0]._unread
    assert sum(r.reroutes for r in reqs) > 0
    assert {r.rid: r.tokens for r in reqs} == want


def test_a_bound_draft_keeps_the_synchronous_order(lm):
    """With a draft bound the tick drafts from the tokens on the host:
    each call dispatches its verify and reads it, nothing stays unread,
    and a session advances in the call after its admission."""
    model, params = lm
    eng = _engine(lm, spec_k=3)
    prompt = _prompts(1, seed=10)[0]
    sess, _ = eng.admit(serving.Request("d", prompt, max_new=9))
    while eng.active:
        emitted = len(sess.emitted)
        advanced, _ = eng.step()
        assert advanced == [sess] and len(sess.emitted) > emitted
        assert not eng._unread
    assert eng.stats["steps_ahead"] == 0 and eng.stats["spec_steps"] > 0
    assert sess.emitted == _offline(model, params, prompt, 9)
