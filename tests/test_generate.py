"""KV-cache autoregressive generation vs the naive full-recompute oracle:
greedy decoding with the cache must produce the exact same tokens as
re-running the full forward on the growing prefix each step."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import TransformerLM, generate


def _model():
    return TransformerLM(vocab=37, embed=32, depth=2, num_heads=4,
                         head_dim=8, max_len=32)


def _naive_greedy(model, params, prompt, steps):
    toks = jnp.asarray(prompt)
    for _ in range(steps):
        logits = model.apply({"params": params}, toks)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32),
                         axis=-1).astype(toks.dtype)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    return np.asarray(toks)


def test_cached_greedy_matches_naive():
    model = _model()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 37, size=(2, 5)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.asarray(prompt))["params"]

    expect = _naive_greedy(model, params, prompt, steps=9)
    got = np.asarray(generate(model, params, prompt, steps=9))
    np.testing.assert_array_equal(got, expect)


def test_temperature_sampling_valid_and_seeded():
    model = _model()
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, 37, size=(1, 3)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(2),
                        jnp.asarray(prompt))["params"]

    a = np.asarray(generate(model, params, prompt, steps=6, temperature=1.0,
                            rng=jax.random.PRNGKey(7)))
    b = np.asarray(generate(model, params, prompt, steps=6, temperature=1.0,
                            rng=jax.random.PRNGKey(7)))
    np.testing.assert_array_equal(a, b)  # same seed, same sample
    assert a.shape == (1, 9)
    assert ((a >= 0) & (a < 37)).all()
    np.testing.assert_array_equal(a[:, :3], prompt)  # prompt preserved


def test_generate_step_count_edges():
    # steps=0 returns the prompt unchanged; steps=1 takes the
    # prefill-only path (no scan) and must match the first token of a
    # longer greedy run.
    model = _model()
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, 37, size=(2, 4)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(6),
                        jnp.asarray(prompt))["params"]
    zero = np.asarray(generate(model, params, prompt, steps=0))
    np.testing.assert_array_equal(zero, prompt)
    one = np.asarray(generate(model, params, prompt, steps=1))
    three = np.asarray(generate(model, params, prompt, steps=3))
    assert one.shape == (2, 5)
    np.testing.assert_array_equal(one, three[:, :5])


def test_generate_rejects_overflow_and_sp():
    model = _model()
    prompt = np.zeros((1, 30), np.int32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(prompt))["params"]
    with pytest.raises(ValueError, match="max_len"):
        generate(model, params, prompt, steps=10)

    # flash-trained models serve WITHOUT rebinding attn_impl (decode
    # attends against the cache either way)...
    fl = TransformerLM(vocab=8, embed=16, depth=1, num_heads=2, head_dim=8,
                       max_len=16, attn_impl="flash")
    p2 = np.zeros((1, 2), np.int32)
    params2 = fl.init(jax.random.PRNGKey(0), jnp.asarray(p2))["params"]
    assert generate(fl, params2, p2, steps=2).shape == (1, 4)

    # ...but ring impls have no decode path (sequence-sharded cache).
    rg = TransformerLM(vocab=8, embed=16, depth=1, num_heads=2, head_dim=8,
                       max_len=16, attn_impl="ring", seq_axis="ici")
    with pytest.raises(ValueError, match="local"):
        generate(rg, params2, p2, steps=2)


def test_generate_parallel_ep_matches_naive(hier_runtime):
    # Expert-parallel decode (VERDICT r2 next #7): the cached greedy scan
    # under shard_map — MoE dispatch/combine all-to-all over ici each
    # step — must produce exactly the tokens of the naive full-recompute
    # greedy loop on the same sharded model.  capacity_factor is high so
    # routing never overflows: decode-time capacity (few tokens/step) and
    # prefill-time capacity (all tokens) then agree exactly.
    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import generate_parallel
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mpi.world_mesh()
    model = TransformerLM(vocab=29, embed=32, depth=2, num_heads=4,
                          head_dim=8, max_len=24, moe_axis="ici",
                          moe_experts_per_device=1, moe_k=2,
                          moe_capacity_factor=8.0)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 29, size=(4, 5)).astype(np.int32)

    def init_fn(tok):
        return model.init(jax.random.PRNGKey(4), tok)["params"]

    params = jax.jit(shard_map(init_fn, mesh=mesh, in_specs=P("dcn"),
                               out_specs=P(), check_vma=False))(
        jax.device_put(prompt, NamedSharding(mesh, P("dcn"))))

    got = np.asarray(generate_parallel(model, params, prompt, steps=7,
                                       mesh=mesh, batch_axis="dcn"))

    # Naive oracle: full-forward greedy on the growing prefix, same mesh.
    def fwd(params, toks):
        return model.apply({"params": params}, toks)

    fwd_jit = jax.jit(shard_map(fwd, mesh=mesh, in_specs=(P(), P("dcn")),
                                out_specs=P("dcn"), check_vma=False))
    toks = jax.device_put(jnp.asarray(prompt),
                          NamedSharding(mesh, P("dcn")))
    for _ in range(7):
        logits = fwd_jit(params, toks)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32),
                         axis=-1).astype(toks.dtype)
        toks = jax.device_put(
            jnp.concatenate([toks, nxt[:, None]], axis=1),
            NamedSharding(mesh, P("dcn")))
    np.testing.assert_array_equal(got, np.asarray(toks))


def test_generate_parallel_ulysses_matches_local(hier_runtime):
    # Ulysses decode: head-sharded KV cache over ici (1/n cache memory
    # per device) must produce exactly the tokens of the single-device
    # dense decode with the same params — attention params are identical
    # across attn impls, so the local model IS the oracle.
    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import generate_parallel

    mesh = mpi.world_mesh()
    kw = dict(vocab=41, embed=32, depth=2, num_heads=4, head_dim=8,
              max_len=24)
    ul = TransformerLM(attn_impl="ulysses", seq_axis="ici", **kw)
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, 41, size=(4, 6)).astype(np.int32)
    params = TransformerLM(**kw).init(jax.random.PRNGKey(8),
                                      jnp.asarray(prompt))["params"]

    got = np.asarray(generate_parallel(ul, params, prompt, steps=9,
                                       mesh=mesh, batch_axis="dcn"))
    expect = np.asarray(generate(TransformerLM(**kw), params, prompt,
                                 steps=9))
    np.testing.assert_array_equal(got, expect)

    # Without the mesh, ulysses decode must refuse with a pointer to
    # generate_parallel, not fail deep inside axis resolution.
    with pytest.raises(ValueError, match="generate_parallel"):
        generate(ul, params, prompt, steps=2)


def test_generate_parallel_sampling_shards_differ(hier_runtime):
    # batch_axis rng folding: sharded batch rows must not sample in
    # lockstep (identical rows across shards would betray a shared rng).
    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import generate_parallel

    mesh = mpi.world_mesh()
    model = TransformerLM(vocab=31, embed=32, depth=1, num_heads=2,
                          head_dim=8, max_len=20)
    prompt = np.zeros((4, 2), np.int32)  # identical rows on purpose
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(prompt))["params"]
    out = np.asarray(generate_parallel(
        model, params, prompt, steps=10, mesh=mesh, batch_axis="dcn",
        temperature=1.0, rng=jax.random.PRNGKey(11)))
    assert out.shape == (4, 12)
    # Rows 0/1 live on dcn shard 0, rows 2/3 on shard 1: folded rngs must
    # decorrelate the shards.
    assert not np.array_equal(out[0], out[2])


@pytest.mark.slow  # windowed-attention equivalence also covered by
# test_flash's window tests (tier-1 budget, ISSUE 4 satellite)
def test_generate_windowed_model_matches_full_recompute():
    """A sliding-window model decodes through the cache with the SAME
    band mask it trained with: cached greedy == full-recompute greedy of
    the windowed model, even past the window length."""
    model = TransformerLM(vocab=37, embed=32, depth=2, num_heads=2,
                          head_dim=8, max_len=32, window=4)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, 37, size=(2, 6)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(2),
                        jnp.asarray(prompt))["params"]
    expect = _naive_greedy(model, params, prompt, steps=10)  # 16 > window
    got = np.asarray(generate(model, params, prompt, steps=10))
    np.testing.assert_array_equal(got, expect)


def test_top_k_and_top_p_sampling():
    """Support-restriction semantics: top_k=1 and a tiny nucleus both
    collapse sampling to greedy; top_k=vocab is a no-op filter (same draw
    as unfiltered at the same rng); moderate settings stay in-vocab."""
    model = _model()
    rng = np.random.RandomState(8)
    prompt = rng.randint(0, 37, size=(2, 5)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.asarray(prompt))["params"]

    greedy = np.asarray(generate(model, params, prompt, steps=8))

    # top_k=1 at any temperature == greedy.
    k1 = np.asarray(generate(model, params, prompt, steps=8,
                             temperature=5.0, top_k=1,
                             rng=jax.random.PRNGKey(4)))
    np.testing.assert_array_equal(k1, greedy)

    # A tiny nucleus at low temperature keeps only the argmax token.
    p_tiny = np.asarray(generate(model, params, prompt, steps=8,
                                 temperature=0.05, top_p=1e-6,
                                 rng=jax.random.PRNGKey(5)))
    np.testing.assert_array_equal(p_tiny, greedy)

    # top_k=vocab filters nothing: identical draw to the unfiltered
    # sampler at the same rng/temperature.
    free = np.asarray(generate(model, params, prompt, steps=8,
                               temperature=1.0,
                               rng=jax.random.PRNGKey(6)))
    k_all = np.asarray(generate(model, params, prompt, steps=8,
                                temperature=1.0, top_k=37,
                                rng=jax.random.PRNGKey(6)))
    np.testing.assert_array_equal(k_all, free)

    # Moderate nucleus+k sampling stays in-vocab and seeded-reproducible.
    s1 = np.asarray(generate(model, params, prompt, steps=8,
                             temperature=1.0, top_k=8, top_p=0.9,
                             rng=jax.random.PRNGKey(7)))
    s2 = np.asarray(generate(model, params, prompt, steps=8,
                             temperature=1.0, top_k=8, top_p=0.9,
                             rng=jax.random.PRNGKey(7)))
    np.testing.assert_array_equal(s1, s2)
    assert s1.max() < 37 and s1.min() >= 0


def test_top_k_parallel_matches_single_device(hier_runtime):
    """The filters ride generate_parallel too: top_k=1 sharded-batch
    decode equals single-device greedy."""
    import torchmpi_tpu as mpi
    from torchmpi_tpu.models.generate import generate_parallel

    mesh = mpi.world_mesh()
    model = _model()
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, 37, size=(4, 5)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(8),
                        jnp.asarray(prompt))["params"]
    greedy = np.asarray(generate(model, params, prompt, steps=6))
    got = np.asarray(generate_parallel(
        model, params, prompt, steps=6, mesh=mesh, batch_axis="dcn",
        temperature=3.0, top_k=1, rng=jax.random.PRNGKey(9)))
    np.testing.assert_array_equal(got, greedy)


def test_sampling_knobs_validated():
    model = _model()
    prompt = np.zeros((1, 4), np.int32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(prompt))["params"]
    with pytest.raises(ValueError, match="top_k"):
        generate(model, params, prompt, steps=2, top_k=0)
    with pytest.raises(ValueError, match="top_p"):
        generate(model, params, prompt, steps=2, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        generate(model, params, prompt, steps=2, top_p=1.5)


def _seq_logprob(model, params, seq, prompt_len):
    """Teacher-forced cumulative log-prob of seq's generated suffix."""
    logits = model.apply({"params": params}, jnp.asarray(seq[:, :-1]))
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    total = np.zeros(seq.shape[0])
    for t in range(prompt_len - 1, seq.shape[1] - 1):
        total += np.asarray(jnp.take_along_axis(
            lp[:, t], jnp.asarray(seq[:, t + 1])[:, None], 1))[:, 0]
    return total


def test_beam_search_beams1_equals_greedy():
    from torchmpi_tpu.models import beam_search

    model = _model()
    rng = np.random.RandomState(10)
    prompt = rng.randint(0, 37, size=(3, 5)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(10),
                        jnp.asarray(prompt))["params"]
    greedy = np.asarray(generate(model, params, prompt, steps=7))
    beam1 = np.asarray(beam_search(model, params, prompt, steps=7,
                                   beams=1))
    np.testing.assert_array_equal(beam1, greedy)


def test_beam_search_exhaustive_at_steps2():
    # With beams == vocab, the first expansion keeps EVERY token, so at
    # steps=2 beam search IS exhaustive search over all vocab^2
    # continuations — compare against brute force.
    from torchmpi_tpu.models import beam_search

    model = TransformerLM(vocab=11, embed=16, depth=1, num_heads=2,
                          head_dim=8, max_len=16)
    rng = np.random.RandomState(11)
    prompt = rng.randint(0, 11, size=(2, 4)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(11),
                        jnp.asarray(prompt))["params"]
    got = np.asarray(beam_search(model, params, prompt, steps=2,
                                 beams=11))

    best_seq, best_lp = None, np.full(2, -np.inf)
    for t1 in range(11):
        for t2 in range(11):
            cand = np.concatenate(
                [prompt, np.full((2, 1), t1, np.int32),
                 np.full((2, 1), t2, np.int32)], axis=1)
            lp = _seq_logprob(model, params, cand, prompt_len=4)
            if best_seq is None:
                best_seq = cand.copy()
            take = lp > best_lp + 1e-9
            best_seq[take] = cand[take]
            best_lp = np.maximum(best_lp, lp)

    got_lp = _seq_logprob(model, params, got, prompt_len=4)
    # Compare by SCORE (ties between equal-score sequences are legal).
    np.testing.assert_allclose(got_lp, best_lp, rtol=1e-5, atol=1e-5)


def test_exhaustive_beam_dominates_all():
    # Beam search does NOT guarantee dominance over greedy in general
    # (the greedy prefix can be pruned), so the true invariant tested
    # here is: with beams == vocab at steps=2 the search is EXACT, and
    # the exact optimum's score >= any other decode's score.
    from torchmpi_tpu.models import beam_search

    model = TransformerLM(vocab=11, embed=16, depth=1, num_heads=2,
                          head_dim=8, max_len=16)
    rng = np.random.RandomState(12)
    prompt = rng.randint(0, 11, size=(4, 5)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(12),
                        jnp.asarray(prompt))["params"]
    exact = np.asarray(beam_search(model, params, prompt, steps=2,
                                   beams=11))
    greedy = np.asarray(generate(model, params, prompt, steps=2))
    beam3 = np.asarray(beam_search(model, params, prompt, steps=2,
                                   beams=3))
    e_lp = _seq_logprob(model, params, exact, prompt_len=5)
    for other in (greedy, beam3):
        o_lp = _seq_logprob(model, params, other, prompt_len=5)
        assert (e_lp >= o_lp - 1e-5).all(), (e_lp, o_lp)


def test_beam_search_validates():
    from torchmpi_tpu.models import beam_search

    model = _model()
    prompt = np.zeros((1, 4), np.int32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(prompt))["params"]
    with pytest.raises(ValueError, match="beams"):
        beam_search(model, params, prompt, steps=2, beams=0)
    with pytest.raises(ValueError, match="vocab"):
        beam_search(model, params, prompt, steps=2, beams=99)


def test_generate_eos_stopping():
    # Once a row emits eos_id, every later position is eos_id; rows that
    # never emit it are unchanged vs the eos-free decode.
    model = _model()
    rng = np.random.RandomState(20)
    prompt = rng.randint(0, 37, size=(4, 5)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(20),
                        jnp.asarray(prompt))["params"]
    free = np.asarray(generate(model, params, prompt, steps=10))
    # Pick the token the first row greedily emits mid-stream as the eos:
    # that row must then flatline while identical-prefix rows continue.
    eos = int(free[0, 5 + 3])
    got = np.asarray(generate(model, params, prompt, steps=10,
                              eos_id=eos))
    for b in range(4):
        gen_free, gen = free[b, 5:], got[b, 5:]
        # Same tokens until the first eos emission, eos-padding after.
        hits = np.where(gen_free == eos)[0]
        cut = hits[0] if hits.size else None
        if cut is None:
            np.testing.assert_array_equal(gen, gen_free)
        else:
            np.testing.assert_array_equal(gen[:cut + 1],
                                          gen_free[:cut + 1])
            assert (gen[cut:] == eos).all()


def test_beam_search_eos_freezes_score():
    # With eos_id set, a finished beam's forced eos continuations add
    # zero log-prob: at steps=2 with exhaustive beams, the winner must
    # be the argmax over {stop-at-eos scores} U {full 2-token scores} —
    # brute-forced here.
    from torchmpi_tpu.models import beam_search

    V, EOS = 11, 3
    model = TransformerLM(vocab=V, embed=16, depth=1, num_heads=2,
                          head_dim=8, max_len=16)
    rng = np.random.RandomState(21)
    prompt = rng.randint(0, V, size=(3, 4)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(21),
                        jnp.asarray(prompt))["params"]
    got = np.asarray(beam_search(model, params, prompt, steps=2,
                                 beams=V, eos_id=EOS))

    best_lp = np.full(3, -np.inf)
    for t1 in range(V):
        if t1 == EOS:
            # Finished after t1: score = lp(t1), suffix eos-padded.
            cand = np.concatenate(
                [prompt, np.full((3, 2), EOS, np.int32)], axis=1)
            lp = _seq_logprob(model, params, cand[:, :5], prompt_len=4)
            best_lp = np.maximum(best_lp, lp)
            continue
        for t2 in range(V):
            cand = np.concatenate(
                [prompt, np.full((3, 1), t1, np.int32),
                 np.full((3, 1), t2, np.int32)], axis=1)
            lp = _seq_logprob(model, params, cand, prompt_len=4)
            best_lp = np.maximum(best_lp, lp)

    # Score the returned sequence under the same rule (sum until eos).
    got_lp = np.zeros(3)
    for b in range(3):
        gen = got[b, 4:]
        hit = np.where(gen == EOS)[0]
        upto = (hit[0] + 1) if hit.size else gen.size
        got_lp[b] = _seq_logprob(model, params,
                                 got[b:b + 1, :4 + upto], prompt_len=4)[0]
    np.testing.assert_allclose(got_lp, best_lp, rtol=1e-5, atol=1e-5)


def test_beam_length_penalty_prefers_longer():
    # Length normalization divides by len**alpha: among an eos-stopped
    # 1-token hypothesis and a 2-token one with a more-negative raw
    # score, a large alpha must flip the ranking toward the longer one
    # whenever raw/1 < raw2/2**alpha.  Verified against brute force.
    from torchmpi_tpu.models import beam_search

    V, EOS = 7, 2
    model = TransformerLM(vocab=V, embed=16, depth=1, num_heads=2,
                          head_dim=8, max_len=12)
    rng = np.random.RandomState(22)
    prompt = rng.randint(0, V, size=(5, 3)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(22),
                        jnp.asarray(prompt))["params"]

    def brute_best(alpha):
        best = np.full(5, -np.inf)
        for t1 in range(V):
            if t1 == EOS:
                cand = np.concatenate(
                    [prompt, np.full((5, 2), EOS, np.int32)], axis=1)
                lp = _seq_logprob(model, params, cand[:, :4],
                                  prompt_len=3)
                best = np.maximum(best, lp / 1.0 ** alpha)
                continue
            for t2 in range(V):
                cand = np.concatenate(
                    [prompt, np.full((5, 1), t1, np.int32),
                     np.full((5, 1), t2, np.int32)], axis=1)
                lp = _seq_logprob(model, params, cand, prompt_len=3)
                best = np.maximum(best, lp / 2.0 ** alpha)
        return best

    for alpha in (0.0, 1.0, 3.0):
        got = np.asarray(beam_search(model, params, prompt, steps=2,
                                     beams=V, eos_id=EOS,
                                     length_penalty=alpha))
        got_score = np.zeros(5)
        for b in range(5):
            gen = got[b, 3:]
            hit = np.where(gen == EOS)[0]
            upto = (hit[0] + 1) if hit.size else gen.size
            lp = _seq_logprob(model, params, got[b:b + 1, :3 + upto],
                              prompt_len=3)[0]
            got_score[b] = lp / float(upto) ** alpha
        np.testing.assert_allclose(got_score, brute_best(alpha),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # beam+EP composition; EP generate and beam search
# each have their own fast oracles (tier-1 budget, ISSUE 4 satellite)
def test_beam_parallel_ep_matches_oracles(hier_runtime):
    # Expert-parallel beam search (VERDICT r3 #7): beam decode under
    # shard_map with MoE dispatch/combine over ici each step.  Two
    # oracles on the SAME sharded model (its expert count is a property
    # of the mesh, so a dense single-device rerun is not comparable):
    # beams=1 must equal the greedy parallel decode exactly, and at
    # steps=2 with beams=vocab the search is exhaustive, so its
    # teacher-forced score must match brute force over all vocab^2
    # continuations computed with the sharded forward.
    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import generate_parallel, beam_search_parallel
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mpi.world_mesh()
    V = 13
    model = TransformerLM(vocab=V, embed=32, depth=2, num_heads=4,
                          head_dim=8, max_len=24, moe_axis="ici",
                          moe_experts_per_device=1, moe_k=2,
                          moe_capacity_factor=8.0)
    rng = np.random.RandomState(23)
    prompt = rng.randint(0, V, size=(4, 5)).astype(np.int32)

    def init_fn(tok):
        return model.init(jax.random.PRNGKey(23), tok)["params"]

    params = jax.jit(shard_map(init_fn, mesh=mesh, in_specs=P("dcn"),
                               out_specs=P(), check_vma=False))(
        jax.device_put(prompt, NamedSharding(mesh, P("dcn"))))

    greedy = np.asarray(generate_parallel(model, params, prompt, steps=6,
                                          mesh=mesh, batch_axis="dcn"))
    beam1 = np.asarray(beam_search_parallel(
        model, params, prompt, steps=6, beams=1, mesh=mesh,
        batch_axis="dcn"))
    np.testing.assert_array_equal(beam1, greedy)

    # Exhaustive oracle at steps=2: teacher-forced scores from the
    # sharded full forward (batch replicated so every candidate scores
    # on every device identically).
    def fwd(params, toks):
        return model.apply({"params": params}, toks)

    fwd_jit = jax.jit(shard_map(fwd, mesh=mesh, in_specs=(P(), P()),
                                out_specs=P(), check_vma=False))

    def lp_of(seqs):
        logits = np.asarray(fwd_jit(params, jnp.asarray(seqs[:, :-1])))
        lp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), -1)
        total = np.zeros(seqs.shape[0])
        for t in range(4, seqs.shape[1] - 1):
            total += np.asarray(jnp.take_along_axis(
                lp[:, t], jnp.asarray(seqs[:, t + 1])[:, None], 1))[:, 0]
        return total

    got = np.asarray(beam_search_parallel(
        model, params, prompt, steps=2, beams=V, mesh=mesh))
    best_lp = np.full(4, -np.inf)
    for t1 in range(V):
        for t2 in range(V):
            cand = np.concatenate(
                [prompt, np.full((4, 1), t1, np.int32),
                 np.full((4, 1), t2, np.int32)], axis=1)
            best_lp = np.maximum(best_lp, lp_of(cand))
    np.testing.assert_allclose(lp_of(got), best_lp, rtol=1e-5, atol=1e-5)


def test_beam_parallel_ulysses_matches_dense_beam(hier_runtime):
    # Ulysses beam search: head-sharded KV cache + parent-gather beam
    # reindexing must equal the dense local-attention beam with the same
    # params (attention params are impl-independent).
    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import beam_search, beam_search_parallel

    mesh = mpi.world_mesh()
    dense = TransformerLM(vocab=23, embed=32, depth=2, num_heads=8,
                          head_dim=8, max_len=24)
    ulys = dense.clone(attn_impl="ulysses", seq_axis="ici")
    rng = np.random.RandomState(24)
    prompt = rng.randint(0, 23, size=(2, 4)).astype(np.int32)
    params = dense.init(jax.random.PRNGKey(24),
                        jnp.asarray(prompt))["params"]

    expect = np.asarray(beam_search(dense, params, prompt, steps=6,
                                    beams=4, eos_id=2,
                                    length_penalty=1.0))
    got = np.asarray(beam_search_parallel(
        ulys, params, prompt, steps=6, beams=4, mesh=mesh, eos_id=2,
        length_penalty=1.0))
    np.testing.assert_array_equal(got, expect)


# ---------------------------------------------------------------------------
# _filter_logits edge cases (the contract every serving sampler builds on)
# ---------------------------------------------------------------------------


def test_filter_logits_edge_cases():
    """top_k=1 == greedy support, top_p=1.0 keeps everything, temp -> 0
    sampling == argmax, and the k-then-p composition order is pinned."""
    from torchmpi_tpu.models.generate import _filter_logits, _sample

    rng = np.random.RandomState(4)
    logits = jnp.asarray(rng.randn(5, 23).astype(np.float32))

    # top_k=1: exactly the argmax survives each row.
    f = np.asarray(_filter_logits(logits, 1.0, 1, None))
    assert (np.isfinite(f).sum(axis=-1) == 1).all()
    np.testing.assert_array_equal(np.argmax(f, -1),
                                  np.asarray(jnp.argmax(logits, -1)))

    # top_p=1.0: the exclusive-cumsum nucleus rule (cum - p_i < 1)
    # keeps every token — a bitwise no-op filter.
    f = np.asarray(_filter_logits(logits, 1.0, None, 1.0))
    np.testing.assert_array_equal(f, np.asarray(logits))

    # temperature=0 through _sample: argmax, whatever the filters say
    # (top-k keeps the max by construction; the temp->0 nucleus
    # collapses to the top token — which IS the argmax).
    toks = np.asarray(_sample(logits, jax.random.PRNGKey(0), 0.0, 5,
                              0.9, jnp.int32))
    np.testing.assert_array_equal(toks,
                                  np.asarray(jnp.argmax(logits, -1)))

    # Composition order is k FIRST, then p over the k-renormalized
    # support — pinned by a row where the other order differs.  Top-2
    # renormalization gives the max 0.525 mass, so p=0.5 drops the
    # runner-up; p-first over the full row (max mass 0.335) would have
    # kept it.
    row = np.zeros((1, 10), np.float32)
    row[0, 0], row[0, 1] = 2.0, 1.9
    f = np.asarray(_filter_logits(jnp.asarray(row), 1.0, 2, 0.5))
    assert np.isfinite(f[0, 0]) and not np.isfinite(f[0, 1:]).any()


def test_filter_logits_rows_matches_static_and_sentinels():
    """The per-row dynamic filter (one executable for a slot pool
    mixing greedy and sampled rows) equals the static filter for
    uniform knobs, and the sentinel row (top_k=0, top_p=2.0) is a
    bitwise no-op — what keeps serving's greedy tokens identical to the
    pre-sampling engine."""
    from torchmpi_tpu.models.generate import _filter_logits, \
        _filter_logits_rows

    rng = np.random.RandomState(6)
    logits = jnp.asarray(rng.randn(4, 19).astype(np.float32))
    got = np.asarray(_filter_logits_rows(
        logits, jnp.full((4,), 0.8, jnp.float32),
        jnp.full((4,), 3, jnp.int32), jnp.full((4,), 0.7, jnp.float32)))
    exp = np.asarray(_filter_logits(logits, 0.8, 3, 0.7))
    np.testing.assert_array_equal(got, exp)

    noop = np.asarray(_filter_logits_rows(
        logits, jnp.zeros((4,), jnp.float32),
        jnp.zeros((4,), jnp.int32), jnp.full((4,), 2.0, jnp.float32)))
    np.testing.assert_array_equal(noop, np.asarray(logits))


# ---------------------------------------------------------------------------
# _sample_rows: the tail does what its rows ask (one lax.cond on the
# device), bitwise the always-filter form it replaced
# ---------------------------------------------------------------------------


def _sample_rows_always_filter(logits, keys, temps, top_ks, top_ps, dtype):
    """The form every pooled program ran until PR 36, kept here as the
    reference: filter every row, draw every row, then pick by row."""
    from torchmpi_tpu.models.generate import _filter_logits_rows

    logits = _filter_logits_rows(logits.astype(jnp.float32), temps,
                                 top_ks, top_ps)
    drawn = jax.vmap(jax.random.categorical)(
        keys, logits / jnp.maximum(temps, 1e-6)[:, None])
    return jnp.where(temps > 0.0, drawn,
                     jnp.argmax(logits, axis=-1)).astype(dtype)


# (temperature, top_k, top_p) of the rows a mix cycles through
_GREEDY, _GREEDY_KNOBS = (0.0, 0, 2.0), (0.0, 3, 0.6)
_TEMP, _TOP_K, _TOP_P, _K_AND_P = (0.8, 0, 2.0), (1.3, 5, 2.0), \
    (0.7, 0, 0.9), (1.0, 7, 0.8)
_SAMPLE_MIXES = {
    # name: (rows, logits dtype, the knobs cycled over the rows, branch)
    "all_greedy": (5, jnp.float32, [_GREEDY], 0),
    "greedy_with_knobs_set": (5, jnp.float32, [_GREEDY_KNOBS, _GREEDY], 0),
    "temperature_only": (5, jnp.float32, [_TEMP, (1.5, 0, 2.0)], 1),
    "greedy_and_temperature": (5, jnp.float32, [_GREEDY, _TEMP], 1),
    "greedy_knobs_and_temperature": (5, jnp.float32,
                                     [_GREEDY_KNOBS, _TEMP], 1),
    "greedy_filtered_and_temperature": (5, jnp.float32,
                                        [_GREEDY, _TOP_K, _TEMP, _TOP_P], 1),
    "every_row_filtered": (5, jnp.float32, [_TOP_K, _TOP_P, _K_AND_P], 1),
    "bfloat16_greedy": (5, jnp.bfloat16, [_GREEDY], 0),
    "bfloat16_mixed": (5, jnp.bfloat16,
                       [_GREEDY, _TEMP, _K_AND_P, _GREEDY_KNOBS], 1),
    "one_row_greedy": (1, jnp.float32, [_GREEDY], 0),
    "one_row_temperature": (1, jnp.float32, [_TEMP], 1),
    "one_row_filtered": (1, jnp.float32, [_K_AND_P], 1),
    "verify_rows_greedy": (4 * 3, jnp.float32, [_GREEDY], 0),
    "verify_rows_mixed": (4 * 3, jnp.float32,
                          [_GREEDY, _GREEDY, _GREEDY, _TEMP, _TEMP, _TEMP,
                           _TOP_P, _TOP_P, _TOP_P], 1),
}


def _sample_mix(name):
    from torchmpi_tpu.models.generate import _sample_keys

    R, dtype, knobs, branch = _SAMPLE_MIXES[name]
    rng = np.random.RandomState(len(name) + R)
    # a coarse, clipped grid: every row holds ties, its maximum among them
    logits = np.clip(np.round(rng.randn(R, 257) * 2.0) / 2.0, -2.0, 2.0)
    rows = [knobs[i % len(knobs)] for i in range(R)]
    temps, top_ks, top_ps = (jnp.asarray(col, dt) for col, dt in zip(
        zip(*rows), (jnp.float32, jnp.int32, jnp.float32)))
    keys = _sample_keys(jnp.arange(R, dtype=jnp.uint32) + 11,
                        jnp.arange(R, dtype=jnp.int32) * 3)
    return (jnp.asarray(logits, dtype), keys, temps, top_ks, top_ps), branch


@pytest.mark.parametrize("mix", sorted(_SAMPLE_MIXES))
def test_sample_rows_is_bitwise_the_always_filter_form(mix):
    from torchmpi_tpu.models.generate import _sample_rows

    operands, _ = _sample_mix(mix)
    got = jax.jit(_sample_rows, static_argnums=5)(*operands, jnp.int32)
    want = jax.jit(_sample_rows_always_filter, static_argnums=5)(
        *operands, jnp.int32)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    top = np.asarray(operands[0].astype(jnp.float32))
    assert ((top == top.max(-1, keepdims=True)).sum(-1) > 1).all()  # ties


def _primitives(jaxpr, found=None):
    """Names of every primitive of ``jaxpr``, sub-jaxprs included."""
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


@pytest.mark.parametrize("mix", ["all_greedy", "greedy_and_temperature",
                                 "every_row_filtered"])
def test_sample_rows_is_one_cond_and_each_branch_holds_its_own_work(mix):
    """One ``cond`` at the top; the sort, the running sum and the random
    bits only in its last branch; and the index the operands give is the
    branch the mix asks for."""
    from torchmpi_tpu.models.generate import _sample_rows

    operands, branch = _sample_mix(mix)
    jaxpr = jax.make_jaxpr(_sample_rows, static_argnums=5)(
        *operands, jnp.int32).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    outside = {e.primitive.name for e in jaxpr.eqns} - {"cond"}
    heavy = {"sort", "cumsum", "random_bits", "argmax", "exp"}
    assert not outside & heavy, outside & heavy
    greedy, drawn = (
        _primitives(b.jaxpr) for b in conds[0].params["branches"])
    assert "argmax" in greedy and not greedy & (heavy - {"argmax"})
    assert {"sort", "cumsum", "random_bits"} <= drawn
    # the predicate, evaluated: everything the top level computes before
    # the cond is the index
    index = jax.core.eval_jaxpr(
        jaxpr.replace(outvars=[conds[0].invars[0]],
                      eqns=jaxpr.eqns[:jaxpr.eqns.index(conds[0])]),
        [], *operands)[0]
    assert int(index) == branch


# ---------------------------------------------------------------------------
# A decode=True prompt block through the flash forward kernel
# (models/transformer.prefill_runs_flash decides; the CPU keeps dense scores
# unless a test calls conftest's chip_rule: the kernel is then interpreted)
# ---------------------------------------------------------------------------

from torchmpi_tpu.models import transformer  # noqa: E402
from torchmpi_tpu.models.generate import (  # noqa: E402
    slot_decode_step, slot_prefill, slot_write)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("T", [1, 2, 64, 4096])
@pytest.mark.parametrize("per_row", [False, True])
def test_prefill_runs_flash_rule(platform, T, per_row):
    # platform x T x per_row: a prompt block on a fresh cache, on the chip,
    # and nothing else.  Per-row T > 1 is the speculative verify step and
    # the prefix-hit extend; T == 1 is the decode step.
    want = platform == "tpu" and T > 1 and not per_row
    assert transformer.prefill_runs_flash(T, per_row, platform) is want


def test_prefill_runs_flash_reads_the_kernels_own_platform():
    # With no platform given the rule asks what every kernel of ops/ asks:
    # here the CPU (dense), and the chip wherever real lowering is forced,
    # as tests/test_chip_compile.py forces it for a described device.
    from torchmpi_tpu.ops import ring

    assert not transformer.prefill_runs_flash(64, False)
    ring.set_interpret(False)
    try:
        assert transformer.prefill_runs_flash(64, False)
        assert not transformer.prefill_runs_flash(64, True)
        assert not transformer.prefill_runs_flash(1, False)
    finally:
        ring.set_interpret(None)
    assert not transformer.prefill_runs_flash(64, False)


@pytest.fixture(scope="module")
def gqa_window_lm():
    # 4 q / 2 kv heads, a window smaller than the prompts below; sizes no
    # other test of this process uses, so no other test's trace is met
    model = TransformerLM(vocab=61, embed=32, depth=2, num_heads=4,
                          num_kv_heads=2, head_dim=8, max_len=1040,
                          window=24, pos_emb="rope")
    params = jax.jit(model.init)(jax.random.PRNGKey(3),
                                 jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _padded_prompt(bucket, true_len, seed=0):
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :true_len] = np.random.RandomState(seed).randint(
        0, 61, size=true_len)
    return prompt


def _prefill(dmodel):
    """The layer's own prefill call: (params, prompt) -> (logits, cache)."""
    return lambda p, x: dmodel.apply({"params": p}, x, pos_offset=0,
                                     mutable=["cache"])


def _count_kernels(fn, *args):
    """Kernel calls a run of ``fn`` makes.  Interpreted, a kernel leaves no
    custom call behind, so the calls are counted in the jaxpr, every call
    site of a shared inner jaxpr for itself (the layers share one trace of
    the kernel, ``transformer._prompt_attention``)."""
    def calls(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name == "pallas_call"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += calls(sub)
        return n

    return calls(jax.make_jaxpr(fn)(*args).jaxpr)


# 64: less than the kernel's smallest block (128), so q and k are padded
# inside it; 1024: two blocks of 512 each way, no padding.
@pytest.mark.parametrize("bucket,true_len", [(64, 41), (1024, 900)])
def test_slot_prefill_through_the_kernel_matches_dense(
        chip_rule, gqa_window_lm, bucket, true_len):
    model, params = gqa_window_lm
    dmodel = model.clone(decode=True)
    prompt = _padded_prompt(bucket, true_len)
    dense_logits, _ = jax.jit(_prefill(dmodel))(params, prompt)
    dense_cache, dense_first = slot_prefill(dmodel, params, prompt,
                                            true_len=true_len)
    assert _count_kernels(_prefill(dmodel), params, prompt) == 0
    chip_rule()
    assert _count_kernels(_prefill(dmodel), params, prompt) == 2  # a layer
    # ... which share ONE trace of the kernel: a second layer costs no
    # second lowering to Mosaic (set-up time on the chip)
    shared = [eqn.params["jaxpr"] for eqn in jax.make_jaxpr(
        _prefill(dmodel))(params, prompt).jaxpr.eqns
        if eqn.params.get("name") == "_prompt_attention"]
    assert len(shared) == 2 and shared[0] is shared[1]
    logits, _ = jax.jit(_prefill(dmodel))(params, prompt)
    cache, first = slot_prefill(dmodel, params, prompt, true_len=true_len)
    np.testing.assert_array_equal(np.asarray(first),
                                  np.asarray(dense_first))
    # the real positions' logits (a pad's are never read)
    np.testing.assert_allclose(
        np.asarray(logits, np.float32)[:, :true_len],
        np.asarray(dense_logits, np.float32)[:, :true_len],
        rtol=2e-5, atol=2e-5)
    # the cache is written by the same code either way: the first layer's
    # rows are the same bits, the second's differ by the first's o
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(cache),
                            jax.tree.leaves(dense_cache)):
        if a.ndim:   # k and v, [1, max_len, 2, 8]; idx is a scalar
            a, b = a[:, :true_len], b[:, :true_len]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5, err_msg=str(path))
    layer0 = cache["Block_0"]["SPAttention_0"]
    np.testing.assert_array_equal(
        np.asarray(layer0["k"]),
        np.asarray(dense_cache["Block_0"]["SPAttention_0"]["k"]))


def _pool_like(one, slots):
    """A zero pool of ``slots`` rows for a one-row cache; it shares no leaf
    with the row (the scalar ``idx`` neither: the pool is consumed by the
    programs it is handed to, the row is not)."""
    return jax.tree.map(
        lambda s: jnp.zeros((slots,) + s.shape[1:] if s.ndim else (),
                            s.dtype), one)


def _served_tokens(dmodel, params, prompt, true_len, steps):
    """slot_prefill, the write into row 1 of a two-row pool, then the
    pooled per-row step: -> (the tokens, the prompt's cache, the pool)."""
    one, first = slot_prefill(dmodel, params, prompt, true_len=true_len)
    pool = _pool_like(one, 2)
    pool = slot_write(pool, one, 1)
    toks, pos = [int(np.asarray(first)[0])], true_len
    for _ in range(steps - 1):
        pool, nxt = slot_decode_step(
            dmodel, params, pool, np.asarray([0, toks[-1]], np.int32),
            np.asarray([0, pos], np.int32))
        toks.append(int(np.asarray(nxt)[1]))
        pos += 1
    return toks, one, pool


def test_kernel_prefill_then_the_untouched_steps_serve_the_dense_tokens(
        gqa_window_lm, chip_rule):
    # A prompt prefilled through the kernel, written to a pool row and
    # decoded through the pooled per-row step, gives the tokens the dense
    # prefill gives.  The step and the per-row extend hold no kernel.
    from torchmpi_tpu.models.generate import (
        _greedy_sampling, _slot_extend_jit, _slot_prefill_jit,
        _slot_step_jit)

    model, params = gqa_window_lm
    dmodel = model.clone(decode=True, max_len=96)
    true_len, steps = 41, 6
    prompt = _padded_prompt(64, true_len, seed=4)
    dense_toks, _, _ = _served_tokens(dmodel, params, prompt, true_len,
                                      steps)
    chip_rule()
    toks, one, pool = _served_tokens(dmodel, params, prompt, true_len,
                                     steps)
    assert toks == dense_toks
    assert _count_kernels(
        lambda *a: _slot_prefill_jit(dmodel, *a), params, prompt,
        jnp.asarray(true_len, jnp.int32), *_greedy_sampling(1)) == 2
    assert _count_kernels(
        lambda *a: _slot_step_jit(dmodel, *a), params, pool,
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        *_greedy_sampling(2)) == 0
    # the prefix-hit extend: a suffix at a per-row depth, T > 1
    assert _count_kernels(
        lambda *a: _slot_extend_jit(dmodel, *a), params, one,
        jnp.zeros((1, 8), jnp.int32), jnp.asarray([16], jnp.int32),
        jnp.asarray(8, jnp.int32), *_greedy_sampling(1)) == 0


def test_generate_prefills_through_the_kernel_to_the_dense_tokens(
        gqa_window_lm, chip_rule):
    # generate()'s one full-prompt pass (a scalar offset, T > 1, a batch of
    # two) is the same layer code: the same tokens, the scan behind it
    # untouched.
    model, params = gqa_window_lm
    model = model.clone(max_len=64)
    prompt = np.random.RandomState(6).randint(
        0, 61, size=(2, 37)).astype(np.int32)
    dense = np.asarray(generate(model, params, prompt, steps=5))
    chip_rule()
    np.testing.assert_array_equal(
        np.asarray(generate(model, params, prompt, steps=5)), dense)


# ---------------------------------------------------------------------------
# Who owns a cache (PR 34): the pooled programs consume the pool they are
# handed (donate_argnums: one generation alive, updated in place); a row is
# never consumed.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_row(gqa_window_lm):
    model, params = gqa_window_lm
    dmodel = model.clone(decode=True, max_len=48)
    one, first = slot_prefill(dmodel, params, _padded_prompt(16, 11, seed=8),
                              true_len=11)
    return dmodel, params, one, int(np.asarray(first)[0])


def _deleted(tree):
    return [leaf.is_deleted() for leaf in jax.tree.leaves(tree)]


@pytest.mark.parametrize("program", ["write", "step", "verify"])
def test_pooled_programs_consume_the_pool_and_leave_the_row(one_row,
                                                            program):
    from torchmpi_tpu.models.generate import slot_verify_step

    dmodel, params, one, first = one_row
    kept = jax.tree.map(np.asarray, one)
    pool = slot_write(_pool_like(one, 2), one, 1)
    went_in = pool
    if program == "write":
        pool = slot_write(pool, one, 0)
    elif program == "step":
        pool, _ = slot_decode_step(
            dmodel, params, pool, np.asarray([0, first], np.int32),
            np.asarray([0, 11], np.int32))
    else:
        pool, _ = slot_verify_step(
            dmodel, params, pool, np.asarray([[0, 0], [first, 3]], np.int32),
            np.asarray([0, 11], np.int32))
    # every leaf of the pool that went in is gone, the scalar idx too ...
    assert all(_deleted(went_in))
    with pytest.raises((RuntimeError, ValueError), match="deleted"):
        slot_write(went_in, one, 0)
    # ... what came back is whole, and the row is as it was
    assert not any(_deleted(pool)) and not any(_deleted(one))
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(kept)):
        np.testing.assert_array_equal(np.asarray(a), b)
    row1 = jax.tree.map(lambda p: p[1:2] if p.ndim else p, pool)
    for a, b in zip(jax.tree.leaves(row1), jax.tree.leaves(kept)):
        if b.ndim:   # the prompt's positions: no program here rewrites them
            np.testing.assert_array_equal(np.asarray(a)[:, :11], b[:, :11])


def test_donated_step_serves_the_tokens_of_an_undonated_one(one_row):
    # The same arithmetic writes the same positions: a pool stepped through
    # the donating program and a pool stepped through the SAME function
    # jitted without donation hold the same bits and give the same tokens.
    from torchmpi_tpu.models.generate import _greedy_sampling, _slot_step_jit

    dmodel, params, one, first = one_row
    plain = jax.jit(_slot_step_jit.__wrapped__, static_argnums=(0,))
    donated = slot_write(_pool_like(one, 2), one, 1)
    copied = jax.tree.map(jnp.copy, donated)
    tok_a = tok_b = first
    for pos in range(11, 17):
        args = (jnp.asarray([0, pos], jnp.int32),) + _greedy_sampling(2)
        donated, nxt_a, _ = _slot_step_jit(
            dmodel, params, donated, jnp.asarray([0, tok_a], jnp.int32),
            *args)
        before = copied
        copied, nxt_b, _ = plain(
            dmodel, params, copied, jnp.asarray([0, tok_b], jnp.int32),
            *args)
        assert not any(_deleted(before))     # the control copies, as PR 33
        tok_a, tok_b = int(nxt_a[1]), int(nxt_b[1])
        assert tok_a == tok_b
    for a, b in zip(jax.tree.leaves(donated), jax.tree.leaves(copied)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("program", ["cache_write", "extend"])
def test_row_programs_leave_the_row_they_are_given(one_row, program):
    # ReplicaEngine._row_zero is ONE template for every admission: the row
    # programs hand back a new row and leave theirs alone.
    from torchmpi_tpu.models.generate import (
        slot_cache_slice, slot_cache_write, slot_extend)

    dmodel, params, one, _ = one_row
    zero = jax.tree.map(jnp.zeros_like, one)
    if program == "cache_write":
        out = slot_cache_write(zero, slot_cache_slice(one, 0, 8), 0)
    else:
        out, _ = slot_extend(dmodel, params, zero,
                             np.zeros((1, 4), np.int32), pos_offset=[8])
    assert not any(_deleted(zero)) and not any(_deleted(out))
    assert not any(np.asarray(leaf).any() for leaf in jax.tree.leaves(zero))
    assert any(np.asarray(leaf).any() for leaf in jax.tree.leaves(out))
