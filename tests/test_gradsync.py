"""Gradient-sync tests (reference analog: test/nn*.lua + MNIST convergence
smoke, SURVEY.md §5).

Key correctness property (reference §4.3): a data-parallel step over N
devices with gradient averaging must match a single-device step on the full
batch — the sum-of-shard-gradients IS the full-batch gradient.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torchmpi_tpu as mpi
from torchmpi_tpu.models import LeNet
from torchmpi_tpu.parallel import gradsync
from torchmpi_tpu.utils import data as dutil


def _tools(lr=0.01, momentum=0.9, seed=0):
    model = LeNet()
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 28, 28, 1)))
    tx = optax.sgd(lr, momentum=momentum)
    opt_state = tx.init(params)

    def local_loss(p, images, labels):
        logits = model.apply(p, images)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    return model, params, tx, opt_state, local_loss


def _dp_step_fn(tx, local_loss, mesh, backend=None, n_buckets=None):
    def step(params, opt_state, images, labels):
        loss, grads = jax.value_and_grad(local_loss)(params, images, labels)
        grads = gradsync.synchronize_gradients(grads, backend=backend,
                                               n_buckets=n_buckets)
        loss = mpi.collectives.allreduce_in_axis(loss, mesh.axis_names,
                                                 op="mean")
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


def test_synchronize_parameters_replicates(flat_runtime):
    _, params, _, _, _ = _tools()
    rep = gradsync.synchronize_parameters(params)
    leaf = jax.tree.leaves(rep)[0]
    assert leaf.sharding.is_fully_replicated


def test_dp_step_matches_single_device(flat_runtime):
    """8-device DP step == single-device full-batch step, numerically."""
    mesh = mpi.world_mesh()
    model, params, tx, opt_state, local_loss = _tools()
    X, Y = dutil.synthetic_mnist(256, seed=1)
    xb, yb = X[:64], Y[:64]

    # single-device full batch
    loss1, grads1 = jax.value_and_grad(local_loss)(
        params, jnp.asarray(xb), jnp.asarray(yb))
    up1, _ = tx.update(grads1, opt_state, params)
    p1 = optax.apply_updates(params, up1)

    # 8-device DP
    dp = gradsync.data_parallel_step(
        _dp_step_fn(tx, local_loss, mesh), batch_argnums=(2, 3),
        donate_argnums=())
    p2, _, loss2 = dp(gradsync.synchronize_parameters(params),
                      gradsync.synchronize_parameters(opt_state), xb, yb)

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


def test_bucketed_matches_unbucketed(flat_runtime):
    mesh = mpi.world_mesh()
    model, params, tx, opt_state, local_loss = _tools()
    X, Y = dutil.synthetic_mnist(64, seed=2)

    outs = []
    for n_buckets in (1, 4):
        dp = gradsync.data_parallel_step(
            _dp_step_fn(tx, local_loss, mesh, n_buckets=n_buckets),
            batch_argnums=(2, 3), donate_argnums=())
        p, _, _ = dp(gradsync.synchronize_parameters(params),
                     gradsync.synchronize_parameters(opt_state), X, Y)
        outs.append(p)
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


def test_barrier_buckets_match_and_survive_compiler(flat_runtime):
    # gradsync_barrier must (a) not change numerics and (b) actually keep
    # the bucketed all-reduces distinct through XLA's combiner — the
    # measured default is that sub-threshold buckets merge to ONE
    # compiled collective, so the barrier is the lever that makes
    # bucket-count tuning real.
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mpi.world_mesh()
    g = {"a": np.random.RandomState(0).randn(8, 4096).astype(np.float32),
         "b": np.random.RandomState(1).randn(8, 513).astype(np.float32)}

    def body(barrier):
        def f(t):
            return gradsync.synchronize_gradients(
                t, mesh.axis_names, op="sum", n_buckets=4, barrier=barrier)

        return jax.jit(shard_map(
            f, mesh=mesh, in_specs=P(mesh.axis_names),
            out_specs=P(mesh.axis_names), check_vma=False))

    gd = jax.device_put(g, NamedSharding(mesh, P(mesh.axis_names)))
    plain = body(False)
    chained = body(True)
    for a, b in zip(jax.tree.leaves(plain(gd)),
                    jax.tree.leaves(chained(gd))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6)
    # Emitted-IR contract: 4 distinct all_reduces, chained by 3 barriers.
    # (The compiled count is backend-dependent: the CPU pipeline expands
    # barriers before its combiner and merges to 1; TPU's combiner
    # respects barriers — benchmarks/overlap_analyze.py records the
    # compiled truth per platform.)
    txt = chained.lower(gd).as_text()
    assert txt.count("stablehlo.all_reduce") == 4
    assert txt.count("optimization_barrier") == 3
    assert plain.lower(gd).as_text().count("optimization_barrier") == 0


def test_bucket_count_exceeding_params(flat_runtime):
    # More buckets than elements must clamp, not crash.
    mesh = mpi.world_mesh()

    def body(g):
        return gradsync.synchronize_gradients(g, mesh.axis_names, op="sum",
                                              n_buckets=64)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    fn = jax.jit(shard_map(body, mesh=mesh,
                           in_specs=P(mesh.axis_names),
                           out_specs=P()))
    res = fn(np.arange(8, dtype=np.float32).reshape(8, 1))
    np.testing.assert_allclose(np.asarray(res), [[28.0]])


def test_sum_vs_mean_op(flat_runtime):
    mpi.set_config(gradsync_average=False)  # reference default: sum
    mesh = mpi.world_mesh()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    fn = jax.jit(shard_map(
        lambda g: gradsync.synchronize_gradients(g, mesh.axis_names),
        mesh=mesh, in_specs=P(mesh.axis_names), out_specs=P()))
    res = fn(np.ones((8, 2), np.float32))
    np.testing.assert_allclose(np.asarray(res), [[8.0, 8.0]])


def test_hierarchical_gradsync(hier_runtime):
    """Gradient sync routed through the 2-level backend converges the same."""
    mesh = mpi.world_mesh()
    model, params, tx, opt_state, local_loss = _tools()
    X, Y = dutil.synthetic_mnist(64, seed=3)
    outs = []
    for backend in ("xla", "hierarchical"):
        dp = gradsync.data_parallel_step(
            _dp_step_fn(tx, local_loss, mesh, backend=backend),
            batch_argnums=(2, 3), donate_argnums=())
        p, _, _ = dp(gradsync.synchronize_parameters(params),
                     gradsync.synchronize_parameters(opt_state), X, Y)
        outs.append(p)
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


@pytest.mark.slow
def test_mnist_convergence_smoke(flat_runtime):
    """Config-1 milestone: LeNet DP on the 8-device mesh learns (SURVEY §8.3)."""
    mesh = mpi.world_mesh()
    model, params, tx, opt_state, local_loss = _tools()
    dp = gradsync.data_parallel_step(_dp_step_fn(tx, local_loss, mesh),
                                     batch_argnums=(2, 3))
    params = gradsync.synchronize_parameters(params)
    opt_state = gradsync.synchronize_parameters(opt_state)
    X, Y = dutil.synthetic_mnist(2048)
    first = None
    for xb, yb in dutil.batches(X, Y, 256, steps=60):
        params, opt_state, loss = dp(params, opt_state, xb, yb)
        if first is None:
            first = float(loss)
    last = float(loss)
    assert last < 0.25 * first, f"no convergence: {first} -> {last}"


def test_bf16_compression_close_to_exact(flat_runtime):
    mesh = mpi.world_mesh()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    g = np.random.RandomState(0).randn(8, 1024).astype(np.float32)

    def body(compress):
        def f(x):
            return gradsync.synchronize_gradients(
                x, mesh.axis_names, op="mean", compress=compress)
        return jax.jit(shard_map(f, mesh=mesh, in_specs=P(mesh.axis_names),
                                 out_specs=P(), check_vma=False))(g)

    exact = np.asarray(body(None))
    comp = np.asarray(body("bf16"))
    assert comp.dtype == np.float32  # cast back after the wire
    np.testing.assert_allclose(comp, exact, rtol=0.05, atol=5e-3)
    with pytest.raises(ValueError):
        body("int3")


def test_replicate_does_not_alias_template(flat_runtime):
    # Donating the replicated copy must never delete the caller's template
    # (device_put of an on-device array can alias buffers).
    mesh = mpi.world_mesh()
    template = jax.device_put(jnp.arange(16.0))  # on-device original
    rep = gradsync.synchronize_parameters({"w": template})
    # donate the replicated copy through a jitted identity
    f = jax.jit(lambda t: jax.tree.map(lambda a: a + 1, t),
                donate_argnums=(0,))
    _ = f(rep)
    # template must still be alive and readable
    np.testing.assert_allclose(np.asarray(template), np.arange(16.0))
    rep2 = gradsync.synchronize_parameters({"w": template})
    np.testing.assert_allclose(np.asarray(rep2["w"]), np.arange(16.0))


def test_accumulate_gradients_matches_full_batch(flat_runtime):
    # Microbatched accumulation == full-batch gradient for a mean loss
    # (MLP, no batch statistics), and composes with the DP sync.
    import optax
    from torchmpi_tpu.parallel.gradsync import accumulate_gradients

    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(8, 4), jnp.float32),
              "b": jnp.asarray(rng.randn(4), jnp.float32)}
    X = jnp.asarray(rng.randn(16, 8), jnp.float32)
    Y = jnp.asarray(rng.randint(0, 4, size=16), jnp.int32)

    def loss_fn(p, x, y):
        logits = x @ p["w"] + p["b"]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    full_loss, full_g = jax.value_and_grad(loss_fn)(params, X, Y)
    acc_loss, acc_g = jax.jit(
        lambda p, x, y: accumulate_gradients(loss_fn, p, x, y, n_accum=4)
    )(params, X, Y)

    np.testing.assert_allclose(float(acc_loss), float(full_loss),
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(full_g), jax.tree.leaves(acc_g)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)

    with pytest.raises(ValueError, match="divisible"):
        accumulate_gradients(loss_fn, params, X[:15], Y[:15], n_accum=4)

    # n_accum=1 short-circuits to plain value_and_grad.
    l1, g1 = accumulate_gradients(loss_fn, params, X, Y, n_accum=1)
    np.testing.assert_allclose(float(l1), float(full_loss), rtol=1e-6)


# ---------------------------------------------------------------------------
# Backprop-overlapped gradient sync (docs/OVERLAP.md): per-bucket
# allreduces fired inside the backward pass via custom_vjp hooks.
# ---------------------------------------------------------------------------


def _mixed_tree_tools():
    """A small mixed fp32/bf16 MLP: enough leaves/dtypes to force
    several overlap buckets at a tiny byte bound."""
    key = jax.random.PRNGKey(0)
    params = {
        "l1": {"w": jax.random.normal(key, (8, 32), jnp.float32),
               "b": jnp.zeros((32,), jnp.float32)},
        "l2": {"w": jax.random.normal(key, (32, 32)).astype(jnp.bfloat16)},
        "l3": {"w": jax.random.normal(key, (32, 4), jnp.float32)},
    }

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["l1"]["w"] + p["l1"]["b"])
        h = jnp.tanh(h.astype(jnp.bfloat16) @ p["l2"]["w"])
        out = h.astype(jnp.float32) @ p["l3"]["w"]
        return jnp.mean((out - y) ** 2)

    X = np.random.RandomState(0).rand(64, 8).astype(np.float32)
    Y = np.random.RandomState(1).rand(64, 4).astype(np.float32)
    return params, loss_fn, X, Y


def test_overlap_bucket_assignment():
    # Reverse parameter order, dtype-pure buckets, byte bound honored.
    leaves = [
        jnp.zeros((100,), jnp.float32),   # 400 B
        jnp.zeros((10,), jnp.float32),    # 40 B
        jnp.zeros((50,), jnp.bfloat16),   # 100 B
        jnp.zeros((5,), jnp.float32),     # 20 B
    ]
    buckets = gradsync.assign_overlap_buckets(leaves, 256)
    flat = [i for b in buckets for i in b]
    assert flat == [3, 2, 1, 0]  # last leaf fires first
    for b in buckets:
        dts = {str(leaves[i].dtype) for i in b}
        assert len(dts) == 1  # never mixes dtypes in one bucket
    # leaf 0 (400 B > bound) sits alone; leaf 2's dtype break isolates it
    assert [len(b) for b in buckets] == [1, 1, 1, 1]
    # A generous bound merges same-dtype neighbors but never dtypes.
    buckets = gradsync.assign_overlap_buckets(leaves, 1 << 20)
    assert buckets == [[3], [2], [1, 0]]


def test_overlap_matches_sync_bitwise_mixed_dtypes(flat_runtime):
    """Acceptance: the overlapped schedule's gradients equal
    synchronize_gradients BIT-FOR-BIT on a mixed fp32/bf16 tree, and
    the lowered HLO carries one all-reduce per bucket."""
    mesh = mpi.world_mesh()
    axes = tuple(mesh.axis_names)
    params, loss_fn, X, Y = _mixed_tree_tools()

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def step_overlap(p, x, y):
        vag = gradsync.make_overlapped_grad_fn(loss_fn, p, axes,
                                               max_bytes=1024)
        return vag(p, x, y)

    def step_sync(p, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        return loss, gradsync.synchronize_gradients(grads, axes)

    specs = dict(mesh=mesh, in_specs=(P(), P(axes), P(axes)),
                 out_specs=(P(), P()), check_vma=False)
    fo = jax.jit(shard_map(step_overlap, **specs))
    fs = jax.jit(shard_map(step_sync, **specs))
    lo, go = fo(params, X, Y)
    ls, gs = fs(params, X, Y)
    assert float(lo) == float(ls)
    for a, b in zip(jax.tree.leaves(go), jax.tree.leaves(gs)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # One collective per bucket survives lowering (4 buckets at 1 KiB:
    # l3.w | l2.w (bf16) | l1.b | l1.w — dtype breaks + byte bound).
    n_buckets = len(gradsync.assign_overlap_buckets(
        jax.tree.leaves(params), 1024))
    assert fo.lower(params, X, Y).as_text().count(
        "stablehlo.all_reduce") == n_buckets


def test_overlap_dp_step_matches_plain(flat_runtime):
    """End-to-end LeNet DP step: overlapped grads drive the optimizer
    to bit-identical parameters."""
    mesh = mpi.world_mesh()
    axes = tuple(mesh.axis_names)
    model, params, tx, opt_state, local_loss = _tools()
    X, Y = dutil.synthetic_mnist(64, seed=3)

    def dp_plain(p, o, xb, yb):
        loss, grads = jax.value_and_grad(local_loss)(p, xb, yb)
        grads = gradsync.synchronize_gradients(grads, axes)
        u, o = tx.update(grads, o, p)
        return optax.apply_updates(p, u), o, loss

    def dp_over(p, o, xb, yb):
        loss, grads = gradsync.make_overlapped_grad_fn(
            local_loss, p, axes)(p, xb, yb)
        u, o = tx.update(grads, o, p)
        return optax.apply_updates(p, u), o, loss

    outs = []
    for fn in (dp_plain, dp_over):
        dp = gradsync.data_parallel_step(fn, batch_argnums=(2, 3),
                                         donate_argnums=())
        p2, _, loss = dp(gradsync.synchronize_parameters(params),
                         gradsync.synchronize_parameters(opt_state), X, Y)
        outs.append((p2, float(loss)))
    (p_ref, l_ref), (p_over, l_over) = outs
    assert l_ref == l_over
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_over)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_overlap_zero1_presynced_matches(flat_runtime):
    """ZeRO-1 with overlap: the already-reduced grads reach the
    optimizer through a local shard slice (update(presynced=True));
    resulting params match the reduce_scatter path.  Tight allclose,
    not bitwise: psum and psum_scatter may order the cross-device sum
    differently."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from torchmpi_tpu.parallel import zero as pzero

    mesh = mpi.world_mesh()
    axes = tuple(mesh.axis_names)
    model, params, tx, _, local_loss = _tools()
    X, Y = dutil.synthetic_mnist(64, seed=4)
    opt_state = pzero.init(params, tx, axes, mesh=mesh)
    sspecs = pzero.specs_like(opt_state, axes)

    def z_plain(p, o, xb, yb):
        loss, grads = jax.value_and_grad(local_loss)(p, xb, yb)
        p2, o2 = pzero.update(p, grads, o, tx, axes)
        return p2, o2, loss

    def z_over(p, o, xb, yb):
        loss, grads = gradsync.make_overlapped_grad_fn(
            local_loss, p, axes)(p, xb, yb)
        p2, o2 = pzero.update(p, grads, o, tx, axes, presynced=True)
        return p2, o2, loss

    outs = []
    for fn in (z_plain, z_over):
        f = jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(P(), sspecs, P(axes), P(axes)),
            out_specs=(P(), sspecs, P()), check_vma=False))
        p2, _, loss = f(gradsync.synchronize_parameters(params),
                        opt_state, X, Y)
        outs.append((p2, float(loss)))
    (p_ref, l_ref), (p_over, l_over) = outs
    np.testing.assert_allclose(l_ref, l_over, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_over)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def _jaxpr_holding(jaxpr, prim):
    """The (sub)jaxpr whose own equations include primitive ``prim``."""
    from torchmpi_tpu.analysis.events import _subjaxprs

    if any(e.primitive.name == prim for e in jaxpr.eqns):
        return jaxpr
    for e in jaxpr.eqns:
        for v in e.params.values():
            for sub in _subjaxprs(v):
                got = _jaxpr_holding(sub, prim)
                if got is not None:
                    return got
    return None


def _matmuls_behind(jaxpr, eqn):
    """How many dot_general equations ``eqn`` transitively depends on."""
    producer = {id(o): e for e in jaxpr.eqns for o in e.outvars}
    seen, stack = {}, list(eqn.invars)
    while stack:
        e = producer.get(id(stack.pop()))
        if e is not None and id(e) not in seen:
            seen[id(e)] = e
            stack.extend(e.invars)
    return sum(e.primitive.name == "dot_general" for e in seen.values())


def test_overlap_flight_recorder_ordering(flat_runtime):
    """The overlap invariants that hold on the CPU sim whatever the
    host's load.  (a) Dataflow of the traced step: the FIRST-FIRED
    bucket's allreduce depends on only part of the backward pass, so
    communication may start while backward compute is still producing
    the later buckets' gradients (a post-backward sync depends on all of
    it).  (b) Flight ring: every device records one ``grads`` and one
    ``launch`` event per bucket.  The ORDER of ring events is not
    asserted: they come from unordered ``jax.debug.callback``s on eight
    device threads, and nothing but the host scheduler orders two
    callbacks whose operands are ready together."""
    from collections import Counter

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = mpi.world_mesh()
    axes = tuple(mesh.axis_names)
    params, loss_fn, X, Y = _mixed_tree_tools()
    mpi.set_config(obs="metrics")
    try:
        from torchmpi_tpu import obs

        obs.reset()

        def step(p, x, y):
            return gradsync.make_overlapped_grad_fn(
                loss_fn, p, axes, max_bytes=1024)(p, x, y)

        f = jax.jit(shard_map(step, mesh=mesh,
                              in_specs=(P(), P(axes), P(axes)),
                              out_specs=(P(), P()), check_vma=False))
        body = _jaxpr_holding(jax.make_jaxpr(f)(params, X, Y).jaxpr, "psum")
        launches = [e for e in body.eqns if e.primitive.name == "psum"]
        behind = [_matmuls_behind(body, e) for e in launches]
        n_matmuls = sum(e.primitive.name == "dot_general"
                        for e in body.eqns)
        assert len(launches) >= 2  # several buckets, or nothing to hide
        assert behind == sorted(behind), behind  # firing order = depth
        assert behind[0] < n_matmuls, (behind, n_matmuls)
        assert behind[-1] == n_matmuls, (behind, n_matmuls)

        out = f(params, X, Y)
        jax.block_until_ready(out)
        jax.effects_barrier()  # every debug callback has landed
        ov = Counter((e[3], e[4]) for e in obs.recorder().events()
                     if e[2] == "overlap")  # (stage, bucket) -> count
        n_dev = mesh.devices.size
        assert ov == {(stage, b): n_dev for stage in ("grads", "launch")
                      for b in range(len(launches))}, ov
    finally:
        mpi.set_config(obs="off")


def test_overlap_bucket_bytes_from_tuning_plan(flat_runtime, tmp_path):
    """Bucket sizing derives from the tuning-plan size buckets: with a
    plan holding measured allreduce entries for this mesh, the bound
    snaps to the largest measured bucket <= fuse_max_bytes; without
    one it is fuse_max_bytes rounded down to a bucket edge."""
    from torchmpi_tpu import tuning

    mesh = mpi.world_mesh()
    # No plan active: fuse_max_bytes (32 MiB default) -> its own edge.
    assert gradsync.overlap_bucket_bytes(mesh) == 1 << 25
    # Explicit override wins outright.
    mpi.set_config(gradsync_overlap_bytes=12345)
    assert gradsync.overlap_bucket_bytes(mesh) == 12345
    mpi.set_config(gradsync_overlap_bytes=0)
    # Seed a plan with a measured 1 MiB-bucket allreduce entry.
    path = str(tmp_path / "plan.json")
    cache = tuning.PlanCache(path)
    key = tuning.make_fingerprint("allreduce", 1 << 20, np.float32, mesh)
    cache.put(key, tuning.PlanEntry(backend="xla", source="measured"))
    cache.save()
    tuning.configure(path, auto_active=False)
    try:
        assert gradsync.overlap_bucket_bytes(mesh) == 1 << 20
    finally:
        tuning.reset()


# ---- the step span on the profiler's clock (throttle_dispatch) ----------


def _traced_spans(trace_dir, body):
    """Run ``body`` under ``jax.profiler``; -> the host plane's ``tm.*``
    events in order of their start, as ``[(name, stats)]``."""
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
    events = [(e.start_ns, e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith("tm.")]
    return [(name, stats) for _, name, stats in sorted(
        events, key=lambda e: e[0])]


def test_dp_step_writes_one_tm_step_span_a_call(flat_runtime, tmp_path):
    def step_fn(params, opt_state, batch):
        grads = jax.grad(lambda p: ((batch @ p) ** 2).mean())(params)
        grads = gradsync.synchronize_gradients(grads)
        return params - 0.1 * grads, opt_state

    # five calls never fill a window of eight: no throttle span, however
    # slowly the steps run (the CPU default of two would wait for some)
    step = gradsync.data_parallel_step(step_fn, donate_argnums=(),
                                       max_inflight=8)
    state = [jnp.ones((8, 3)), jnp.zeros(())]
    batch = jnp.ones((16, 8))
    state = step(*state, batch)       # call 0: compiles, untraced

    def four_calls():
        s = state
        for _ in range(4):
            s = step(*s, batch)
        jax.block_until_ready(s)

    spans = _traced_spans(tmp_path, four_calls)
    assert [name for name, _ in spans] == ["tm.step"] * 4
    # the stepper's own count, from its first call on
    assert [stats["step_num"] for _, stats in spans] == [1, 2, 3, 4]


@pytest.mark.parametrize("running,held_back", [(False, 0), (True, 3)])
def test_throttle_span_marks_only_a_wait_for_a_running_step(
        tmp_path, running, held_back):
    """``tm.step.throttle`` counts the steps the throttle held back: past
    ``max_inflight`` calls the window is always full, but a token that is
    ready when its turn comes was not waited for."""
    waited = []

    class Token:
        def is_ready(self):
            return not running

        def block_until_ready(self):
            waited.append(self)
            return self

    step = gradsync.throttle_dispatch(lambda: ("out", Token()),
                                      max_inflight=2)
    spans = _traced_spans(
        tmp_path, lambda: [step() for _ in range(5)])
    names = [name for name, _ in spans]
    assert names.count("tm.step") == 5
    assert names.count("tm.step.throttle") == held_back
    assert len(waited) == 3     # calls 3, 4, 5 each retire the oldest token
    assert [s["step_num"] for n, s in spans if n == "tm.step"] == [
        0, 1, 2, 3, 4]
    if held_back:               # the wait comes before that call's dispatch
        assert names[:4] == ["tm.step", "tm.step", "tm.step.throttle",
                             "tm.step"]
