"""CollectivePlan cache behavior (torchmpi_tpu/planner.py).

The dispatch-path planner's contract (docs/PLANNER.md): plan once per
(op, tree structure, mesh, config epoch), replay thereafter —
hit/miss on same-structure different-values calls, invalidation on
mesh change / config-epoch bump / clear_cache(), plan reuse across the
eager and in-axis entry points, and for every routed consumer (eager,
in-axis, gradsync, overlap, ZeRO) the same results as a reference that
shares no code with the library: the plain ``lax`` collective per leaf
under the same ``shard_map``, NumPy, or optax on the unsharded tree.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

import torchmpi_tpu as mpi
from torchmpi_tpu import planner
from torchmpi_tpu.parallel import gradsync, zero


def rank_major(elems=32, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    return rng.rand(8, elems).astype(dtype)


def mixed_tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "a": jnp.asarray(rng.randn(8, 4), np.float32),
        "b": jnp.asarray(rng.randn(8, 4), jnp.bfloat16),
        "c": jnp.asarray(rng.randn(8, 2), np.float32),
    }


AXES = ("dcn", "ici")


@pytest.fixture()
def planned_runtime(flat_runtime):
    planner.reset_stats()
    return flat_runtime


def _replicated(mesh, body, *args):
    """``body`` under shard_map on replicated arguments: how every
    in-axis case below runs the library and its plain reference."""
    return jax.jit(shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False))(*args)


def _assert_trees_bit_equal(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Hit/miss + replay
# ---------------------------------------------------------------------------


def test_eager_hit_on_same_structure_different_values(planned_runtime):
    x1, x2 = rank_major(seed=1), rank_major(seed=2)
    out1 = np.asarray(mpi.allreduce(x1))
    st = planner.stats()
    assert st["misses"] == 1 and st["hits"] == 0
    out2 = np.asarray(mpi.allreduce(x2))
    st = planner.stats()
    assert st["misses"] == 1 and st["hits"] == 1  # same plan, new values
    np.testing.assert_allclose(out1[0], x1.sum(axis=0), rtol=1e-5)
    np.testing.assert_allclose(out2[0], x2.sum(axis=0), rtol=1e-5)


def test_eager_new_shape_or_dtype_is_new_plan(planned_runtime):
    mpi.allreduce(rank_major(32))
    mpi.allreduce(rank_major(64))            # new shape
    mpi.allreduce(rank_major(32, np.float16))  # new dtype
    assert planner.stats()["misses"] == 3


def test_eager_bitwise_vs_plain_reference(planned_runtime):
    """Bit-equal throughout: the ``xla`` backend issues the same one
    collective as the plain ``lax`` call, a broadcast copies, and the
    ``host`` backend reduces with NumPy's own sum."""
    mesh = planned_runtime
    x = rank_major()

    def plain(fn):
        return np.asarray(jax.jit(shard_map(
            lambda xs: fn(xs[0])[None], mesh=mesh, in_specs=P(AXES),
            out_specs=P(AXES), check_vma=False))(x))

    for got, want in (
            (mpi.allreduce(x), plain(lambda v: lax.psum(v, AXES))),
            (mpi.broadcast(x, root=2), np.broadcast_to(x[2], x.shape)),
            (mpi.reduce_scatter(x),
             plain(lambda v: lax.psum_scatter(v, AXES, scatter_dimension=0,
                                              tiled=True))),
            (mpi.allreduce(x, backend="host"),
             np.broadcast_to(x.sum(axis=0), x.shape))):
        np.testing.assert_array_equal(np.asarray(got), want)


def test_in_axis_plan_reuse_across_retraces(planned_runtime):
    mesh = planned_runtime
    tree = mixed_tree()

    def body(t):
        return mpi.collectives.allreduce_in_axis(t, ("dcn", "ici"))

    planner.reset_stats()
    r1 = jax.jit(shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False))(tree)
    assert planner.stats()["misses"] == 1
    # A fresh jit retraces; the in-axis plan replays (hit, no rebuild).
    r2 = jax.jit(shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False))(tree)
    st = planner.stats()
    assert st["misses"] == 1 and st["hits"] >= 1
    for a, b in zip(jax.tree.leaves(r1), jax.tree.leaves(r2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_in_axis_bitwise_vs_plain_lax(planned_runtime):
    """The fused buckets against one plain collective per leaf.
    Bit-equal: the reductions are elementwise, so repacking the leaves
    changes no element's cross-device order, and no dtype is promoted
    on the wire (the bf16 leaf reduces in bf16 on both sides)."""
    mesh = planned_runtime
    tree = mixed_tree()
    C = mpi.collectives
    for verb, kw, plain in (
            (C.allreduce_in_axis, {"op": "sum"},
             lambda v: lax.psum(v, AXES)),
            (C.reduce_scatter_in_axis, {},
             lambda v: lax.psum_scatter(v, AXES, scatter_dimension=0,
                                        tiled=True)),
            (C.allgather_in_axis, {},
             lambda v: lax.all_gather(v, AXES, axis=0, tiled=False))):
        _assert_trees_bit_equal(
            _replicated(mesh, lambda t: verb(t, AXES, **kw), tree),
            _replicated(mesh, lambda t: jax.tree.map(plain, t), tree))
    # A broadcast of a replicated tree is the tree (NumPy's copy).
    _assert_trees_bit_equal(
        _replicated(mesh, lambda t: C.broadcast_in_axis(t, AXES, root=1),
                    tree),
        jax.tree.map(np.asarray, tree))


@pytest.mark.parametrize("entry", ["allreduce_in_axis",
                                   "synchronize_gradients"])
@pytest.mark.parametrize("case", ["python_scalar_leaf", "empty_tree"])
def test_in_axis_trees_without_array_leaves(planned_runtime, entry, case):
    """A Python-scalar leaf is planned as the array ``lax.psum`` makes
    of it (one miss, then hits); an empty tree comes back as it is and
    plans nothing."""
    mesh = planned_runtime
    w = jnp.asarray(np.random.RandomState(3).randn(8, 4), np.float32)
    if entry == "allreduce_in_axis":
        verb = lambda t: mpi.collectives.allreduce_in_axis(t, AXES)  # noqa: E731
        plain = lambda v: lax.psum(v, AXES)  # noqa: E731
    else:
        verb = lambda t: gradsync.synchronize_gradients(t, AXES)  # noqa: E731
        plain = lambda v: lax.pmean(v, AXES)  # noqa: E731
    if case == "empty_tree":
        empty = {"a": {}, "b": []}
        assert _replicated(mesh, lambda v: verb(empty), w) == empty
        assert planner.stats()["misses"] == 0 and not planner.describe()
        return

    def tree(v):   # built in the body: jit would make the 2.5 an array
        return {"w": v, "s": 2.5}

    got = _replicated(mesh, lambda v: verb(tree(v)), w)
    assert planner.stats()["misses"] == 1
    _assert_trees_bit_equal(
        got, _replicated(mesh, lambda v: jax.tree.map(plain, tree(v)), w))
    _replicated(mesh, lambda v: verb(tree(v)), w)   # a fresh jit retraces
    st = planner.stats()
    assert st["misses"] == 1 and st["hits"] >= 1


def test_eager_and_in_axis_entry_points_share_the_table(planned_runtime):
    """One table serves both entry points: each keys its own kind (an
    eager rank-major program is not an in-axis fragment) and replays
    independently."""
    mesh = planned_runtime
    x = rank_major()
    planner.reset_stats()
    mpi.allreduce(x)

    def body(v):
        return mpi.collectives.allreduce_in_axis(v, ("dcn", "ici"))

    jax.jit(shard_map(body, mesh=mesh, in_specs=P(("dcn", "ici")),
                      out_specs=P(("dcn", "ici")),
                      check_vma=False))(jnp.asarray(x))
    kinds = {r["kind"] for r in planner.describe()}
    assert "eager" in kinds and any(k.startswith("in_axis")
                                    for k in kinds)
    # Both replay on repeat — no cross-entry-point interference.
    planner.reset_stats()
    mpi.allreduce(x)
    jax.jit(shard_map(body, mesh=mesh, in_specs=P(("dcn", "ici")),
                      out_specs=P(("dcn", "ici")),
                      check_vma=False))(jnp.asarray(x))
    assert planner.stats()["misses"] == 0


# ---------------------------------------------------------------------------
# Invalidation: config epoch, clear_cache, mesh identity
# ---------------------------------------------------------------------------


def test_set_config_bumps_epoch_and_replans(planned_runtime):
    x = rank_major()
    mpi.allreduce(x)
    e0 = mpi.runtime.config_epoch()
    planner.reset_stats()
    mpi.set_config(custom_min_bytes=128)
    assert mpi.runtime.config_epoch() == e0 + 1
    mpi.allreduce(x)
    assert planner.stats()["misses"] == 1  # re-planned, not replayed


def test_set_config_backend_switch_replans_regression(hier_runtime):
    """The latent staleness bug (ISSUE 7 satellite): switching the
    backend live must invalidate the planned implementation — the next
    call re-plans and resolves the NEW backend."""
    planner.reset_stats()
    x = rank_major()
    mpi.allreduce(x)
    assert [r["backend"] for r in planner.describe()] == ["xla"]
    mpi.set_config(backend="hierarchical", custom_min_bytes=0)
    out = np.asarray(mpi.allreduce(x))
    rows = planner.describe()
    assert [r["backend"] for r in rows] == ["hierarchical"]
    np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-5)


def test_set_config_fuse_bytes_replans_regression(planned_runtime):
    """Flipping fuse_max_bytes live re-plans the in-axis fusion
    decision: the same tree goes from fused buckets to per-leaf
    launches (lowered HLO collective count changes)."""
    mesh = planned_runtime
    tree = mixed_tree()

    def body(t):
        return mpi.collectives.allreduce_in_axis(t, ("dcn", "ici"))

    def launches():
        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False))
        return fn.lower(tree).as_text().count("stablehlo.all_reduce")

    assert launches() == 2  # two dtype groups, fused
    mpi.set_config(fuse_max_bytes=0)
    assert launches() == 3  # per-leaf: the stale fused plan is gone
    mpi.set_config(fuse_max_bytes=32 * 1024 * 1024)
    assert launches() == 2


def test_selector_reregister_strands_stale_plans(planned_runtime):
    """Re-registering an implementation at runtime must re-plan (the
    selector generation is part of every key — the planner analog of
    a cache keyed on the resolved impl object)."""
    from torchmpi_tpu import selector

    x = rank_major()
    mpi.allreduce(x)
    planner.reset_stats()
    impl = selector.available("allreduce")["xla"]
    selector.register("allreduce", "xla", impl)  # same fn, new generation
    out = np.asarray(mpi.allreduce(x))
    assert planner.stats()["misses"] == 1
    np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-5)


def test_clear_cache_is_the_invalidation_point(planned_runtime):
    mpi.allreduce(rank_major())
    assert planner.stats()["entries"] == 1
    mpi.collectives.clear_cache()
    assert planner.stats()["entries"] == 0
    assert planner.stats()["invalidations"] >= 1


def test_mesh_change_invalidates():
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1))
    x = rank_major()
    mpi.allreduce(x)
    assert planner.stats()["entries"] >= 1
    mpi.stop()  # mesh teardown routes through the invalidation point
    assert planner.stats()["entries"] == 0
    mesh2 = mpi.init(mpi.Config(dcn_size=2))
    planner.reset_stats()
    out = np.asarray(mpi.allreduce(x))
    assert planner.stats()["misses"] == 1  # re-planned for the new mesh
    np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-5)
    assert mesh2.shape["dcn"] == 2
    mpi.stop()


def test_pushed_communicator_is_its_own_key(planned_runtime):
    """A pushed sub-communicator changes the dispatch mesh without any
    invalidation: the mesh object is part of the key, so the sub-mesh
    call plans separately and the world plan keeps replaying."""
    x = rank_major()
    mpi.allreduce(x)
    planner.reset_stats()
    devs = list(planned_runtime.devices.flat)[:4]
    with mpi.communicator("half", devices=devs, shape={"ici": 4}):
        out = np.asarray(mpi.allreduce(x[:4]))
    np.testing.assert_allclose(out[0], x[:4].sum(axis=0), rtol=1e-5)
    assert planner.stats()["misses"] == 1
    mpi.allreduce(x)  # world plan survived the push/pop
    assert planner.stats()["hits"] >= 1


# ---------------------------------------------------------------------------
# gradsync + ZeRO consumers
# ---------------------------------------------------------------------------


def test_gradsync_bucketed_planned_bitwise(planned_runtime):
    """Three buckets against ``lax.pmean`` per leaf: bit-equal, the
    mean being elementwise like the sum (see the in-axis case)."""
    mesh = planned_runtime
    tree = mixed_tree()

    def run():
        return _replicated(
            mesh, lambda t: gradsync.synchronize_gradients(t, AXES,
                                                           n_buckets=3),
            tree)

    planner.reset_stats()
    planned = run()
    assert any(r["kind"] == "gradsync" for r in planner.describe())
    _assert_trees_bit_equal(
        planned,
        _replicated(mesh, lambda t: jax.tree.map(
            lambda v: lax.pmean(v, AXES), t), tree))
    # Second step build replays the gradsync plan.
    planner.reset_stats()
    run()
    assert planner.stats()["misses"] == 0


def test_overlap_grad_fn_decision_planned(planned_runtime):
    mesh = planned_runtime
    params = {"w1": jnp.ones((16, 16), jnp.float32),
              "w2": jnp.ones((16, 16), jnp.float32)}

    def loss(p, x):
        return jnp.mean((x @ p["w1"] @ p["w2"]) ** 2)

    x = np.random.RandomState(0).rand(8, 16).astype(np.float32)

    def run():
        def body(p, xb):
            return gradsync.make_overlapped_grad_fn(
                loss, p, ("dcn", "ici"), max_bytes=16 * 16 * 4)(p, xb)

        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(), P(("dcn", "ici"))),
            out_specs=(P(), P()), check_vma=False))(params, x)

    planner.reset_stats()
    l1, g1 = run()
    assert any(r["kind"] == "overlap" for r in planner.describe())
    misses_after_first = planner.stats()["misses"]
    l2, g2 = run()  # same structure: the overlap decision replays
    assert planner.stats()["misses"] == misses_after_first

    # Reference: jax.value_and_grad, then lax.pmean per leaf.  Bit-equal:
    # the bucket syncs are identities in the forward pass, so each
    # device's gradient is the same computation, and the mean of a
    # concatenation is the concatenation of the means.
    def plain(p, xb):
        loss_v, g = jax.value_and_grad(loss)(p, xb)
        return loss_v, jax.tree.map(lambda v: lax.pmean(v, AXES), g)

    l3, g3 = jax.jit(shard_map(
        plain, mesh=mesh, in_specs=(P(), P(AXES)), out_specs=(P(), P()),
        check_vma=False))(params, x)
    _assert_trees_bit_equal((l1, g1), (l3, g3))


def test_zero_update_planned_bitwise(planned_runtime):
    mesh = planned_runtime
    params = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
              "b": jnp.ones((8,), jnp.float32)}
    grads = jax.tree.map(lambda p: p * 0.1, params)
    tx = optax.sgd(0.1)
    axes = ("dcn", "ici")

    def run():
        opt_state = zero.init(params, tx, axes, mesh=mesh)

        def body(p, g, s):
            return zero.update(p, g, s, tx, axes)

        specs = zero.state_specs(params, tx, axes, mesh=mesh)
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(), P(), specs),
            out_specs=(P(), specs), check_vma=False))(params, grads,
                                                      opt_state)

    planner.reset_stats()
    p1, _ = run()
    assert any(r["kind"] == "flatspec" for r in planner.describe())
    # Reference: optax on the unsharded tree, as tests/test_zero.py does
    # (the gradients are replicated, so their mean is themselves).  Its
    # tolerance too: the sharded update runs on a flat float32 view
    # inside one compiled program, whose multiply-add XLA may contract
    # where the eager reference rounds twice.
    updates, _ = tx.update(grads, tx.init(params), params)
    p2 = optax.apply_updates(params, updates)
    for k in params:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# Obs integration
# ---------------------------------------------------------------------------


def test_plan_obs_counters_and_flight_event(tmp_path):
    mpi.stop()
    mpi.init(mpi.Config(dcn_size=1, obs="metrics",
                        obs_dir=str(tmp_path)))
    try:
        from torchmpi_tpu import obs

        obs.reset()
        x = rank_major()
        mpi.allreduce(x)
        mpi.allreduce(x)
        reg = obs.registry()
        assert reg.counter_total("tm_plan_miss_total") == 1
        assert reg.counter_total("tm_plan_hit_total") == 1
        hist = [r for r in reg.snapshot()
                if r["name"] == "tm_plan_build_seconds"]
        assert hist and hist[0]["count"] == 1
        assert any(e[2] == "plan" for e in obs.recorder().events())
    finally:
        from torchmpi_tpu import obs

        obs.reset()
        mpi.stop()


def test_plan_off_mode_no_obs_branches(planned_runtime):
    """With obs off, the plan record carries obs=False and the replay
    closure holds no recorder at all (the zero-branch claim)."""
    mpi.allreduce(rank_major())
    (row,) = planner.describe()
    assert row["obs"] is False


def test_describe_rows_shape(planned_runtime):
    mpi.allreduce(rank_major())
    (row,) = planner.describe()
    for field in ("kind", "op", "backend", "nbytes", "launches", "epoch",
                  "build_ms", "hits", "staged", "obs", "faults",
                  "analysis"):
        assert field in row


def test_plan_tool_dump_live_prints_the_table(planned_runtime, capsys):
    """``scripts/plan_tool.py dump-live`` (its own warm-up dispatches,
    each made twice) prints one row a plan and the hit/miss line."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import plan_tool

    assert plan_tool.main(["dump-live"]) == 0
    out = capsys.readouterr().out
    assert "3 live plan(s); 3 hits / 3 misses" in out
    assert out.count("eager") == 3 and "broadcast" in out
