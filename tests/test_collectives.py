"""Collective correctness sweep.

Rebuild of the reference's ``test/collectives*.lua`` strategy (SURVEY.md §5):
sweep op x dtype x size (incl. non-power-of-two and sizes straddling the
chunking cutover) x {sync,async} x {flat,hierarchical}.  Oracle: fill each
rank's tensor as f(rank) and compare against the closed-form numpy reduction —
no mocks; the 8-device mesh is the fixture.
"""

import numpy as np
import pytest

import torchmpi_tpu as mpi
from torchmpi_tpu import collectives, selector

N = 8
SIZES = [1, 7, 128, 1000, 4096]  # non-pow2 + straddling shapes
DTYPES = [np.float32, np.int32]


def rank_data(size, dtype, n=N):
    # f(rank): distinct per rank, exact in float32.
    base = np.arange(size, dtype=dtype) % 13
    return np.stack([(base + r).astype(dtype) for r in range(n)])


# ---------------------------------------------------------------------------
# Flat mesh sweep (xla backend)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_allreduce_sum(flat_runtime, size, dtype):
    x = rank_data(size, dtype)
    out = np.asarray(mpi.allreduce(x))
    expect = x.sum(axis=0)
    for r in range(N):
        np.testing.assert_allclose(out[r], expect)


@pytest.mark.parametrize("op,npf", [("max", np.max), ("min", np.min)])
def test_allreduce_maxmin(flat_runtime, op, npf):
    x = rank_data(100, np.float32)
    out = np.asarray(mpi.allreduce(x, op=op))
    expect = npf(x, axis=0)
    for r in range(N):
        np.testing.assert_allclose(out[r], expect)


def test_allreduce_mean(flat_runtime):
    x = rank_data(64, np.float32)
    out = np.asarray(mpi.allreduce(x, op="mean"))
    np.testing.assert_allclose(out[0], x.mean(axis=0), rtol=1e-6)


@pytest.mark.parametrize("root", [0, 3, 7])
def test_broadcast(flat_runtime, root):
    x = rank_data(33, np.float32)
    out = np.asarray(mpi.broadcast(x, root=root))
    for r in range(N):
        np.testing.assert_allclose(out[r], x[root])


@pytest.mark.parametrize("root", [0, 3, 7])
@pytest.mark.parametrize("size", [4096, 5000])
def test_broadcast_chain_path(flat_runtime, root, size):
    # Above chunk_bytes the broadcast takes the pipelined-chain schedule
    # (~1x wire instead of masked-psum's ~2x); must be bit-exact with the
    # small-path result, including non-divisible sizes (padding).
    mpi.set_config(chunk_bytes=1024)
    x = rank_data(size, np.float32)
    out = np.asarray(mpi.broadcast(x, root=root))
    for r in range(N):
        np.testing.assert_array_equal(out[r], x[root])


def test_broadcast_chain_on_2d_mesh(hier_runtime):
    mpi.set_config(chunk_bytes=1024)
    x = rank_data(4096, np.float32)
    out = np.asarray(mpi.broadcast(x, root=5))
    for r in range(N):
        np.testing.assert_array_equal(out[r], x[5])


@pytest.mark.parametrize("root", [0, 5])
def test_reduce(flat_runtime, root):
    x = rank_data(50, np.float32)
    out = np.asarray(mpi.reduce(x, root=root))
    np.testing.assert_allclose(out[root], x.sum(axis=0))
    for r in range(N):
        if r != root:
            np.testing.assert_allclose(out[r], x[r])  # untouched, like MPI_Reduce


def test_allgather(flat_runtime):
    x = rank_data(17, np.float32)
    out = np.asarray(mpi.allgather(x))
    assert out.shape == (N, N, 17)
    for r in range(N):
        np.testing.assert_allclose(out[r], x)


def test_reduce_scatter(flat_runtime):
    x = rank_data(64, np.float32)
    out = np.asarray(mpi.reduce_scatter(x))
    expect = x.sum(axis=0).reshape(N, -1)
    for r in range(N):
        np.testing.assert_allclose(out[r], expect[r])


@pytest.mark.parametrize("root", [0, 4])
def test_gather(flat_runtime, root):
    # MPI_Gather: root's slice is the stack of all ranks' tensors; non-root
    # slices are zeros (the defined SPMD analog of "untouched").
    x = rank_data(21, np.float32)
    out = np.asarray(mpi.gather(x, root=root))
    assert out.shape == (N, N, 21)
    np.testing.assert_allclose(out[root], x)
    for r in range(N):
        if r != root:
            np.testing.assert_allclose(out[r], np.zeros_like(x))


@pytest.mark.parametrize("root", [0, 6])
@pytest.mark.parametrize("size", [8, 64, 1000 * 8])
def test_scatter(flat_runtime, root, size):
    # MPI_Scatter: rank i receives chunk i of root's tensor.
    x = rank_data(size, np.float32)
    out = np.asarray(mpi.scatter(x, root=root))
    expect = x[root].reshape(N, -1)
    assert out.shape == (N, size // N)
    for r in range(N):
        np.testing.assert_allclose(out[r], expect[r])


def test_scatter_indivisible(flat_runtime):
    with pytest.raises(Exception):
        mpi.scatter(rank_data(7, np.float32))


@pytest.mark.parametrize("root", [0, 4])
def test_gather_chain_large(flat_runtime, root):
    # Above the chunk_bytes cutover gather takes the convergecast chain
    # (O(size) wire, VERDICT r2 weak #4) — same contract as the masked
    # form.
    mpi.set_config(chunk_bytes=1024)
    x = rank_data(4096, np.float32)  # 16 KiB/rank >= cutover
    out = np.asarray(mpi.gather(x, root=root))
    assert out.shape == (N, N, 4096)
    np.testing.assert_allclose(out[root], x)
    for r in range(N):
        if r != root:
            np.testing.assert_allclose(out[r], np.zeros_like(x))


@pytest.mark.parametrize("root", [0, 6])
def test_scatter_chain_large(flat_runtime, root):
    # Above the cutover scatter streams farthest-destination-first down
    # the chain; every rank must still land exactly its own chunk.
    mpi.set_config(chunk_bytes=1024)
    size = 1024 * N
    x = rank_data(size, np.float32)
    out = np.asarray(mpi.scatter(x, root=root))
    expect = x[root].reshape(N, -1)
    assert out.shape == (N, size // N)
    for r in range(N):
        np.testing.assert_allclose(out[r], expect[r])


@pytest.mark.parametrize("root", [0, 5])
def test_hier_scatter_chain_large(hier_runtime, root):
    # Two-level chain scatter: dcn chain delivers slice blocks (one DCN
    # crossing per block), ici chain splits within each slice.
    mpi.set_config(chunk_bytes=1024)
    size = 1024 * N
    x = rank_data(size, np.float32)
    out = np.asarray(mpi.scatter(x, root=root, backend="hierarchical"))
    expect = x[root].reshape(N, -1)
    for r in range(N):
        np.testing.assert_allclose(out[r], expect[r])


@pytest.mark.parametrize("root", [0, 5])
def test_hier_gather_chain_large(hier_runtime, root):
    # Two-level chain gather: ici convergecast to slice leaders, then one
    # dcn chain — each tensor crosses the dcn level at most once.
    mpi.set_config(chunk_bytes=1024)
    x = rank_data(4096, np.float32)
    g = np.asarray(mpi.gather(x, root=root, backend="hierarchical"))
    np.testing.assert_allclose(g[root], x)
    for r in range(N):
        if r != root:
            np.testing.assert_allclose(g[r], np.zeros_like(x))


@pytest.mark.parametrize("src,dst", [(0, 1), (2, 7), (6, 3)])
def test_sendreceive(flat_runtime, src, dst):
    x = rank_data(21, np.float32)
    out = np.asarray(mpi.sendreceive(x, src=src, dst=dst))
    np.testing.assert_allclose(out[dst], x[src])
    for r in range(N):
        if r != dst:
            np.testing.assert_allclose(out[r], x[r])


def test_alltoall(flat_runtime):
    x = rank_data(N * 3, np.float32)  # each rank: 8 blocks of 3
    out = np.asarray(mpi.alltoall(x))
    blocks = x.reshape(N, N, 3)
    expect = np.transpose(blocks, (1, 0, 2)).reshape(N, N * 3)
    np.testing.assert_allclose(out, expect)


def test_multidim_tensor(flat_runtime):
    x = np.stack([np.full((4, 5, 3), float(r + 1), np.float32)
                  for r in range(N)])
    out = np.asarray(mpi.allreduce(x))
    np.testing.assert_allclose(out[0], np.full((4, 5, 3), 36.0))


def test_pytree(flat_runtime):
    tree = {"a": rank_data(16, np.float32),
            "b": [rank_data(9, np.float32)]}
    out = mpi.allreduce(tree)
    np.testing.assert_allclose(np.asarray(out["a"])[0],
                               tree["a"].sum(axis=0))
    np.testing.assert_allclose(np.asarray(out["b"][0])[3],
                               tree["b"][0].sum(axis=0))


def test_wrong_leading_axis(flat_runtime):
    with pytest.raises(ValueError):
        mpi.allreduce(np.zeros((3, 4), np.float32))


# ---------------------------------------------------------------------------
# Async (reference: mpi.async.* + syncHandle; SURVEY §4.4)
# ---------------------------------------------------------------------------


def test_async_allreduce(flat_runtime):
    x = rank_data(256, np.float32)
    h = mpi.async_.allreduce(x)
    assert isinstance(h, mpi.AsyncHandle)
    out = np.asarray(mpi.sync_handle(h))
    np.testing.assert_allclose(out[0], x.sum(axis=0))
    assert h.done


def test_async_ordering_same_tensor(flat_runtime):
    # Two async collectives chained on the same data must respect order
    # (the reference's §4.4 correctness subtlety; JAX data deps enforce it).
    x = rank_data(64, np.float32)
    h1 = mpi.async_.allreduce(x)
    h2 = mpi.async_.allreduce(h1.wait())
    out = np.asarray(mpi.sync_handle(h2))
    np.testing.assert_allclose(out[0], x.sum(axis=0) * N)


def test_async_many_inflight(flat_runtime):
    xs = [rank_data(128, np.float32) + i for i in range(6)]
    handles = [mpi.async_.allreduce(x) for x in xs]
    for x, h in zip(xs, handles):
        np.testing.assert_allclose(np.asarray(h.wait())[0], x.sum(axis=0))


def test_async_staged_matches_sync_bitwise(flat_runtime):
    # The staged-host handle dispatches on the background worker; its
    # result must equal the synchronous staged exchange bit-for-bit.
    for op_fn, sync_fn in [
        (mpi.async_.allreduce, mpi.allreduce),
        (mpi.async_.broadcast, mpi.broadcast),
        (mpi.async_.reduce_scatter, mpi.reduce_scatter),
    ]:
        x = rank_data(1000, np.float32)
        h = op_fn(x, backend="host")
        assert isinstance(h, mpi.AsyncHandle)
        out = np.asarray(h.wait())
        ref = np.asarray(sync_fn(x, backend="host"))
        assert np.array_equal(out, ref)
        assert h.done and h.error is None


def test_async_direct_matches_sync_bitwise(flat_runtime):
    x = rank_data(512, np.float32)
    out = np.asarray(mpi.async_.allreduce(x).wait())
    assert np.array_equal(out, np.asarray(mpi.allreduce(x)))


def test_wait_all_returns_input_order(flat_runtime):
    # Mixed direct + staged handles; the staged ones complete on the
    # worker in FIFO order, but wait_all must return results in INPUT
    # order regardless of completion order.
    xs = [rank_data(64, np.float32) + i for i in range(5)]
    handles = [mpi.async_.allreduce(x, backend="host" if i % 2 else None)
               for i, x in enumerate(xs)]
    outs = mpi.wait_all(handles)
    assert len(outs) == len(xs)
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(np.asarray(o)[0], x.sum(axis=0))
    assert all(h.done for h in handles)


def test_wait_all_surfaces_first_error(flat_runtime):
    good = rank_data(64, np.float32)
    bad = rank_data(3, np.float32).reshape(N, 3)  # 3 % 8 != 0
    hs = [mpi.async_.allreduce(good, backend="host"),
          mpi.async_.scatter(bad, backend="host"),
          mpi.async_.allreduce(good, backend="host")]
    with pytest.raises(ValueError, match="divisible"):
        mpi.wait_all(hs)
    # The batch was still driven to completion: the good handles hold
    # usable results, the bad one keeps its error.
    assert all(h.done for h in hs)
    assert hs[1].error is not None
    np.testing.assert_allclose(np.asarray(hs[0].wait())[0],
                               good.sum(axis=0))


def test_async_done_surfaces_error(flat_runtime):
    # A FAILED computation polls done=True with its error exposed —
    # never the old never-done-forever masking — and wait() re-raises.
    import time

    bad = rank_data(3, np.float32).reshape(N, 3)
    h = mpi.async_.scatter(bad, backend="host")
    for _ in range(500):
        if h.done:
            break
        time.sleep(0.01)
    assert h.done
    assert isinstance(h.error, ValueError)
    with pytest.raises(ValueError, match="divisible"):
        h.wait()
    with pytest.raises(ValueError, match="divisible"):
        h.wait()  # every wait re-raises; no half-initialized buffers


def test_async_staged_donate_releases_input(flat_runtime):
    import jax

    x = jax.device_put(rank_data(256, np.float32))
    ref = np.asarray(mpi.allreduce(np.asarray(x), backend="host"))
    h = mpi.async_.allreduce(x, backend="host", donate=True)
    out = np.asarray(h.wait())
    assert np.array_equal(out, ref)
    assert x.is_deleted()  # the staged worker consumed the device buffer


def test_async_in_axis_deferred_wait(flat_runtime):
    # Handle-returning in-axis verb inside shard_map: dispatch at the
    # call, data dependency deferred to wait() — the overlap window.
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = mpi.world_mesh()
    axes = tuple(mesh.axis_names)

    def body(x):
        h = mpi.async_in_axis.allreduce(x, axes, op="sum")
        y = x * 3.0  # compute issued between dispatch and wait
        return h.wait() + y

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(axes),),
                           out_specs=P(axes), check_vma=False))
    X = rank_data(16, np.float32)
    out = np.asarray(fn(X))
    np.testing.assert_allclose(out, X.sum(axis=0) + X * 3.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# Hierarchical backend on the 2x4 mesh (reference: custom hierarchical path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("size", [1, 7, 128, 1000])
def test_hier_allreduce_matches_flat(hier_runtime, size):
    x = rank_data(size, np.float32)
    flat = np.asarray(mpi.allreduce(x, backend="xla"))
    hier = np.asarray(mpi.allreduce(x, backend="hierarchical"))
    np.testing.assert_allclose(hier, flat, rtol=1e-6)


@pytest.mark.parametrize("op", ["max", "min", "mean"])
def test_hier_allreduce_ops(hier_runtime, op):
    x = rank_data(96, np.float32)
    flat = np.asarray(mpi.allreduce(x, op=op, backend="xla"))
    hier = np.asarray(mpi.allreduce(x, op=op, backend="hierarchical"))
    np.testing.assert_allclose(hier, flat, rtol=1e-6)


@pytest.mark.parametrize("root", [0, 3, 5])
def test_hier_broadcast(hier_runtime, root):
    x = rank_data(40, np.float32)
    out = np.asarray(mpi.broadcast(x, root=root, backend="hierarchical"))
    for r in range(N):
        np.testing.assert_allclose(out[r], x[root])


@pytest.mark.parametrize("root", [0, 6])
def test_hier_reduce(hier_runtime, root):
    x = rank_data(40, np.float32)
    out = np.asarray(mpi.reduce(x, root=root, backend="hierarchical"))
    np.testing.assert_allclose(out[root], x.sum(axis=0))


@pytest.mark.parametrize("root", [0, 5])
def test_hier_gather_scatter(hier_runtime, root):
    x = rank_data(16, np.float32)
    g = np.asarray(mpi.gather(x, root=root, backend="hierarchical"))
    np.testing.assert_allclose(g[root], x)
    for r in range(N):
        if r != root:
            np.testing.assert_allclose(g[r], np.zeros_like(x))
    s = np.asarray(mpi.scatter(x, root=root, backend="hierarchical"))
    np.testing.assert_allclose(s.reshape(-1), x[root])


def test_hier_allgather(hier_runtime):
    x = rank_data(12, np.float32)
    out = np.asarray(mpi.allgather(x, backend="hierarchical"))
    for r in range(N):
        np.testing.assert_allclose(out[r], x)


def test_hierarchical_config_default(hier_runtime):
    # config.hierarchical=True routes allreduce through the 2-level path.
    mpi.set_config(hierarchical=True, backend="hierarchical",
                   custom_min_bytes=0)
    x = rank_data(200, np.float32)
    out = np.asarray(mpi.allreduce(x))
    np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-6)


def test_size_cutover_falls_back(hier_runtime):
    # Below custom_min_bytes the selector must fall back to the stock path
    # (the reference's size cutover constants).
    mpi.set_config(backend="hierarchical", custom_min_bytes=1 << 20)
    x = rank_data(8, np.float32)  # tiny
    out = np.asarray(mpi.allreduce(x))
    np.testing.assert_allclose(out[0], x.sum(axis=0))


def test_hier_on_flat_mesh_falls_back(flat_runtime):
    # 1x8 mesh: hierarchical degenerates; selector silently uses xla, like
    # the reference when NCCL was compiled out.
    x = rank_data(64, np.float32)
    out = np.asarray(mpi.allreduce(x, backend="hierarchical"))
    np.testing.assert_allclose(out[0], x.sum(axis=0))


# ---------------------------------------------------------------------------
# Selector introspection (reference: mpi.collectiveAvailability)
# ---------------------------------------------------------------------------


def test_selector_availability():
    avail = mpi.selector.available()
    assert "xla" in avail["allreduce"]
    assert "hierarchical" in avail["allreduce"]
    assert "xla" in avail["sendreceive"]


def test_selector_unknown_op():
    with pytest.raises(KeyError):
        mpi.selector.select("nope", "xla")


# ---------------------------------------------------------------------------
# Regressions from review: cache invalidation on backend switch; op guard.
# ---------------------------------------------------------------------------


def test_backend_switch_after_compile(hier_runtime):
    # Compiling the xla path must not pin later calls after set_config
    # switches the backend (cache key includes the resolved impl).
    x = rank_data(1000, np.float32)
    out1 = np.asarray(mpi.allreduce(x))  # xla default
    mpi.set_config(backend="hierarchical", custom_min_bytes=0)
    before = len(collectives._jit_cache)
    out2 = np.asarray(mpi.allreduce(x))  # must resolve hierarchical impl
    assert len(collectives._jit_cache) == before + 1
    np.testing.assert_allclose(out1, out2, rtol=1e-6)


def test_hier_unsupported_op_raises(hier_runtime):
    x = rank_data(1000, np.float32)
    with pytest.raises(KeyError):
        mpi.allreduce(x, op="prod", backend="hierarchical")


def test_explicit_backend_bypasses_cutover(hier_runtime):
    # Per-call backend="hierarchical" must run the 2-level path even for
    # tiny tensors (the cutover only governs the config-driven default).
    mpi.set_config(custom_min_bytes=1 << 30)
    x = rank_data(4, np.float32)
    impl = selector.pick("allreduce", x[0], "hierarchical",
                         mpi.world_mesh().axis_names,
                         mesh=mpi.world_mesh())
    from torchmpi_tpu.parallel.hierarchical import hier_allreduce
    assert impl is hier_allreduce
    out = np.asarray(mpi.allreduce(x, backend="hierarchical"))
    np.testing.assert_allclose(out[0], x.sum(axis=0))


def test_init_does_not_mutate_user_config():
    mpi.stop()
    cfg = mpi.Config(dcn_size=1)
    mpi.init(cfg, hierarchical=True)
    mpi.set_config(chunk_bytes=1)
    assert cfg.hierarchical is False
    assert cfg.chunk_bytes != 1
    mpi.stop()


def test_backend_per_op_override(hier_runtime):
    # Reference parity: the collectiveSelector chose per collective class.
    mpi.set_config(backend="xla", custom_min_bytes=0,
                   backend_per_op={"allreduce": "hierarchical"})
    x = rank_data(64, np.float32)
    from torchmpi_tpu.parallel.hierarchical import hier_allreduce
    impl = selector.pick("allreduce", x[0], None,
                         mpi.world_mesh().axis_names,
                         mesh=mpi.world_mesh())
    assert impl is hier_allreduce
    # other ops keep the default backend
    from torchmpi_tpu.collectives import _xla_broadcast
    impl_b = selector.pick("broadcast", x[0], None,
                           mpi.world_mesh().axis_names,
                           mesh=mpi.world_mesh())
    assert impl_b is _xla_broadcast
    out = np.asarray(mpi.allreduce(x))
    np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-6)


def test_backend_per_op_validation_and_isolation(hier_runtime):
    # Typos fail loudly; the runtime never aliases the caller's dict.
    with pytest.raises(ValueError):
        mpi.set_config(backend_per_op={"all_reduce": "hierarchical"})
    with pytest.raises(ValueError):
        mpi.set_config(backend_per_op={"allreduce": "nccl"})
    table = {"allreduce": "hierarchical"}
    mpi.set_config(backend_per_op=table)
    table["allreduce"] = "pallas"  # caller mutation must not leak in
    assert mpi.config().backend_per_op == {"allreduce": "hierarchical"}


def test_backend_per_op_bypasses_cutover_and_validates(hier_runtime):
    # Per-op entries are deliberate: size cutover must not silently discard
    # them, and entries for ops without that backend must fail loudly.
    mpi.set_config(backend_per_op={"allreduce": "pallas"},
                   custom_min_bytes=1 << 30)
    x = rank_data(4, np.float32)  # tiny: under any cutover
    from torchmpi_tpu.ops.ring import ring_allreduce
    impl = selector.pick("allreduce", x[0], None,
                         mpi.world_mesh().axis_names,
                         mesh=mpi.world_mesh())
    assert impl is ring_allreduce
    with pytest.raises(ValueError):
        mpi.set_config(backend_per_op={"broadcast": "pallas"})  # no impl
    # init(**overrides) path validates too
    mpi.stop()
    with pytest.raises(ValueError):
        mpi.init(backend_per_op={"all_reduce": "hierarchical"})
    mpi.stop()
