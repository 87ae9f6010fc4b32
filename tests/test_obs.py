"""Runtime observability tests (torchmpi_tpu/obs/ — docs/OBSERVABILITY.md):
registry semantics, flight-recorder ring + dump + SIGTERM, obs_tool
parsing/aggregation/blame, and the call-site hooks across the eager
collectives, in-axis fusion path, gradsync/ZeRO, tuning, PS stats, and
the off-mode never-imported guarantee.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import torchmpi_tpu as mpi

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_by_path(name, *rel):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, *rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _obs_tool():
    return _load_by_path("_obs_tool_under_test", "scripts", "obs_tool.py")


@pytest.fixture()
def obs_runtime(tmp_path):
    """Flat 8-device runtime with obs="trace" dumping into tmp_path."""
    mpi.stop()
    mesh = mpi.init(mpi.Config(dcn_size=1, obs="trace",
                               obs_dir=str(tmp_path)))
    from torchmpi_tpu import obs

    obs.reset()
    yield mesh, obs, tmp_path
    obs.deactivate()
    obs.reset()
    mpi.stop()


# ---------------------------------------------------------------------------
# Registry (pure python, no runtime)
# ---------------------------------------------------------------------------


def test_registry_counters_and_hist():
    from torchmpi_tpu.obs.registry import Registry

    r = Registry()
    r.counter_inc("c", op="allreduce")
    r.counter_inc("c", 4, op="allreduce")
    r.counter_inc("c", op="broadcast")
    assert r.counter("c", op="allreduce") == 5
    assert r.counter_total("c") == 6
    r.hist_observe("h", 100)   # floor(log2(100)) = 6
    r.hist_observe("h", 127)
    r.hist_observe("h", 128)   # bucket 7
    snap = r.snapshot()
    hist = [s for s in snap if s["kind"] == "hist"][0]
    assert hist["buckets"] == {"6": 2, "7": 1}
    assert hist["count"] == 3 and hist["sum"] == 355.0


def test_prometheus_text():
    from torchmpi_tpu.obs.registry import Registry

    r = Registry()
    r.counter_inc("tm_x_total", 3, op="a")
    r.hist_observe("tm_y", 100, op="a")
    text = r.to_prometheus()
    assert '# TYPE tm_x_total counter' in text
    assert 'tm_x_total{op="a"} 3' in text
    # log2 bucket 6 renders with its upper edge 2^7 = 128, cumulative.
    assert 'tm_y_bucket{le="128",op="a"} 1' in text
    assert 'tm_y_bucket{le="+Inf",op="a"} 1' in text
    assert 'tm_y_count{op="a"} 1' in text


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_ring_wraparound():
    from torchmpi_tpu.obs.recorder import FlightRecorder

    r = FlightRecorder(8)
    for i in range(20):
        r.append("eager", f"op{i}", i)
    assert len(r) == 8
    assert r.total == 20 and r.dropped == 12
    evs = r.events()
    assert [e[0] for e in evs] == list(range(12, 20))  # seq-contiguous
    assert evs[0][3] == "op12" and evs[-1][3] == "op19"
    recs = r.to_records()
    assert recs[0]["kind"] == "event" and recs[0]["ev"] == "eager"


def test_best_effort_snapshot_survives_held_locks():
    """The SIGTERM dump path must not self-deadlock when the signal
    lands while the interrupted frame holds a registry/recorder lock:
    best_effort bounds the acquire and falls back to a lock-free copy
    (safe — the holder is the suspended frame, every other writer is
    blocked on the same lock)."""
    from torchmpi_tpu.obs.recorder import FlightRecorder
    from torchmpi_tpu.obs.registry import Registry

    r = Registry()
    r.counter_inc("c", 3)
    fr = FlightRecorder(8)
    fr.append("eager", "allreduce", 64, "xla")
    r._lock.acquire()
    fr._lock.acquire()
    try:
        snap = r.snapshot(best_effort=True)  # must return, not hang
        assert snap[0]["value"] == 3
        evs = fr.events(best_effort=True)
        assert evs[0][3] == "allreduce"
    finally:
        r._lock.release()
        fr._lock.release()


def test_ring_resize_preserves_history():
    """activate() with a new obs_ring_size must carry events + seq
    forward — resizing must not destroy the deadlock evidence."""
    from torchmpi_tpu.obs.recorder import FlightRecorder

    r = FlightRecorder(8)
    for i in range(10):
        r.append("eager", f"op{i}", i)
    big = r.resized(32)
    assert big.total == 10 and big.size == 32
    assert [e[0] for e in big.events()] == list(range(2, 10))
    assert big.events()[-1][3] == "op9"
    small = r.resized(4)  # shrink keeps the newest 4
    assert [e[0] for e in small.events()] == [6, 7, 8, 9]
    small.append("eager", "next", 0)
    assert small.events()[-1][0] == 10  # seq continues, no reset


def test_sigterm_dump(tmp_path):
    from torchmpi_tpu import obs

    # A file that ran earlier in this process may have left obs activated
    # (mpi.stop() does not deactivate it): its SIGTERM handler would be
    # hidden under the one installed below and activate() would not re-arm.
    obs.deactivate()
    hits = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: hits.append(s))
    try:
        obs.activate("trace", out_dir=str(tmp_path), host="sig")
        obs.reset()
        obs.recorder().append("eager", "allreduce", 64, "xla")
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)  # let the interpreter deliver the signal
        # Our handler dumped, then chained to the pre-activation one.
        assert hits == [signal.SIGTERM]
        fpath = tmp_path / "flight_hostsig.jsonl"
        assert fpath.exists()
        lines = [json.loads(ln) for ln in fpath.read_text().splitlines()]
        assert lines[0]["kind"] == "meta" and lines[0]["stream"] == "flight"
        assert any(r.get("op") == "allreduce" for r in lines[1:])
    finally:
        obs.deactivate()
        obs.reset()
        signal.signal(signal.SIGTERM, prev)


# ---------------------------------------------------------------------------
# obs_tool: parse, aggregate, diff, prom, blame
# ---------------------------------------------------------------------------


def _write_flight(path, host, records):
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "meta", "stream": "flight",
                            "host": host, "mode": "trace"}) + "\n")
        for r in records:
            f.write(json.dumps(r) + "\n")


def _mk_stream(ops):
    from torchmpi_tpu.obs.recorder import FlightRecorder

    r = FlightRecorder(64)
    for op, nbytes in ops:
        r.append("eager", op, nbytes, "xla", "m")
    return r.to_records()


def test_blame_divergence(tmp_path, capsys):
    tool = _obs_tool()
    common = [("allreduce", 1024)] * 4
    a = tmp_path / "flight_host0.jsonl"
    b = tmp_path / "flight_host1.jsonl"
    _write_flight(a, 0, _mk_stream(common + [("broadcast", 2048)]))
    _write_flight(b, 1, _mk_stream(common + [("allreduce", 1024)]))
    rc = tool.main(["blame", str(a), str(b)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "DIVERGENCE at seq 4" in out
    assert "broadcast" in out and "allreduce" in out


def test_blame_tail_hang(tmp_path, capsys):
    """No mismatch in the overlap, but one host launched past the
    others' last event: blame names the first extra collective."""
    tool = _obs_tool()
    common = [("allreduce", 1024)] * 3
    a = tmp_path / "flight_host0.jsonl"
    b = tmp_path / "flight_host1.jsonl"
    _write_flight(a, 0, _mk_stream(common))
    _write_flight(b, 1, _mk_stream(common + [("reduce_scatter", 4096)]))
    rc = tool.main(["blame", str(a), str(b)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "continued past" in out and "reduce_scatter" in out


def test_blame_aligned(tmp_path, capsys):
    tool = _obs_tool()
    s = _mk_stream([("allreduce", 1024)] * 3)
    a = tmp_path / "flight_host0.jsonl"
    b = tmp_path / "flight_host1.jsonl"
    _write_flight(a, 0, s)
    _write_flight(b, 1, s)
    assert tool.main(["blame", str(a), str(b)]) == 0
    assert "aligned" in capsys.readouterr().out


def test_blame_wrapped_rings_align_on_overlap(tmp_path, capsys):
    """Rings trimmed to different depths still align: seq numbers in the
    dump anchor the comparison, not list positions."""
    from torchmpi_tpu.obs.recorder import FlightRecorder

    tool = _obs_tool()
    big, small = FlightRecorder(64), FlightRecorder(4)
    for i in range(10):
        big.append("eager", f"op{i}", 8, "xla")
        small.append("eager", f"op{i}", 8, "xla")
    a = tmp_path / "flight_host0.jsonl"
    b = tmp_path / "flight_host1.jsonl"
    _write_flight(a, 0, big.to_records())    # seqs 0..9
    _write_flight(b, 1, small.to_records())  # seqs 6..9 only
    assert tool.main(["blame", str(a), str(b)]) == 0
    assert "6..9" in capsys.readouterr().out


def test_tool_agg_diff_and_malformed(tmp_path, capsys):
    tool = _obs_tool()

    def snap(path, val):
        with open(path, "w") as f:
            f.write(json.dumps({"kind": "meta", "stream": "metrics",
                                "host": 0, "mode": "metrics"}) + "\n")
            f.write(json.dumps({"kind": "counter", "name": "tm_c_total",
                                "labels": {"op": "allreduce"},
                                "value": val}) + "\n")
            f.write(json.dumps({"kind": "hist", "name": "tm_h",
                                "labels": {}, "buckets": {"4": val},
                                "count": val, "sum": 16.0 * val}) + "\n")

    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    snap(a, 2)
    snap(b, 5)
    agg = tool.aggregate([str(a), str(b)])
    c = [r for r in agg if r["kind"] == "counter"][0]
    h = [r for r in agg if r["kind"] == "hist"][0]
    assert c["value"] == 7 and h["buckets"]["4"] == 7 and h["count"] == 7
    assert tool.main(["diff", str(a), str(b)]) == 0
    assert "(+3)" in capsys.readouterr().out
    # prom over files round-trips through the registry renderer
    assert tool.main(["prom", str(a)]) == 0
    assert 'tm_c_total{op="allreduce"} 2' in capsys.readouterr().out
    bad = tmp_path / "bad.jsonl"
    bad.write_text("not json\n")
    assert tool.main(["dump", str(bad)]) == 1


# ---------------------------------------------------------------------------
# Call-site hooks through the real runtime
# ---------------------------------------------------------------------------


def test_eager_collective_records_and_dump(obs_runtime):
    mesh, obs, tmp_path = obs_runtime
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    mpi.allreduce(x)
    mpi.allreduce(x, backend="host")  # staged path records too
    mpi.barrier()
    reg = obs.registry()
    assert reg.counter_total("tm_collectives_total") == 2
    assert reg.counter("tm_collectives_total", op="allreduce",
                       backend="host", mesh="dcn:1,ici:8",
                       dtype="float32", nbytes_bucket="b4") == 1
    assert reg.counter_total("tm_collective_bytes_total") == 32
    assert reg.counter_total("tm_barriers_total") == 1
    evs = obs.recorder().events()
    # Each cold dispatch is a plan build (flight event "plan") followed
    # by its planned replay's eager event (docs/PLANNER.md) and — since
    # the watchdog PR — the matching completion edge, which is what
    # lets blame tell "launched and stuck" from "done, next never
    # launched" (docs/WATCHDOG.md).
    assert [e[2] for e in evs] == ["plan", "eager", "eager_done",
                                   "plan", "eager", "eager_done",
                                   "barrier", "barrier_done"]
    eager = [e for e in evs if e[2] == "eager"]
    assert eager[0][5] == "xla" and eager[1][5] == "host"
    done = [e for e in evs if e[2] == "eager_done"]
    assert done[0][5] == "xla" and done[1][5] == "host"
    assert reg.counter_total("tm_plan_miss_total") == 2
    # dump -> obs_tool parses both files
    paths = obs.dump()
    assert len(paths) == 2
    tool = _obs_tool()
    assert tool.main(["dump"] + paths) == 0
    meta, records = tool.load_jsonl(paths[1])
    assert meta["stream"] == "flight"
    assert [r["seq"] for r in records] == list(range(8))


def test_set_config_obs_off_stops_recording(obs_runtime):
    mesh, obs, tmp_path = obs_runtime
    x = np.ones((8, 2), np.float32)
    mpi.allreduce(x)
    assert obs.registry().counter_total("tm_collectives_total") == 1
    mpi.set_config(obs="off")
    mpi.allreduce(np.ones((8, 4), np.float32))
    assert obs.registry().counter_total("tm_collectives_total") == 1
    mpi.set_config(obs="trace")
    mpi.allreduce(np.ones((8, 8), np.float32))
    assert obs.registry().counter_total("tm_collectives_total") == 2


def test_in_axis_fusion_gradsync_records(obs_runtime):
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, obs, tmp_path = obs_runtime
    axes = tuple(mesh.axis_names)
    tree = {"a": np.ones((8, 4), np.float32),
            "b": np.ones((8, 2), np.float32)}

    def body(t):
        t = mpi.collectives.allreduce_in_axis(t, axes)
        return mpi.nn.synchronize_gradients(t, axes, op="sum")

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(axes),),
                           out_specs=P(axes), check_vma=False))
    fn(tree)
    reg = obs.registry()
    # Two leaves, one dtype -> ONE fused launch per collective round.
    # In-axis calls see PER-DEVICE shards: (1,4)+(1,2) f32 = 24 bytes -> b4.
    assert reg.counter("tm_inaxis_calls_total", op="allreduce",
                       axes=",".join(axes), nbytes_bucket="b4") >= 1
    assert reg.counter_total("tm_fusion_trees_total") >= 2
    assert reg.counter("tm_fusion_leaves_total", op="allreduce") >= 2
    assert reg.counter_total("tm_gradsync_rounds_total") == 1
    assert reg.counter_total("tm_step_builds_total") == 0  # no builder used


def test_zero_and_step_builder_records(obs_runtime):
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, obs, tmp_path = obs_runtime
    axes = tuple(mesh.axis_names)
    zero = mpi.parallel.zero
    params = {"w": jnp.ones((5, 3), jnp.float32)}
    tx = optax.sgd(0.1)
    opt_state = zero.init(params, tx, mesh=mesh)
    params_r = mpi.nn.synchronize_parameters(params, mesh=mesh)

    def step(p, s):
        g = jax.tree.map(jnp.ones_like, p)
        return zero.update(p, g, s, tx, axes, op="mean")

    sspecs = zero.specs_like(opt_state, axes)
    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=(P(), sspecs),
                           out_specs=(P(), sspecs), check_vma=False))
    fn(params_r, opt_state)
    reg = obs.registry()
    assert reg.counter_total("tm_zero_sync_rounds_total") == 1
    assert reg.counter("tm_zero_groups_total", kind="reduce_scatter") == 1

    # The data_parallel_step builder leaves a build marker.
    def dp_body(p, batch):
        return mpi.nn.synchronize_gradients(
            jax.tree.map(jnp.ones_like, p), axes)

    dp = mpi.nn.data_parallel_step(dp_body, mesh=mesh, batch_argnums=(1,),
                                   donate_argnums=())
    dp(params_r, np.ones((8, 2), np.float32))
    assert reg.counter("tm_step_builds_total",
                       label="data_parallel_step") == 1


def test_tuning_records(obs_runtime, tmp_path):
    import jax.numpy as jnp

    mesh, obs, _ = obs_runtime
    from torchmpi_tpu import tuning

    tuning.configure(str(tmp_path / "plan.json"), rounds=1)
    try:
        runner = lambda b: jnp.zeros(8)  # noqa: E731
        first = tuning.resolve_eager("allreduce", 4096, np.float32, mesh,
                                     runner)
        second = tuning.resolve_eager("allreduce", 4096, np.float32, mesh,
                                      runner)
        assert first == second
        reg = obs.registry()
        assert reg.counter("tm_tuning_plan_lookups_total",
                           event="measured", op="allreduce") == 1
        assert reg.counter("tm_tuning_plan_lookups_total",
                           event="hit", op="allreduce") == 1
        assert "tm_tuning_measured_us" in reg.names()  # per-candidate hist
    finally:
        tuning.reset()


def test_metrics_logger_feeds_registry(obs_runtime):
    from torchmpi_tpu.utils import metrics

    mesh, obs, tmp_path = obs_runtime
    lg = metrics.MetricsLogger(str(tmp_path / "steps.jsonl"), name="steps")
    lg.log(step=0, loss=1.0)
    lg.log(step=1, loss=0.5)
    assert obs.registry().counter("tm_log_records_total",
                                  logger="steps") == 2
    assert len((tmp_path / "steps.jsonl").read_text().splitlines()) == 2


def test_ps_stats_retry_and_registry(obs_runtime):
    mesh, obs, tmp_path = obs_runtime
    template = {"w": np.zeros((64,), np.float32)}
    ps = mpi.parameterserver.init(template, num_shards=2)
    try:
        ps.send(template, rule="add").wait()
        s1 = ps.stats()
        assert s1["ops"] >= 1  # init copy + our add
        s2 = ps.stats()
        assert all(s2[k] >= s1[k] for k in s1)  # monotone snapshots
        reg = obs.registry()
        assert reg.counter_total("tm_ps_ops_total") >= s1["ops"]
        assert reg.counter_total("tm_ps_bytes_in_total") > 0
    finally:
        ps.shutdown()


def test_off_mode_never_imports_obs():
    """Acceptance: with obs off (the default), torchmpi_tpu.obs is never
    imported — one branch per call site is the entire off-path cost."""
    code = (
        "import sys\n"
        "import numpy as np\n"
        "import torchmpi_tpu as mpi\n"
        "mpi.init(mpi.Config(dcn_size=1))\n"
        "mpi.allreduce(np.ones((2, 4), np.float32))\n"
        "mpi.barrier()\n"
        "mpi.stop()\n"
        "assert 'torchmpi_tpu.obs' not in sys.modules, 'obs imported!'\n"
        "print('OFF-MODE-OK')\n"
    )
    env = dict(os.environ)
    env.pop("TORCHMPI_TPU_OBS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env, cwd=_REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OFF-MODE-OK" in out.stdout


@pytest.mark.slow
def test_two_process_blame_identifies_injected_divergence(tmp_path):
    """Acceptance: a 2-process host-staged run under obs="metrics"
    produces per-host dumps whose blame output names the injected
    rank-divergent collective (rank 1's extra broadcast)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    worker = os.path.join(os.path.dirname(__file__), "_obs_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    procs = [subprocess.Popen(
        [sys.executable, worker, str(i), "2", str(port), str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert f"CHECK rank={i} done" in out, out
    flights = sorted(str(f) for f in tmp_path.glob("flight_host*.jsonl"))
    assert len(flights) == 2, flights
    tool = _obs_tool()
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "obs_tool.py"),
         "blame"] + flights, capture_output=True, text=True)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "continued past" in out.stdout and "broadcast" in out.stdout, \
        out.stdout
    # The metrics dumps aggregate across hosts too.
    metrics_files = sorted(str(f) for f in
                           tmp_path.glob("metrics_host*.jsonl"))
    agg = tool.aggregate(metrics_files)
    tot = sum(r["value"] for r in agg
              if r["name"] == "tm_collectives_total")
    assert tot == 7  # 3 allreduce x 2 hosts + 1 injected broadcast


def test_async_handle_records_wait_hist(obs_runtime):
    """AsyncHandle lifecycle telemetry: creates/waits counted, wait
    time on the tm_async_wait_seconds histogram, and the handle events
    land in the flight ring next to the collectives they wrap."""
    mesh, obs, _ = obs_runtime
    x = np.ones((8, 64), np.float32)
    h = mpi.async_.allreduce(x, backend="host")
    h.wait()
    hs = [mpi.async_.allreduce(x) for _ in range(2)]
    mpi.wait_all(hs)
    reg = obs.registry()
    assert reg.counter_total("tm_async_handles_total") >= 6  # 3c + 3w
    snap = reg.snapshot()
    hist = [r for r in snap if r.get("name") == "tm_async_wait_seconds"]
    # One observation per BLOCKING CALL: h.wait() + one for the whole
    # wait_all batch (never one per handle — that would inflate sum).
    assert hist and sum(r["count"] for r in hist) == 2
    assert any(r["labels"].get("op") == "wait_all" for r in hist)
    evs = [e for e in obs.recorder().events() if e[2] == "async"]
    assert {e[6] for e in evs} == {"create", "wait"}
