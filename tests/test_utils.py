"""Checkpoint/metrics/tracing utility tests (SURVEY.md §6 subsystems)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchmpi_tpu.utils import checkpoint, compilecache, metrics, tracing


def tree():
    return {"layer": {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
                      "b": np.ones((4,), np.float32)},
            "scale": np.float32(2.5) * np.ones((), np.float32)}


def test_checkpoint_roundtrip(tmp_path):
    t = tree()
    path = checkpoint.save(str(tmp_path), t, step=3)
    assert os.path.exists(path)
    template = jax.tree.map(np.zeros_like, t)
    back = checkpoint.restore(str(tmp_path), template)
    np.testing.assert_allclose(back["layer"]["w"], t["layer"]["w"])
    np.testing.assert_allclose(back["scale"], t["scale"])


def test_checkpoint_latest_step(tmp_path):
    t = tree()
    checkpoint.save(str(tmp_path), t, step=1)
    checkpoint.save(str(tmp_path), t, step=10)
    assert checkpoint.latest_step(str(tmp_path)) == 10
    back = checkpoint.restore(str(tmp_path), t)  # picks 10
    assert back is not None


def test_checkpoint_missing(tmp_path):
    with pytest.raises(FileNotFoundError):
        checkpoint.restore(str(tmp_path / "nope"), tree())


def test_fence_and_timer():
    x = jnp.ones((8, 8))
    timer = metrics.Timer()
    timer.start(fence_on=x)
    y = x @ x
    timer.tick()
    dt = timer.stop(fence_on=y)
    assert dt >= 0 and timer.steps == 1


def test_metrics_logger(tmp_path):
    log = metrics.MetricsLogger(str(tmp_path / "m.jsonl"))
    log.log(step=1, img_s=123.0)
    log.log(step=2, img_s=125.0)
    assert len(log.records) == 2
    lines = (tmp_path / "m.jsonl").read_text().strip().split("\n")
    assert len(lines) == 2 and '"img_s": 123.0' in lines[0]


def test_bus_bandwidth_formula():
    # 8 devices, 1 GB reduced in 1 s: algbw 1 GB/s, busbw = 2*7/8.
    bw = metrics.allreduce_bus_bandwidth(int(1e9), 8, 1.0)
    assert abs(bw - 2 * 7 / 8) < 1e-9
    assert metrics.allreduce_bus_bandwidth(100, 1, 1.0) == 0.0


def test_trace_nested_degrades_to_noop(tmp_path):
    """jax allows one profiler trace per process: a trace() inside
    another must degrade to a no-op span (and a failed start must not
    let the finally's stop_trace mask the body's real exception)."""
    ran = []
    with tracing.trace(str(tmp_path / "outer")):
        with tracing.trace(str(tmp_path / "inner")):  # nested: no-op
            ran.append(1)
    assert ran == [1]
    # The profiler fully stopped: a fresh trace still works.
    with tracing.trace(str(tmp_path / "again")):
        ran.append(2)
    assert ran == [1, 2]


def test_trace_failed_start_propagates_body_error(tmp_path):
    with tracing.trace(str(tmp_path / "outer")):
        # Inner start fails (already tracing); the body's ValueError
        # must surface — not a masking stop_trace RuntimeError.
        with pytest.raises(ValueError, match="the real error"):
            with tracing.trace(str(tmp_path / "inner")):
                raise ValueError("the real error")


# ---------------------------------------------------------------------------
# Persistent compile cache: one rule for where it lives
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cache_config(monkeypatch):
    """Record what the code sets, and leave this process's jax config as
    it was (the rest of the suite runs with the persistent cache off)."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    prev = {n: getattr(jax.config, n) for n in names}
    updates = {}
    real = jax.config.update

    def recording_update(name, value):
        updates[name] = value
        real(name, value)

    monkeypatch.setattr(jax.config, "update", recording_update)
    yield updates
    for n, v in prev.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("calls", [1, 2])
def test_compile_cache_env_set_code_sets_no_directory(
        cache_config, monkeypatch, tmp_path, calls):
    # JAX_COMPILATION_CACHE_DIR set: jax already uses it; repo code
    # reports it and never touches jax_compilation_cache_dir.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    got = {compilecache.enable_persistent_cache() for _ in range(calls)}
    assert got == {str(tmp_path)}
    assert "jax_compilation_cache_dir" not in cache_config
    assert cache_config["jax_persistent_cache_min_compile_time_secs"] == 1.0


@pytest.mark.parametrize("calls", [1, 2])
@pytest.mark.parametrize("old_knob", [None, "/somewhere/else"])
def test_compile_cache_env_unset_fixed_checkout_path(
        cache_config, monkeypatch, calls, old_knob):
    # Unset: the fixed <checkout>/.jax_compile_cache, the same on every
    # call (the path is part of the cache's key).  The removed
    # TORCHMPI_TPU_COMPILE_CACHE knob places nothing.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    if old_knob:
        monkeypatch.setenv("TORCHMPI_TPU_COMPILE_CACHE", old_knob)
    want = os.path.join(_REPO, ".jax_compile_cache")
    got = {compilecache.enable_persistent_cache() for _ in range(calls)}
    assert got == {want} and compilecache.DEFAULT_DIR == want
    assert cache_config["jax_compilation_cache_dir"] == want
    assert jax.config.jax_compilation_cache_dir == want
