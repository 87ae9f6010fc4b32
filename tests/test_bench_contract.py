"""bench.py's contract: `python bench.py` runs its ladder in ONE process,
prints one JSON record per completed stage (the last is the headline
ResNet-50 stage), names the device every record ran on, and FAILS when it
finds no TPU unless the CPU smoke knob is set.  Exercised via the CPU tiny
preset (full code path, about a minute)."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_emits_one_json_line():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["TORCHMPI_TPU_BENCH_CPU"] = "4"
    env["TORCHMPI_TPU_BENCH_PRESET"] = "tiny"
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        capture_output=True, text=True, timeout=480, env=env, cwd=_REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    recs = [json.loads(l) for l in lines]  # every stdout line is a record
    for rec in recs:
        for key in ("metric", "value", "unit", "vs_baseline"):
            assert key in rec, rec
        assert rec["value"] > 0
        # every record names the device it ran on; a CPU smoke never
        # carries a fraction of a chip's peak
        assert rec["extra"]["platform"] == "cpu"
        assert rec["extra"]["device_kind"]
        assert rec["extra"]["devices"] == 4
        assert rec["vs_baseline"] is None
    # TPU-only stages (C, C2, D2) are skipped on the CPU; the last line
    # is the headline stage
    assert [r["metric"] for r in recs] == [
        "matmul_bf16_tflops", "transformer_lm_train_throughput",
        "transformer_lm_large_train_throughput",
        "resnet50_dp_train_throughput"]


def test_bench_fails_without_a_tpu():
    # No TPU and no CPU knob: non-zero exit, no record — a benchmark
    # number comes only from the chip.
    env = dict(os.environ)
    env.pop("TORCHMPI_TPU_BENCH_CPU", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=_REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout
    assert "no TPU found" in out.stderr


@pytest.mark.parametrize("field,value", [("bf16_tflops", 197.0),
                                         ("hbm_gbps", 819.0)])
def test_bench_peaks_known_kind(field, value):
    import bench

    assert bench.peak_for("TPU v5 lite")[field] == value  # a v5e chip


@pytest.mark.parametrize("kind", ["cpu", "TPU v5e", "TPU v9 imaginary", ""])
def test_bench_peaks_unknown_kind_is_an_error(kind):
    import bench

    with pytest.raises(KeyError, match="no published peak"):
        bench.peak_for(kind)


@pytest.mark.slow
def test_memory_bench_measures_the_ladder():
    # replicated -> zero1 -> zero3/fsdp per-device persistent bytes must
    # actually shrink as measured from addressable shards (not theory):
    # with Adam (state = 2x params) on n=8, zero1 = (1+2/8)/3 and
    # zero3/fsdp = 3/8/3 of replicated.
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "benchmarks", "memory_bench.py"),
         "--devices", "8", "--model", "lenet", "--json"],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = {r["strategy"]: r for r in
            (json.loads(l) for l in out.stdout.strip().splitlines())}
    assert rows["replicated_dp"]["vs_replicated"] == 1.0
    assert abs(rows["zero1"]["vs_replicated"] - (1 + 2 / 8) / 3) < 0.02
    assert abs(rows["zero3"]["vs_replicated"] - 3 / 8 / 3) < 0.02
    assert abs(rows["fsdp"]["vs_replicated"] - 3 / 8 / 3) < 0.03


@pytest.mark.slow
def test_scanned_train_step_matches_sequential():
    # Stage D2's scan wrapper (scanned_train_step, n_carry=3) must
    # compute the same training math as sequential dispatches of the
    # same step — bf16 tolerance, since scanned vs sequential are
    # different compiled programs.
    import numpy as np

    from torchmpi_tpu.utils.simulation import force_cpu_devices

    force_cpu_devices(4)
    import jax
    import jax.numpy as jnp
    import optax

    import bench
    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import ResNet50
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mpi.init()
    model = ResNet50(dtype=jnp.bfloat16)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)),
                   train=False)
    params, bst = v["params"], v["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt = tx.init(params)
    dp_ref = mpi.recipes.make_bn_dp_train_step(model, tx, mesh=mesh,
                                               donate=False)
    params, opt, bst = mpi.recipes.replicate_bn_state(params, opt, bst,
                                                      mesh=mesh)
    shard = NamedSharding(mesh, P(mesh.axis_names))
    im = jax.device_put(np.random.RandomState(0).rand(8, 64, 64, 3)
                        .astype(np.float32), shard)
    lb = jax.device_put(np.random.RandomState(1).randint(
        0, 1000, size=8).astype(np.int32), shard)

    multi = jax.jit(bench.scanned_train_step(dp_ref, 2, n_carry=3))
    p1, o1, b1, _ = dp_ref(params, opt, bst, im, lb)
    p2, _, _, l2 = dp_ref(p1, o1, b1, im, lb)
    ps, _, _, ls = multi(params, opt, bst, im, lb)
    np.testing.assert_allclose(float(ls), float(l2), rtol=5e-3)
    pa = np.concatenate([np.asarray(x, np.float32).ravel()
                         for x in jax.tree.leaves(p2)])
    pb = np.concatenate([np.asarray(x, np.float32).ravel()
                         for x in jax.tree.leaves(ps)])
    np.testing.assert_allclose(pa, pb, atol=2e-2)


def test_summary_bank_round_trip_trim_and_latest(tmp_path):
    """--bank persistence (benchmarks/banking.py): records land newest
    first with stamp/commit/platform/argv context, the bank keeps only
    KEEP_PER_KIND per summary kind, latest() can refuse the wrong
    platform (a sim number must never stand in for silicon), and a
    clobbered bank file fails loudly instead of being silently reset."""
    from benchmarks import banking

    path = str(tmp_path / "SUMMARY_BANK.json")
    r1 = banking.bank_summary("GUARD-SUMMARY", {"verified": 3},
                              path=path, argv=["--guard-compare"])
    assert r1["stamp"] and r1["argv"] == ["--guard-compare"]
    banking.bank_summary("GUARD-SUMMARY", {"verified": 4}, path=path,
                         argv=[])
    banking.bank_summary("RECOVERY-SUMMARY",
                         {"ram": {"steps_lost": 0}}, path=path, argv=[])
    bank = banking.load_bank(path)
    assert [r["summary"]["verified"] for r in bank["GUARD-SUMMARY"]] \
        == [4, 3]  # newest first
    got = banking.latest("GUARD-SUMMARY", path=path)
    assert got["summary"] == {"verified": 4}
    assert banking.latest("RECOVERY-SUMMARY", path=path,
                          platform="tpu") is None  # refuse sim/None
    assert banking.latest("NOPE-SUMMARY", path=path) is None
    for i in range(banking.KEEP_PER_KIND + 3):
        banking.bank_summary("RECOVERY-SUMMARY", {"i": i}, path=path,
                             argv=[])
    rows = banking.load_bank(path)["RECOVERY-SUMMARY"]
    assert len(rows) == banking.KEEP_PER_KIND
    assert rows[0]["summary"] == {"i": banking.KEEP_PER_KIND + 2}
    with pytest.raises(TypeError):
        banking.bank_summary("X", ["not-a-dict"], path=path)
    with open(path, "w") as f:
        json.dump(["clobbered"], f)
    with pytest.raises(ValueError, match="bank"):
        banking.load_bank(path)
