"""The benchmark's own tests: the manifest against the contract's rules,
every cell rehearsed end to end on the CPU, the two halves of the trace
reduction, the operation counts against hand counts.

    python -m pytest chipbench/tests -q

Rehearsals run the command as the driver does, in a new process each: this
process never decides a platform for them.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import flops, harness, xplane  # noqa: E402

MANIFEST = harness.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TESTDATA = os.path.join(harness.BENCH, "testdata")


def run_cell(*argv, cwd=ROOT, env=None):
    """The command as the driver runs it; -> (rc, last stdout line, err)."""
    env = {**os.environ, **(env or {})}
    env.pop("XLA_FLAGS", None)          # conftest-style device counts
    cmd = [sys.executable, os.path.join(cwd, "chipbench", "run.py"), *argv]
    p = subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                       text=True, timeout=300)
    lines = p.stdout.strip().splitlines()
    return p.returncode, (lines[-1] if lines else ""), p.stderr


# ---------------------------------------------------------------- manifest


def test_every_name_resolves_to_a_file():
    for name in CELLS:
        cell = harness.resolve(MANIFEST, name)
        cfg = cell.config
        harness.find(MANIFEST, "runners", cfg["runner"] + ".py")
        harness.find(MANIFEST, "steps", cfg["step"] + ".py")
        harness.find(MANIFEST, "reference",
                     cfg["reference"]["module"] + ".py")
        assert callable(getattr(flops, cfg["flops"]["function"]))
        assert harness.flops_per_item(cell) > 0
        assert 0 < cfg["tolerance"]["loss_rtol"] <= 0.05
        assert cfg["tolerance"]["reason"]
        for m in cell.per_layer:
            harness.find(MANIFEST, "readers", m["reader"] + ".py")
        assert cell.per_layer and len(cell.end_to_end) >= 2
    for c in MANIFEST["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert set(c["reduced"]) <= set(body)
        assert c["reduced"] == body["reduced"]
        assert any(w["config"] == c["name"] for w in MANIFEST["workloads"])


def test_manifest_keeps_the_contracts_rules():
    m = MANIFEST
    assert set(m) - {"_base", "_dirs"} == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and len(m["command"]) <= 32
    metrics = m["end_to_end"] + m["per_layer"]
    names = ([x["name"] for x in metrics + m["workloads"] + m["configs"]]
             + [w["traffic"] for w in m["workloads"]]
             + [k for c in m["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(x["unit"]) for x in metrics)
    assert all(x["better"] in ("lower", "higher") for x in metrics)
    assert all(x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
               for x in metrics)
    for group in (metrics, m["workloads"], m["configs"]):
        assert len({x["name"] for x in group}) == len(group)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for x in m["workloads"] + m["configs"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert all(0 < x["bound"] <= 0.1 for x in e2e.values())
    assert all(x["source"] in ("host_clock", "device_trace")
               for x in e2e.values())
    for cell in CELLS:
        mine = {x["name"] for x in harness.metrics_of(m, "end_to_end", cell)}
        assert "setup_s" in mine and len(mine) >= 2
        for x in harness.metrics_of(m, "per_layer", cell):
            # a layer metric's cells all report the metric it moves
            assert x["moves"] in mine, (cell, x["name"])
    layers = {x["layer"] for x in m["per_layer"]}
    assert all(len(la) <= 200 and "\n" not in la for la in layers)


def test_no_topology_described_at_import():
    """Only one process may load libtpu: nothing under chipbench/ may
    describe a topology while it is imported."""
    for dirpath, _, files in os.walk(harness.BENCH):
        for f in files:
            if not f.endswith(".py") or f == os.path.basename(__file__):
                continue
            with open(os.path.join(dirpath, f)) as fh:
                for line in fh:
                    if "get_topology_desc(" in line:
                        assert line.startswith((" ", "\t")), (f, line)


# -------------------------------------------------------------- rehearsals


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_end_to_end(cell):
    rc, line, err = run_cell("--workload", cell, "--seed", "4294967301",
                             "--seconds", "0.3", "--trace", "0",
                             "--rehearse")
    assert rc == 0, err[-2000:]
    out = json.loads(line)
    assert LINE_KEYS <= set(out)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert out["device"]["platform"] == "cpu" and out["rehearsal"] is True
    chips = harness.by_name(MANIFEST["workloads"], cell, "w")["chips"]
    assert out["device"]["count"] == chips
    want = {x["name"] for x in
            harness.metrics_of(MANIFEST, "end_to_end", cell)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_traced_rehearsal_reports_layer_metrics_and_leaves_out_the_rest():
    rc, line, err = run_cell("--workload", "rn50-dp4", "--seed", "3",
                             "--seconds", "0.3", "--trace", "1",
                             "--rehearse")
    assert rc == 0, err[-2000:]
    out = json.loads(line)
    want = {x["name"] for x in
            harness.metrics_of(MANIFEST, "per_layer", "rn50-dp4")}
    # The CPU has no device plane and no peak: those readers find nothing
    # to read and are left out; the host span is there.
    assert set(out["metrics"]) <= want
    assert "dispatch_ms_per_step.img" in out["metrics"]
    assert not any("roofline" in k or "mfu" in k or "idle" in k
                   for k in out["metrics"])
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    rc, line, err = run_cell("--workload", "rn50-1chip", "--seed", "1",
                             "--seconds", "1", "--trace", "0",
                             env={"JAX_PLATFORMS": "cpu"})
    assert rc != 0 and line == "" and "no TPU" in err


def test_traffic_made_for_another_chip_count_is_refused(tmp_path):
    """A cell that asks for one chip with the four-chip traffic file:
    refused before anything runs, no result."""
    m = {k: v for k, v in MANIFEST.items() if not k.startswith("_")}
    m["workloads"] = [dict(w, chips=1) if w["name"] == "rn50-dp4" else w
                      for w in m["workloads"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    rc, line, err = run_cell("--manifest", str(tmp_path / "BENCHMARK.json"),
                             "--workload", "rn50-dp4", "--rehearse")
    assert rc != 0 and line == "" and "chip" in err


def test_bare_directory_fails(tmp_path):
    """Only BENCHMARK.json and chipbench/: there is no system to test."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, line, err = run_cell("--workload", "rn50-1chip", "--rehearse",
                             "--seconds", "0.3", cwd=str(tmp_path))
    assert rc != 0 and line == "" and "torchmpi_tpu" in err


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A throwaway configuration, traffic mix, per-layer metric and reader
    from a temporary directory: new files and manifest entries, no edit."""
    bench = tmp_path / "chipbench"
    for d in ("configs", "traffic", "layer_metrics", "readers"):
        (bench / d).mkdir(parents=True)
    with open(os.path.join(harness.BENCH, "configs", "resnet50.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "resnet-throwaway"
    cfg["rehearse"]["sizes"]["num_classes"] = 7
    (bench / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "b2.json").write_text(json.dumps(
        {"name": "b2", "chips": 1, "batch_per_chip": 2, "ring": 2,
         "why": "throwaway"}))
    (bench / "layer_metrics" / "wait_ms_per_step.img.json").write_text(
        json.dumps({"reader": "host_span", "args": {"span": "wait_loss"}}))
    (bench / "layer_metrics" / "answer.img.json").write_text(
        json.dumps({"reader": "constant", "args": {"value": 42}}))
    (bench / "readers" / "constant.py").write_text(
        "def read(ctx, value):\n    return value\n")
    m = {k: v for k, v in MANIFEST.items() if not k.startswith("_")}
    m["configs"] = m["configs"] + [{
        "name": "resnet-throwaway", "source": "test", "reduced": [],
        "file": "chipbench/configs/throwaway.json", "why": "test"}]
    m["workloads"] = m["workloads"] + [{
        "name": "throwaway", "config": "resnet-throwaway", "traffic": "b2",
        "chips": 1, "why": "test"}]
    for x in m["end_to_end"]:
        if x["name"] == "images_per_s_chip":
            x["workloads"] = x["workloads"] + ["throwaway"]
    m["per_layer"] = m["per_layer"] + [
        {"name": n, "unit": u, "better": "lower", "source": "host_clock",
         "layer": "entry points and dispatch", "moves": "images_per_s_chip",
         "workloads": ["throwaway"]}
        for n, u in (("wait_ms_per_step.img", "ms"), ("answer.img", "1"))]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    rc, line, err = run_cell("--manifest", str(tmp_path / "BENCHMARK.json"),
                             "--workload", "throwaway", "--seconds", "0.3",
                             "--trace", "1", "--rehearse")
    assert rc == 0, err[-2000:]
    out = json.loads(line)
    assert out["correct"] is True
    assert out["metrics"]["answer.img"] == {"value": 42, "unit": "1"}
    assert out["metrics"]["wait_ms_per_step.img"]["value"] > 0


# ------------------------------------------------------ trace: file -> events


def test_xplane_load_reads_the_recorded_trace():
    with open(os.path.join(TESTDATA, "expected.json")) as f:
        want = json.load(f)
    trace = xplane.load(os.path.join(TESTDATA, want["file"]))
    assert sorted(trace.devices) == want["devices"]
    assert {d: len(es) for d, es in trace.devices.items()} == want["events"]
    assert list(trace.window) == want["window_ns"]
    assert {k: len(v) for k, v in trace.host.items()} == want["host_spans"]
    busy = xplane.device_busy(trace)
    assert busy["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert busy["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < busy["busy_s"] <= busy["window_s"]
    top = xplane.breakdown(trace)["device_ops"]
    assert top[0][0] == want["top_op"] and len(top) <= 10
    assert {d: len(es) for d, es in trace.in_flight.items()} == \
        want["in_flight"]
    # the step's one all-reduce (102 MB of gradients, BatchNorm statistics
    # and the loss, combined by XLA): synchronous, so all of it is exposed
    coll = harness.load_module(MANIFEST, "readers", "xplane_collectives")
    ctx = {"trace": trace, "traced_steps": 1}
    assert coll.read(ctx) == pytest.approx(want["collective_ms_per_step"])
    assert 1.7 < coll.read(ctx) < 1.8
    assert coll.read(ctx, exposed=True) == pytest.approx(coll.read(ctx))


# --------------------------------------------------- trace: events -> numbers

E = xplane.Event


def ev(name, start, end):
    return E(name, start, end, {})


def test_union_sums_and_gaps_on_hand_made_events():
    es = [ev("a", 0, 10), ev("b", 5, 20), ev("a", 30, 40), ev("c", 40, 45)]
    assert xplane.union((e.start, e.end) for e in es) == [(0, 20), (30, 45)]
    assert xplane.union_s(es) == pytest.approx(35e-9)
    assert xplane.sum_by_name(es) == pytest.approx(
        {"a": 20e-9, "b": 15e-9, "c": 5e-9})
    assert xplane.gaps(es, (0, 50)) == [(20, 30), (45, 50)]
    assert xplane.gaps(es, (-5, 45)) == [(-5, 0), (20, 30)]
    clipped = xplane.clip(es, (8, 32))
    assert [(e.name, e.start, e.end) for e in clipped] == [
        ("a", 8, 10), ("b", 8, 20), ("a", 30, 32)]


def test_exposed_collective_time_on_hand_made_events():
    coll = [ev("all-reduce.1", 10, 30), ev("all-reduce.2", 50, 60)]
    other = [ev("fusion.1", 0, 15), ev("fusion.2", 25, 28),
             ev("fusion.3", 55, 70)]
    # [10,30] hidden on [10,15] and [25,28] -> 12 exposed; [50,60] hidden
    # on [55,60] -> 5 exposed.
    assert xplane.exposed_s(coll, other) == pytest.approx(17e-9)
    assert xplane.exposed_s(coll, []) == pytest.approx(30e-9)
    assert xplane.exposed_s([], other) == 0


ALLREDUCE = ("%all-reduce.7 = f32[25557032]{0:T(1024)} all-reduce(f32[25557032]"
             "{0:T(1024)} %fusion.9), replica_groups={{0,1}}, to_apply=%add")
CONV = ("%fusion.1 = bf16[256,56,56,64]{0,3,2,1:T(8,128)(2,1)} fusion(bf16[256,"
        "56,56,64]{0,3,2,1} %all-reduce.7, f32[64]{0} %p), kind=kOutput")
FLASH = ("%SPAttention_0.19 = (f32[1,24,8192,128]{3,2,1,0:T(8,128)}) custom-call("
         "s32[1]{0} %c), custom_call_target=\"tpu_custom_call\"")


def test_busy_breakdown_and_readers_on_a_hand_made_trace():
    ns = 1_000_000          # 1 ms
    host = {"wait_prime": [(0, 1 * ns)], "dispatch": [(1 * ns, 2 * ns)],
            "wait_loss": [(2 * ns, 11 * ns)]}
    d0 = [ev(CONV, 0, 1 * ns),                 # before the window: clipped
          ev(CONV, 1 * ns, 5 * ns), ev(ALLREDUCE, 5 * ns, 7 * ns),
          ev(FLASH, 8 * ns, 10 * ns)]
    d1 = [ev(CONV, 1 * ns, 11 * ns), ev(ALLREDUCE, 6 * ns, 8 * ns)]
    trace = xplane.make_trace({"/device:TPU:0": d0, "/device:TPU:1": d1},
                              host)
    assert trace.window == (1 * ns, 11 * ns)
    busy = xplane.device_busy(trace)
    assert busy == pytest.approx({"busy_s": (8e-3 + 10e-3) / 2,
                                  "window_s": 10e-3})
    bd = xplane.breakdown(trace)
    assert bd["device_ops"][0] == ["%fusion.* (1 instructions)",
                                   pytest.approx(14e-3)]
    assert bd["device_ops"][3] == [
        "%fusion.1 = bf16[256,56,56,64] fusion(bf16[256,56,56,64] "
        "%all-reduce.7, f32[64] %p), kind=kOutput", pytest.approx(14e-3)]
    assert len(bd["device_ops"]) == 6
    assert bd["idle_gaps"][0] == ["wait_loss", pytest.approx(1e-3)]
    cell = harness.resolve(MANIFEST, "sc2-3b-t8k")
    ctx = {"trace": trace, "traced_steps": 2, "kind": "TPU v5 lite",
           "cell": cell, "platform": "tpu"}
    busy_r = harness.load_module(MANIFEST, "readers", "xplane_busy")
    coll_r = harness.load_module(MANIFEST, "readers", "xplane_collectives")
    ops_r = harness.load_module(MANIFEST, "readers", "xplane_ops")
    assert busy_r.read(ctx) == pytest.approx(10.0)
    # union of collectives: 2 ms on each device, two steps, two devices;
    # the fusion that only NAMES %all-reduce.7 as an operand is not one
    assert coll_r.read(ctx) == pytest.approx(1.0)
    # device 0's runs alone (2 ms); device 1's is under the convolution
    assert coll_r.read(ctx, exposed=True) == pytest.approx(0.5)
    flash = harness.by_name(cell.per_layer, "flash_roofline_pct.tok", "m")
    xent = harness.by_name(cell.per_layer, "xent_roofline_pct.tok", "m")
    need = flops.flash_train_flops(batch=1, seq=8192, layers=4,
                                   **cell.config) / 197e12
    assert ops_r.read(ctx, **flash["args"]) == pytest.approx(
        100 * need / (2e-3 / 2 / 2))
    assert ops_r.read(ctx, **xent["args"]) is None     # no such event
    empty = {**ctx, "trace": xplane.Trace({}, {}, (0, 0))}
    assert busy_r.read(empty) is None and coll_r.read(empty) is None


# ------------------------------------------------------------ counts, peaks


def test_flops_against_hand_counts():
    # ResNet-50 at 224: 4.09 GMAC = 8.2 GFLOP forward, 24.6 a train step.
    assert flops.resnet_train_flops_per_image() == pytest.approx(
        24.6e9, rel=0.01)
    sc2 = harness.resolve(MANIFEST, "sc2-3b-t8k").config
    p = flops.lm_matmul_params(**sc2)
    # q 3072x3072, kv 3072x512, out 3072x3072, MLP 2x3072x12288
    assert p["layer"] == 9437184 + 1572864 + 9437184 + 75497472 == 95944704
    assert p["head"] == 3072 * 49152
    # window 4096 in 8192: 4096*4097/2 + 4096*4096 pairs
    assert flops.attended_pairs(8192, 4096) == 8390656 + 16777216
    assert flops.attended_pairs(1024, 4096) == 1024 * 1025 // 2
    assert flops.attended_pairs(5) == 15
    per_tok = flops.lm_train_flops_per_token(seq=8192, **sc2)
    layers = sc2["num_hidden_layers"]
    matmul = 6 * (layers * 95944704 + 150994944)
    attn = 12 * 24 * 128 * 25167872 * layers / 8192
    assert per_tok == pytest.approx(matmul + attn)
    assert flops.xent_train_flops(rows=8191, **sc2) == pytest.approx(
        7.42e12, rel=1e-3)
    # compute-bound both: far more than 240 operations a byte
    assert (flops.xent_train_flops(rows=8191, **sc2)
            / flops.xent_train_bytes(rows=8191, **sc2)) > 240
    assert (flops.flash_train_flops(batch=1, seq=8192, **sc2)
            / flops.flash_train_bytes(batch=1, seq=8192, **sc2)) > 240


def test_unknown_device_kind_is_an_error():
    assert flops.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peak"):
        flops.peak_for("TPU v9 imaginary")


def test_percentile_and_contract_line():
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3
    assert harness.percentile(list(range(11)), 90) == pytest.approx(9.0)
    assert harness.percentile([10.0, 20.0], 90) == pytest.approx(19.0)
    with pytest.raises(KeyError):
        harness.contract_line({"correct": True})
