"""The benchmark's readers of the program's own names (``chipbench/``):
``xplane_meta`` (what the profile's event metadata says of each operation:
``tf_op``, ``hlo_category``, ``flops``, ``bytes_accessed``), ``xplane_scopes``
(device time under a transform, a module or a Pallas kernel's ``tm_kernel``
identity) and ``program_span`` (the program's ``tm.step`` host span).

Here and not under ``chipbench/tests/`` so that the tier-1 command collects
them.  No test imports the chip's library; the rehearsal runs the command
as the driver does, in a process of its own.
"""

import gzip
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness, xplane, xplane_meta  # noqa: E402

MANIFEST = harness.load_manifest()
TESTDATA = os.path.join(harness.BENCH, "testdata")
SCOPES = harness.load_module(MANIFEST, "readers", "xplane_scopes")
SPAN = harness.load_module(MANIFEST, "readers", "program_span")

# what PR 24 added to BENCHMARK.json: metric -> the cells that report it.
# PR 26's cell joined the lists of the metrics whose reader is right for it
# unchanged (not ``mlp``: its feed-forward is no ``/Block_n/Dense_n/``).
ST = "st-21b-ep4-t8k"
TOK, IMG = ["sc2-3b-t8k", "sc2-3b-t1k"], ["rn50-1chip", "rn50-dp4"]
NEW_METRICS = {
    **{f"{k}_ms_per_step.tok": TOK + [ST]
       for k in ("flash_fwd", "flash_bwd", "xent_fwd", "xent_bwd", "fwd",
                 "bwd", "attn_proj")},
    "mlp_ms_per_step.tok": TOK,
    "fwd_ms_per_step.img": IMG, "bwd_ms_per_step.img": IMG,
    "step_span_ms.tok": TOK + [ST], "step_span_ms.img": IMG,
}
# what PR 26 added, in order: metric -> (reader, layer, source)
ST_METRICS = {
    "mfu_moe_pct.tok": ("mfu_module", "model step", "host_clock"),
    "moe_route_ms_per_step.tok": ("xplane_scopes", "expert layer",
                                  "device_trace"),
    "moe_permute_ms_per_step.tok": ("xplane_scopes_named", "expert layer",
                                    "device_trace"),
    "moe_experts_ms_per_step.tok": ("xplane_scopes_named", "expert layer",
                                    "device_trace"),
    "moe_experts_roofline_pct.tok": ("xplane_roofline", "expert layer",
                                     "device_trace"),
    "flash_mixed_roofline_pct.tok": ("xplane_roofline", "kernels",
                                     "device_trace"),
    "moe_rows_computed_over_routed.tok": ("counter_ratio", "expert layer",
                                          "program_counter"),
    "moe_routes_held_per_token.tok": ("step_counts", "expert layer",
                                      "program_counter"),
}
# what PR 27 added: the live share of the rows the dispatch moved
LIVE_SHARE = "moe_live_share_of_rows_moved.tok"
# the accepted metrics PR 26's cell reports besides (PR 23's)
ST_JOINS = ["dispatch_ms_per_step.tok", "xent_roofline_pct.tok",
            "device_idle_pct.tok"]
ST_STAYS_OUT = ["mfu_pct.tok", "flash_roofline_pct.tok",
                "mlp_ms_per_step.tok"]
# what PR 30 added, in order: the served cells' metrics (``.srv`` moves
# ``out_tokens_per_s_chip``, ``.lat`` moves ``itl_ms_p99``)
SAT, R80 = "sc2-3b-serve-sat", "sc2-3b-serve-r80"
SRV_METRICS = [
    "served_tokens_per_s.srv", "batch_occupancy_pct.srv",
    "prefill_share_pct.srv", "decode_step_ms.srv", "prefill_ms_per_ktok.srv",
    "cache_tokens_used_over_reserved.srv", "device_idle_pct.srv",
    "generator_late_ms_p95.srv", "itl_ms_p50.srv", "itl_ms_p99.srv",
    "ttft_ms_p50.srv", "mfu_pct.srv", "decode_hbm_roofline_pct.srv"]
LAT_METRICS = [
    "prefill_ms_per_ktok.lat", "prefill_share_pct.lat", "decode_step_ms.lat",
    "itl_ms_mean.lat", "itl_ms_p50.lat", "batch_occupancy_pct.lat",
    "device_idle_pct.lat", "generator_late_ms_p95.lat", "ttft_ms_p50.lat",
    "ttft_ms_p90.lat", "queue_wait_ms_p90.lat"]
# what PR 31 added, in order: metric -> (reader, layer, source, unit, better)
IMOE = "imoe-16b-serve-conv-sat"
IMOE_METRICS = {
    "decode_moe_hbm_roofline_pct.srv": (
        "serve_moe_decode_roofline", "serving engine", "device_trace", "%",
        "higher"),
    "moe_experts_ms_per_decode_step.srv": (
        "serve_scopes_in_module", "expert layer", "device_trace", "ms",
        "lower"),
    "moe_permute_ms_per_decode_step.srv": (
        "serve_scopes_in_module", "expert layer", "device_trace", "ms",
        "lower"),
    "latent_attn_ms_per_decode_step.srv": (
        "serve_scopes_in_module", "serving engine", "device_trace", "ms",
        "lower"),
    "moe_experts_touched_share.srv": (
        "moe_touched_share", "expert layer", "program_counter", "1",
        "higher"),
}
# a dense model's count of a step's bytes: not the sparse cell's
IMOE_STAYS_OUT = ["decode_hbm_roofline_pct.srv"]
# what PR 33 added, in order: metric -> (reader, layer, source, unit, better)
NM3 = "nm3s-120b-serve-chat-sat"
NM3_CONFIG = "nemotron3-super-120b-a12b-serve"
NM3_METRICS = {
    "ssm_ms_per_decode_step.srv": (
        "serve_scopes_in_module", "state-space layers", "device_trace", "ms",
        "lower"),
    "attn_ms_per_decode_step.srv": (
        "serve_scopes_in_module", "serving engine", "device_trace", "ms",
        "lower"),
    "moe_latent_ms_per_decode_step.srv": (
        "serve_scopes_in_module", "expert layer", "device_trace", "ms",
        "lower"),
    "ssm_scan_ms_per_prefill.srv": (
        "serve_scopes_in_module_per", "state-space layers", "device_trace",
        "ms", "lower"),
    "moe_routes_held_share.srv": (
        "counter_ratio", "expert layer", "program_counter", "1", "higher"),
    "decode_hybrid_hbm_roofline_pct.srv": (
        "serve_hybrid_decode_roofline", "serving engine", "device_trace",
        "%", "higher"),
}
# PR 31's metrics whose readers are right for the hybrid as they stand ...
NM3_JOINS_OF_IMOE = ["moe_experts_ms_per_decode_step.srv",
                     "moe_permute_ms_per_decode_step.srv",
                     "moe_experts_touched_share.srv"]
# ... and the three it stays out of: a dense model's and a latent-cache
# model's counts of a step's bytes, and latent attention's scope
NM3_STAYS_OUT = {"decode_hbm_roofline_pct.srv": [SAT],
                 "decode_moe_hbm_roofline_pct.srv": [IMOE],
                 "latent_attn_ms_per_decode_step.srv": [IMOE]}

# what PR 37 added, in order: metric -> (reader, layer, source, unit, better)
JAMBA = "jamba2-3b-serve-reason-sat"
JAMBA_CONFIG = "jamba2-3b-serve"
JAMBA_METRICS = {
    "mamba_ms_per_decode_step.srv": (
        "serve_scopes_in_module", "state-space layers", "device_trace", "ms",
        "lower"),
    "mamba_state_ms_per_decode_step.srv": (
        "serve_scopes_in_module", "state-space layers", "device_trace", "ms",
        "lower"),
    "mamba_state_hbm_roofline_pct.srv": (
        "serve_state_roofline", "state-space layers", "device_trace", "%",
        "higher"),
    "decode_ssm_hbm_roofline_pct.srv": (
        "serve_state_roofline", "state-space layers", "device_trace", "%",
        "higher"),
    "mamba_scan_ms_per_prefill.srv": (
        "serve_scopes_in_module_per", "state-space layers", "device_trace",
        "ms", "lower"),
    "mlp_ms_per_decode_step.srv": (
        "serve_scopes_in_module", "model step", "device_trace", "ms",
        "lower"),
}
# PR 33's metric whose reader is right for it as it stands (the scope of
# ``SPAttention``) ...
JAMBA_JOINS_OF_NM3 = ["attn_ms_per_decode_step.srv"]
# ... and what it stays out of: the other models' counts of a step's bytes,
# latent attention's and Mamba-2's scopes, everything of an expert layer
JAMBA_STAYS_OUT = {
    "decode_hbm_roofline_pct.srv": [SAT],
    "decode_moe_hbm_roofline_pct.srv": [IMOE],
    "decode_hybrid_hbm_roofline_pct.srv": [NM3],
    "latent_attn_ms_per_decode_step.srv": [IMOE],
    "ssm_ms_per_decode_step.srv": [NM3],
    "ssm_scan_ms_per_prefill.srv": [NM3],
    "moe_experts_ms_per_decode_step.srv": [IMOE, NM3],
    "moe_permute_ms_per_decode_step.srv": [IMOE, NM3],
    "moe_experts_touched_share.srv": [IMOE, NM3],
    "moe_latent_ms_per_decode_step.srv": [NM3],
    "moe_routes_held_share.srv": [NM3]}

# what PR 35 added, in order: the program's own ``tm.serve.*`` spans, read
# by ``program_span`` (mean ms) and ``serve_idle_by_span`` (the idle share
# under them): metric -> (reader, the reader's ``span``, layer)
SATS = [SAT, IMOE, NM3, JAMBA]
ENGINE, SCHEDULER = "serving engine", "serving scheduler"
SPAN_METRICS = {
    "decode_span_ms.srv": ("program_span", "tm.serve.step", ENGINE),
    "admit_span_ms.srv": ("program_span", "tm.serve.admit", ENGINE),
    "decode_span_ms.lat": ("program_span", "tm.serve.step", ENGINE),
    "admit_span_ms.lat": ("program_span", "tm.serve.admit", ENGINE),
    "idle_step_operands_pct.srv": (
        "serve_idle_by_span", r"^tm\.serve\.step\.(operands|draft)$", ENGINE),
    "idle_step_dispatch_pct.srv": (
        "serve_idle_by_span", r"^tm\.serve\.step\.dispatch$", ENGINE),
    "idle_step_read_pct.srv": (
        "serve_idle_by_span", r"^tm\.serve\.step\.read$", ENGINE),
    "idle_step_book_pct.srv": (
        "serve_idle_by_span", r"^tm\.serve\.step\.book$", ENGINE),
    "idle_admit_operands_pct.srv": (
        "serve_idle_by_span", r"^tm\.serve\.admit\.operands$", ENGINE),
    "idle_admit_dispatch_pct.srv": (
        "serve_idle_by_span", r"^tm\.serve\.admit\.(prefill|slot_write)$",
        ENGINE),
    "idle_admit_read_pct.srv": (
        "serve_idle_by_span", r"^tm\.serve\.admit\.read$", ENGINE),
    "idle_tick_self_pct.srv": (
        "serve_idle_by_span",
        r"^tm\.serve\.(tick|step|admit|admit\.book|gate)$", SCHEDULER),
    "idle_outside_tick_pct.srv": (
        "serve_idle_by_span", r"^outside$", SCHEDULER),
    "idle_in_step_pct.lat": (
        "serve_idle_by_span", r"^tm\.serve\.step", ENGINE),
    "idle_in_admit_pct.lat": (
        "serve_idle_by_span", r"^tm\.serve\.admit", ENGINE),
    "idle_outside_tick_pct.lat": (
        "serve_idle_by_span", r"^outside$", SCHEDULER),
}
SPAN_SRV = {m for m in SPAN_METRICS if m.endswith(".srv")}
# what PR 36 added: the sampling tail's device time a decode step, by the
# scope the program gives it (``generate._sample_rows``)
SAMPLE_MS = "sample_ms_per_decode_step.srv"
# what PR 38 added: the per-row cache write's device time a decode step, by
# the scope the program gives it (``models.transformer.write_rows``)
KV_WRITE_MS = "kv_write_ms_per_decode_step.srv"
# the decode expert kernel's share of its roofline, in the three cells
# whose pooled step has expert layers
MOE_DECODE = "moe_decode_hbm_roofline_pct.srv"
# what the window-ring cell added, in order: metric -> (reader, layer,
# source, unit, better)
KEX = "kex-236b-serve-mixlen-sat"
KEX_CONFIG = "k-exaone-236b-a23b-serve"
KEX_METRICS = {
    "window_attn_ms_per_decode_step.srv": (
        "serve_scopes_in_module", ENGINE, "device_trace", "ms", "lower"),
    "decode_window_hbm_roofline_pct.srv": (
        "serve_window_decode_roofline", ENGINE, "device_trace", "%",
        "higher"),
    "moe_held_experts_touched_share.srv": (
        "moe_touched_share", "expert layer", "program_counter", "1",
        "higher"),
}
# the accepted metrics whose readers' arguments resolve in its file: the
# scope of ``SPAttention``, the sparse cell's two scopes of the expert
# layer, the hybrid's share of routes held (16 of 128 experts held) and the
# scope of a block's own dense feed-forward (layer 0's SwiGLU)
# (``moe_experts_touched_share.srv`` reads ``n_routed_experts``, a key this
# configuration does not have: its own share reads ``num_experts``)
KEX_JOINS = ["attn_ms_per_decode_step.srv",
             "moe_experts_ms_per_decode_step.srv",
             "moe_permute_ms_per_decode_step.srv",
             "moe_routes_held_share.srv",
             "mlp_ms_per_decode_step.srv"]
KEX_STAYS_OUT = {
    "decode_hbm_roofline_pct.srv": [SAT],
    "decode_moe_hbm_roofline_pct.srv": [IMOE],
    "decode_hybrid_hbm_roofline_pct.srv": [NM3],
    "decode_ssm_hbm_roofline_pct.srv": [JAMBA],
    "latent_attn_ms_per_decode_step.srv": [IMOE],
    "moe_experts_touched_share.srv": [IMOE, NM3]}
IDLE_SRV = [m for m, v in SPAN_METRICS.items()
            if v[0] == "serve_idle_by_span" and m.endswith(".srv")]
IDLE = harness.load_module(MANIFEST, "readers", "serve_idle_by_span")

with open(os.path.join(TESTDATA, "expected_names.json")) as _f:
    WANT = json.load(_f)


with open(os.path.join(TESTDATA, "expected_served.json")) as _f:
    SERVED = json.load(_f)


@pytest.fixture(scope="module")
def recorded_served(tmp_path_factory):
    """Three recorded ticks of ``sc2-3b-serve-sat`` with one admission in
    the middle one, cut from PR 35's traced chip run: (the file, its Trace
    and module executions as the served runner loads them)."""
    from chipbench import xplane_serve

    path = os.path.join(TESTDATA, SERVED["file"])
    raw = tmp_path_factory.mktemp("served") / "ticks.xplane.pb"
    with gzip.open(path, "rb") as f:
        raw.write_bytes(f.read())
    trace, modules = xplane_serve.load(str(raw))
    return path, trace, modules


@pytest.fixture(scope="module")
def recorded():
    """The recorded ``sc2-3b-t8k`` step: (Trace, metadata of its plane)."""
    path = os.path.join(TESTDATA, WANT["file"])
    return xplane.load(path), xplane_meta.load(path)


# ------------------------------------------------------------ the manifest


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metric_resolves_to_a_file_and_a_reader(metric):
    entry = harness.by_name(MANIFEST["per_layer"], metric, "metric")
    assert entry["workloads"] == NEW_METRICS[metric]
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    spec = harness.load_json(MANIFEST, "layer_metrics", metric)
    reader = harness.load_module(MANIFEST, "readers", spec["reader"])
    if spec["reader"] == "program_span":
        assert entry["source"] == "program_span"
        assert spec["args"] == {"span": "tm.step"}
    else:
        assert entry["source"] == "device_trace" and reader is SCOPES
        assert set(spec["args"]) <= {"op_name", "not_op_name", "kernel"}
    for cell in entry["workloads"]:
        mine = harness.by_name(harness.resolve(MANIFEST, cell).per_layer,
                               metric, "metric")
        assert mine["args"] == spec["args"]


@pytest.mark.parametrize("metric", sorted(ST_METRICS))
def test_expert_cell_metric_resolves_to_a_file_and_a_reader(metric):
    reader, layer, source = ST_METRICS[metric]
    entry = harness.by_name(MANIFEST["per_layer"], metric, "metric")
    assert entry["workloads"] == [ST]
    assert (entry["layer"], entry["source"]) == (layer, source)
    assert entry["moves"] == "tokens_per_s_chip"
    assert (entry["unit"], entry["better"]) == (
        ("%", "higher") if "_pct." in metric else
        ("ms", "lower") if "_ms_" in metric else ("1", "lower"))
    spec = harness.load_json(MANIFEST, "layer_metrics", metric)
    assert spec["reader"] == reader
    assert callable(harness.load_module(MANIFEST, "readers", reader).read)
    mine = harness.by_name(harness.resolve(MANIFEST, ST).per_layer, metric,
                           "metric")
    assert mine["args"] == spec.get("args", {})
    for pattern in ("op_name", "name", "not_name", "pattern"):
        re.compile(spec.get("args", {}).get(pattern, ""))


def test_live_share_of_rows_moved_is_a_ratio_of_the_programs_counters():
    entry = harness.by_name(MANIFEST["per_layer"], LIVE_SHARE, "metric")
    assert entry == {
        "name": LIVE_SHARE, "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "expert layer",
        "moves": "tokens_per_s_chip", "workloads": [ST]}
    assert MANIFEST["per_layer"][30] == entry   # where PR 27 appended it
    spec = harness.load_json(MANIFEST, "layer_metrics", LIVE_SHARE)
    assert spec == {"reader": "counter_ratio", "args": {
        "numerator": "tm_moe_routes_held_total",
        "denominator": "tm_moe_rows_moved_total"}}
    # a program without the counter (the parent) gives no number and no
    # error; with it, the ratio
    from torchmpi_tpu import obs
    read = harness.load_module(MANIFEST, "readers", "counter_ratio").read
    obs.reset()
    assert read({}, **spec["args"]) is None
    obs.registry().counter_inc("tm_moe_routes_held_total", 250, layer="a")
    obs.registry().counter_inc("tm_moe_rows_moved_total", 256, layer="a")
    assert read({}, **spec["args"]) == 250 / 256
    obs.reset()


def test_expert_cell_joins_the_lists_whose_readers_are_right_for_it():
    mine = {m["name"] for m in harness.resolve(MANIFEST, ST).per_layer}
    joined = {m for m, cells in NEW_METRICS.items() if ST in cells}
    assert mine == joined | set(ST_JOINS) | set(ST_METRICS) | {LIVE_SHARE}
    for metric in ST_JOINS:
        assert harness.by_name(MANIFEST["per_layer"], metric,
                               "metric")["workloads"] == TOK + [ST]
    # dense, one-window counts and patterns: not this cell's
    for metric in ST_STAYS_OUT:
        assert harness.by_name(MANIFEST["per_layer"], metric,
                               "metric")["workloads"] == TOK
    e2e = {m["name"] for m in harness.resolve(MANIFEST, ST).end_to_end}
    assert e2e == {"tokens_per_s_chip", "step_ms_p90", "setup_s"}
    cell = harness.by_name(MANIFEST["workloads"], ST, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b", "b1-t8192", 1)
    assert [w["name"] for w in MANIFEST["workloads"]][4] == ST
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


@pytest.mark.parametrize("metric", sorted(IMOE_METRICS))
def test_sparse_served_cell_metric_resolves_to_a_file_and_a_reader(metric):
    reader, layer, source, unit, better = IMOE_METRICS[metric]
    entry = harness.by_name(MANIFEST["per_layer"], metric, "metric")
    assert entry == {
        "name": metric, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "out_tokens_per_s_chip",
        "workloads": entry["workloads"]}
    assert entry["workloads"][0] == IMOE
    spec = harness.load_json(MANIFEST, "layer_metrics", metric)
    assert spec["reader"] == reader
    assert callable(harness.load_module(MANIFEST, "readers", reader).read)
    mine = harness.by_name(harness.resolve(MANIFEST, IMOE).per_layer, metric,
                           "metric")
    assert mine["args"] == spec["args"]
    for pattern in ("module", "op_name", "name", "not_name"):
        re.compile(spec["args"].get(pattern, ""))
    if source == "device_trace":     # the pooled decode step's program
        assert spec["args"]["module"] == "jit__slot_step_jit"


def test_sparse_served_cell_joins_the_lists_whose_readers_are_right_for_it():
    """PR 31's cell: the ``.srv`` metrics of PR 30 whose readers are right
    for it unchanged, its own five, and not the dense model's count of a
    decode step's bytes; one four-chip cell of eight."""
    mine = {m["name"] for m in harness.resolve(MANIFEST, IMOE).per_layer}
    joined = set(SRV_METRICS) - set(IMOE_STAYS_OUT)
    assert mine == joined | set(IMOE_METRICS) | SPAN_SRV | {
        SAMPLE_MS, KV_WRITE_MS, MOE_DECODE}
    for metric in joined:
        assert harness.by_name(MANIFEST["per_layer"], metric,
                               "metric")["workloads"][:2] == [SAT, IMOE]
    for metric in IMOE_STAYS_OUT:
        assert harness.by_name(MANIFEST["per_layer"], metric,
                               "metric")["workloads"] == [SAT]
    for metric in LAT_METRICS:
        assert harness.by_name(MANIFEST["per_layer"], metric,
                               "metric")["workloads"] == [R80]
    cell = harness.resolve(MANIFEST, IMOE)
    assert {m["name"] for m in cell.end_to_end} == {
        "out_tokens_per_s_chip", "setup_s"}
    assert harness.by_name(MANIFEST["end_to_end"], "out_tokens_per_s_chip",
                           "metric")["workloads"][:2] == [SAT, IMOE]
    entry = harness.by_name(MANIFEST["workloads"], IMOE, "workload")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "instella-moe-16b-a3b-serve", "open-conv-sat", 1)
    assert cell.config["runner"] == "serve_open_loop_experts"
    assert cell.config["reduced"] == harness.by_name(
        MANIFEST["configs"], "instella-moe-16b-a3b-serve",
        "config")["reduced"] == ["num_hidden_layers",
                                 "num_nextn_predict_layers"]
    names = [w["name"] for w in MANIFEST["workloads"]]
    assert names[5:8] == [R80, SAT, IMOE]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"][:8]) == 1


@pytest.mark.parametrize("metric", sorted(NM3_METRICS))
def test_hybrid_served_cell_metric_resolves_to_a_file_and_a_reader(metric):
    reader, layer, source, unit, better = NM3_METRICS[metric]
    entry = harness.by_name(MANIFEST["per_layer"], metric, "metric")
    assert entry == {
        "name": metric, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "out_tokens_per_s_chip",
        "workloads": entry["workloads"]}
    assert entry["workloads"][0] == NM3
    spec = harness.load_json(MANIFEST, "layer_metrics", metric)
    assert spec["reader"] == reader
    assert callable(harness.load_module(MANIFEST, "readers", reader).read)
    mine = harness.by_name(harness.resolve(MANIFEST, NM3).per_layer, metric,
                           "metric")
    assert mine["args"] == spec["args"]
    for pattern in ("module", "op_name", "name", "not_name"):
        re.compile(spec["args"].get(pattern, ""))
    if source == "device_trace":     # one of the served path's two programs
        assert spec["args"]["module"] == (
            "jit__slot_prefill_jit" if "prefill" in metric
            else "jit__slot_step_jit")


def test_hybrid_served_cell_joins_the_lists_whose_readers_are_right_for_it():
    """PR 33's cell: the twelve generic ``.srv`` metrics of PR 30, three of
    PR 31's, its own six, and not the three whose counts or scopes are
    another model's; nine cells, one of them on four chips; its
    configuration the sixth, with the six keys it cut."""
    mine = {m["name"] for m in harness.resolve(MANIFEST, NM3).per_layer}
    joined = (set(SRV_METRICS) - set(IMOE_STAYS_OUT)) | set(
        NM3_JOINS_OF_IMOE)
    assert mine == joined | set(NM3_METRICS) | SPAN_SRV | {
        SAMPLE_MS, KV_WRITE_MS, MOE_DECODE}
    for metric in joined:
        assert NM3 in harness.by_name(MANIFEST["per_layer"], metric,
                                      "metric")["workloads"]
    for metric, cells in NM3_STAYS_OUT.items():
        assert harness.by_name(MANIFEST["per_layer"], metric,
                               "metric")["workloads"][:len(cells)] == cells
        assert metric not in mine
    cell = harness.resolve(MANIFEST, NM3)
    assert {m["name"] for m in cell.end_to_end} == {
        "out_tokens_per_s_chip", "setup_s"}
    assert harness.by_name(MANIFEST["end_to_end"], "out_tokens_per_s_chip",
                           "metric")["workloads"][:3] == [SAT, IMOE, NM3]
    entry = harness.by_name(MANIFEST["workloads"], NM3, "workload")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NM3_CONFIG, "open-chat-sat", 1)
    assert cell.config["runner"] == "serve_open_loop_hybrid"
    assert cell.config["reduced"] == harness.by_name(
        MANIFEST["configs"], NM3_CONFIG, "config")["reduced"] == [
            "num_hidden_layers", "hybrid_override_pattern",
            "n_routed_experts", "vocab_size", "num_nextn_predict_layers",
            "mtp_hybrid_override_pattern"]
    # the share: 128 of the router's 512 held, a quarter of the vocabulary
    assert (cell.config["n_routed_experts"], cell.config["router_width"],
            cell.config["experts_held"]) == (128, 512, [0, 128])
    assert cell.config["published"]["n_routed_experts"] == 512
    assert cell.config["vocab_size"] * 4 == cell.config["published"][
        "vocab_size"]
    # the traffic's ids come from the slice, its lengths fit a slot
    srv, traffic = cell.config["serving"], cell.traffic
    assert (traffic["prompt_tokens"]["max"] + traffic["answer_tokens"]["max"]
            <= srv["slot_tokens"])
    names = [w["name"] for w in MANIFEST["workloads"]]
    assert names[8:9] == [NM3]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"][:9]) == 1


@pytest.mark.parametrize("metric", sorted(JAMBA_METRICS))
def test_state_served_cell_metric_resolves_to_a_file_and_a_reader(metric):
    reader, layer, source, unit, better = JAMBA_METRICS[metric]
    entry = harness.by_name(MANIFEST["per_layer"], metric, "metric")
    assert entry == {
        "name": metric, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "out_tokens_per_s_chip",
        "workloads": entry["workloads"]}
    assert entry["workloads"][0] == JAMBA
    spec = harness.load_json(MANIFEST, "layer_metrics", metric)
    assert spec["reader"] == reader
    assert callable(harness.load_module(MANIFEST, "readers", reader).read)
    mine = harness.by_name(harness.resolve(MANIFEST, JAMBA).per_layer,
                           metric, "metric")
    assert mine["args"] == spec["args"]
    for pattern in ("module", "op_name", "name", "not_name"):
        re.compile(spec["args"].get(pattern, ""))
    assert spec["args"]["module"] == (
        "jit__slot_prefill_jit" if "prefill" in metric
        else "jit__slot_step_jit")


def test_state_served_cell_joins_the_lists_whose_readers_are_right_for_it():
    """PR 37's cell: the generic ``.srv`` metrics of PR 30, PR 35 and PR 36,
    one of PR 33's, its own six, and nothing of another model's counts,
    scopes or expert layers; ten cells, one of them on four chips; its
    configuration the seventh, with nothing cut."""
    mine = {m["name"] for m in harness.resolve(MANIFEST, JAMBA).per_layer}
    joined = (set(SRV_METRICS) - set(IMOE_STAYS_OUT)) | set(
        JAMBA_JOINS_OF_NM3) | SPAN_SRV | {SAMPLE_MS, KV_WRITE_MS}
    assert mine == joined | set(JAMBA_METRICS)
    # pinned by name and by "still there, in order", not by the tail: the
    # next served cell appends after these and trips nothing (ROADMAP C0)
    for metric in joined:
        cells = harness.by_name(MANIFEST["per_layer"], metric,
                                "metric")["workloads"]
        assert JAMBA in cells and (
            NM3 not in cells or cells.index(NM3) < cells.index(JAMBA))
    for metric, cells in JAMBA_STAYS_OUT.items():
        assert harness.by_name(MANIFEST["per_layer"], metric,
                               "metric")["workloads"][:len(cells)] == cells
        assert metric not in mine
    cell = harness.resolve(MANIFEST, JAMBA)
    assert {m["name"] for m in cell.end_to_end} == {
        "out_tokens_per_s_chip", "setup_s"}
    assert harness.by_name(MANIFEST["end_to_end"], "out_tokens_per_s_chip",
                           "metric")["workloads"][:4] == SATS
    entry = harness.by_name(MANIFEST["workloads"], JAMBA, "workload")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        JAMBA_CONFIG, "open-reason-sat", 1)
    assert 1 <= len(entry["why"]) <= 200
    config = harness.by_name(MANIFEST["configs"], JAMBA_CONFIG, "config")
    assert config["reduced"] == cell.config["reduced"] == []
    assert 1 <= len(config["why"]) <= 200
    assert cell.config["runner"] == "serve_open_loop_jamba"
    # the whole model as published: every key of the catalog's config, the
    # layers' order from the period and the offset, the parameters counted
    assert (cell.config["num_hidden_layers"], cell.config["hidden_size"],
            cell.config["intermediate_size"], cell.config["vocab_size"],
            cell.config["mamba_d_state"], cell.config["mamba_dt_rank"],
            cell.config["num_key_value_heads"],
            cell.config["tie_word_embeddings"]) == (
                28, 2560, 8192, 65536, 16, 160, 1, True)
    assert cell.config["layer_pattern"] == "".join(
        "a" if i % cell.config["attn_layer_period"]
        == cell.config["attn_layer_offset"] else "m" for i in range(28))
    from chipbench import flops_jamba_serve as counts

    sizes = {k: cell.config[k] for k in cell.config["flops"]["sizes"]}
    assert counts.parameters(**sizes) == 3_029_337_472
    assert counts.state_bytes_per_slot(**sizes) == 10_117_120
    assert "3,029,337,472" in cell.config["parameters"]
    # the traffic's lengths fit a slot; decode-heavy: answers over prompts
    srv, traffic = cell.config["serving"], cell.traffic
    assert (traffic["prompt_tokens"]["max"] + traffic["answer_tokens"]["max"]
            == srv["slot_tokens"])
    assert traffic["answer_tokens"]["median"] == 4 * traffic[
        "prompt_tokens"]["median"]
    names = [w["name"] for w in MANIFEST["workloads"]]
    assert names[9:10] == [JAMBA]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"][:10]) == 1


@pytest.mark.parametrize("metric", sorted(KEX_METRICS))
def test_window_served_cell_metric_resolves_to_a_file_and_a_reader(metric):
    reader, layer, source, unit, better = KEX_METRICS[metric]
    entry = harness.by_name(MANIFEST["per_layer"], metric, "metric")
    assert entry == {
        "name": metric, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "out_tokens_per_s_chip",
        "workloads": [KEX]}
    spec = harness.load_json(MANIFEST, "layer_metrics", metric)
    assert spec["reader"] == reader
    assert callable(harness.load_module(MANIFEST, "readers", reader).read)
    mine = harness.by_name(harness.resolve(MANIFEST, KEX).per_layer,
                           metric, "metric")
    assert mine["args"] == spec["args"]
    for key, value in spec["args"].items():
        if key == "module":
            assert value == "jit__slot_step_jit"
        elif key == "experts":
            assert value in harness.resolve(MANIFEST, KEX).config
        else:
            re.compile(value)


def test_window_served_cell_joins_the_lists_whose_readers_are_right_for_it():
    """The window-ring cell: every ``-sat`` cell's metrics, the
    attention's and the expert layer's scopes, its own three, and nothing
    of another model's counts of a step's bytes; eleven cells, one of them
    on four chips; its configuration the eighth, cut to one chip's share
    of eight."""
    mine = {m["name"] for m in harness.resolve(MANIFEST, KEX).per_layer}
    joined = (set(SRV_METRICS) - set(IMOE_STAYS_OUT)) | set(KEX_JOINS) | (
        SPAN_SRV | {SAMPLE_MS, KV_WRITE_MS})
    assert mine == joined | set(KEX_METRICS) | {MOE_DECODE}
    for metric in joined:
        cells = harness.by_name(MANIFEST["per_layer"], metric,
                                "metric")["workloads"]
        assert cells[-1] == KEX
    for metric, cells in KEX_STAYS_OUT.items():
        assert harness.by_name(MANIFEST["per_layer"], metric,
                               "metric")["workloads"] == cells
        assert metric not in mine
    cell = harness.resolve(MANIFEST, KEX)
    assert {m["name"] for m in cell.end_to_end} == {
        "out_tokens_per_s_chip", "setup_s"}
    assert harness.by_name(MANIFEST["end_to_end"], "out_tokens_per_s_chip",
                           "metric")["workloads"] == SATS + [KEX]
    entry = harness.by_name(MANIFEST["workloads"], KEX, "workload")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        KEX_CONFIG, "open-mixlen-sat", 1)
    assert 1 <= len(entry["why"]) <= 200
    config = harness.by_name(MANIFEST["configs"], KEX_CONFIG, "config")
    assert config["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert 1 <= len(config["why"]) <= 200
    assert cell.config["runner"] == "serve_open_loop_exaone"
    # one chip's share of eight: 16 of the router's 128, an eighth of the
    # vocabulary, layers 0-4 (one whole LLLG period after the dense layer)
    published = cell.config["published"]
    assert (cell.config["num_experts"], cell.config["router_width"],
            cell.config["experts_held"]) == (16, 128, [0, 16])
    assert published["num_experts"] == 8 * cell.config["num_experts"]
    assert published["vocab_size"] == 8 * cell.config["vocab_size"]
    assert (cell.config["num_hidden_layers"], published["num_hidden_layers"]
            ) == (5, 48)
    assert cell.config["window_layout"] == cell.config["rope_layout"] == [
        1 if w else 0 for w in cell.config["sliding_windows"][:5]]
    assert cell.config["layer_types"][:5] == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    from chipbench import flops_exaone_serve as counts

    sizes = {k: cell.config[k] for k in cell.config["flops"]["sizes"]}
    assert counts.parameters(**sizes) == 3_712_028_416
    assert "3,712,028,416" in cell.config["parameters"]
    # the traffic's lengths fit a slot, and every prompt passes the window
    srv, traffic = cell.config["serving"], cell.traffic
    assert (traffic["prompt_tokens"]["max"] + traffic["answer_tokens"]["max"]
            == srv["slot_tokens"])
    assert traffic["prompt_tokens"]["min"] >= 2 * cell.config[
        "sliding_window"]
    names = [w["name"] for w in MANIFEST["workloads"]]
    assert names[10:11] == [KEX]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"][:11]) == 1


def test_window_roofline_reader_counts_the_required_bytes(monkeypatch):
    """No live slots among the window's numbers, no traced step, no chip or
    no counters: no number and no error.  With them: the weights outside
    the routed experts, the held experts touched, the live ring rows and the
    global layer's live keys and values, over the step's time."""
    from chipbench import flops, flops_exaone_serve as counts

    roof = harness.load_module(MANIFEST, "readers",
                               "serve_window_decode_roofline")
    cell = harness.resolve(MANIFEST, KEX)
    args = harness.load_json(MANIFEST, "layer_metrics",
                             "decode_window_hbm_roofline_pct.srv")["args"]
    ctx = {"cell": cell, "platform": "tpu", "kind": "TPU v5 lite",
           "traced": {"steps": 5}, "serve": {},
           "trace": xplane.Trace({}, {}, (0, 1))}
    assert roof.read(ctx, **args) is None                   # no live slots
    ctx["serve"] = {"live_slots_per_step": 20.0,
                    "live_tokens_per_step": 20 * 5000.0}
    monkeypatch.setattr(roof, "touched_per_step", lambda: None)
    assert roof.read(ctx, **args) is None                   # no counters
    monkeypatch.setattr(roof, "touched_per_step", lambda: 40.0)
    assert roof.read({**ctx, "platform": "cpu"}, **args) is None
    monkeypatch.setattr(harness.load_module(
        MANIFEST, "readers", "serve_module_ms"), "read",
        lambda ctx, module, per: 15.0)
    sizes = {k: cell.config[k] for k in cell.config["flops"]["sizes"]}
    row = 2 * 8 * 128 * 4
    need = (2 * (counts.matmul_params(**sizes)["outside_routed"]
                 + 40 * 3 * 6144 * 2048)
            + row * (4 * 20 * 128 + 20 * 5000))
    assert counts.decode_step_bytes(20 * 5000.0, 4 * 20 * 128, 40.0,
                                    **sizes) == need
    hbm = flops.peak_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert roof.read(ctx, **args) == pytest.approx(100 * 1e3 * need / hbm
                                                   / 15.0)


def test_traced_rehearsal_of_the_window_served_cell_comes_out_correct():
    """``run.py --rehearse`` as the benchmark calls it, with a seed past
    2**31: rings of the rehearsal's window of 8 under prompts of 12-48 tokens,
    bucketed prefill told its true length, the pooled step, correct against
    the plain reference by the two shares; the counters of the live ring
    rows are on the line; the device's times and the rooflines are left
    out."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", KEX, "--seed", "3900000011", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True
    assert out["failed"] == 0 and out["attempted"] > 50
    assert list(out["compared"])[:2] == ["off_the_top_share",
                                         "worst_request_off_share"]
    assert out["compared"]["off_the_top_share"][0] == 0.0
    # the global layer's key and value of 2 heads of 16 in float32, and four
    # rings of 8 such rows
    assert out["compared"]["cache_bytes_per_token"] == [256, 256]
    assert out["compared"]["ring_bytes_per_slot"] == [4 * 8 * 256] * 2
    counters = out["checks"]["counters"]
    assert counters["pool_donated"] == counters["pool_calls"] > 0
    # four ring layers of 8 rows, every prompt past the window
    assert counters["window_rows_live"] == 4 * 8 * counters["live_slot_steps"]
    assert not {"window_attn_ms_per_decode_step.srv",
                "decode_window_hbm_roofline_pct.srv",
                "mlp_ms_per_decode_step.srv"} & set(out["metrics"])
    assert 0 < out["metrics"]["moe_held_experts_touched_share.srv"][
        "value"] <= 1
    # 4 of the router's 32 experts held: about an eighth of the routes
    assert 0.05 < out["metrics"]["moe_routes_held_share.srv"]["value"] < 0.3
    served_spans_are_read_and_the_idle_split_left_out(out)


def test_window_served_cell_holds_the_cache_to_its_stated_type(monkeypatch):
    """What the engine books for a cached position and for a slot's rings
    is what the file's ``cache_dtype`` makes them; a booking of keys and
    values one precision below (bfloat16) is not correct, whatever the
    served tokens read, and a configuration that asks for no such check is
    judged by what was bound before."""
    import types

    from torchmpi_tpu import serving

    runner = harness.load_module(MANIFEST, "runners",
                                 "serve_open_loop_exaone")
    cell = harness.resolve(MANIFEST, KEX, rehearse=True)
    import jax
    import jax.numpy as jnp

    lm = harness.build_model(cell)
    shapes = jax.eval_shape(lambda: lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    engine = serving.ReplicaEngine(
        lm, shapes, slots=2, slot_tokens=cell.config["serving"]["slot_tokens"])
    stated = runner.stated_cache_bytes(cell.config)
    assert stated == {"cache_bytes_per_token": engine.cache_bytes_per_token,
                      "ring_bytes_per_slot": engine.ring_bytes_per_slot}
    assert runner.stated_cache_bytes(harness.resolve(MANIFEST, KEX).config) == {
        "cache_bytes_per_token": 8192, "ring_bytes_per_slot": 4 * 128 * 8192}
    monkeypatch.setattr(runner.experts, "judge",
                        lambda *a: ({}, {"off_the_top_share": [0.0, 0.08]},
                                    True))
    for scale, correct in ((1, True), (0.5, False)):
        s = types.SimpleNamespace(cache_bytes={
            k: int(v * scale) for k, v in stated.items()})
        _, compared, ok = runner.judge(cell, 1, s)
        assert ok is correct
        assert compared["ring_bytes_per_slot"] == [
            int(stated["ring_bytes_per_slot"] * scale),
            stated["ring_bytes_per_slot"]]
    other = harness.resolve(MANIFEST, IMOE, rehearse=True)
    monkeypatch.setattr(runner, "_before", lambda *a: "before")
    assert runner.judge(other, 1, None) == "before"


def test_state_roofline_reader_counts_the_work_from_the_windows_live_slots(
        monkeypatch):
    """No live slots among the window's numbers (no decode step in it),
    no traced step or no chip: no number and no error.  With them: the
    recurrent updates' bytes over the scope's time, the whole step's bytes
    over the program's time, both over the chip's bandwidth."""
    from chipbench import flops, flops_jamba_serve as counts

    roof = harness.load_module(MANIFEST, "readers", "serve_state_roofline")
    cell = harness.resolve(MANIFEST, JAMBA)
    args = {m: harness.load_json(MANIFEST, "layer_metrics", m)["args"]
            for m in ("mamba_state_hbm_roofline_pct.srv",
                      "decode_ssm_hbm_roofline_pct.srv")}
    ctx = {"cell": cell, "platform": "tpu", "kind": "TPU v5 lite",
           "traced": {"steps": 5, "live_tokens_per_step": 400 * 700.0},
           "serve": {}, "trace": xplane.Trace({}, {}, (0, 1))}
    for spec in args.values():
        assert roof.read(ctx, **spec) is None               # no live slots
    ctx["serve"]["live_slots_per_step"] = 400.0
    for spec in args.values():
        assert roof.read(ctx, **spec) is None               # nothing traced
        assert roof.read({**ctx, "platform": "cpu"}, **spec) is None
    # 10 ms under the scope, 40 ms a step
    monkeypatch.setattr(harness.load_module(
        MANIFEST, "readers", "serve_scopes_in_module"), "read",
        lambda ctx, module, op_name=None: 10.0)
    monkeypatch.setattr(harness.load_module(
        MANIFEST, "readers", "serve_module_ms"), "read",
        lambda ctx, module, per: 40.0)
    hbm = flops.peak_for("TPU v5 lite")["hbm_bytes_per_s"]
    sizes = {k: cell.config[k] for k in cell.config["flops"]["sizes"]}
    state = 2 * 400 * 26 * 16 * 5120 * 4
    assert roof.read(ctx, **args["mamba_state_hbm_roofline_pct.srv"]) == (
        pytest.approx(100 * 1e3 * state / hbm / 10.0))
    whole = 2 * 3_029_337_472 + 2 * 400 * 10_117_120 + 4 * 512 * 400 * 700
    assert counts.decode_step_bytes(400, 400 * 700.0, **sizes) == whole
    assert roof.read(ctx, **args["decode_ssm_hbm_roofline_pct.srv"]) == (
        pytest.approx(100 * 1e3 * whole / hbm / 40.0))
    with pytest.raises(ValueError, match="unknown work"):
        roof.read(ctx, module="jit__slot_step_jit", what="weights")


def test_hybrid_served_readers_give_no_number_without_the_programs_names():
    """A program without the scopes or the counters (the parent) gives no
    number and no error; with the counters, the live slots a step."""
    from torchmpi_tpu import obs
    per = harness.load_module(MANIFEST, "readers",
                              "serve_scopes_in_module_per")
    roof = harness.load_module(MANIFEST, "readers",
                               "serve_hybrid_decode_roofline")
    cell = harness.resolve(MANIFEST, NM3)
    scan = harness.load_json(MANIFEST, "layer_metrics",
                             "ssm_scan_ms_per_prefill.srv")["args"]
    obs.reset()
    # no prefill in the traced stretch; no device plane; no counters
    ctx = {"cell": cell, "platform": "tpu", "traced": {"steps": 5}}
    assert per.read(ctx, **scan) is None
    ctx["traced"]["prefills"] = 3
    ctx["trace"] = xplane.Trace({}, {}, (0, 1))
    assert per.read(ctx, **scan) is None
    assert roof.live_slots_per_step(22) is None
    assert roof.read(ctx, module="jit__slot_step_jit") is None
    for layer in ("a", "b"):
        obs.registry().counter_inc("tm_moe_decode_routes_total", 22 * 60,
                                   layer=layer)
    obs.registry().counter_inc("tm_moe_decode_steps_total", 2, replica="r")
    assert roof.live_slots_per_step(22) == 30.0
    # ... and still no number without the held experts' counter
    assert roof.read(ctx, module="jit__slot_step_jit") is None
    obs.reset()


def test_sparse_served_readers_give_no_number_without_the_programs_names():
    """A program without the counters (the parent) gives no number and no
    error; with them, the share of the experts a step touched."""
    from torchmpi_tpu import obs
    share = harness.load_module(MANIFEST, "readers", "moe_touched_share")
    roof = harness.load_module(MANIFEST, "readers",
                               "serve_moe_decode_roofline")
    cell = harness.resolve(MANIFEST, IMOE)
    ctx = {"cell": cell, "platform": "tpu", "traced": {"steps": 0}}
    obs.reset()
    assert share.read(ctx, experts="n_routed_experts") is None
    assert roof.touched_per_step() is None
    assert roof.read(ctx, module="jit__slot_step_jit") is None
    for layer in ("a", "b"):
        obs.registry().counter_inc("tm_moe_experts_touched_total", 96,
                                   layer=layer)
    obs.registry().counter_inc("tm_moe_decode_steps_total", 2, replica="r")
    assert share.read(ctx, experts="n_routed_experts") == 96 / (64 * 2)
    assert roof.touched_per_step() == 96.0
    obs.reset()


@pytest.mark.parametrize("metric", list(SPAN_METRICS))
def test_span_metric_resolves_to_a_file_and_a_reader(metric):
    reader, span, layer = SPAN_METRICS[metric]
    lat = metric.endswith(".lat")
    entry = harness.by_name(MANIFEST["per_layer"], metric, "metric")
    assert entry == {
        "name": metric, "unit": "ms" if "_span_ms" in metric else "%",
        "better": "lower", "source": "program_span", "layer": layer,
        "moves": "itl_ms_p99" if lat else "out_tokens_per_s_chip",
        "workloads": entry["workloads"]}
    assert entry["workloads"][:4] == ([R80] if lat else SATS)
    spec = harness.load_json(MANIFEST, "layer_metrics", metric)
    assert spec == {"reader": reader, "args": {"span": span}}
    assert callable(harness.load_module(MANIFEST, "readers", reader).read)
    for cell in entry["workloads"]:
        mine = harness.by_name(harness.resolve(MANIFEST, cell).per_layer,
                               metric, "metric")
        assert (mine["reader"], mine["args"]) == (reader, spec["args"])


def test_sample_metric_resolves_to_a_file_and_the_pooled_steps_scope():
    entry = harness.by_name(MANIFEST["per_layer"], SAMPLE_MS, "metric")
    assert entry == {
        "name": SAMPLE_MS, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": ENGINE,
        "moves": "out_tokens_per_s_chip", "workloads": entry["workloads"]}
    assert entry["workloads"][:4] == SATS
    spec = harness.load_json(MANIFEST, "layer_metrics", SAMPLE_MS)
    assert spec == {"reader": "serve_scopes_in_module", "args": {
        "module": "jit__slot_step_jit", "op_name": "/sample_rows/"}}
    for cell in SATS:
        mine = harness.by_name(harness.resolve(MANIFEST, cell).per_layer,
                               SAMPLE_MS, "metric")
        assert (mine["reader"], mine["args"]) == (spec["reader"],
                                                  spec["args"])
    # the name the reader looks for is the one the program gives: the scope
    # of generate._sample_rows, on every operation of the tail, the cond
    # and its two branches among them
    import jax
    import jax.numpy as jnp
    from torchmpi_tpu.models.generate import _sample_keys, _sample_rows

    rows = jnp.zeros((3,), jnp.int32)
    text = jax.jit(_sample_rows, static_argnums=5).lower(
        jnp.zeros((3, 16)), _sample_keys(rows.astype(jnp.uint32), rows),
        rows.astype(jnp.float32), rows, rows + 2.0,
        jnp.int32).as_text(debug_info=True)
    named = re.findall(r'loc\("(jit\(_sample_rows\)/[^"]*)"', text)
    assert named and all(re.search(spec["args"]["op_name"], n + "/")
                         for n in named), named
    assert any(n.endswith("sample_rows/cond") for n in named)


def test_sample_metric_reads_no_number_where_the_scope_is_not(
        recorded_served, monkeypatch):
    """The recorded ticks are PR 35's: they predate the scope (and their
    operations' own stats were dropped), so the reader finds the pooled
    step's executions, nothing under the name, and gives no number and no
    error: what a traced run of the parent reads."""
    path, trace, modules = recorded_served
    monkeypatch.setattr(SCOPES, "raw_trace", lambda ctx: path)
    read = harness.load_module(MANIFEST, "readers",
                               "serve_scopes_in_module").read
    args = harness.load_json(MANIFEST, "layer_metrics", SAMPLE_MS)["args"]
    ctx = {"cell": harness.resolve(MANIFEST, SAT), "trace": trace,
           "modules": modules, "traced": {"steps": 3}}
    assert read(ctx, **args) is None
    # the same executions do hold the parent's tail, by instruction name
    assert read(ctx, module=args["module"], name=r"^%sort\.") > 0.1


def test_kv_write_metric_resolves_to_a_file_and_the_pooled_writes_scope():
    entry = harness.by_name(MANIFEST["per_layer"], KV_WRITE_MS, "metric")
    assert entry == {
        "name": KV_WRITE_MS, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": ENGINE,
        "moves": "out_tokens_per_s_chip", "workloads": entry["workloads"]}
    assert entry["workloads"][:4] == SATS
    spec = harness.load_json(MANIFEST, "layer_metrics", KV_WRITE_MS)
    assert spec == {"reader": "serve_scopes_in_module", "args": {
        "module": "jit__slot_step_jit", "op_name": "/kv_write/"}}
    for cell in SATS:
        mine = harness.by_name(harness.resolve(MANIFEST, cell).per_layer,
                               KV_WRITE_MS, "metric")
        assert (mine["reader"], mine["args"]) == (spec["reader"],
                                                  spec["args"])
    # the name the reader looks for is the one the program gives: the
    # scope around every per-row write of a decode layer (PR 38)
    import jax
    import jax.numpy as jnp
    from torchmpi_tpu.models import TransformerLM

    dmodel = TransformerLM(vocab=11, embed=16, depth=1, num_heads=2,
                           head_dim=8, max_len=8,
                           pos_emb="rope").clone(decode=True)
    cache = jax.eval_shape(lambda: dmodel.init(
        jax.random.PRNGKey(0), jnp.zeros((3, 1), jnp.int32),
        pos_offset=jnp.zeros((3,), jnp.int32)))
    text = jax.jit(lambda v, t, p: dmodel.apply(
        v, t, pos_offset=p, mutable=["cache"])).lower(
        cache, jnp.zeros((3, 1), jnp.int32),
        jnp.zeros((3,), jnp.int32)).as_text(debug_info=True)
    named = re.findall(r'loc\("([^"]*/kv_write/[^"]*)"', text)
    assert any(n.endswith("/scatter") for n in named), named


def test_kv_write_metric_reads_no_number_where_the_scope_is_not(
        recorded_served, monkeypatch):
    """The recorded ticks are PR 35's: their per-row write has no scope,
    so the reader finds the pooled step's executions, nothing under the
    name, and gives no number and no error: what a traced run of the
    parent reads."""
    path, trace, modules = recorded_served
    monkeypatch.setattr(SCOPES, "raw_trace", lambda ctx: path)
    read = harness.load_module(MANIFEST, "readers",
                               "serve_scopes_in_module").read
    args = harness.load_json(MANIFEST, "layer_metrics", KV_WRITE_MS)["args"]
    ctx = {"cell": harness.resolve(MANIFEST, SAT), "trace": trace,
           "modules": modules, "traced": {"steps": 3}}
    assert read(ctx, **args) is None


def test_moe_decode_metric_resolves_to_a_file_and_the_kernels_identity():
    entry = harness.by_name(MANIFEST["per_layer"], MOE_DECODE, "metric")
    assert entry == {
        "name": MOE_DECODE, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "expert layer",
        "moves": "out_tokens_per_s_chip", "workloads": [IMOE, NM3, KEX]}
    spec = harness.load_json(MANIFEST, "layer_metrics", MOE_DECODE)
    assert spec == {"reader": "serve_moe_kernel_roofline", "args": {
        "module": "jit__slot_step_jit", "kernel": "moe\\.decode_experts"}}
    for cell in (IMOE, NM3, KEX):
        mine = harness.by_name(harness.resolve(MANIFEST, cell).per_layer,
                               MOE_DECODE, "metric")
        assert (mine["reader"], mine["args"]) == (spec["reader"],
                                                  spec["args"])
    # the identity the reader looks for is the one the program's kernel
    # carries (ops/moe.decode_experts)
    import jax
    import jax.numpy as jnp
    from torchmpi_tpu.ops import moe

    w = jnp.zeros((2, 16, 128), jnp.float32)
    jaxpr = str(jax.make_jaxpr(lambda x, e, p, i, n: moe.decode_experts(
        x, e, p, i, n, 0, w, w, w.transpose(0, 2, 1), act=jax.nn.silu))(
        jnp.zeros((3, 16)), jnp.zeros((3, 2), jnp.int32),
        jnp.zeros((3, 2)), jnp.zeros((2,), jnp.int32), jnp.int32(1)))
    (ident,) = re.findall(r"'tm_kernel': '([^']+)'", jaxpr)
    assert re.search(SCOPES.KERNEL % spec["args"]["kernel"],
                     f'"tm_kernel":"{ident}"')


@pytest.mark.parametrize("cell,expert_bytes", [
    (IMOE, 2 * 3 * 2048 * 1408), (NM3, 2 * 2 * 1024 * 2688),
    (KEX, 2 * 3 * 6144 * 2048)])
def test_moe_decode_reader_counts_the_touched_experts_bytes(
        monkeypatch, cell, expert_bytes):
    """No traced step, no counters or no chip: no number and no error;
    no kernel in the step (the parent): no number.  With them: one expert's
    matrices at the weights' type for each held expert a step touched,
    over the kernel's time a step."""
    from chipbench import flops

    roof = harness.load_module(MANIFEST, "readers",
                               "serve_moe_kernel_roofline")
    scoped = harness.load_module(MANIFEST, "readers",
                                 "serve_scopes_in_module")
    args = harness.load_json(MANIFEST, "layer_metrics", MOE_DECODE)["args"]
    ctx = {"cell": harness.resolve(MANIFEST, cell), "platform": "tpu",
           "kind": "TPU v5 lite", "traced": {"steps": 0},
           "trace": xplane.Trace({}, {}, (0, 1))}
    monkeypatch.setattr(roof, "touched_per_step", lambda: 100.0)
    assert roof.read(ctx, **args) is None                   # no step
    ctx["traced"]["steps"] = 5
    monkeypatch.setattr(roof, "touched_per_step", lambda: None)
    assert roof.read(ctx, **args) is None                   # no counters
    monkeypatch.setattr(roof, "touched_per_step", lambda: 100.0)
    assert roof.read({**ctx, "platform": "cpu"}, **args) is None
    assert roof.read(ctx, **args) is None                   # no device plane
    seen = {}

    def kernel_ms(ctx, module, op_name=None, name=None, not_name=None):
        seen.update(module=module, name=name)
        return 8.0

    monkeypatch.setattr(scoped, "read", kernel_ms)
    hbm = flops.peak_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert roof.read(ctx, **args) == pytest.approx(
        100 * 1e3 * 100.0 * expert_bytes / hbm / 8.0)
    assert seen == {"module": "jit__slot_step_jit",
                    "name": '"tm_kernel":"(?:moe\\.decode_experts)"'}


def test_moe_decode_metric_reads_no_number_where_the_kernel_is_not(
        recorded_served, monkeypatch):
    """The recorded ticks hold no decode expert kernel in the step,
    so the reader finds the pooled step's executions, nothing of the
    kernel, and gives no number and no error: what a traced run of the
    parent reads."""
    path, trace, modules = recorded_served
    monkeypatch.setattr(SCOPES, "raw_trace", lambda ctx: path)
    roof = harness.load_module(MANIFEST, "readers",
                               "serve_moe_kernel_roofline")
    monkeypatch.setattr(roof, "touched_per_step", lambda: 100.0)
    args = harness.load_json(MANIFEST, "layer_metrics", MOE_DECODE)["args"]
    ctx = {"cell": harness.resolve(MANIFEST, IMOE), "platform": "tpu",
           "kind": "TPU v5 lite", "trace": trace, "modules": modules,
           "traced": {"steps": 3}}
    assert roof.read(ctx, **args) is None


def test_the_idle_rules_name_every_program_span_once():
    """The nine ``.srv`` rules are a partition of the program's span names
    and ``outside`` (so their shares add up to the idle share); the three
    ``.lat`` ones a coarser cut that leaves the tick's self time out."""
    from torchmpi_tpu.serving.engine import SPANS

    names = list(SPANS) + [IDLE.OUTSIDE]
    assert len(IDLE_SRV) == 9
    for name in names:
        assert sum(bool(re.search(SPAN_METRICS[m][1], name))
                   for m in IDLE_SRV) == 1, name
    lat = [m for m in SPAN_METRICS if m.startswith("idle") and
           m.endswith(".lat")]
    left_out = [n for n in names
                if not any(re.search(SPAN_METRICS[m][1], n) for m in lat)]
    assert left_out == ["tm.serve.gate", "tm.serve.tick"]
    assert all(sum(bool(re.search(SPAN_METRICS[m][1], n)) for m in lat) <= 1
               for n in names)
    # the two spans the ``*_span_ms`` metrics read are the program's
    assert {v[1] for v in SPAN_METRICS.values()
            if v[0] == "program_span"} <= set(SPANS)


def test_benchmark_json_only_gained_entries_at_the_end():
    """What the benchmark had (PR 23, then PR 24) is still there, first
    and unchanged in order; PR 26's metrics follow it, then PR 27's, PR
    30's, PR 31's, PR 33's, PR 35's, PR 36's and PR 37's, each where its PR
    appended it: the next PR appends after them and adds its own slice here."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert set(names[10:22]) == set(NEW_METRICS)
    assert names[:22] == [
        "dispatch_ms_per_step.img", "dispatch_ms_per_step.tok",
        "collective_ms_per_step.img", "collective_exposed_ms_per_step.img",
        "flash_roofline_pct.tok", "xent_roofline_pct.tok", "mfu_pct.img",
        "mfu_pct.tok", "device_idle_pct.img", "device_idle_pct.tok",
        "flash_fwd_ms_per_step.tok", "flash_bwd_ms_per_step.tok",
        "xent_fwd_ms_per_step.tok", "xent_bwd_ms_per_step.tok",
        "fwd_ms_per_step.img", "fwd_ms_per_step.tok", "bwd_ms_per_step.img",
        "bwd_ms_per_step.tok", "mlp_ms_per_step.tok",
        "attn_proj_ms_per_step.tok", "step_span_ms.img", "step_span_ms.tok"]
    assert names[22:31] == list(ST_METRICS) + [LIVE_SHARE]
    assert names[31:55] == SRV_METRICS + LAT_METRICS
    assert names[55:60] == list(IMOE_METRICS)
    assert names[60:66] == list(NM3_METRICS)
    assert names[66:82] == list(SPAN_METRICS)
    assert names[82:83] == [SAMPLE_MS]
    assert names[83:89] == list(JAMBA_METRICS)
    assert names[89:90] == [KV_WRITE_MS]
    assert names[90:93] == list(KEX_METRICS)
    assert names[93:94] == [MOE_DECODE]
    layers = {m["layer"] for m in MANIFEST["per_layer"][:10]}
    assert {m["layer"] for m in MANIFEST["per_layer"][10:22]} <= layers
    assert {m["layer"] for m in MANIFEST["per_layer"][22:31]} <= layers | {
        "expert layer"}
    assert {m["layer"] for m in MANIFEST["per_layer"][31:60]} <= layers | {
        "expert layer", "serving scheduler", "serving engine",
        "serving slot pool"}
    assert {m["layer"] for m in MANIFEST["per_layer"][60:66]} == {
        "state-space layers", "expert layer", "serving engine"}
    assert {m["layer"] for m in MANIFEST["per_layer"][66:82]} == {
        ENGINE, SCHEDULER}
    assert MANIFEST["per_layer"][82]["layer"] == ENGINE
    assert {m["layer"] for m in MANIFEST["per_layer"][83:89]} == {
        "state-space layers", "model step"}
    assert MANIFEST["per_layer"][89]["layer"] == ENGINE
    assert {m["layer"] for m in MANIFEST["per_layer"][90:93]} == {
        ENGINE, "expert layer"}
    assert MANIFEST["per_layer"][93]["layer"] == "expert layer"
    assert [c["name"] for c in MANIFEST["configs"]][:7] == [
        "resnet50", "starcoder2-3b", "smallthinker-21b-a3b",
        "starcoder2-3b-serve", "instella-moe-16b-a3b-serve", NM3_CONFIG,
        JAMBA_CONFIG]
    assert [c["name"] for c in MANIFEST["configs"]][7:] == [KEX_CONFIG]
    assert [m["name"] for m in MANIFEST["end_to_end"]] == [
        "images_per_s_chip", "tokens_per_s_chip", "step_ms_p90",
        "itl_ms_p99", "out_tokens_per_s_chip", "setup_s"]


# --------------------------------------------------- file -> metadata, sums


def test_xplane_meta_reads_the_recorded_metadata(recorded):
    trace, meta = recorded
    assert sorted(meta) == WANT["planes"]
    ops = meta[WANT["device"]]
    assert len(ops) == WANT["operations"]
    assert sum("tf_op" in v for v in ops.values()) == WANT["with_tf_op"]
    assert all(set(v) <= set(xplane_meta.KEEP) for v in ops.values())
    for name_start, want in WANT["samples"].items():
        (name,) = [n for n in ops if n.startswith(name_start)]
        assert ops[name] == want
    # every event of the trace finds its operation by name
    events = trace.devices[WANT["device"]]
    assert len(events) == WANT["events"]
    assert all(e.name in ops for e in events)
    categories = {}
    for e in events:
        c = ops[e.name].get("hlo_category")
        categories[c] = categories.get(c, 0) + 1
    assert categories == WANT["events_by_hlo_category"]


def test_scopes_sum_the_recorded_step(recorded):
    trace, meta = recorded
    ctx = {"trace": trace, "traced_steps": 1}
    got = {}
    for metric, want_ms in WANT["ms_per_step"].items():
        args = harness.load_json(MANIFEST, "layer_metrics", metric)["args"]
        seconds, named, total = SCOPES.scope_s(trace.devices, meta, **args)
        got[metric] = 1e3 * seconds / ctx["traced_steps"]
        assert got[metric] == pytest.approx(want_ms, rel=1e-9), metric
    assert 100.0 * named / total == pytest.approx(WANT["tf_op_pct"])
    assert named / total > 0.9
    busy_ms = 1e3 * xplane.device_busy(trace)["busy_s"]
    g = {k.split("_ms_")[0]: v for k, v in got.items()}
    # the kernels' two halves are the time the roofline metrics divide by
    ops_r = harness.load_module(MANIFEST, "readers", "xplane_ops")
    for family, parts in (("flash", ("flash_fwd", "flash_bwd")),
                          ("xent", ("xent_fwd", "xent_bwd"))):
        pattern = harness.load_json(
            MANIFEST, "layer_metrics",
            f"{family}_roofline_pct.tok")["args"]["pattern"]
        rx = re.compile(pattern)
        by_name = 1e3 * sum(
            (e.end - e.start) / 1e9 for es in trace.devices.values()
            for e in es if ops_r.matches(e, rx))
        assert sum(g[p] for p in parts) == pytest.approx(by_name, rel=1e-9)
    # forward and backward are most of the step, and inside it
    assert 0.9 * busy_ms < g["fwd"] + g["bwd"] < busy_ms
    assert g["mlp"] + g["attn_proj"] + g["flash_fwd"] + g["flash_bwd"] \
        < g["fwd"] + g["bwd"]


def test_scopes_reader_end_to_end_on_the_recorded_step(recorded, monkeypatch):
    """``read`` as the runner calls it, the profile being the fixture."""
    trace, _ = recorded
    monkeypatch.setattr(
        SCOPES, "raw_trace", lambda ctx: os.path.join(TESTDATA, WANT["file"]))
    ctx = {"trace": trace, "traced_steps": 1}
    for metric in ("flash_fwd_ms_per_step.tok", "bwd_ms_per_step.tok"):
        args = harness.load_json(MANIFEST, "layer_metrics", metric)["args"]
        assert SCOPES.read(ctx, **args) == pytest.approx(
            WANT["ms_per_step"][metric], rel=1e-9)
    # a program without the identity (the parent commit): no number
    assert SCOPES.read(ctx, kernel=r"flash\.nothing") is None


def test_program_span_reads_the_recorded_host_plane(recorded):
    trace, _ = recorded
    spans = SPAN.host_spans(os.path.join(TESTDATA, WANT["file"]), "tm.step")
    assert [st["step_num"] for _, _, st in spans] == WANT["step_nums"]
    assert SPAN.span_ms(spans, trace.window) == pytest.approx(
        WANT["step_span_ms"], rel=1e-9)
    assert SPAN.host_spans(os.path.join(TESTDATA, WANT["file"]),
                           "tm.step.throttle") == []


def test_idle_by_span_reads_the_recorded_ticks(recorded_served, monkeypatch):
    path, trace, modules = recorded_served
    assert list(trace.window) == SERVED["window_ns"]
    assert {d: len(es) for d, es in trace.devices.items()} == {
        SERVED["device"]: SERVED["device_events"]}
    assert [e.name.split("(")[0] for e in modules[SERVED["device"]]] == \
        SERVED["modules"]
    spans = IDLE.program_spans(path, SPAN.HOST_PLANE)
    counts = {}
    for _, _, name in spans:
        counts[name] = counts.get(name, 0) + 1
    assert counts == SERVED["spans"]
    shares, rows = IDLE.split(trace, spans)
    assert shares == pytest.approx(SERVED["idle_pct_by_span"], rel=1e-9)
    assert {r["span"]: r["n"] for r in rows if r["n"]} == SERVED["spans"]
    # the reader as the runner calls it, the profile being the fixture
    monkeypatch.setattr(SCOPES, "raw_trace", lambda ctx: path)
    ctx = {"cell": harness.resolve(MANIFEST, SAT), "trace": trace}
    got = {m: IDLE.read(ctx, span=v[1]) for m, v in SPAN_METRICS.items()
           if v[0] == "serve_idle_by_span"}
    assert got == pytest.approx(SERVED["metrics"], rel=1e-9)
    # the partition: the nine shares ARE the idle share of the same trace
    busy = harness.load_module(MANIFEST, "readers", "xplane_busy")
    assert busy.read(ctx) == pytest.approx(SERVED["device_idle_pct"])
    assert sum(got[m] for m in IDLE_SRV) == pytest.approx(
        SERVED["device_idle_pct"], rel=1e-12)
    # on the chip the read owns most of a step's idle time, not the loop
    assert got["idle_step_read_pct.srv"] > got["idle_step_operands_pct.srv"] \
        > got["idle_step_dispatch_pct.srv"] > got["idle_outside_tick_pct.srv"]


def test_program_span_reads_the_recorded_ticks(recorded_served):
    path, trace, modules = recorded_served
    stats = {name: [st for _, _, st in SPAN.host_spans(
        path, f"tm.serve.{name}")] for name in (
            "tick", "step", "admit", "gate", "admit.prefill")}
    assert [st["tick"] for st in stats["tick"]] == SERVED["tick_nums"]
    assert stats["step"] == SERVED["step_stats"]
    assert stats["admit"] == SERVED["admit_stats"]
    assert stats["admit.prefill"] == SERVED["prefill_stats"]
    # a request's path: its gate, then the admission that carries its rid
    assert stats["gate"] == SERVED["gate_stats"] == [
        {"rid": stats["admit"][0]["rid"]}]
    decode, admit = (
        SPAN.span_ms(SPAN.host_spans(path, name), trace.window)
        for name in ("tm.serve.step", "tm.serve.admit"))
    assert decode == pytest.approx(SERVED["decode_span_ms"], rel=1e-9)
    assert admit == pytest.approx(SERVED["admit_span_ms"], rel=1e-9)
    # the span is the step as the caller sees it: never below its device time
    module_ms = harness.load_module(MANIFEST, "readers", "serve_module_ms")
    ctx = {"modules": modules, "traced": {"steps": 3}}
    device_ms = module_ms.read(ctx, module="jit__slot_step_jit", per="steps")
    assert 12.0 < device_ms < decode < device_ms + 6.0


# ------------------------------------------------- events -> numbers, by hand

E = xplane.Event
FWD = "jit(wrapped)/jvp(TransformerLM)/"
BWD = "jit(wrapped)/transpose(jvp(TransformerLM))/"


def kernel(name, identity):
    return (f"%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p), "
            'custom_call_target="tpu_custom_call", frontend_attributes='
            f'{{kernel_metadata={{\n"tm_kernel":"{identity}"\n}}}}')


HAND_META = {"/device:TPU:0": {
    "%fusion.1 = dot": {"tf_op": FWD + "Block_0/Dense_0/dot_general:"},
    "%fusion.2 = dot": {"tf_op": BWD + "Block_0/Dense_1/dot_general:"},
    "%fusion.3 = qkv": {"tf_op": FWD + "Block_0/SPAttention_0/q/dot_general:"},
    kernel("SPAttention_0.4", "flash.fwd"): {
        "tf_op": FWD + "Block_0/SPAttention_0/pallas_call:"},
    # ONE backward kernel since PR 29: it writes dq too, under this name
    kernel("SPAttention_0.5", "flash.dkv"): {
        "tf_op": BWD + "Block_0/SPAttention_0/pallas_call:"},
    kernel("jvp__.6", "xent.fwd"): {"tf_op": "jit(wrapped)/jvp()/pallas_call:"},
    "%fusion.7 = adamw": {"tf_op": "jit(wrapped)/add:"},
    "%copy-done.8 = copy": {},
}}


def hand_trace():
    ns = 1_000_000
    names = list(HAND_META["/device:TPU:0"])
    events = [E(n, i * ns, (i + 1) * ns, {}) for i, n in enumerate(names)]
    return xplane.Trace({"/device:TPU:0": events}, {}, (0, 8 * ns))


SCOPE_CASES = {
    "forward": ({"op_name": r"jvp\(", "not_op_name": r"transpose\("}, 4),
    "backward": ({"op_name": r"transpose\(jvp\("}, 2),
    "mlp": ({"op_name": r"/Block_\d+/Dense_\d+/"}, 2),
    "attention_outside_the_kernel": (
        {"op_name": r"/SPAttention_\d+/", "not_op_name": "pallas_call"}, 1),
    "one_kernel": ({"kernel": r"flash\.fwd"}, 1),
    # flash_bwd_ms_per_step.tok's pattern: either name, one kernel left
    "backward_kernel_by_either_name": ({"kernel": r"flash\.d(q|kv)"}, 1),
    "two_kernels": ({"kernel": r"flash\.(fwd|dkv)"}, 2),
    "kernel_and_pass": ({"kernel": r"flash\..*",
                         "op_name": r"transpose\("}, 1),
    "a_prefix_is_not_the_identity": ({"kernel": "flash"}, 0),
    "everything": ({}, 8),
    "nothing": ({"op_name": "no such scope"}, 0),
}


@pytest.mark.parametrize("case", sorted(SCOPE_CASES))
def test_scopes_on_hand_made_events(case):
    args, want_ms = SCOPE_CASES[case]
    trace = hand_trace()
    seconds, named, total = SCOPES.scope_s(trace.devices, HAND_META, **args)
    assert seconds == pytest.approx(want_ms * 1e-3)
    assert named == pytest.approx(7e-3) and total == pytest.approx(8e-3)


def test_scopes_reader_gives_no_number_without_a_device_plane():
    # a rehearsal on the CPU: no device events, so no file is looked for
    ctx = {"trace": xplane.Trace({}, {}, (0, 0)), "traced_steps": 10}
    assert SCOPES.read(ctx, op_name=r"jvp\(") is None
    assert SCOPES.read({**ctx, "trace": hand_trace(), "traced_steps": 0},
                       kernel="flash.fwd") is None


def test_program_span_on_hand_made_spans():
    ms = 1_000_000
    spans = [(0 * ms, 1 * ms, {"step_num": 3}),       # before the window
             (10 * ms, 12 * ms, {"step_num": 4}),
             (20 * ms, 21 * ms, {"step_num": 5}),
             (29 * ms, 31 * ms, {"step_num": 6})]     # runs past its end
    assert SPAN.span_ms(spans, (5 * ms, 30 * ms)) == pytest.approx(1.5)
    assert SPAN.span_ms(spans, (40 * ms, 50 * ms)) is None
    assert SPAN.span_ms([], (0, 50 * ms)) is None


# the program's spans of two ticks (ms): an admission and a step, a request
# entering between the ticks, a step alone
HAND_SPANS = [
    (0, 40, "tm.serve.tick"),
    (1, 18, "tm.serve.admit"),
    (2, 4, "tm.serve.admit.operands"), (4, 8, "tm.serve.admit.prefill"),
    (8, 9, "tm.serve.admit.slot_write"), (9, 16, "tm.serve.admit.read"),
    (16, 17.5, "tm.serve.admit.book"),
    (19, 39, "tm.serve.step"),
    (20, 22, "tm.serve.step.operands"), (22, 25, "tm.serve.step.dispatch"),
    (25, 37, "tm.serve.step.read"), (37, 38, "tm.serve.step.book"),
    (44, 45, "tm.serve.gate"),
    (50, 70, "tm.serve.tick"),
    (51, 69, "tm.serve.step"),
    (52, 54, "tm.serve.step.operands"), (54, 56, "tm.serve.step.dispatch"),
    (56, 67, "tm.serve.step.read"), (67, 68, "tm.serve.step.book")]
HAND_WINDOW_MS = 80
# chip 0 runs the prefill, then the two steps; its four gaps straddle up to
# twelve spans each.  Chip 1 idles 4 ms, three spans deep in the first read
HAND_BUSY = {"/device:TPU:0": [(6, 15), (26, 36), (57.5, 66)],
             "/device:TPU:1": [(0, 30), (34, 80)]}
HAND_IDLE_MS = {        # chip 0's 52.5 idle ms by the innermost span
    "tm.serve.tick": 5, "tm.serve.admit": 1.5,
    "tm.serve.admit.operands": 2, "tm.serve.admit.prefill": 2,
    "tm.serve.admit.read": 1, "tm.serve.admit.book": 1.5,
    "tm.serve.step": 4, "tm.serve.step.operands": 4,
    "tm.serve.step.dispatch": 5, "tm.serve.step.read": 4.5,
    "tm.serve.step.book": 2, "tm.serve.gate": 1, "outside": 19}


def hand_served(chips):
    ms = 1_000_000
    spans = [(s * ms, t * ms, name) for s, t, name in HAND_SPANS]
    devices = {d: [E("%op", s * ms, t * ms, {}) for s, t in busy]
               for d, busy in list(HAND_BUSY.items())[:chips]}
    return xplane.Trace(devices, {}, (0, HAND_WINDOW_MS * ms)), spans


@pytest.mark.parametrize("chips", [1, 2])
def test_idle_by_span_on_hand_made_events(chips, monkeypatch):
    trace, spans = hand_served(chips)
    want = dict(HAND_IDLE_MS)
    if chips == 2:
        want["tm.serve.step.read"] += 4
    idle = IDLE.idle_ns_by_span(trace.devices, trace.window, spans)
    assert {k: v / 1e6 for k, v in idle.items()} == want
    assert "tm.serve.admit.slot_write" not in idle      # never idle there
    # the innermost span: each instant once, in order, none left open
    cover = IDLE.innermost(spans)
    assert all(a[1] <= b[0] for a, b in zip(cover, cover[1:]))
    assert sum(t - s for s, t, _ in cover) == (40 + 1 + 20) * 1_000_000
    # the reader, as the runner calls it: a share of the window, a chip
    monkeypatch.setattr(SCOPES, "raw_trace", lambda ctx: "hand.xplane.pb")
    monkeypatch.setattr(IDLE, "program_spans", lambda path, plane: spans)
    ctx = {"cell": harness.resolve(MANIFEST, SAT), "trace": trace}
    got = {m: IDLE.read(ctx, span=SPAN_METRICS[m][1]) for m in IDLE_SRV}
    scale = 100.0 / (HAND_WINDOW_MS * chips)
    assert got["idle_step_read_pct.srv"] == pytest.approx(
        want["tm.serve.step.read"] * scale)
    assert got["idle_step_operands_pct.srv"] == pytest.approx(4 * scale)
    assert got["idle_admit_dispatch_pct.srv"] == pytest.approx(2 * scale)
    assert got["idle_tick_self_pct.srv"] == pytest.approx(
        (5 + 1.5 + 1.5 + 4 + 1) * scale)
    assert got["idle_outside_tick_pct.srv"] == pytest.approx(19 * scale)
    # the nine shares are the idle share, to the last digit that matters
    busy = harness.load_module(MANIFEST, "readers", "xplane_busy")
    assert sum(got.values()) == pytest.approx(busy.read(ctx), rel=1e-12)
    # ``-r80``'s coarser cut leaves the tick's and the gate's self time out
    lat = {m: IDLE.read(ctx, span=SPAN_METRICS[m][1])
           for m in ("idle_in_step_pct.lat", "idle_in_admit_pct.lat",
                     "idle_outside_tick_pct.lat")}
    assert sum(lat.values()) == pytest.approx(busy.read(ctx) - 6 * scale)


def test_idle_by_span_gives_no_number_without_a_plane_or_the_spans(
        monkeypatch):
    """A rehearsal on the CPU has no device plane, the parent's profile no
    ``tm.serve.*`` span: no number from either, and no error."""
    trace, spans = hand_served(1)
    ctx = {"cell": harness.resolve(MANIFEST, SAT),
           "trace": xplane.Trace({}, {}, trace.window)}
    monkeypatch.setattr(
        SCOPES, "raw_trace",
        lambda ctx: pytest.fail("no device plane: no file is looked for"))
    assert IDLE.read(ctx, span="^outside$") is None
    # the recorded train step: a device plane and a host plane, no tm.serve
    path = os.path.join(TESTDATA, WANT["file"])
    assert IDLE.program_spans(path, SPAN.HOST_PLANE) == []
    monkeypatch.setattr(SCOPES, "raw_trace", lambda ctx: path)
    ctx["trace"] = xplane.load(path)
    for metric in IDLE_SRV:
        assert IDLE.read(ctx, span=SPAN_METRICS[metric][1]) is None
    assert IDLE.innermost([]) == []


def test_wire_reader_on_a_hand_made_message():
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def field(number, payload):
        if isinstance(payload, int):
            return varint(number << 3) + varint(payload)
        return varint(number << 3 | 2) + varint(len(payload)) + payload

    def entry(key, message):
        return field(1, key) + field(2, message)

    stat_names = {1: b"tf_op", 2: b"flops", 4: b"other"}
    plane = field(2, b"/device:TPU:0")
    plane += field(3, b"\x08\x01" * 40)              # a line: skipped
    for key, name in stat_names.items():
        plane += field(5, entry(key, field(1, key) + field(2, name)))
    op = (field(1, 7) + field(2, b"%fusion.1 = f32[] fusion()")
          + field(5, field(1, 1) + field(5, b"jit(f)/mul:"))
          + field(5, field(1, 2) + field(3, 300))   # flops, a uint64
          + field(5, field(1, 4) + field(5, b"not kept"))
          + bytes([9 << 3 | 1]) + b"\x00" * 8)     # a fixed64: skipped
    plane += field(4, entry(7, op))
    plane += field(4, entry(8, field(1, 8) + field(2, b"%copy.2 = copy()")))
    space = field(1, plane) + field(1, field(2, b"/host:CPU"))
    got = dict(xplane_meta.plane_meta(memoryview(v))
               for n, _, v in xplane_meta.fields(memoryview(space)) if n == 1)
    assert got == {
        "/device:TPU:0": {
            "%fusion.1 = f32[] fusion()": {"tf_op": "jit(f)/mul:",
                                           "flops": 300},
            "%copy.2 = copy()": {}},
        "/host:CPU": {}}
    with pytest.raises(ValueError, match="not an XSpace"):
        list(xplane_meta.fields(memoryview(b"\x0b")))    # a group: never


# ---------------------------------------------------------------- rehearsal


def test_traced_rehearsal_leaves_the_new_device_metrics_out():
    """On the CPU there is no device plane: every ``device_trace`` metric
    this PR added is left out and nothing fails; the program's span is on
    the host plane, so ``step_span_ms`` is there."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "sc2-3b-t8k", "--seed", "4294967301", "--seconds",
         "0.3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True
    new = {m for m, cells in NEW_METRICS.items() if "sc2-3b-t8k" in cells}
    assert new & set(out["metrics"]) == {"step_span_ms.tok"}
    assert out["metrics"]["step_span_ms.tok"]["value"] > 0
    assert "dispatch_ms_per_step.tok" in out["metrics"]


def test_traced_rehearsal_of_the_expert_cell_reads_its_counters():
    """The program's counters need no device plane: the ratio of rows
    computed to routes held is there on the CPU too, and reads 1, and the
    routes every traced step held are a share of the k a token sends."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", ST, "--seed", "4294967301", "--seconds", "0.3",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True
    check = out["checks"]["reference"]
    assert check["routes_held"] == check["routes_chosen_in_range"]
    assert check["routes_held"] == check["rows_computed"]
    assert 0 < sum(check["routes_held"])
    assert set(ST_METRICS) & set(out["metrics"]) == {
        "moe_rows_computed_over_routed.tok", "moe_routes_held_per_token.tok"}
    assert out["metrics"]["moe_rows_computed_over_routed.tok"] == {
        "value": 1.0, "unit": "1"}
    # every block of the buffer the dispatch wrote holds a live route
    assert 0 < out["metrics"][LIVE_SHARE]["value"] <= 1
    # the rehearsal holds 2 of 8 experts and sends 3 routes a token
    assert 0 < out["metrics"]["moe_routes_held_per_token.tok"]["value"] < 3
    assert min(check["routing_agree"]) == 1.0
    assert max(check["router_rel_err"]) < 1e-5
    assert {"step_span_ms.tok", "dispatch_ms_per_step.tok"} <= set(
        out["metrics"])


def served_spans_are_read_and_the_idle_split_left_out(out):
    """The program's ``tm.serve.*`` spans need a host plane only: their
    means are there on the CPU, an admission longer than the prefill it
    holds would be alone; the idle split needs a device plane."""
    assert SPAN_SRV & set(out["metrics"]) == {"decode_span_ms.srv",
                                              "admit_span_ms.srv"}
    assert out["metrics"]["decode_span_ms.srv"]["value"] > 0
    assert out["metrics"]["admit_span_ms.srv"]["value"] > 0
    assert out["checks"]["compiles_in_window"] == []


def test_traced_rehearsal_of_the_sparse_served_cell_comes_out_correct():
    """``run.py --rehearse`` as the driver calls it, through
    ``serving.Server``: correct against the plain reference; the program's
    counter needs no device plane and is there on the CPU, the device's
    times and the roofline are left out."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", IMOE, "--seed", "2931000011", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True
    assert out["failed"] == 0 and out["attempted"] > 50
    assert out["compared"]["logit_gap"][0] <= 0.05
    assert 0 < out["metrics"]["moe_experts_touched_share.srv"]["value"] <= 1
    device_only = {m for m, v in IMOE_METRICS.items()
                   if v[2] == "device_trace"}
    assert not device_only & set(out["metrics"])
    assert {"batch_occupancy_pct.srv", "prefill_share_pct.srv",
            "itl_ms_p50.srv"} <= set(out["metrics"])
    served_spans_are_read_and_the_idle_split_left_out(out)


def test_traced_rehearsal_of_the_hybrid_served_cell_comes_out_correct():
    """``run.py --rehearse`` as the driver calls it: bucketed prefill with
    its true length, the state's slot write and the pooled recurrent step,
    correct against the plain reference; the program's counters need no
    device plane and are there on the CPU, the device's times and the
    roofline are left out."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", NM3, "--seed", "3300000011", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True
    assert out["failed"] == 0 and out["attempted"] > 50
    assert out["compared"]["logit_gap"][0] <= 0.05
    assert out["compared"]["off_the_top_share"][0] == 0.0
    # 16 of the router's 64 held: a quarter of the routes, more or less
    assert 0.1 < out["metrics"]["moe_routes_held_share.srv"]["value"] < 0.5
    assert 0 < out["metrics"]["moe_experts_touched_share.srv"]["value"] <= 1
    device_only = {m for m, v in NM3_METRICS.items()
                   if v[2] == "device_trace"}
    assert not device_only & set(out["metrics"])
    assert {"batch_occupancy_pct.srv", "prefill_share_pct.srv",
            "itl_ms_p50.srv"} <= set(out["metrics"])
    served_spans_are_read_and_the_idle_split_left_out(out)


def test_traced_rehearsal_of_the_state_served_cell_comes_out_correct():
    """``run.py --rehearse`` as the driver calls it, with a seed past 2**31:
    bucketed prefill with its true length, the state's slot write and the
    pooled recurrent step under a tied head, correct against the plain
    reference by the window's ONE limit and by the long answers' mean gap; the
    window's live slots a step are the harness's own (the engine's count,
    which covers the warm-up too, is among the counters); the device's times
    and the rooflines are left out."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", JAMBA, "--seed", "3700000011", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True
    assert out["failed"] == 0 and out["attempted"] > 50
    assert list(out["compared"])[0] == "logit_gap"
    assert out["compared"]["logit_gap"][0] <= 0.05
    counters = out["checks"]["counters"]
    assert counters["pool_donated"] == counters["pool_calls"] > 0
    assert out["compared"]["long_gap_when_off_the_top"][0] == 0.0
    assert (out["compared"]["long_tokens_checked"][0]
            == out["checks"]["reference"]["long_answers"]["served_tokens"]
            >= out["compared"]["long_tokens_checked"][1])
    assert 0 < out["window"]["live_slots_per_step"] <= 4
    assert counters["live_slot_steps"] >= counters["steps"] > 0
    assert not set(JAMBA_METRICS) & set(out["metrics"])
    assert {"batch_occupancy_pct.srv", "prefill_share_pct.srv",
            "itl_ms_p50.srv"} <= set(out["metrics"])
    served_spans_are_read_and_the_idle_split_left_out(out)
