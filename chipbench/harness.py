"""The benchmark's harness: manifest, lookup by name, the contract line.

Everything that belongs to one configuration, one traffic mix, one kind of
cell or one per-layer metric sits in a file of its own, found here by the
name ``BENCHMARK.json`` gives it:

    configs/<config>.json         sizes, builder, optimizer, tolerance
    traffic/<traffic>.json        chips, batch per chip, sequence, ring size
    runners/<kind>.py             run(cell, args) -> result   (config "runner")
    steps/<step>.py               build(cell, mesh, key)      (config "step")
    reference/<module>.py         the plain reference         (config "reference")
    layer_metrics/<metric>.json   reader and its arguments
    readers/<reader>.py           read(ctx, **args) -> number or None

A reader's ``ctx`` holds ``cell``, ``kind`` and ``platform`` (the device's),
``items_per_s_chip`` and ``spans`` (the untraced window's throughput and
host spans), ``trace`` (an ``xplane.Trace``) and ``traced_steps``.

A later PR adds files and ``BENCHMARK.json`` entries and edits nothing
here.  Files are looked for beside the manifest first
(``<manifest dir>/chipbench/...``) and then in this directory, so a
manifest elsewhere can bring files of its own.

Nothing here imports jax: ``run.py`` decides the platform first.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
import time
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
DEFAULT_MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

T0 = time.perf_counter()    # process start, as near as this module sees it


def log(*a):
    print(time.strftime("[%H:%M:%S]"), *a, file=sys.stderr, flush=True)


def load_manifest(path=DEFAULT_MANIFEST):
    with open(path) as f:
        manifest = json.load(f)
    manifest["_base"] = os.path.dirname(os.path.abspath(path))
    manifest["_dirs"] = [d for d in dict.fromkeys((
        os.path.join(manifest["_base"], "chipbench"), BENCH))
        if os.path.isdir(d)]
    return manifest


def find(manifest, kind, filename):
    """``<kind>/<filename>`` beside the manifest, else in this directory."""
    for d in manifest["_dirs"]:
        path = os.path.join(d, kind, filename)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"no {kind}/{filename} under {manifest['_dirs']}: a name in "
        "BENCHMARK.json needs a file of that name")


def load_json(manifest, kind, name):
    with open(find(manifest, kind, name + ".json")) as f:
        return json.load(f)


def load_module(manifest, kind, name):
    """A runner, step, reader or reference, imported from its file."""
    path = find(manifest, kind, name + ".py")
    mod_name = f"chipbench_{kind}_{name}".replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(have {[e['name'] for e in entries]})")


def metrics_of(manifest, group, workload):
    """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def resolve(manifest, workload, rehearse=False):
    """One cell with every file its names point at."""
    w = by_name(manifest["workloads"], workload, "workload")
    c = by_name(manifest["configs"], w["config"], "config")
    bases = (manifest["_base"], ROOT)
    path = next((p for p in (os.path.join(b, c["file"]) for b in bases)
                 if os.path.isfile(p)), None)
    if path is None:
        raise FileNotFoundError(f"config file {c['file']} not under {bases}")
    with open(path) as f:
        config = json.load(f)
    traffic = load_json(manifest, "traffic", w["traffic"])
    if rehearse:
        # The rehearsal's tiny sizes: same files, same code, CPU devices.
        config = {**config, **config.get("rehearse", {}).get("sizes", {})}
        traffic = {**traffic, **traffic.get("rehearse", {})}
    if traffic.get("chips", w["chips"]) != w["chips"]:
        raise ValueError(f"{workload}: traffic {w['traffic']} is for "
                         f"{traffic['chips']} chip(s), the cell asks for "
                         f"{w['chips']}")
    layer = []
    for m in metrics_of(manifest, "per_layer", workload):
        spec = load_json(manifest, "layer_metrics", m["name"])
        layer.append({**m, "reader": spec["reader"],
                      "args": spec.get("args", {})})
    return types.SimpleNamespace(
        name=workload, chips=w["chips"], config=config, traffic=traffic,
        rehearse=rehearse, manifest=manifest,
        end_to_end=metrics_of(manifest, "end_to_end", workload),
        per_layer=layer)


def resolve_path(path):
    """``"package.module.attr"`` -> the attribute."""
    mod, _, attr = path.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def sized_kwargs(config, kwargs):
    """Builder arguments: a string names a key of the configuration, a
    string that starts with ``=`` is itself, anything else passes."""
    import jax.numpy as jnp

    def value(v):
        if isinstance(v, str) and v.startswith("="):
            return v[1:]
        if isinstance(v, str):
            v = config[v]
        if isinstance(v, str) and hasattr(jnp, v) and "float" in v:
            return getattr(jnp, v)          # a dtype by name
        return tuple(v) if isinstance(v, list) else v

    return {k: value(v) for k, v in kwargs.items()}


def build_model(cell):
    b = cell.config["builder"]
    model = resolve_path(b["path"])(**sized_kwargs(cell.config, b["kwargs"]))
    clone = cell.config.get("rehearse", {}).get("clone") if cell.rehearse \
        else None
    if clone:
        model = model.clone(**{k: tuple(v) if isinstance(v, list) else v
                               for k, v in clone.items()})
    return model


def build_optimizer(cell):
    o = cell.config["optimizer"]
    return resolve_path(o["path"])(**o["kwargs"])


def flops_per_item(cell):
    """Required forward + backward operations per image or token."""
    from chipbench import flops

    f = cell.config["flops"]
    sizes = {k: cell.config[k] for k in f["sizes"]}
    if "seq" in cell.traffic:
        sizes["seq"] = cell.traffic["seq"]
    return getattr(flops, f["function"])(**sizes)


def loss_check(what, library, reference, rtol):
    """The record of one comparison against the plain reference."""
    return {"what": what, "library": library, "reference": reference,
            "rtol": rtol,
            "ok": abs(library - reference) <= rtol * abs(reference)}


def seed_key(seed):
    """A PRNG key from any whole number: the driver's seeds pass 2**31,
    and ``PRNGKey`` takes 32 signed bits when x64 is off."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def percentile(values, q):
    """Linear-interpolated percentile (numpy's default), q in [0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def device_record(devices):
    """The ``device`` object of the contract line, as jax reports it.

    ``memory_peak_bytes`` is, on the fullest chip, ``peak_bytes_in_use``
    plus ``peak_bytes_reserved``: the TPU client counts live buffers under
    the first and a running program's temporaries under the second (seen
    on a v5e, PR 23: 1.51 GB in use beside 9.12 GB reserved for a step
    whose temporaries the compiler gives as 9.15 GB; the two and the free
    block add up to ``bytes_limit``).  The whole counter set of device 0
    rides along as ``memory_stats``."""
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
             for s in stats if "peak_bytes_in_use" in s]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None,
            "memory_stats": stats[0] or None}


def contract_line(result):
    """The last line of standard output: one JSON object."""
    keys = ("correct", "attempted", "failed", "metrics", "device")
    missing = [k for k in keys if k not in result]
    if missing:
        raise KeyError(f"result lacks {missing}")
    return json.dumps(result)
