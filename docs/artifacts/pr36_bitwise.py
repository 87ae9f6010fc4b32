#!/usr/bin/env python3
"""One deterministic trace (a virtual clock: the same admissions, the same
co-batching on both sides) through the cell's own server at the cell's own
sizes and weights; writes every request's tokens and one digest.
Run from the root of a checkout: python3 <this> <cell> <seed> <out.json> [--rehearse]"""
import hashlib, json, os, sys, time
sys.path.insert(0, os.getcwd())
import numpy as np
from chipbench import harness

cell_name, seed, out = sys.argv[1], int(sys.argv[2]), sys.argv[3]
rehearse = "--rehearse" in sys.argv
if rehearse:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
manifest = harness.load_manifest()
cell = harness.resolve(manifest, cell_name, rehearse=rehearse)
runner = harness.load_module(manifest, "runners", cell.config["runner"])
base = getattr(runner, "base", runner)
srv = cell.config["serving"]
rng = np.random.default_rng(seed)
room = srv["slot_tokens"]
n = 3 * srv["slots"] if not rehearse else 8
hi = min(600, room // 2)
lens = rng.integers(min(24, hi - 1), hi, size=n)
news = rng.integers(16, min(56, room - hi), size=n)
ready = base.setup(cell, seed, [int(x) for x in lens])
import jax
reqs = [ready.serving.Request(
    rid=f"b{i}", max_new=int(news[i]), eos_id=None, arrival_s=0.002 * i,
    prompt=rng.integers(0, cell.config["vocab_size"], size=int(lens[i]), dtype=np.int32))
    for i in range(n)]
t = time.monotonic()
done = ready.server.run_trace(reqs, tick_seconds=0.001)
took = time.monotonic() - t
assert len(done) == n and not any(r.error for r in done), [r.error for r in done if r.error]
toks = {r.rid: [int(x) for x in r.tokens] for r in reqs}
digest = hashlib.sha256(json.dumps(toks, sort_keys=True).encode()).hexdigest()
stats = dict(ready.engine.stats)
row = {"cell": cell_name, "seed": seed, "requests": n, "tokens": sum(map(len, toks.values())),
       "digest": digest, "seconds": round(took, 2), "platform": jax.devices()[0].platform,
       "device_kind": jax.devices()[0].device_kind,
       "stats": {k: v for k, v in stats.items() if k.startswith("sample_") or k in ("steps", "prefills")}}
json.dump({**row, "tokens_by_request": toks}, open(out, "w"))
print(json.dumps(row))
