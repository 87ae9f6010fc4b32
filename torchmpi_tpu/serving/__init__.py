"""Continuous-batching serving layer (docs/SERVING.md).

The request-level server over the decode stack: an admission queue +
iteration-level scheduler (:mod:`.scheduler`) injects newly-arrived
requests into the running decode batch at token boundaries and retires
finished sequences immediately; a paged KV slot pool (:mod:`.slots`)
bounds cache memory at ``slots x block`` instead of ``batch x
max_len``; a health-routed multi-replica router (:mod:`.router`)
spreads sessions over replicas — single-device dense engines
(:mod:`.engine`) or whole TP mesh slices (:mod:`.tp_engine` /
``Server.sharded``) — and drains + re-routes a dead replica's
in-flight sessions instead of crashing the server; and per-request SLO
telemetry (TTFT / inter-token latency histograms, queue-depth and
slot-occupancy gauges) rides the obs registry as ``tm_serving_*`` when
telemetry is on.

The pool carries whatever the decode model declares as its ``cache``
collection, a row a slot: per-head keys and values (``SPAttention``;
61,440 B a token for a 30-layer model of 2 kv heads of 128 in float32)
or a latent and one rotary key (``LatentAttention``; 19,584 B a token for
nine layers of rank 512 + 32), read as ``ReplicaEngine.
cache_bytes_per_token``.  It reserves ``slot_tokens`` positions for every
layer alike, whatever its kind.  A state-space layer's leaves have NO token
axis (``Mamba2Mixer``: a recurrent state and the last inputs of its
convolution, one value a slot; 21,585,920 B a slot for five mixers of 128
heads x 64 x a state of 128), booked apart as ``ReplicaEngine.
state_bytes_per_slot``; a model that has them is refused the prefix cache
and speculation (``docs/SERVING.md``).

Decode is per-request greedy OR sampled (temperature / top-k / top-p /
seed on each :class:`Request`), bitwise-reproducible given (seed,
prompt) — which is also what keeps re-routing token-exact.  Prefill
optionally pads to pow-2 length buckets (compiles O(buckets), streams
unchanged), and speculative decoding (:mod:`.spec`: ngram prompt-lookup
or a small draft LM) lands up to K+1 tokens per target forward while
staying bitwise-identical to the non-speculative stream.

Off by default and **never imported unless used** — the analysis/obs/
faults discipline: nothing in the library imports this package; a
session that never serves pays zero import cost
(``tests/test_serving.py`` subprocess-asserts it).  Import explicitly:

    from torchmpi_tpu import serving

    server = serving.Server(model, params, replicas=2, slots=8)
    results = server.run_trace([
        serving.Request("r0", prompt, max_new=32, arrival_s=0.0,
                        temperature=0.8, top_k=40, seed=7),
        ...
    ])

``benchmarks/serving_bench.py`` measures the continuous-vs-static,
TP-sharded, sampled, bucketed-prefill and speculative wins on a
synthetic Poisson trace; greedy tokens stay bit-identical per request
to the offline ``models.generate.generate`` path.
"""

from __future__ import annotations

from .engine import ReplicaEngine, RequestRejected, Session  # noqa: F401
from .fleet import AdmissionController, AdmissionRejected, \
    FleetController  # noqa: F401
from .prefix_cache import PrefixCache  # noqa: F401
from .router import Router  # noqa: F401
from .scheduler import Request, Server  # noqa: F401
from .slots import SlotPool  # noqa: F401
from .spec import ModelDraft, NgramDraft  # noqa: F401
from .tp_engine import TPReplicaEngine  # noqa: F401

__all__ = ["AdmissionController", "AdmissionRejected", "FleetController",
           "ModelDraft", "NgramDraft", "PrefixCache", "ReplicaEngine",
           "Request", "RequestRejected", "Router", "Server", "Session",
           "SlotPool", "TPReplicaEngine"]
