"""A Router replica that is a tensor-parallel MESH SLICE.

:class:`TPReplicaEngine` runs the same continuous-batching slot-pool
protocol as the dense :class:`~.engine.ReplicaEngine` — same
:class:`~.slots.SlotPool`, same :class:`~.engine.Session` lifecycle,
same sampling/bucketing/speculative machinery, driven by the same
scheduler — but its backend forwards are the shard_map primitives of
:mod:`~torchmpi_tpu.models.tp_generate` (``tp_slot_prefill`` /
``tp_slot_decode``): weights column/row-sharded 1/n over the model
axis, the pool KV cache head-sharded the same way, one psum per
sublayer per token plus the tiled LM-head all_gather.  A replica stops
being one device and becomes a mesh: the host spreads its chips over
``Server.sharded(...)`` replicas of ``tp`` devices each, continuous
batching included — the PR 9 dense-only limit, lifted.

The planner records one decision-only ``serving`` plan per replica at
construction, keyed by the replica's mesh via the topology fingerprint
(:func:`~torchmpi_tpu.planner.plan_serving_replica`), so a multi-mesh
serving fleet shows up in ``plan_tool.py dump-live`` as per-topology
rows.

Sampling keys, bucket padding, the accept loop, drain/re-route — all
inherited unchanged, and all bitwise-compatible: a session served by a
dense replica and one served by a TP replica emit identical streams
for the same checkpoint math, and a drained TP session re-prefills
token-exactly on ANY healthy replica.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import runtime
from ..models.tp_generate import tp_slot_decode, tp_slot_prefill
from .engine import ReplicaEngine


class TPReplicaEngine(ReplicaEngine):
    """Slot-pooled decode engine whose replica is a TP mesh slice.

    ``params`` is a full tree from
    :func:`~torchmpi_tpu.models.tp_generate.init_tp_lm` (placed on
    ``mesh`` here via ``shard_tp_lm``).  ``slot_tokens`` must resolve
    to a positive block size (argument or ``serving_slot_tokens`` —
    the TP stack is rope-only, there is no ``max_len`` to default to).
    """

    def __init__(self, params, *, mesh, axis: str = "model",
                 num_heads: int, name: str = "tp0",
                 slots: Optional[int] = None,
                 slot_tokens: Optional[int] = None,
                 sample: Optional[float] = None,
                 prefill_bucket: Optional[int] = None,
                 spec_k: Optional[int] = None, draft=None,
                 prefix_cache: Optional[int] = None,
                 prefix_block: int = 8):
        from ..models.tp_generate import shard_tp_lm

        cfg = runtime.effective_config()
        slots = int(slots if slots is not None else cfg.serving_slots)
        st = int(slot_tokens if slot_tokens is not None
                 else (cfg.serving_slot_tokens or 0))
        if st <= 0:
            raise ValueError(
                "TPReplicaEngine needs an explicit slot block size "
                "(slot_tokens= or serving_slot_tokens > 0): the TP "
                "stack has no max_len to default to")
        self.mesh = mesh
        self.axis = axis
        self.num_heads = int(num_heads)
        self.depth = len(params["blocks"])
        self.vocab = int(params["embed"].shape[0])
        self.param_count = sum(int(np.prod(p.shape))
                               for p in jax.tree.leaves(params))
        self.params, self._specs = shard_tp_lm(params, mesh, axis)
        self.dmodel = None  # shard_map path — no flax decode clone
        self._device = None
        self._init_serving(cfg, name, slots, st, sample=sample,
                           prefill_bucket=prefill_bucket, spec_k=spec_k,
                           draft=draft, prefix_cache=prefix_cache,
                           prefix_block=prefix_block)
        # Zero pool cache: per block a head-sharded (k, v) pair
        # [S, slot_tokens, H, dh] — slots replicated, heads 1/n.
        from jax.sharding import NamedSharding, PartitionSpec as P

        hd = params["blocks"][0]["wq"].shape[-1] // self.num_heads
        self._head_dim = int(hd)
        self._cache_dtype = params["embed"].dtype
        sh = NamedSharding(mesh, P(None, None, axis, None))
        zero = jnp.zeros((slots, st, self.num_heads, hd),
                         params["embed"].dtype)
        self._cache = [(jax.device_put(zero, sh),
                        jax.device_put(zero, sh))
                       for _ in range(self.depth)]
        # One per-topology plan row per replica (dump-live evidence).
        from .. import planner

        planner.plan_serving_replica(name, mesh, (axis,))

    # -- backend hooks ------------------------------------------------------

    def _backend_prefill(self, prompt, true_len, sampling):
        return tp_slot_prefill(self.params, jnp.asarray(prompt),
                               mesh=self.mesh, axis=self.axis,
                               num_heads=self.num_heads,
                               t_max=self.pool.slot_tokens,
                               true_len=true_len, sampling=sampling)

    def _prefill_runs_flash(self, padded_len):
        return False  # tp_generate._block_prefill: dense scores

    def _backend_step(self, toks, pos, sampling):
        return self._backend_verify(
            np.asarray(toks, np.int32)[:, None], pos, sampling)[:, 0]

    def _backend_verify(self, toks, pos, sampling):
        return self._pooled(
            lambda pool: tp_slot_decode(
                self.params, pool, toks, pos, mesh=self.mesh,
                axis=self.axis, num_heads=self.num_heads,
                sampling=sampling), read=True)[0]

    def _row_template(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self.mesh, P(None, None, self.axis, None))
        zero = jnp.zeros((1, self.pool.slot_tokens, self.num_heads,
                          self._head_dim), self._cache_dtype)
        return [(jax.device_put(zero, sh), jax.device_put(zero, sh))
                for _ in range(self.depth)]

    def _backend_extend(self, row_cache, suffix, depth, true_len,
                        sampling):
        # The extend forward IS tp_slot_decode on a 1-row cache:
        # [1, Ts] suffix tokens at per-row depth take the cache-masked
        # branch the speculative verify already uses, which is
        # shape-generic in both the row and token dims.  tp_slot_decode
        # keys position j on (seed, idx + j), so shift the idx operand
        # by -(true_len - 1): the TRUE last suffix position then
        # samples with exactly the request's global token index, and
        # the (discarded) earlier positions' keys don't matter.
        # tp_slot_decode consumes the row it is given: that is the row
        # assembled for this hit (at least one fragment was written onto
        # it), never the shared zero template.
        seeds, idxs, temps, tks, tps = sampling
        shifted = (seeds, idxs - jnp.int32(true_len - 1), temps, tks,
                   tps)
        row_cache, out = tp_slot_decode(
            self.params, row_cache,
            np.asarray(suffix, np.int32),
            np.asarray([depth], np.int32), mesh=self.mesh,
            axis=self.axis, num_heads=self.num_heads, sampling=shifted)
        return row_cache, np.asarray(out)[:, true_len - 1]
