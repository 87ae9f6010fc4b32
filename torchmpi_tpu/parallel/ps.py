"""Asynchronous parameter server: Python client/orchestration over the C++
host transport (csrc/ps.cpp).

Rebuild of the reference's C8 parameter-server shards + C11 Lua client
(``lib/parameterserver.cpp``, ``torchmpi/parameterserver.lua`` [MED],
SURVEY.md §3/§4.5 — reconstructed, reference mount empty):

- a flat parameter vector is sharded across server instances (the reference
  sharded across ranks; here each host runs servers as native threads and
  clients reach them over TCP/DCN);
- clients ``send(tree, rule)`` / ``receive()`` asynchronously and wait on
  opaque handles (the prefetch pattern in §4.5);
- server-side update rules: ``copy``/``add``/``zero``/``axpy`` plus the
  EASGD ``elastic`` rule (server returns the elastic delta so client and
  center move symmetrically).

This lives deliberately outside SPMD: async PS traffic cannot ride
gang-scheduled XLA collectives (SURVEY.md §8.2.5); device arrays are staged
host-side (numpy) exactly as the reference staged GPU tensors through pinned
buffers.

Dtype contract: the wire/shard format is float32; f32/bf16/f16 leaves round
trip bit-exactly, anything lossy raises (see utils/tree.py).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from .. import runtime
from ..utils import native
from ..utils import tree as tree_util

PyTree = Any

RULES = {"copy": 0, "add": 1, "zero": 2, "axpy": 3, "elastic": 4}


def _timeout_ms() -> int:
    """Socket timeout armed on every client connection: a wedged shard
    server surfaces as a failed future within this bound instead of
    hanging wait() (ADVICE round 1).  0 disables.  Config-driven
    (``Config.ps_timeout_s`` / ``TORCHMPI_TPU_PS_TIMEOUT``, normalized
    in ``runtime.init``); standalone use (no init) falls back to the
    env, including the legacy millisecond spelling."""
    if runtime.is_initialized():
        return int(runtime.config().ps_timeout_s * 1000)
    v = os.environ.get("TORCHMPI_TPU_PS_TIMEOUT")
    if v is not None:
        return int(float(v) * 1000)
    v = os.environ.get("TORCHMPI_TPU_PS_TIMEOUT_MS")
    if v is not None:
        return int(v)
    return 30000


def _faults_armed() -> bool:
    """One string compare per call — ``torchmpi_tpu.faults`` is never
    imported unless the config armed it (docs/FAULTS.md)."""
    return runtime.effective_config().faults != "off"


def _wire_guard() -> bool:
    """One string compare per call — the wire-integrity guard
    (docs/GUARD.md); ``faults.integrity`` is never imported unless the
    config armed it."""
    return runtime.effective_config().guard in ("wire", "full")

_LIB_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None

# Last-resort keep-alive for buffers whose native op never completed within
# the destructor's bounded wait (should be unreachable with socket timeouts
# armed): leaking beats a native write into freed numpy memory.
_ORPHANED_BUFFERS: List[Any] = []


def _bind(lib: ctypes.CDLL) -> None:
    lib.tm_ps_server_create.restype = ctypes.c_int64
    lib.tm_ps_server_create.argtypes = [ctypes.c_uint64, ctypes.c_int]
    lib.tm_ps_server_port.restype = ctypes.c_int
    lib.tm_ps_server_port.argtypes = [ctypes.c_int64]
    lib.tm_ps_server_ops.restype = ctypes.c_uint64
    lib.tm_ps_server_ops.argtypes = [ctypes.c_int64]
    lib.tm_ps_server_stats.restype = ctypes.c_int
    lib.tm_ps_server_stats.argtypes = [
        ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
    lib.tm_ps_server_destroy.restype = None
    lib.tm_ps_server_destroy.argtypes = [ctypes.c_int64]
    lib.tm_ps_client_connect.restype = ctypes.c_int64
    lib.tm_ps_client_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                             ctypes.c_int]
    lib.tm_ps_client_destroy.restype = None
    lib.tm_ps_client_destroy.argtypes = [ctypes.c_int64]
    lib.tm_ps_send.restype = ctypes.c_int64
    lib.tm_ps_send.argtypes = [
            ctypes.c_int64, ctypes.c_uint32, ctypes.c_float, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_uint64]
    lib.tm_ps_receive.restype = ctypes.c_int64
    lib.tm_ps_receive.argtypes = [
            ctypes.c_int64, ctypes.c_uint64, ctypes.POINTER(ctypes.c_float),
            ctypes.c_uint64]
    lib.tm_ps_wait.restype = ctypes.c_int
    lib.tm_ps_wait.argtypes = [ctypes.c_int64]
    lib.tm_ps_wait_for.restype = ctypes.c_int
    lib.tm_ps_wait_for.argtypes = [ctypes.c_int64, ctypes.c_int]
    lib.tm_ps_test.restype = ctypes.c_int
    lib.tm_ps_test.argtypes = [ctypes.c_int64]
    lib.tm_ps_forget.restype = None
    lib.tm_ps_forget.argtypes = [ctypes.c_int64]
    lib.tm_ps_ping.restype = ctypes.c_int64
    lib.tm_ps_ping.argtypes = [ctypes.c_int64]


def _load_lib() -> ctypes.CDLL:
    """Load (building if necessary) the host-transport shared library via
    the shared native loader (hash-keyed staleness; ADVICE round 1)."""
    global _LIB
    with _LIB_LOCK:
        if _LIB is None:
            _LIB = native.load_native("libtorchmpi_ps.so", "ps.cpp", _bind)
        return _LIB


def _fptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


class PSHandle:
    """Opaque async handle (reference: parameterserver.syncHandle target).

    Holds references to the numpy buffers the native side writes into, so
    they stay alive until ``wait()``.
    """

    def __init__(self, lib, future_ids: List[int],
                 buffers: List[np.ndarray], result_fn=None):
        self._lib = lib
        self._pending = list(future_ids)  # not yet waited/freed
        self._n_futures = len(self._pending)
        self._buffers = buffers  # keep-alive
        self._result_fn = result_fn
        self._done = False
        self._failed = False
        self._result = None
        # Shard index (enqueue order) of the first failed/timed-out
        # future — how the fault layer's health ledger attributes a
        # failed exchange to the right peer.  None = no failure seen.
        self.failed_index: Optional[int] = None

    def wait(self, timeout_ms: int = 0):
        """Block until every shard future resolves.  ``timeout_ms > 0``
        bounds each PER-SHARD native wait; on expiry raises
        ``TimeoutError`` with the future left live (the handle can be
        waited again, or abandoned to the bounded destructor drain) —
        the hook the resilient-dispatch layer uses to retransmit
        instead of hanging."""
        if self._failed:
            raise RuntimeError("parameter-server op already failed")
        was_done = self._done
        wd = None
        wd_tok = -1
        if not self._done and \
                runtime.effective_config().watchdog != "off":
            # Live hang detection over the native shard waits
            # (docs/WATCHDOG.md): a wedged shard server past its socket
            # timeout still shows up as a stalled in-flight window — and
            # under an unbounded timeout_ms=0 wait, the watchdog is the
            # ONLY thing bounding it.  One string compare when off.
            from .. import watchdog

            wd = watchdog
            wd.raise_pending()
            wd_tok = wd.begin("ps.response", op="ps_wait",
                              nbytes=self._n_futures)
        try:
            self._wait_pending(timeout_ms)
        finally:
            if wd is not None:
                wd.end(wd_tok)
        if self._done and not was_done:
            from ..utils import telemetry

            # The completion edge for PS waits (flight ring via the
            # sys.modules-gated shim, ONCE per handle): lets blame see
            # "the PS exchange completed; the hang is elsewhere".
            telemetry.emit("record_ps_wait", self._n_futures)
        return self._result

    def _wait_pending(self, timeout_ms: int = 0):
        if not self._done:
            while self._pending:
                fid = self._pending[0]
                if timeout_ms and timeout_ms > 0:
                    status = self._lib.tm_ps_wait_for(fid, int(timeout_ms))
                    if status == -3:  # still in flight; future stays live
                        self.failed_index = (self._n_futures
                                             - len(self._pending))
                        raise TimeoutError(
                            f"parameter-server op still in flight after "
                            f"{timeout_ms}ms (shard {self.failed_index})")
                else:
                    status = self._lib.tm_ps_wait(fid)  # frees the future
                self._pending.pop(0)
                if status != 1:
                    self.failed_index = (self._n_futures
                                         - len(self._pending) - 1)
                    self._failed = True
                    self._drain_pending()
                    raise RuntimeError(f"parameter-server op failed "
                                       f"(status {status}, shard "
                                       f"{self.failed_index})")
            self._done = True
            self._result = (self._result_fn() if self._result_fn is not None
                            else None)
        return self._result

    def _drain_pending(self):
        """Retire remaining futures after a failure.  Futures whose native
        ops write into our numpy buffers (other shards of a receive may
        still be in flight — shard failures are per-connection) must be
        drained with a bounded wait; if one is STILL in flight after the
        budget, its buffers are parked in _ORPHANED_BUFFERS rather than
        freed under a writing native thread."""
        t_ms = _timeout_ms()
        budget_ms = 2 * t_ms if t_ms > 0 else 0
        for rest in self._pending:
            if self._result_fn is None:
                self._lib.tm_ps_forget(rest)
            elif budget_ms > 0:
                if self._lib.tm_ps_wait_for(rest, budget_ms) == -3:
                    _ORPHANED_BUFFERS.append(self._buffers)
                    self._lib.tm_ps_forget(rest)
            else:
                self._lib.tm_ps_wait(rest)
        self._pending = []

    @property
    def done(self) -> bool:
        if self._done or self._failed:
            return True
        return all(self._lib.tm_ps_test(fid) == 1 for fid in self._pending)

    def __del__(self):
        # Fire-and-forget handles (async pushes never waited on) must not
        # leak future registry entries in the native layer.  Handles whose
        # ops write back into Python-owned buffers (receive / elastic —
        # marked by result_fn) must instead be drained: forgetting them
        # would free numpy memory the native thread still writes.  The
        # drain is BOUNDED (2x the socket timeout) so GC/interpreter
        # shutdown can never hang on a wedged server; a timed-out op's
        # buffers are parked in _ORPHANED_BUFFERS instead of freed.
        try:
            if getattr(self, "_pending", None):
                self._drain_pending()
        except Exception:
            pass


class ShardedParameterServer:
    """Server-side: owns `num_shards` shard servers as native threads.

    The reference co-located one shard per rank; on TPU hosts run
    ``init_servers`` once per host (one process), and every worker connects
    with :class:`PSClient`.
    """

    def __init__(self, total_floats: int, num_shards: int = 1,
                 base_port: int = 0):
        self._lib = _load_lib()
        self.total = int(total_floats)
        self.num_shards = num_shards
        bounds = np.linspace(0, self.total, num_shards + 1).astype(np.int64)
        self.shard_bounds: List[Tuple[int, int]] = [
            (int(bounds[i]), int(bounds[i + 1])) for i in range(num_shards)]
        self.server_ids: List[int] = []
        self.ports: List[int] = []
        for i, (lo, hi) in enumerate(self.shard_bounds):
            port = 0 if base_port == 0 else base_port + i
            sid = self._lib.tm_ps_server_create(hi - lo, port)
            if sid < 0:
                raise RuntimeError("failed to start PS shard server")
            self.server_ids.append(sid)
            self.ports.append(self._lib.tm_ps_server_port(sid))
        # Previous stats() snapshot as recorded into the telemetry
        # registry (torchmpi_tpu.obs) — deltas, not cumulative re-adds.
        self._last_stats = None

    def ops_served(self) -> int:
        return sum(self._lib.tm_ps_server_ops(s) for s in self.server_ids)

    def _read_counters(self) -> np.ndarray:
        """One pass over every shard's 8 native counters (each shard's
        pass is mutex-consistent in the native layer; an older .so that
        only knows 7 leaves ``elastic_bytes_out`` at 0)."""
        tot = np.zeros(8, dtype=np.uint64)
        buf = (ctypes.c_uint64 * 8)()
        for sid in self.server_ids:
            if self._lib.tm_ps_server_stats(sid, buf, 8) >= 7:
                tot += np.ctypeslib.as_array(buf)
        return tot

    def stats(self) -> dict:
        """Cycle-cost decomposition of the server loop (VERDICT r4 #8),
        summed over shards: where a served op's time went, in seconds —
        ``recv_s`` (payload read syscalls), ``lock_wait_s`` (shard-mutex
        contention), ``apply_s`` (rule loop / memcpy under the mutex),
        ``send_s`` (response writes) — plus ``ops``, ``bytes_in``,
        ``bytes_out``, and ``elastic_bytes_out`` (the RULE_ELASTIC
        response payloads inside ``bytes_out``, tracked separately so
        throughput models don't count them as apply work —
        benchmarks/ps_bench.py).  The idle wait between requests is in
        no bucket.  Backs ps_bench's loopback breakdown and its scaling
        model.

        Consistency (ADVICE round 5): the native counters update in
        groups under the shard mutex and the snapshot reads under the
        same mutex, so a per-shard pass can no longer tear mid-op.
        Every op a completed ``wait()`` observed is fully counted in
        ``ops``/``bytes_in``/``recv_s``/``lock_wait_s``/``apply_s``
        (they land before the response unblocks the client — tests
        assert ``==`` at quiescence); ``send_s``/``bytes_out``/
        ``elastic_bytes_out`` land after the response write and may lag
        by the ops still in flight.

        With ``Config.obs`` on, each snapshot's deltas against the
        previous one are folded into the telemetry registry as
        ``tm_ps_*_total`` counters (docs/OBSERVABILITY.md)."""
        tot = self._read_counters()
        out = {
            "ops": int(tot[0]),
            "bytes_in": int(tot[1]),
            "bytes_out": int(tot[2]),
            "recv_s": float(tot[3]) / 1e9,
            "lock_wait_s": float(tot[4]) / 1e9,
            "apply_s": float(tot[5]) / 1e9,
            "send_s": float(tot[6]) / 1e9,
            "elastic_bytes_out": int(tot[7]),
        }
        if runtime.effective_config().obs != "off":
            from .. import obs

            obs.record_ps_stats(out, self._last_stats)
            self._last_stats = dict(out)
        return out

    def shutdown(self) -> None:
        for sid in self.server_ids:
            self._lib.tm_ps_server_destroy(sid)
        self.server_ids = []

    def __del__(self):  # best effort
        try:
            self.shutdown()
        except Exception:
            pass


class _ResilientPSHandle:
    """PSHandle facade returned when ``Config.faults`` is armed: the
    exchange is already enqueued (async overlap preserved); ``wait()``
    runs under the retry policy, retransmitting the WHOLE exchange on a
    transient failure and recording per-shard peer health — see
    ``faults.ps_wait``.  ``done`` reflects the currently-enqueued
    attempt."""

    def __init__(self, inner: PSHandle, make_handle, peers: List[str]):
        self._inner = inner
        self._make = make_handle
        self._peers = peers
        self._result = None
        self._waited = False

    def wait(self):
        if not self._waited:
            from .. import faults

            self._result = faults.ps_wait(self._peers, self._make,
                                          self._inner)
            self._waited = True
        return self._result

    @property
    def done(self) -> bool:
        return self._waited or self._inner.done


class PSClient:
    """Client-side: async send/receive against the shard servers.

    With ``Config.faults`` armed, ``send``/``receive`` return handles
    whose ``wait()`` retries the exchange under the fault policy (sites
    ``ps.request``/``ps.response``) and feeds the per-peer health
    ledger; with the default ``faults="off"`` nothing here changes and
    ``torchmpi_tpu.faults`` is never imported."""

    def __init__(self, template: PyTree,
                 ports: Sequence[int],
                 shard_bounds: Sequence[Tuple[int, int]],
                 host: str = "127.0.0.1"):
        self._lib = _load_lib()
        flat, self.spec = tree_util.flatten_f32(template)
        self.total = self.spec.total
        self.shard_bounds = list(shard_bounds)
        self.client_ids: List[int] = []
        self.peers: List[str] = [f"{host}:{int(p)}" for p in ports]
        for port in ports:
            cid = self._lib.tm_ps_client_connect(host.encode(), int(port),
                                                 _timeout_ms())
            if cid < 0:
                raise RuntimeError(f"failed to connect to PS at "
                                   f"{host}:{port}")
            self.client_ids.append(cid)

    def _per_shard(self, flat: np.ndarray):
        if not self.client_ids:
            raise RuntimeError("PS client is shut down")
        for cid, (lo, hi) in zip(self.client_ids, self.shard_bounds):
            yield cid, lo, hi, flat[lo:hi]

    def send(self, tree: PyTree, rule: str = "add",
             alpha: float = 1.0) -> PSHandle:
        """Async push (reference: ``ps.send(handle, grads, rule)``).

        For ``rule="elastic"`` the handle's ``wait()`` returns the elastic
        delta pytree (subtract it from the local params — EASGD).

        With the wire guard armed (``Config.guard`` in ``wire``/``full``
        — docs/GUARD.md) each attempt's staged flat payload is blake2b-
        digested at staging and verified at the native-transport
        handoff; a mismatch is a transient the fault policy retries by
        re-staging from ``tree``."""
        wire = _wire_guard()
        if _faults_armed() or wire:
            from .. import faults

            stage = lambda: self._stage(tree)  # noqa: E731
            enq = lambda flat: self._send_flat(flat, rule, alpha)  # noqa: E731
            make = lambda: faults.ps_exchange_once(  # noqa: E731
                self.peers, stage, enq, wire_guard=wire)
            return _ResilientPSHandle(
                faults.ps_enqueue(self.peers, enq, stage=stage,
                                  wire_guard=wire), make, self.peers)
        return self._send_once(tree, rule, alpha)

    def _stage(self, tree: PyTree) -> np.ndarray:
        """Stage a pytree to the flat f32 wire format (one attempt's
        host payload; retries re-stage from the tree — the buffers the
        faults/corruption cannot touch)."""
        flat, _ = tree_util.flatten_f32(tree)
        if flat.shape[0] != self.total:
            raise ValueError(f"tree has {flat.shape[0]} floats, PS holds "
                             f"{self.total}")
        return flat

    def _send_once(self, tree: PyTree, rule: str,
                   alpha: float) -> PSHandle:
        return self._send_flat(self._stage(tree), rule, alpha)

    def _send_flat(self, flat: np.ndarray, rule: str,
                   alpha: float) -> PSHandle:
        rid = RULES[rule]
        fids, bufs = [], []
        inout_full = (np.zeros_like(flat) if rule == "elastic" else None)
        for cid, lo, hi, seg in self._per_shard(flat):
            seg = np.ascontiguousarray(seg, np.float32)
            inout = (inout_full[lo:hi] if inout_full is not None
                     else np.zeros((0,), np.float32))
            if inout_full is not None and not inout.flags.c_contiguous:
                inout = np.ascontiguousarray(inout)
            fid = self._lib.tm_ps_send(cid, rid, float(alpha), 0, _fptr(seg),
                                       _fptr(inout), hi - lo)
            if fid < 0:
                raise RuntimeError("ps send failed to enqueue")
            fids.append(fid)
            bufs.extend([seg, inout])
        result_fn = None
        if rule == "elastic":
            result_fn = lambda: tree_util.unflatten_f32(self.spec, inout_full)
        return PSHandle(self._lib, fids, bufs, result_fn)

    def receive(self) -> PSHandle:
        """Async pull of the full parameter vector (prefetch pattern);
        ``wait()`` returns the pytree."""
        if _faults_armed():
            from .. import faults

            make = lambda: faults.ps_exchange_once(  # noqa: E731
                self.peers, None, self._receive_once)
            return _ResilientPSHandle(
                faults.ps_enqueue(self.peers, self._receive_once),
                make, self.peers)
        return self._receive_once()

    def _receive_once(self) -> PSHandle:
        out = np.zeros((self.total,), np.float32)
        fids, bufs = [], []
        for cid, lo, hi, _ in self._per_shard(out):
            seg = out[lo:hi]
            if not seg.flags.c_contiguous:
                seg = np.ascontiguousarray(seg)
            fid = self._lib.tm_ps_receive(cid, 0, _fptr(seg), hi - lo)
            if fid < 0:
                raise RuntimeError("ps receive failed to enqueue")
            fids.append(fid)
            bufs.append(seg)
        return PSHandle(self._lib, fids, bufs,
                        lambda: tree_util.unflatten_f32(self.spec, out))

    def ping(self) -> List[bool]:
        """Liveness of each shard server (failure detection, SURVEY §6.3):
        OP_PING round-trips on every connection; False = shard unreachable."""
        if not self.client_ids:
            raise RuntimeError("PS client is shut down")
        handles = [PSHandle(self._lib, [self._lib.tm_ps_ping(cid)], [])
                   for cid in self.client_ids]
        alive = []
        for h in handles:
            try:
                h.wait()
                alive.append(True)
            except RuntimeError:
                alive.append(False)
        if _faults_armed():
            from .. import faults

            # Liveness probes feed the same per-peer ledger the
            # resilient exchanges use (degrade-or-raise input).
            for peer, ok in zip(self.peers, alive):
                faults.ledger().record(peer, ok)
        return alive

    def shutdown(self) -> None:
        for cid in self.client_ids:
            self._lib.tm_ps_client_destroy(cid)
        self.client_ids = []

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass


class ParameterServer:
    """Single-process convenience: servers + one client, the shape the
    reference exposed via ``parameterserver.init(flatParams)``."""

    def __init__(self, template: PyTree, num_shards: int = 2,
                 host: str = "127.0.0.1", base_port: int = 0,
                 init: str = "copy"):
        flat, spec = tree_util.flatten_f32(template)
        self.servers = ShardedParameterServer(spec.total, num_shards,
                                              base_port)
        self.client = PSClient(template, self.servers.ports,
                               self.servers.shard_bounds, host)
        if init == "copy":
            self.client.send(template, rule="copy").wait()

    def send(self, tree: PyTree, rule: str = "add",
             alpha: float = 1.0) -> PSHandle:
        return self.client.send(tree, rule, alpha)

    def receive(self) -> PSHandle:
        return self.client.receive()

    def ops_served(self) -> int:
        return self.servers.ops_served()

    def stats(self) -> dict:
        """Server-loop cycle-cost decomposition — see
        :meth:`ShardedParameterServer.stats`."""
        return self.servers.stats()

    def healthy(self) -> bool:
        """All shard servers reachable (see PSClient.ping)."""
        return all(self.client.ping())

    def shutdown(self) -> None:
        self.client.shutdown()
        self.servers.shutdown()


def sync_handle(h: PSHandle):
    """Reference: ``parameterserver.syncHandle(h)``."""
    return h.wait()
