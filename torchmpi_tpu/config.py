"""Runtime configuration for torchmpi_tpu.

The reference exposed three knob mechanisms (SURVEY.md §6.6, reconstructed from
facebookarchive/TorchMPI — reference mount empty, see SURVEY.md §0): arguments to
``mpi.start``, C-level setters (``torchmpi_set_{flat,hierarchical}_collectives``,
``torchmpi_set_{staged,direct}_collectives``, chunk-size setters), and the Lua
``collectiveSelector`` table.  Here all of that collapses into one dataclass plus
environment-variable overrides, while keeping the reference's key property that
implementations are *runtime-switchable* (benchmarks compare them).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v is not None else default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return float(v) if v is not None else default


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name, default)


@dataclasses.dataclass
class Config:
    """All runtime knobs.

    Attributes mirror the reference's setters:

    - ``hierarchical``  <-> torchmpi_set_{flat,hierarchical}_collectives
    - ``backend``       <-> mpi.collectiveSelector ({mpi,nccl,gloo,p2p} ->
                            {"xla","hierarchical","pallas"})
    - ``chunk_bytes``   <-> torchmpi_set_*_buffer_size / chunk setters; used by the
                            chunked Pallas ring collective and PS staging.
    """

    # --- topology -----------------------------------------------------------
    # Number of devices along the inner (ICI, intra-slice) mesh axis.  None =
    # auto: local device count for a single process; all devices for one slice.
    ici_size: Optional[int] = None
    # Number of slices / outer (DCN) axis.  None = auto (process count // hosts
    # per slice, or 1).
    dcn_size: Optional[int] = None
    # First-class N-D world mesh (VERDICT r3 #6; SURVEY.md §6.7: the mesh
    # design must not hard-code axes): ordered dict of axis-name -> size,
    # e.g. {"pp": 2, "tp": 2, "dp": 2}.  Built as ONE mesh at init with
    # those named axes — no communicator pushes needed for N-D
    # parallelism; push_communicator remains the split/subset API on
    # top.  Dict order is major -> minor: the LAST axis varies fastest
    # over the raw device order, i.e. is the most interconnect-local —
    # put tensor-parallel innermost, data/pipeline outermost.  At most
    # one size may be -1 (inferred from the device count).  Mutually
    # exclusive with ici_size/dcn_size (which build the classic 2-level
    # (dcn, ici) world).  Env: TORCHMPI_TPU_MESH_SHAPE="pp=2,tp=2,dp=-1".
    mesh_shape: Optional[dict] = None
    # Use GPU/TPU devices if available (mirrors mpi.start(withCuda)).
    use_accelerator: bool = True

    # --- collective implementation selection -------------------------------
    # Default backend for collectives: "xla" (stock, = reference's mpi/nccl
    # path), "hierarchical" (2-level ICI+DCN, = reference's custom
    # hierarchical path), "pallas" (chunked ring kernels, = reference's custom
    # chunked/pipelined path), or "auto" (measured online per (op, size
    # bucket, mesh, platform) and persisted in the tuning plan DB — see
    # torchmpi_tpu/tuning/ and docs/TUNING.md).
    backend: str = "xla"
    # Path of the persistent tuning-plan JSON consulted/extended by
    # backend="auto" (and loadable from benchmarks/autotune.py --plan-out).
    # None resolves to TORCHMPI_TPU_TUNING_PLAN, then the repo-local
    # default (tuning.DEFAULT_PLAN_PATH).  Corrupt/mismatched files
    # degrade silently to static selection; they never crash a job.
    tuning_plan_path: Optional[str] = None
    # Fenced timing rounds per candidate for the online measurement (the
    # median is scored; the noise gate needs >= 3 rounds to be
    # meaningful — same discipline as benchmarks/autotune.py).
    tuning_rounds: int = 3
    # Per-op overrides of `backend` (reference: the collectiveSelector table
    # chose an implementation per collective class).  e.g.
    # {"allreduce": "pallas", "broadcast": "xla"}.
    backend_per_op: Optional[dict] = None
    # Flat vs hierarchical collectives (reference: torchmpi_set_flat/
    # hierarchical_collectives).  When True, allreduce over a 2-level mesh is
    # staged: reduce_scatter(ici) -> allreduce(dcn) -> all_gather(ici).
    hierarchical: bool = False
    # Subchunk size in bytes for the chunked/pipelined pallas ring allreduce:
    # when a tensor's per-ring-chunk payload (size/n) exceeds this, the ring
    # streams ~chunk_bytes subchunks HBM->VMEM with the next subchunk's RDMA
    # in flight, keeping VMEM residency at ~4*chunk_bytes (2 comm + 2
    # accumulate slots) however large the tensor.  Smaller tensors use the
    # VMEM-resident kernels.  Changing it via set_config invalidates cached
    # executables, so the new schedule takes effect immediately.
    chunk_bytes: int = 4 * 1024 * 1024
    # Tensors smaller than this stay on the stock path even when a custom
    # backend is selected (the reference had size cutover constants).
    custom_min_bytes: int = 64 * 1024
    # Bidirectional pallas ring allreduce: halves rotate in opposite
    # directions concurrently (2x bandwidth bound on full-duplex ICI).
    pallas_bidirectional: bool = False
    # Staged vs direct collectives (reference: torchmpi_set_staged/
    # direct_collectives — GPU tensors staged through pinned host buffers
    # when MPI was not CUDA-aware, SURVEY.md §6.6/§3 C5).  TPU mapping:
    # when True, the EAGER tensor verbs round-trip through host memory
    # and reduce on the host CPU (devices -> host -> devices), the same
    # data path the reference's staged mode took.  In-axis collectives
    # (inside jit/shard_map) are always direct — the device fabric is
    # "CUDA-aware" by construction — so direct is the default and staged
    # exists for debugging/bring-up, exactly the reference's fallback
    # role.  Env: TORCHMPI_TPU_STAGED.
    staged: bool = False

    # --- pallas kernel tilings ---------------------------------------------
    # Default block sizes for the flash-attention and fused linear+xent
    # kernels when the call site does not pass them explicitly — the knobs
    # benchmarks/autotune.py measures per platform (the reference's tuned
    # chunk constants, kernel edition).  512x512 flash blocks measured
    # fastest on a real v5e chip (2026-07-30 sweep, scripts/flash_sweep.py:
    # 8.6 ms vs 10.6 ms at 256x256 for B=4 T=4096 H=8 D=128 causal);
    # sequences shorter than a block use one tile-aligned block covering
    # the whole sequence (ops/flash._clamp_block).  128/512 are safe v5e
    # xent defaults.
    flash_block_q: int = 512
    flash_block_k: int = 512
    # 256-token xent tiles measured above the noise gate on a v5e in
    # July 2026 (14.6 ms median vs 15.4 at 128, jitter ~0.6 ms; not
    # re-measured on today's code); the VMEM block-fit
    # clamp (ops/xent._fit_blocks) shrinks them automatically where E is
    # too large for the scoped budget.  They tile the forward kernel; the
    # backward has a tile of its own (ops/xent._BWD_BLOCK_N/V).
    xent_block_n: int = 256
    xent_block_v: int = 512
    # Fold the attention scale into q once at the kernel boundary
    # (q' = bf16(q * scale), kernels run scale=1) instead of scaling
    # every [block_q, block_k] score block on the VPU — removes one
    # full elementwise pass per block (~10% of the kernel's VPU work).
    # Numerics: q is rounded to its dtype after scaling, so scores move
    # by ~1 bf16 ulp relative; gradients stay consistent (the VJP
    # prescales fwd AND bwd recompute identically and rescales dq by
    # the chain rule).  Off by default pending a measured win on
    # silicon; the ring/residual paths ignore it (their backward
    # composes flash_attention_bwd directly at the caller's scale).
    # Env: TORCHMPI_TPU_FLASH_PRESCALE.
    flash_prescale: bool = False

    # --- fused pytree collectives ------------------------------------------
    # Upper bound (bytes) on one fused bucket when the in-axis pytree
    # collectives (allreduce/reduce/broadcast/reduce_scatter _in_axis,
    # and nn.synchronize_gradients on top of them) coalesce a tree's
    # leaves into dtype-grouped flat transfers: leaves group by dtype
    # (never promoted — mixed fp32/bf16 trees keep bf16 on the wire),
    # each group concatenates and splits into ceil(bytes/fuse_max_bytes)
    # buckets, and ONE selector-routed collective is issued per bucket.
    # O(dtypes x buckets) launches instead of O(leaves), and the
    # selector size cutover + tuning plan keys see the true fused
    # transfer size instead of per-leaf crumbs (the torchmpi coalescing
    # move; same shape as DDP's gradient buckets).  0 disables fusion
    # (per-leaf launches).  Env: TORCHMPI_TPU_FUSE_MAX_BYTES.
    fuse_max_bytes: int = 32 * 1024 * 1024

    # --- two-level (DCN) collective staging ---------------------------------
    # Chunk bound (bytes) for the pipelined hierarchical allreduce
    # (parallel/hierarchical.py): when the ICI-scattered shard exceeds
    # this, the tensor splits into chunks so the DCN transfer of chunk i
    # overlaps the ICI reduce/gather work of chunk i+1 (the reference's
    # hand-rolled chunk pipelining, two-level edition).  0 disables
    # chunking (one shard, the pre-chunking schedule — results are
    # bit-identical either way).  Env: TORCHMPI_TPU_DCN_CHUNK_BYTES.
    dcn_chunk_bytes: int = 4 * 1024 * 1024
    # Wire codec for the inter-slice (DCN) leg of two-level collectives
    # (torchmpi_tpu/compress.py — docs/HIERARCHICAL.md): "off" (default
    # — the module is never imported, dispatch is bit-identical to the
    # uncompressed path), "bf16", "int8", or "fp8".  Only the small
    # post-reduce_scatter shard crossing DCN is quantized; the ICI legs
    # always run full precision.  The gradient-sync paths additionally
    # support error-feedback residuals (the deep-gradient-compression
    # trade) via explicit residual state.  Resolved at trace/plan-build
    # time like analysis/obs/faults, so "off" costs zero runtime
    # branches.  Env: TORCHMPI_TPU_DCN_COMPRESS.
    dcn_compress: str = "off"
    # DCN legs below this stay uncompressed even when dcn_compress is
    # on — compared against the post-reduce_scatter shard (1/ici_n of
    # the tensor), the bytes that would actually be quantized (the
    # quantization + scale bookkeeping costs more than it saves on tiny
    # shards — the same latency/bandwidth cutover shape as
    # custom_min_bytes).  Env: TORCHMPI_TPU_DCN_COMPRESS_MIN_BYTES.
    dcn_compress_min_bytes: int = 64 * 1024

    # --- static collective-consistency analysis ----------------------------
    # Opt-in runtime hook for torchmpi_tpu.analysis (the SPMD
    # collective-consistency checker — docs/ANALYSIS.md): "off" (default,
    # zero added cost), "warn" (findings become Python warnings), or
    # "error" (error-severity findings raise AnalysisError before the
    # offending program compiles).  The checker runs once per jit-cache
    # entry inside the eager collectives and the step builders —
    # trace-time only, never per step.  Env: TORCHMPI_TPU_ANALYSIS.
    analysis: str = "off"

    # --- runtime observability ---------------------------------------------
    # Opt-in runtime telemetry (torchmpi_tpu.obs — docs/OBSERVABILITY.md):
    # "off" (default: one branch per collective call site, the module is
    # never even imported — same discipline as ``analysis``), "metrics"
    # (counter/histogram registry — per-collective launch+byte
    # accounting, fusion/gradsync/ZeRO/tuning/PS counters — plus the
    # deadlock flight recorder: a ring of the last obs_ring_size
    # collective events per host, dumped as JSONL/Prometheus on
    # SIGTERM/atexit for scripts/obs_tool.py blame), or "trace"
    # (metrics plus per-event user call-site attribution).
    # Env: TORCHMPI_TPU_OBS.
    obs: str = "off"
    # Directory for the per-host telemetry dumps (metrics_host*.jsonl /
    # flight_host*.jsonl).  None resolves to TORCHMPI_TPU_OBS_DIR, then
    # /tmp/torchmpi_tpu_obs.
    obs_dir: Optional[str] = None
    # Flight-recorder ring capacity (events retained per host).
    # Env: TORCHMPI_TPU_OBS_RING.
    obs_ring_size: int = 1024

    # --- elastic gang membership (torchmpi_tpu.elastic) ----------------------
    # Elastic gang resize (docs/ELASTIC.md): "off" (default — the
    # module is never imported, the dispatch path gains zero branches;
    # same discipline as ``analysis``/``obs``/``faults``) or "on"
    # (the ``elastic.run_elastic`` driver may re-form the gang at N-1
    # when a member dies — membership epochs over a two-phase
    # host-staged reconcile — and re-admit healed members at step
    # boundaries).  The knob is a consent gate for the driver layer,
    # not a dispatch-path switch: collectives never consult it.
    # Env: TORCHMPI_TPU_ELASTIC.
    elastic: str = "off"
    # Directory of the membership board (heartbeats, proposals,
    # commits, join requests — host-staged files on the shared
    # checkpoint filesystem).  None resolves to
    # ``<checkpoint directory>/membership`` inside the driver.
    # Env: TORCHMPI_TPU_ELASTIC_DIR.
    elastic_dir: Optional[str] = None
    # Poll interval for the membership board (reconcile waits, healed-
    # peer admission polls).  Env: TORCHMPI_TPU_ELASTIC_POLL.
    elastic_poll_s: float = 0.05
    # Per-round reconcile deadline: a member that posts neither its
    # proposal nor its commit within this is dropped from the proposed
    # view and the two-phase round retries one smaller (the bounded
    # part of the bounded two-phase reconcile).
    # Env: TORCHMPI_TPU_ELASTIC_DEADLINE.
    elastic_deadline_s: float = 30.0
    # Split-brain protection for the reconcile (docs/ELASTIC.md
    # "Partitions and split-brain"): "off" (default — the historical
    # drop-the-silent-and-commit behavior; a network partition can fork
    # the view.  Detection is shared by both modes: a member whose
    # board heartbeat goes stale past elastic_deadline_s relative to
    # the freshest member is death evidence either way, like the
    # watchdog lease scan — keep the deadline above the slowest
    # legitimate step/filesystem hiccup) or "majority" (a reconcile
    # may only COMMIT a view whose
    # voter set is a strict majority of the LAST COMMITTED view's
    # members; an even split breaks deterministically toward the side
    # containing the lowest-ranked member of the prior view.  A
    # minority side raises the typed ``QuorumLost`` and the driver
    # PARKS — a bounded, heartbeat-visible wait that rejoins the
    # majority's committed epoch once the partition heals, no restart
    # required).  Quorum also arms epoch FENCING: board votes,
    # heartbeats, and elastic-driven checkpoint writes from a writer
    # whose view epoch is behind the board's committed epoch raise
    # ``FencedWriterError`` and never land.  One string compare when
    # off; the fencing/partition modules are never imported.
    # Env: TORCHMPI_TPU_ELASTIC_QUORUM.
    elastic_quorum: str = "off"

    # --- payload integrity + numeric anomaly guard ---------------------------
    # torchmpi_tpu.guard (docs/GUARD.md): "off" (default — the module is
    # never imported, plan build pays one string compare, the planned
    # dispatch path gains zero branches; same discipline as
    # ``analysis``/``obs``/``faults``), "wire" (blake2b digests over
    # every host-staged payload and PS exchange, computed at the sender
    # and verified at the receiver; a mismatch raises a typed
    # ``IntegrityError`` the fault policy retries by re-staging from
    # the device buffers), "numeric" (an all-finite + norm-bound
    # tripwire fused into the synced-gradient paths — gradsync, overlap
    # buckets, ZeRO shard legs — one fused reduction per bucket), or
    # "full" (both).  Env: TORCHMPI_TPU_GUARD.
    guard: str = "off"
    # What the numeric tripwire does on a tripped bucket:
    # "skip_step" (zero the synced update and count it — training
    # continues, ``tm_guard_skipped_step_total`` records the loss) or
    # "raise" (a runtime NumericAnomalyError surfaces from the step).
    # Env: TORCHMPI_TPU_GUARD_POLICY.
    guard_numeric_policy: str = "skip_step"
    # L2-norm ceiling per checked bucket for the numeric tripwire
    # (compared against the fused sum-of-squares, so the finite check
    # and the bound are ONE reduction).  0 disables the bound — the
    # tripwire then checks finiteness only.
    # Env: TORCHMPI_TPU_GUARD_NORM_BOUND.
    guard_norm_bound: float = 0.0
    # Rolling window (steps) of the loss-spike detector used by the
    # anomaly-rewind driver (``guard.run_guarded`` /
    # ``guard.LossSpikeDetector``).  Env: TORCHMPI_TPU_GUARD_WINDOW.
    guard_spike_window: int = 16
    # Trip threshold in MADs (median absolute deviations) above the
    # rolling median.  Env: TORCHMPI_TPU_GUARD_THRESHOLD.
    guard_spike_threshold: float = 8.0

    # --- durable checkpoints (utils/checkpoint.py + utils/durable.py) --------
    # Checkpoint-resilience mode (docs/CHECKPOINT.md): "off" (default —
    # utils/durable.py is never imported, save/restore pay exactly one
    # string compare at entry; same discipline as ``analysis``/``obs``/
    # ``faults``/``guard``), "verify" (a blake2b digest over the
    # serialized checkpoint bytes is recorded in the per-file metadata
    # and re-checked on every restore — bit-rot raises a typed
    # ``CheckpointCorruptError`` the recovery walk-back treats as
    # evidence, never a silent garbage restore), or "buddy" (verify
    # PLUS each process mirrors its checkpoint pair to ``ckpt_buddies``
    # buddy locations — ranks (proc+1..K) mod world — so a restore
    # whose local file is missing or corrupt repairs from a buddy copy
    # bit-identically).  Env: TORCHMPI_TPU_CKPT_REDUNDANCY.
    ckpt_redundancy: str = "off"
    # Buddy copies per checkpoint file under ckpt_redundancy="buddy"
    # (K in the (proc+1..K) mod world placement; a single-process sim
    # mirrors to one separate on-disk location).
    # Env: TORCHMPI_TPU_CKPT_BUDDIES.
    ckpt_buddies: int = 1
    # Retention: keep only the newest K checkpoint steps per process
    # (primaries AND buddy mirrors), never pruning the step recovery
    # last settled on (the agreed/rewind step) so a chaos soak cannot
    # prune its own rewind target.  0 = keep everything (the
    # pre-retention behavior).  Only enforced when ckpt_redundancy is
    # on — off-mode saves stay untouched.  Env: TORCHMPI_TPU_CKPT_KEEP.
    ckpt_keep: int = 0

    # --- hot-state replication tier (torchmpi_tpu.hotstate) ------------------
    # In-memory (RAM-buddy) state replication above the durable disk
    # buddies (docs/HOTSTATE.md): "off" (default — the module is never
    # imported, the dispatch path gains zero branches; like
    # ``elastic``, the knob is a consent gate for a driver layer the
    # user calls explicitly) or "on" (``hotstate.enable`` may arm the
    # replicator: after each completed step a rank ships its state
    # delta — int8-quantized with an exact residual correction — to its
    # buddy's RAM, tagged (step, epoch, incarnation, blake2b digest)
    # and epoch-fenced like board writes; ``restart.recover`` and the
    # elastic shrink path then consult the RAM tier FIRST, before disk
    # buddies and primaries — the three-rung recovery ladder).
    # Env: TORCHMPI_TPU_HOTSTATE.
    hotstate: str = "off"
    # Full-snapshot cadence: every N-th stream ships the full exact
    # state instead of a delta, bounding the reconstruction chain a
    # restore must replay (and the window a single lost delta can
    # invalidate).  1 = every stream is a full snapshot.
    # Env: TORCHMPI_TPU_HOTSTATE_INTERVAL.
    hotstate_interval: int = 8
    # Per-process RAM budget (MiB) for received replicas: the inbox
    # evicts whole generations (snapshot + its delta chain), oldest
    # first — never the newest restorable generation of any peer.
    # Env: TORCHMPI_TPU_HOTSTATE_BUDGET_MB.
    hotstate_budget_mb: int = 64

    # --- collective watchdog (torchmpi_tpu.watchdog) -------------------------
    # Live hang detection over the blocking dispatch surfaces
    # (docs/WATCHDOG.md): "off" (default — the module is never
    # imported, plan build / site entry pay one string compare, the
    # planned dispatch path gains zero branches; same discipline as
    # ``analysis``/``obs``/``faults``/``guard``), "warn" (a per-process
    # monitor thread flags any in-flight collective older than
    # ``watchdog_deadline_s`` — ``tm_watchdog_*`` counters, a
    # ``watchdog`` flight event, a Python warning — and renews liveness
    # leases, but never intervenes), or "break" (warn PLUS typed
    # hang-breaking: the stalled wait is converted into a
    # ``CollectiveHangError`` the restart/elastic recovery paths heal,
    # escalating to a clean ``os._exit`` when the stall cannot be
    # unwound).  Env: TORCHMPI_TPU_WATCHDOG ("1"/"true"/"on" mean
    # "break" — the everything-armed reading a boolean opt-in wants).
    watchdog: str = "off"
    # Age at which an in-flight collective is declared stalled.  Tune
    # ABOVE the slowest legitimate collective (first-compile stalls are
    # excluded by construction — the watchdog brackets runtime waits,
    # not trace/compile time, but a genuinely slow DCN allreduce must
    # not trip it); docs/WATCHDOG.md has the tuning guidance.  The
    # break-mode ladder is staged on this value: stalled at 1x (the
    # blame --live window), typed break at 1.5x, clean-exit escalation
    # at 2.5x.  Env: TORCHMPI_TPU_WATCHDOG_DEADLINE.
    watchdog_deadline_s: float = 30.0
    # Monitor tick (scan + cooperative-break latency; lease renewal is
    # throttled separately to ~deadline/4).
    # Env: TORCHMPI_TPU_WATCHDOG_POLL.
    watchdog_poll_s: float = 0.05
    # Directory for the liveness lease files (``wd_lease_<rank>.json``
    # — read live by ``obs_tool blame --live`` and by
    # ``elastic.ElasticGang.poll`` as death evidence).  None resolves
    # to TORCHMPI_TPU_WATCHDOG_DIR, then ``elastic_dir`` (the
    # membership board — the transport still standing when the gang
    # wedged), else leases are disabled and the watchdog is
    # process-local.  Env: TORCHMPI_TPU_WATCHDOG_DIR.
    watchdog_dir: Optional[str] = None

    # --- fault injection + resilient dispatch -------------------------------
    # torchmpi_tpu.faults (docs/FAULTS.md): "off" (default — one string
    # compare per cross-host call site, the module is never imported;
    # same discipline as ``analysis``/``obs``), "policy" (resilience
    # only: bounded retries + deadline budgets + per-peer health on the
    # host-staged/PS/aio/barrier sites, nothing injected), or the path
    # of a fault-plan JSON (chaos runs: deterministic seed+site-keyed
    # injection, with the policy armed to survive it).  A corrupt or
    # version-mismatched plan raises at init.  Env: TORCHMPI_TPU_FAULTS.
    faults: str = "off"
    # Re-attempts after the first try at a faulted site (0 disables
    # retries: transient faults surface immediately, timeouts become
    # PeerTimeoutError).  Env: TORCHMPI_TPU_FAULT_RETRIES.
    fault_retries: int = 2
    # First backoff between attempts; doubles per retry, deterministic
    # jitter on top (policy.Policy).  Env: TORCHMPI_TPU_FAULT_BACKOFF.
    fault_backoff_s: float = 0.05
    # Per-site wall-clock budget: a site that makes no progress within
    # this converts the hang into a typed PeerTimeoutError carrying the
    # flight-recorder tail.  0 = unbounded (the pre-faults behavior).
    # Env: TORCHMPI_TPU_FAULT_DEADLINE.
    fault_deadline_s: float = 30.0

    # --- gradient synchronization ------------------------------------------
    # Number of buckets for bucketed/overlapped gradient allreduce.
    gradsync_buckets: int = 1
    # Chain buckets through optimization barriers so they stay distinct
    # through XLA's all-reduce combiner (measured: the combiner otherwise
    # merges sub-threshold buckets into one collective).  Off by default:
    # one fused all-reduce is usually fastest below the combine threshold.
    gradsync_barrier: bool = False
    # Backprop-overlapped gradient sync (docs/OVERLAP.md): "off"
    # (default — the step builders run the post-backward
    # synchronize_gradients path byte-for-byte as before) or "auto"
    # (recipes' step builders compute gradients through
    # gradsync.make_overlapped_grad_fn: per-bucket allreduces fire
    # INSIDE the backward pass as each bucket's cotangents materialize
    # — reverse-parameter-order buckets, optimization-barrier chained,
    # so bucket i's communication hides under bucket i+1's backward
    # compute.  Bit-identical gradients to the synchronous path).
    # Env: TORCHMPI_TPU_GRADSYNC_OVERLAP.
    gradsync_overlap: str = "off"
    # Byte bound on one overlap bucket.  0 (default) derives it from
    # the tuning-plan size buckets: the largest measured allreduce
    # bucket for this mesh when a plan is active, else fuse_max_bytes,
    # rounded down to a plan bucket edge so every fired bucket lands on
    # a (potentially measured) plan key.
    # Env: TORCHMPI_TPU_GRADSYNC_OVERLAP_BYTES.
    gradsync_overlap_bytes: int = 0
    # Average (pmean) instead of sum (psum) in synchronize_gradients.
    gradsync_average: bool = True
    # Optional on-the-wire gradient compression: None or "bf16".
    gradsync_compress: Optional[str] = None

    # --- parameter server ---------------------------------------------------
    ps_port: int = 52312
    ps_host: str = "127.0.0.1"
    ps_num_threads: int = 2
    # Socket timeout armed on every PS client connection (seconds): a
    # wedged shard server surfaces as a failed future within this bound
    # instead of hanging wait().  0 disables.  Normalized in
    # ``runtime.init`` with the obs/analysis-style any-config env
    # pickup.  Env: TORCHMPI_TPU_PS_TIMEOUT (seconds; the legacy
    # TORCHMPI_TPU_PS_TIMEOUT_MS is still honored when the new knob is
    # unset).
    ps_timeout_s: float = 30.0

    # --- continuous-batching serving (torchmpi_tpu.serving) -----------------
    # Defaults for the off-by-default serving layer (docs/SERVING.md);
    # the package is only ever imported by explicit use — these knobs
    # just size it.  KV slot blocks per replica (the admission
    # concurrency bound; cache memory = slots x serving_slot_tokens).
    # Env: TORCHMPI_TPU_SERVING_SLOTS.
    serving_slots: int = 8
    # Tokens per slot block (prompt + generated must fit one block).
    # 0 = the model's max_len.  Shrinking below max_len needs
    # pos_emb="rope" (a learned position table is sized by max_len).
    # Env: TORCHMPI_TPU_SERVING_SLOT_TOKENS.
    serving_slot_tokens: int = 0
    # Default replica count for serving.Server (data-parallel decode
    # replicas the router spreads sessions over).
    # Env: TORCHMPI_TPU_SERVING_REPLICAS.
    serving_replicas: int = 1
    # Default sampling temperature for requests that don't set their
    # own (<= 0 = greedy).  Per-request seeds make sampled streams
    # bitwise-reproducible given (seed, prompt).
    # Env: TORCHMPI_TPU_SERVING_SAMPLE.
    serving_sample: float = 0.0
    # Speculative decoding: draft K tokens per tick and verify them in
    # one [S, K+1] target forward (0 = off).  Output is bitwise the
    # non-speculative stream at the same seed; only speed changes.
    # Env: TORCHMPI_TPU_SERVING_SPEC_K.
    serving_spec_k: int = 0
    # Bucketed prefill: right-pad prompts to pow-2 length buckets of at
    # least this many tokens, so prefill compiles are O(buckets) not
    # O(distinct lengths) (0 = off; emitted tokens are bitwise
    # unchanged either way).  Env: TORCHMPI_TPU_SERVING_PREFILL_BUCKETS.
    serving_prefill_buckets: int = 0
    # Radix prefix-sharing KV cache: capacity in shared prefix BLOCKS
    # per replica (0 = off).  Shared prompt prefixes are prefilled once
    # and reused copy-on-extend; emitted tokens stay bitwise the
    # uncached stream.  Env: TORCHMPI_TPU_SERVING_PREFIX_CACHE.
    serving_prefix_cache: int = 0
    # SLO admission control: shed arrivals (typed AdmissionRejected)
    # while live p95 TTFT exceeds this target in microseconds of the
    # scheduler's active clock (0 = admit everything).
    # Env: TORCHMPI_TPU_SERVING_SLO_TTFT_US.
    serving_slo_ttft_us: float = 0.0
    # Queue-depth autoscaling: maximum replica count the
    # FleetController may scale up to (0 = fixed fleet).  Scale-downs
    # drain through the readmit machinery — reroute without the kill.
    # Env: TORCHMPI_TPU_SERVING_AUTOSCALE.
    serving_autoscale: int = 0

    # --- distributed bring-up ----------------------------------------------
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None

    @staticmethod
    def from_env(**overrides) -> "Config":
        """Build a Config from ``TORCHMPI_TPU_*`` environment variables.

        Env overrides (reference analog: FFI setters callable at any time):
          TORCHMPI_TPU_BACKEND, TORCHMPI_TPU_HIERARCHICAL,
          TORCHMPI_TPU_CHUNK_BYTES, TORCHMPI_TPU_FUSE_MAX_BYTES,
          TORCHMPI_TPU_GRADSYNC_BUCKETS,
          TORCHMPI_TPU_PS_PORT, TORCHMPI_TPU_ICI_SIZE, TORCHMPI_TPU_DCN_SIZE,
          TORCHMPI_TPU_TUNING_PLAN, TORCHMPI_TPU_TUNING_ROUNDS
        """
        cfg = Config(
            backend=_env_str("TORCHMPI_TPU_BACKEND", "xla"),
            tuning_plan_path=(
                os.environ.get("TORCHMPI_TPU_TUNING_PLAN") or None),
            tuning_rounds=_env_int("TORCHMPI_TPU_TUNING_ROUNDS", 3),
            hierarchical=_env_bool("TORCHMPI_TPU_HIERARCHICAL", False),
            chunk_bytes=_env_int("TORCHMPI_TPU_CHUNK_BYTES", 4 * 1024 * 1024),
            custom_min_bytes=_env_int("TORCHMPI_TPU_CUSTOM_MIN_BYTES", 64 * 1024),
            staged=_env_bool("TORCHMPI_TPU_STAGED", False),
            analysis=_env_str("TORCHMPI_TPU_ANALYSIS", "off"),
            obs=_env_str("TORCHMPI_TPU_OBS", "off"),
            faults=_env_str("TORCHMPI_TPU_FAULTS", "off"),
            elastic=_env_str("TORCHMPI_TPU_ELASTIC", "off"),
            elastic_dir=(os.environ.get("TORCHMPI_TPU_ELASTIC_DIR")
                         or None),
            elastic_poll_s=_env_float("TORCHMPI_TPU_ELASTIC_POLL", 0.05),
            elastic_deadline_s=_env_float("TORCHMPI_TPU_ELASTIC_DEADLINE",
                                          30.0),
            elastic_quorum=_env_str("TORCHMPI_TPU_ELASTIC_QUORUM",
                                    "off"),
            guard=_env_str("TORCHMPI_TPU_GUARD", "off"),
            guard_numeric_policy=_env_str("TORCHMPI_TPU_GUARD_POLICY",
                                          "skip_step"),
            guard_norm_bound=_env_float("TORCHMPI_TPU_GUARD_NORM_BOUND",
                                        0.0),
            guard_spike_window=_env_int("TORCHMPI_TPU_GUARD_WINDOW", 16),
            guard_spike_threshold=_env_float("TORCHMPI_TPU_GUARD_THRESHOLD",
                                             8.0),
            ckpt_redundancy=_env_str("TORCHMPI_TPU_CKPT_REDUNDANCY",
                                     "off"),
            hotstate=_env_str("TORCHMPI_TPU_HOTSTATE", "off"),
            hotstate_interval=_env_int("TORCHMPI_TPU_HOTSTATE_INTERVAL",
                                       8),
            hotstate_budget_mb=_env_int(
                "TORCHMPI_TPU_HOTSTATE_BUDGET_MB", 64),
            ckpt_buddies=_env_int("TORCHMPI_TPU_CKPT_BUDDIES", 1),
            ckpt_keep=_env_int("TORCHMPI_TPU_CKPT_KEEP", 0),
            watchdog=_env_str("TORCHMPI_TPU_WATCHDOG", "off"),
            watchdog_deadline_s=_env_float(
                "TORCHMPI_TPU_WATCHDOG_DEADLINE", 30.0),
            watchdog_poll_s=_env_float("TORCHMPI_TPU_WATCHDOG_POLL",
                                       0.05),
            watchdog_dir=(os.environ.get("TORCHMPI_TPU_WATCHDOG_DIR")
                          or None),
            fault_retries=_env_int("TORCHMPI_TPU_FAULT_RETRIES", 2),
            fault_backoff_s=_env_float("TORCHMPI_TPU_FAULT_BACKOFF", 0.05),
            fault_deadline_s=_env_float("TORCHMPI_TPU_FAULT_DEADLINE",
                                        30.0),
            obs_dir=(os.environ.get("TORCHMPI_TPU_OBS_DIR") or None),
            obs_ring_size=_env_int("TORCHMPI_TPU_OBS_RING", 1024),
            fuse_max_bytes=_env_int("TORCHMPI_TPU_FUSE_MAX_BYTES",
                                    32 * 1024 * 1024),
            dcn_chunk_bytes=_env_int("TORCHMPI_TPU_DCN_CHUNK_BYTES",
                                     4 * 1024 * 1024),
            dcn_compress=_env_str("TORCHMPI_TPU_DCN_COMPRESS", "off"),
            dcn_compress_min_bytes=_env_int(
                "TORCHMPI_TPU_DCN_COMPRESS_MIN_BYTES", 64 * 1024),
            flash_prescale=_env_bool("TORCHMPI_TPU_FLASH_PRESCALE", False),
            gradsync_buckets=_env_int("TORCHMPI_TPU_GRADSYNC_BUCKETS", 1),
            gradsync_overlap=_env_str("TORCHMPI_TPU_GRADSYNC_OVERLAP",
                                      "off"),
            gradsync_overlap_bytes=_env_int(
                "TORCHMPI_TPU_GRADSYNC_OVERLAP_BYTES", 0),
            gradsync_barrier=_env_bool("TORCHMPI_TPU_GRADSYNC_BARRIER",
                                       False),
            gradsync_average=_env_bool("TORCHMPI_TPU_GRADSYNC_AVERAGE", True),
            gradsync_compress=(
                os.environ.get("TORCHMPI_TPU_GRADSYNC_COMPRESS") or None),
            serving_slots=_env_int("TORCHMPI_TPU_SERVING_SLOTS", 8),
            serving_slot_tokens=_env_int(
                "TORCHMPI_TPU_SERVING_SLOT_TOKENS", 0),
            serving_replicas=_env_int("TORCHMPI_TPU_SERVING_REPLICAS", 1),
            serving_sample=_env_float("TORCHMPI_TPU_SERVING_SAMPLE", 0.0),
            serving_spec_k=_env_int("TORCHMPI_TPU_SERVING_SPEC_K", 0),
            serving_prefill_buckets=_env_int(
                "TORCHMPI_TPU_SERVING_PREFILL_BUCKETS", 0),
            serving_prefix_cache=_env_int(
                "TORCHMPI_TPU_SERVING_PREFIX_CACHE", 0),
            serving_slo_ttft_us=_env_float(
                "TORCHMPI_TPU_SERVING_SLO_TTFT_US", 0.0),
            serving_autoscale=_env_int(
                "TORCHMPI_TPU_SERVING_AUTOSCALE", 0),
            ps_port=_env_int("TORCHMPI_TPU_PS_PORT", 52312),
            ps_host=_env_str("TORCHMPI_TPU_PS_HOST", "127.0.0.1"),
            ps_num_threads=_env_int("TORCHMPI_TPU_PS_THREADS", 2),
            ps_timeout_s=_env_float("TORCHMPI_TPU_PS_TIMEOUT", 30.0),
        )
        ici = os.environ.get("TORCHMPI_TPU_ICI_SIZE")
        if ici is not None:
            cfg.ici_size = int(ici)
        dcn = os.environ.get("TORCHMPI_TPU_DCN_SIZE")
        if dcn is not None:
            cfg.dcn_size = int(dcn)
        mesh = os.environ.get("TORCHMPI_TPU_MESH_SHAPE")
        if mesh:
            cfg.mesh_shape = {}
            for part in mesh.split(","):
                name, _, size = part.partition("=")
                if not name.strip() or not size.strip():
                    raise ValueError(
                        f"TORCHMPI_TPU_MESH_SHAPE: malformed entry {part!r} "
                        "(want name=size,name=size,...)")
                cfg.mesh_shape[name.strip()] = int(size)
        # Set by `python -m torchmpi_tpu.launch` (the mpirun analog):
        coord = os.environ.get("TORCHMPI_TPU_COORDINATOR")
        if coord:
            cfg.coordinator_address = coord
            cfg.num_processes = _env_int("TORCHMPI_TPU_NUM_PROCESSES", 1)
            cfg.process_id = _env_int("TORCHMPI_TPU_PROCESS_ID", 0)
        for k, v in overrides.items():
            if not hasattr(cfg, k):
                raise ValueError(f"unknown config field {k!r}")
            setattr(cfg, k, v)
        return cfg

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)
