"""Process/runtime management and the communicator (mesh) stack.

TPU-native rebuild of the reference's C1 runtime (``lib/torch_mpi.cpp``,
reconstructed — reference mount empty, SURVEY.md §0/§3) and C2 resource manager
(``lib/resources.cpp``): ``mpi.start/stop/rank/size/barrier`` plus the
communicator tree (world / intra-node / inter-node / user splits).

Mapping to TPU (SURVEY.md §6.8):

- ``MPI_Init`` under mpirun        -> ``jax.distributed.initialize`` from slice
                                      metadata (or single-process).
- intra-node communicator (shm/IPC/NCCL) -> the ``ici`` mesh axis (intra-slice
                                      interconnect; XLA collectives ride it).
- inter-node communicator (MPI)    -> the ``dcn`` mesh axis (inter-slice).
- ``push_communicator(key)`` splits -> named sub-``Mesh`` stack, cached by key.

Nothing above this module touches raw device lists — the same invariant the
reference kept for raw ``MPI_Comm`` (SURVEY.md §2 L1).
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from .config import Config

# Canonical axis names for the two-level communicator tree.
DCN_AXIS = "dcn"  # outer: inter-slice / inter-node (reference: interComm)
ICI_AXIS = "ici"  # inner: intra-slice interconnect (reference: intraComm)
WORLD_AXES = (DCN_AXIS, ICI_AXIS)


class _State:
    """Module-level singleton, the analog of the reference's global C state."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.initialized = False
        self.config: Config = Config()
        # Monotonic configuration-change counter: bumped by init(),
        # set_config(), and stop().  Every CollectivePlan key embeds it
        # (torchmpi_tpu/planner.py), so a live config switch makes every
        # previously-built plan unreachable without any cache walking —
        # the single staleness mechanism for all planner-backed caches.
        self.config_epoch = 0
        self.devices: List[jax.Device] = []
        # Stack of (name, Mesh); bottom is always ("world", world_mesh).
        self.mesh_stack: List[Tuple[str, Mesh]] = []
        # Cache of user split meshes keyed by name (reference: communicator
        # cache keyed by the push string).
        self.mesh_cache: Dict[str, Mesh] = {}
        self.distributed_initialized = False


_state = _State()


def _build_world_mesh(cfg: Config, devices: Sequence[jax.Device]) -> Mesh:
    """Build the world mesh.

    Two modes:

    - ``cfg.mesh_shape`` (first-class N-D, VERDICT r3 #6): ONE mesh whose
      named axes are exactly the dict's keys, major -> minor in dict
      order (the last axis is the most interconnect-local).  One size may
      be -1 (inferred).  No communicator pushes needed for N-D
      parallelism.
    - classic 2-level ``(dcn, ici)``: auto shape puts ``dcn`` = number of
      processes when it divides the device count (each process' local
      devices share fast interconnect — the analog of the reference
      splitting MPI_COMM_WORLD by hostname), else 1; ``ici`` = rest.
      ``cfg.ici_size``/``cfg.dcn_size`` override (used by tests to
      emulate a multi-slice topology on a flat 8-device CPU mesh).
    """
    n = len(devices)
    if cfg.mesh_shape is not None:
        if cfg.ici_size is not None or cfg.dcn_size is not None:
            raise ValueError(
                "mesh_shape is mutually exclusive with ici_size/dcn_size "
                "(mesh_shape names its own axes)")
        if not cfg.mesh_shape:
            raise ValueError("mesh_shape must name at least one axis")
        axes = tuple(cfg.mesh_shape.keys())
        sizes = list(cfg.mesh_shape.values())
        wild = [i for i, s in enumerate(sizes) if s == -1]
        if len(wild) > 1:
            raise ValueError(f"mesh_shape {cfg.mesh_shape}: at most one "
                             "axis size may be -1")
        if wild:
            rest = int(np.prod([s for s in sizes if s != -1]))
            if rest == 0 or n % rest != 0:
                raise ValueError(
                    f"mesh_shape {cfg.mesh_shape} cannot be inferred over "
                    f"{n} devices")
            sizes[wild[0]] = n // rest
        if int(np.prod(sizes)) != n:
            raise ValueError(
                f"mesh_shape {dict(zip(axes, sizes))} does not cover "
                f"{n} devices")
        return Mesh(np.asarray(devices).reshape(sizes), axes)
    dcn = cfg.dcn_size
    ici = cfg.ici_size
    if dcn is None and ici is None:
        nproc = jax.process_count()
        dcn = nproc if nproc > 1 and n % nproc == 0 else 1
        ici = n // dcn
    elif dcn is None:
        assert ici is not None
        if n % ici != 0:
            raise ValueError(f"ici_size={ici} does not divide device count {n}")
        dcn = n // ici
    elif ici is None:
        if n % dcn != 0:
            raise ValueError(f"dcn_size={dcn} does not divide device count {n}")
        ici = n // dcn
    if dcn * ici != n:
        raise ValueError(
            f"mesh shape dcn={dcn} x ici={ici} != device count {n}"
        )
    dev_array = np.asarray(devices).reshape(dcn, ici)
    return Mesh(dev_array, WORLD_AXES)


def _normalize_analysis(value) -> Optional[str]:
    """Canonical analysis mode for a config/env value: "off"|"warn"|
    "error", with boolean-ish spellings accepted ("1"/"true"/"yes"/"on"
    mean "warn", "0"/"false"/"no"/"" mean "off").  None = unrecognized
    (the caller raises)."""
    v = str(value).strip().lower()
    if v in ("off", "0", "false", "no", "none", ""):
        return "off"
    if v in ("warn", "1", "true", "yes", "on"):
        return "warn"
    if v == "error":
        return "error"
    return None


def _normalize_obs(value) -> Optional[str]:
    """Canonical obs mode for a config/env value: "off"|"metrics"|
    "trace", with boolean-ish spellings accepted ("1"/"true"/"yes"/"on"
    mean "metrics", "0"/"false"/"no"/"" mean "off").  None =
    unrecognized (the caller raises)."""
    v = str(value).strip().lower()
    if v in ("off", "0", "false", "no", "none", ""):
        return "off"
    if v in ("metrics", "1", "true", "yes", "on"):
        return "metrics"
    if v == "trace":
        return "trace"
    return None


def _normalize_overlap(value) -> Optional[str]:
    """Canonical gradsync_overlap mode for a config/env value:
    "off"|"auto", with boolean-ish spellings accepted ("1"/"true"/
    "yes"/"on" mean "auto", "0"/"false"/"no"/"" mean "off").  None =
    unrecognized (the caller raises)."""
    v = str(value).strip().lower()
    if v in ("off", "0", "false", "no", "none", ""):
        return "off"
    if v in ("auto", "on", "1", "true", "yes"):
        return "auto"
    return None


def _normalize_dcn_compress(value) -> Optional[str]:
    """Canonical dcn_compress codec for a config/env value:
    "off"|"bf16"|"int8"|"fp8" (case-insensitive; boolean-ish off
    spellings accepted).  None = unrecognized (the caller raises)."""
    v = str(value).strip().lower()
    if v in ("off", "0", "false", "no", "none", ""):
        return "off"
    if v in ("bf16", "int8", "fp8"):
        return v
    return None


def _normalize_elastic(value) -> Optional[str]:
    """Canonical elastic mode for a config/env value: "off"|"on", with
    boolean-ish spellings accepted.  None = unrecognized (the caller
    raises)."""
    v = str(value).strip().lower()
    if v in ("off", "0", "false", "no", "none", ""):
        return "off"
    if v in ("on", "1", "true", "yes"):
        return "on"
    return None


def _normalize_hotstate(value) -> Optional[str]:
    """Canonical hotstate mode for a config/env value: "off"|"on", with
    boolean-ish spellings accepted.  None = unrecognized (the caller
    raises)."""
    v = str(value).strip().lower()
    if v in ("off", "0", "false", "no", "none", ""):
        return "off"
    if v in ("on", "1", "true", "yes"):
        return "on"
    return None


def _normalize_elastic_quorum(value) -> Optional[str]:
    """Canonical elastic_quorum mode: "off"|"majority", boolean-ish
    spellings accepted ("1"/"true"/"yes"/"on" mean "majority" — the
    protect-me reading a boolean opt-in wants).  None = unrecognized
    (the caller raises)."""
    v = str(value).strip().lower()
    if v in ("off", "0", "false", "no", "none", ""):
        return "off"
    if v in ("majority", "on", "1", "true", "yes"):
        return "majority"
    return None


def _normalize_guard(value) -> Optional[str]:
    """Canonical guard mode for a config/env value:
    "off"|"wire"|"numeric"|"full", with boolean-ish spellings accepted
    ("1"/"true"/"yes"/"on" mean "full" — the everything-armed reading a
    boolean opt-in wants, "0"/"false"/"no"/"" mean "off").  None =
    unrecognized (the caller raises)."""
    v = str(value).strip().lower()
    if v in ("off", "0", "false", "no", "none", ""):
        return "off"
    if v in ("full", "on", "1", "true", "yes"):
        return "full"
    if v in ("wire", "numeric"):
        return v
    return None


def _normalize_watchdog(value) -> Optional[str]:
    """Canonical watchdog mode for a config/env value:
    "off"|"warn"|"break", with boolean-ish spellings accepted
    ("1"/"true"/"yes"/"on" mean "break" — the everything-armed reading
    a boolean opt-in wants, "0"/"false"/"no"/"" mean "off").  None =
    unrecognized (the caller raises)."""
    v = str(value).strip().lower()
    if v in ("off", "0", "false", "no", "none", ""):
        return "off"
    if v in ("break", "on", "1", "true", "yes"):
        return "break"
    if v == "warn":
        return v
    return None


def _normalize_ckpt_redundancy(value) -> Optional[str]:
    """Canonical ckpt_redundancy mode for a config/env value:
    "off"|"verify"|"buddy", with boolean-ish spellings accepted
    ("1"/"true"/"yes"/"on" mean "buddy" — the everything-armed reading
    a boolean opt-in wants, "0"/"false"/"no"/"" mean "off").  None =
    unrecognized (the caller raises)."""
    v = str(value).strip().lower()
    if v in ("off", "0", "false", "no", "none", ""):
        return "off"
    if v in ("buddy", "on", "1", "true", "yes"):
        return "buddy"
    if v == "verify":
        return v
    return None


def _normalize_guard_policy(value) -> Optional[str]:
    """Canonical guard_numeric_policy: "skip_step"|"raise".  None =
    unrecognized (the caller raises)."""
    v = str(value).strip().lower()
    if v in ("skip_step", "skip"):
        return "skip_step"
    if v == "raise":
        return "raise"
    return None


def _normalize_faults(value) -> str:
    """Canonical faults mode for a config/env value: "off", "policy",
    or a fault-plan path (kept verbatim).  Boolean-ish spellings map to
    the two modes ("0"/"false"/"no" -> off, "1"/"true"/"yes"/"on" ->
    policy); anything else is treated as a path — a typo'd path fails
    loudly when the plan loads, which is the posture a chaos knob
    wants."""
    v = str(value).strip()
    low = v.lower()
    if low in ("off", "0", "false", "no", "none", ""):
        return "off"
    if low in ("policy", "on", "1", "true", "yes"):
        return "policy"
    return v


def _env_default_pickup(cfg: Config, field: str, env: str, cast) -> None:
    """Obs-ring-style any-config env pickup for a numeric knob: a field
    left at its dataclass default defers to the environment, an explicit
    non-default value wins."""
    import dataclasses as _dc

    raw = os.environ.get(env)
    if not raw:
        return
    default = next(f.default for f in _dc.fields(Config)
                   if f.name == field)
    if getattr(cfg, field) == default:
        setattr(cfg, field, cast(raw))


def _faults_activate(cfg: Config) -> None:
    """Import and arm the fault layer (only ever called with
    ``cfg.faults != "off"`` — the off path never imports the module).
    Raises on an unreadable/corrupt plan path: a chaos run that
    silently injects nothing is worse than one that fails to start."""
    from . import faults

    faults.activate(cfg.faults, retries=cfg.fault_retries,
                    backoff_s=cfg.fault_backoff_s,
                    deadline_s=cfg.fault_deadline_s)


def _faults_deactivate_stale() -> None:
    """Disarm a previous session's fault layer without importing it
    (sys.modules only — turning faults off never imports the module)."""
    import sys

    mod = sys.modules.get(__package__ + ".faults")
    if mod is not None and mod.active():
        mod.deactivate()


def _watchdog_activate(cfg: Config) -> None:
    """Import and arm the collective watchdog (only ever called with
    ``cfg.watchdog != "off"`` — the off path never imports the
    module).  The lease directory resolves to ``watchdog_dir``, then
    the membership board (``elastic_dir``), then — on a re-activation
    (a mid-run ``set_config`` deadline tune) — whatever directory the
    already-armed watchdog leases into, so a lease home the elastic
    driver ADOPTED at gang construction (``watchdog.set_lease_dir``)
    survives reconfiguration instead of silently orphaning the rank's
    lease on the board (peers read its expiry as death evidence).
    None disables leases; the in-process monitor still runs."""
    from . import watchdog

    lease_dir = cfg.watchdog_dir or cfg.elastic_dir
    if lease_dir is None and watchdog.active():
        lease_dir = watchdog.lease_dir()
    watchdog.activate(cfg.watchdog, deadline_s=cfg.watchdog_deadline_s,
                      poll_s=cfg.watchdog_poll_s,
                      lease_dir=lease_dir,
                      rank=jax.process_index())


def _watchdog_deactivate_stale() -> None:
    """Disarm a previous session's watchdog without importing it
    (sys.modules only — turning the watchdog off never imports it)."""
    import sys

    mod = sys.modules.get(__package__ + ".watchdog")
    if mod is not None and mod.active():
        mod.deactivate()


def _obs_activate(cfg: Config) -> None:
    """Import and arm the telemetry layer (only ever called with
    ``cfg.obs != "off"`` — the off path never imports the module).

    The same any-config env pickup as the mode itself: obs_dir and
    obs_ring_size left at their defaults defer to TORCHMPI_TPU_OBS_DIR
    / _OBS_RING, so `TORCHMPI_TPU_OBS=metrics python some_script.py`
    honors all three envs even when the script builds its Config
    explicitly; an explicit non-default field still wins."""
    import dataclasses as _dc

    from . import obs

    out_dir = (cfg.obs_dir or os.environ.get("TORCHMPI_TPU_OBS_DIR")
               or obs.DEFAULT_OUT_DIR)
    ring = cfg.obs_ring_size
    env_ring = os.environ.get("TORCHMPI_TPU_OBS_RING")
    default_ring = next(f.default for f in _dc.fields(Config)
                        if f.name == "obs_ring_size")
    if env_ring and ring == default_ring:
        ring = int(env_ring)
    obs.activate(cfg.obs, out_dir=out_dir, ring_size=ring,
                 host=jax.process_index())


def init(config: Optional[Config] = None, **overrides) -> Mesh:
    """Start the runtime (reference: ``mpi.start(withCuda)`` -> torchmpi_start).

    Idempotent.  Returns the world mesh.  Unlike the reference there is no
    mpirun: on a multi-host TPU slice, ``jax.distributed.initialize`` picks up
    topology from the TPU metadata environment; single-process (tests, one
    chip) needs no bring-up at all.
    """
    with _state.lock:
        if _state.initialized:
            return _state.mesh_stack[0][1]
        # Copy so later set_config() calls never mutate the caller's object
        # (incl. a private copy of the mutable per-op table).
        cfg = Config.from_env() if config is None else dataclasses.replace(config)
        if cfg.backend_per_op is not None:
            cfg.backend_per_op = _validate_backend_per_op(cfg.backend_per_op)
        for k, v in overrides.items():
            if not hasattr(cfg, k):
                raise ValueError(f"unknown config field {k!r}")
            if k == "backend_per_op" and v is not None:
                v = _validate_backend_per_op(v)
            setattr(cfg, k, v)

        # Launcher env pickup applies to ANY config (scripts typically pass
        # an explicit Config; they must still join the launched job rather
        # than silently running N disconnected single-process copies).
        import os

        # Same any-config rule for the analyzer opt-in: an operator (or
        # scripts/lint_collectives.py) exporting TORCHMPI_TPU_ANALYSIS
        # must reach scripts that build their Config explicitly.  An
        # explicit non-default field still wins.  Normalization happens
        # in one place for BOTH sources (explicit Config value and env)
        # so "WARN", "1", and "warn" behave identically everywhere.
        if _normalize_analysis(cfg.analysis) == "off":
            cfg.analysis = os.environ.get("TORCHMPI_TPU_ANALYSIS", "off")
        cfg.analysis = _normalize_analysis(cfg.analysis)
        if cfg.analysis is None:
            raise ValueError(
                "config.analysis (or TORCHMPI_TPU_ANALYSIS) must be "
                "off|warn|error")

        # Same any-config env pickup + one-home normalization for the
        # telemetry opt-in (TORCHMPI_TPU_OBS): an explicit non-default
        # field wins; "1"/"true" mean "metrics".
        if _normalize_obs(cfg.obs) == "off":
            cfg.obs = os.environ.get("TORCHMPI_TPU_OBS", "off")
        cfg.obs = _normalize_obs(cfg.obs)
        if cfg.obs is None:
            raise ValueError(
                "config.obs (or TORCHMPI_TPU_OBS) must be "
                "off|metrics|trace")

        # Same any-config rule for the fault layer (TORCHMPI_TPU_FAULTS
        # + the numeric policy/timeout knobs): an explicit non-default
        # field wins, env fills the defaults — so `TORCHMPI_TPU_FAULTS=
        # plan.json python train.py` reaches scripts that build their
        # Config explicitly (the chaos-smoke CI job relies on this).
        if _normalize_faults(cfg.faults) == "off":
            cfg.faults = os.environ.get("TORCHMPI_TPU_FAULTS", "off")
        cfg.faults = _normalize_faults(cfg.faults)
        _env_default_pickup(cfg, "fault_retries",
                            "TORCHMPI_TPU_FAULT_RETRIES", int)
        _env_default_pickup(cfg, "fault_backoff_s",
                            "TORCHMPI_TPU_FAULT_BACKOFF", float)
        _env_default_pickup(cfg, "fault_deadline_s",
                            "TORCHMPI_TPU_FAULT_DEADLINE", float)
        _env_default_pickup(cfg, "ps_timeout_s",
                            "TORCHMPI_TPU_PS_TIMEOUT", float)
        # Payload-integrity + numeric-anomaly guard (docs/GUARD.md):
        # same any-config env pickup + one-home normalization as
        # analysis/obs/faults.  "off" (the default) never imports
        # torchmpi_tpu.guard (or faults.integrity): the mode is read as
        # one string compare at plan build / trace time.
        if _normalize_guard(cfg.guard) == "off":
            cfg.guard = os.environ.get("TORCHMPI_TPU_GUARD", "off")
        cfg.guard = _normalize_guard(cfg.guard)
        if cfg.guard is None:
            raise ValueError(
                "config.guard (or TORCHMPI_TPU_GUARD) must be "
                "off|wire|numeric|full")
        cfg.guard_numeric_policy = _normalize_guard_policy(
            cfg.guard_numeric_policy)
        if cfg.guard_numeric_policy is None:
            raise ValueError(
                "config.guard_numeric_policy (or TORCHMPI_TPU_GUARD_POLICY)"
                " must be skip_step|raise")
        _env_default_pickup(cfg, "guard_norm_bound",
                            "TORCHMPI_TPU_GUARD_NORM_BOUND", float)
        _env_default_pickup(cfg, "guard_spike_window",
                            "TORCHMPI_TPU_GUARD_WINDOW", int)
        _env_default_pickup(cfg, "guard_spike_threshold",
                            "TORCHMPI_TPU_GUARD_THRESHOLD", float)
        if cfg.guard_norm_bound < 0:
            raise ValueError(
                f"config.guard_norm_bound must be >= 0 (0 = finite-only),"
                f" got {cfg.guard_norm_bound}")
        if cfg.guard_spike_window < 2 or cfg.guard_spike_threshold <= 0:
            raise ValueError(
                f"config.guard_spike_window must be >= 2 and "
                f"guard_spike_threshold > 0, got "
                f"{cfg.guard_spike_window}/{cfg.guard_spike_threshold}")
        # Collective watchdog (docs/WATCHDOG.md): same any-config env
        # pickup + one-home normalization as analysis/obs/faults/guard.
        # "off" (default) never imports torchmpi_tpu.watchdog — the
        # mode is read as one string compare at plan build / site
        # entry, and the planned dispatch path gains zero branches.
        if _normalize_watchdog(cfg.watchdog) == "off":
            cfg.watchdog = os.environ.get("TORCHMPI_TPU_WATCHDOG", "off")
        cfg.watchdog = _normalize_watchdog(cfg.watchdog)
        if cfg.watchdog is None:
            raise ValueError(
                "config.watchdog (or TORCHMPI_TPU_WATCHDOG) must be "
                "off|warn|break")
        _env_default_pickup(cfg, "watchdog_deadline_s",
                            "TORCHMPI_TPU_WATCHDOG_DEADLINE", float)
        _env_default_pickup(cfg, "watchdog_poll_s",
                            "TORCHMPI_TPU_WATCHDOG_POLL", float)
        if cfg.watchdog_dir is None:
            cfg.watchdog_dir = (
                os.environ.get("TORCHMPI_TPU_WATCHDOG_DIR") or None)
        if cfg.watchdog_deadline_s <= 0 or cfg.watchdog_poll_s <= 0:
            raise ValueError(
                f"config.watchdog_deadline_s and watchdog_poll_s must "
                f"be > 0, got {cfg.watchdog_deadline_s}/"
                f"{cfg.watchdog_poll_s}")
        # Durable checkpoints (docs/CHECKPOINT.md): same any-config env
        # pickup + one-home normalization.  "off" (default) never
        # imports utils/durable.py — save/restore read the mode as one
        # string compare at entry.
        if _normalize_ckpt_redundancy(cfg.ckpt_redundancy) == "off":
            cfg.ckpt_redundancy = os.environ.get(
                "TORCHMPI_TPU_CKPT_REDUNDANCY", "off")
        cfg.ckpt_redundancy = _normalize_ckpt_redundancy(
            cfg.ckpt_redundancy)
        if cfg.ckpt_redundancy is None:
            raise ValueError(
                "config.ckpt_redundancy (or TORCHMPI_TPU_CKPT_REDUNDANCY)"
                " must be off|verify|buddy")
        _env_default_pickup(cfg, "ckpt_buddies",
                            "TORCHMPI_TPU_CKPT_BUDDIES", int)
        _env_default_pickup(cfg, "ckpt_keep",
                            "TORCHMPI_TPU_CKPT_KEEP", int)
        if cfg.ckpt_buddies < 1 or cfg.ckpt_keep < 0:
            raise ValueError(
                f"config.ckpt_buddies must be >= 1 and ckpt_keep >= 0 "
                f"(0 = keep everything), got "
                f"{cfg.ckpt_buddies}/{cfg.ckpt_keep}")
        # Hot-state replication tier (docs/HOTSTATE.md): same
        # any-config env pickup + one-home normalization.  "on" arms
        # NOTHING here — torchmpi_tpu.hotstate is a driver layer the
        # user enables explicitly, and the knob is its consent gate;
        # "off" (default) never imports the module and the dispatch
        # path has no branch on it at all.
        if _normalize_hotstate(cfg.hotstate) == "off":
            cfg.hotstate = os.environ.get("TORCHMPI_TPU_HOTSTATE", "off")
        cfg.hotstate = _normalize_hotstate(cfg.hotstate)
        if cfg.hotstate is None:
            raise ValueError(
                "config.hotstate (or TORCHMPI_TPU_HOTSTATE) must be "
                "off|on")
        _env_default_pickup(cfg, "hotstate_interval",
                            "TORCHMPI_TPU_HOTSTATE_INTERVAL", int)
        _env_default_pickup(cfg, "hotstate_budget_mb",
                            "TORCHMPI_TPU_HOTSTATE_BUDGET_MB", int)
        if cfg.hotstate_interval < 1 or cfg.hotstate_budget_mb < 1:
            raise ValueError(
                f"config.hotstate_interval and hotstate_budget_mb must "
                f"be >= 1, got {cfg.hotstate_interval}/"
                f"{cfg.hotstate_budget_mb}")
        # Elastic gang membership (docs/ELASTIC.md): same any-config env
        # pickup + one-home normalization.  "on" arms NOTHING here —
        # torchmpi_tpu.elastic is a driver layer the user calls
        # explicitly, and the knob is its consent gate; "off" (default)
        # never imports the module and the dispatch path has no branch
        # on it at all.
        if _normalize_elastic(cfg.elastic) == "off":
            cfg.elastic = os.environ.get("TORCHMPI_TPU_ELASTIC", "off")
        cfg.elastic = _normalize_elastic(cfg.elastic)
        if cfg.elastic is None:
            raise ValueError(
                "config.elastic (or TORCHMPI_TPU_ELASTIC) must be off|on")
        if cfg.elastic_dir is None:
            cfg.elastic_dir = (
                os.environ.get("TORCHMPI_TPU_ELASTIC_DIR") or None)
        _env_default_pickup(cfg, "elastic_poll_s",
                            "TORCHMPI_TPU_ELASTIC_POLL", float)
        _env_default_pickup(cfg, "elastic_deadline_s",
                            "TORCHMPI_TPU_ELASTIC_DEADLINE", float)
        if cfg.elastic_poll_s <= 0 or cfg.elastic_deadline_s <= 0:
            raise ValueError(
                f"config.elastic_poll_s and elastic_deadline_s must be "
                f"> 0, got {cfg.elastic_poll_s}/{cfg.elastic_deadline_s}")
        if _normalize_elastic_quorum(cfg.elastic_quorum) == "off":
            cfg.elastic_quorum = os.environ.get(
                "TORCHMPI_TPU_ELASTIC_QUORUM", "off")
        cfg.elastic_quorum = _normalize_elastic_quorum(cfg.elastic_quorum)
        if cfg.elastic_quorum is None:
            raise ValueError(
                "config.elastic_quorum (or TORCHMPI_TPU_ELASTIC_QUORUM) "
                "must be off|majority")
        # Serving-layer sizing (docs/SERVING.md): same any-config env
        # pickup; the knobs are plain ints, the package itself is only
        # ever imported by explicit use.
        _env_default_pickup(cfg, "serving_slots",
                            "TORCHMPI_TPU_SERVING_SLOTS", int)
        _env_default_pickup(cfg, "serving_slot_tokens",
                            "TORCHMPI_TPU_SERVING_SLOT_TOKENS", int)
        _env_default_pickup(cfg, "serving_replicas",
                            "TORCHMPI_TPU_SERVING_REPLICAS", int)
        _env_default_pickup(cfg, "serving_sample",
                            "TORCHMPI_TPU_SERVING_SAMPLE", float)
        _env_default_pickup(cfg, "serving_spec_k",
                            "TORCHMPI_TPU_SERVING_SPEC_K", int)
        _env_default_pickup(cfg, "serving_prefill_buckets",
                            "TORCHMPI_TPU_SERVING_PREFILL_BUCKETS", int)
        _env_default_pickup(cfg, "serving_prefix_cache",
                            "TORCHMPI_TPU_SERVING_PREFIX_CACHE", int)
        _env_default_pickup(cfg, "serving_slo_ttft_us",
                            "TORCHMPI_TPU_SERVING_SLO_TTFT_US", float)
        _env_default_pickup(cfg, "serving_autoscale",
                            "TORCHMPI_TPU_SERVING_AUTOSCALE", int)
        if cfg.serving_prefix_cache < 0 or cfg.serving_autoscale < 0 \
                or cfg.serving_slo_ttft_us < 0:
            raise ValueError(
                f"config.serving_prefix_cache / serving_autoscale / "
                f"serving_slo_ttft_us must be >= 0 (0 = off), got "
                f"{cfg.serving_prefix_cache}/{cfg.serving_autoscale}/"
                f"{cfg.serving_slo_ttft_us}")
        if cfg.serving_spec_k < 0 or cfg.serving_prefill_buckets < 0:
            raise ValueError(
                f"config.serving_spec_k and serving_prefill_buckets "
                f"must be >= 0 (0 = off), got {cfg.serving_spec_k}/"
                f"{cfg.serving_prefill_buckets}")
        if cfg.serving_slots < 1 or cfg.serving_replicas < 1 \
                or cfg.serving_slot_tokens < 0:
            raise ValueError(
                f"config.serving_slots/serving_replicas must be >= 1 and "
                f"serving_slot_tokens >= 0 (0 = model max_len), got "
                f"{cfg.serving_slots}/{cfg.serving_replicas}/"
                f"{cfg.serving_slot_tokens}")
        if (os.environ.get("TORCHMPI_TPU_PS_TIMEOUT") is None
                and os.environ.get("TORCHMPI_TPU_PS_TIMEOUT_MS")):
            # Legacy millisecond spelling (pre-Config knob): honored
            # when the new env is unset, as config.py promises.
            _env_default_pickup(cfg, "ps_timeout_s",
                                "TORCHMPI_TPU_PS_TIMEOUT_MS",
                                lambda v: float(v) / 1000.0)
        if cfg.ps_timeout_s < 0:
            raise ValueError(
                f"config.ps_timeout_s must be >= 0 (0 disables), got "
                f"{cfg.ps_timeout_s}")

        # Backprop-overlapped gradient sync (docs/OVERLAP.md): same
        # any-config env pickup + normalization as analysis/obs/faults.
        if _normalize_overlap(cfg.gradsync_overlap) == "off":
            cfg.gradsync_overlap = os.environ.get(
                "TORCHMPI_TPU_GRADSYNC_OVERLAP", "off")
        cfg.gradsync_overlap = _normalize_overlap(cfg.gradsync_overlap)
        if cfg.gradsync_overlap is None:
            raise ValueError(
                "config.gradsync_overlap (or TORCHMPI_TPU_GRADSYNC_OVERLAP)"
                " must be off|auto")
        _env_default_pickup(cfg, "gradsync_overlap_bytes",
                            "TORCHMPI_TPU_GRADSYNC_OVERLAP_BYTES", int)
        if cfg.gradsync_overlap_bytes < 0:
            raise ValueError(
                f"config.gradsync_overlap_bytes must be >= 0 (0 = derive "
                f"from the tuning plan), got {cfg.gradsync_overlap_bytes}")

        # Two-level DCN staging knobs (docs/HIERARCHICAL.md): same
        # any-config env pickup + one-home normalization as the layers
        # above.  The codec itself is resolved at trace/plan-build time
        # — "off" never imports torchmpi_tpu.compress.
        if _normalize_dcn_compress(cfg.dcn_compress) == "off":
            cfg.dcn_compress = os.environ.get("TORCHMPI_TPU_DCN_COMPRESS",
                                              "off")
        cfg.dcn_compress = _normalize_dcn_compress(cfg.dcn_compress)
        if cfg.dcn_compress is None:
            raise ValueError(
                "config.dcn_compress (or TORCHMPI_TPU_DCN_COMPRESS) must "
                "be off|bf16|int8|fp8")
        _env_default_pickup(cfg, "dcn_compress_min_bytes",
                            "TORCHMPI_TPU_DCN_COMPRESS_MIN_BYTES", int)
        _env_default_pickup(cfg, "dcn_chunk_bytes",
                            "TORCHMPI_TPU_DCN_CHUNK_BYTES", int)
        if cfg.dcn_compress_min_bytes < 0 or cfg.dcn_chunk_bytes < 0:
            raise ValueError(
                "config.dcn_compress_min_bytes and dcn_chunk_bytes must "
                "be >= 0 (0 = no floor / no chunking)")

        if cfg.coordinator_address is None:
            coord = os.environ.get("TORCHMPI_TPU_COORDINATOR")
            if coord:
                cfg.coordinator_address = coord
                cfg.num_processes = int(
                    os.environ.get("TORCHMPI_TPU_NUM_PROCESSES", "1"))
                cfg.process_id = int(
                    os.environ.get("TORCHMPI_TPU_PROCESS_ID", "0"))

        # Multi-process bring-up (reference: MPI_Init_thread under mpirun).
        if cfg.coordinator_address is not None and not _state.distributed_initialized:
            if os.environ.get("TORCHMPI_TPU_LOCAL_CPU"):
                # Launched by `python -m torchmpi_tpu.launch`: emulated
                # multi-host on CPU devices with gloo cross-process
                # collectives (the mpirun-on-localhost test rig).
                jax.config.update("jax_platforms", "cpu")
                jax.config.update("jax_cpu_collectives_implementation",
                                  "gloo")
            jax.distributed.initialize(
                coordinator_address=cfg.coordinator_address,
                num_processes=cfg.num_processes,
                process_id=cfg.process_id,
            )
            _state.distributed_initialized = True

        # Arm (or disarm a stale) fault layer BEFORE the runtime marks
        # itself initialized: a corrupt/missing fault plan must fail
        # init outright — never leave a half-armed runtime behind a
        # chaos knob that silently injects nothing.  Off (the default)
        # never imports torchmpi_tpu.faults.
        if cfg.faults != "off":
            _faults_activate(cfg)
        else:
            _faults_deactivate_stale()

        _state.config = cfg
        _state.devices = list(jax.devices())
        world = _build_world_mesh(cfg, _state.devices)
        _state.mesh_stack = [("world", world)]
        _state.mesh_cache = {"world": world}
        _state.initialized = True
        _state.config_epoch += 1
    # Outside the lock: tuning.configure reads runtime state via the
    # public accessors.  Loads the persistent collective plan DB and
    # registers the selector's plan provider when the config opts into
    # measured selection (backend="auto", a per-op "auto", or an
    # explicit plan path — e.g. one emitted by benchmarks/autotune.py).
    if _tuning_opted_in(cfg):
        from . import tuning

        tuning.configure(cfg.tuning_plan_path, rounds=cfg.tuning_rounds,
                         auto_active=_tuning_auto_active(cfg))
    if cfg.analysis != "off":
        # Arm the findings capture (and the TORCHMPI_TPU_ANALYSIS_OUT
        # atexit report) so even a process that dies before its first
        # checked compile leaves an (empty) report behind.
        from . import analysis

        analysis.arm_runtime_capture()
    if cfg.obs != "off":
        # Arm telemetry (registry + flight recorder + SIGTERM/atexit
        # dump).  Off (the default) never imports torchmpi_tpu.obs.
        _obs_activate(cfg)
    else:
        # A previous session's telemetry must not survive a re-init
        # that opted out (stale mode, SIGTERM handler, atexit dump) —
        # but only via sys.modules: turning obs off never imports it.
        import sys

        mod = sys.modules.get(__package__ + ".obs")
        if mod is not None and mod.active():
            mod.deactivate()
    # Collective watchdog: armed AFTER obs so the monitor's first
    # events land in an armed registry.  Off (the default) never
    # imports torchmpi_tpu.watchdog.
    if cfg.watchdog != "off":
        _watchdog_activate(cfg)
    else:
        _watchdog_deactivate_stale()
    return world


def stop() -> None:
    """Tear down (reference: ``mpi.stop`` -> torchmpi_stop -> MPI_Finalize)."""
    with _state.lock:
        _state.initialized = False
        _state.mesh_stack = []
        _state.mesh_cache = {}
        _state.config_epoch += 1
    from . import collectives, tuning

    collectives.clear_cache()
    tuning.reset()
    # A quorum-armed elastic gang published an epoch fence for the
    # checkpoint seam (faults/fencing.py) — retract it with the
    # runtime so a later non-elastic session's saves are not checked
    # against a dead board.  sys.modules on purpose: the module is
    # only ever imported when quorum was armed.
    fencing = sys.modules.get("torchmpi_tpu.faults.fencing")
    if fencing is not None:
        fencing.disarm()


def is_initialized() -> bool:
    return _state.initialized


def _require_init() -> None:
    if not _state.initialized:
        raise RuntimeError(
            "torchmpi_tpu runtime not initialized; call torchmpi_tpu.init() first "
            "(the reference raised the same way when mpi.start() was skipped)"
        )


def config() -> Config:
    return _state.config


def config_epoch() -> int:
    """Monotonic counter of configuration changes (init / set_config /
    stop each bump it).  ``torchmpi_tpu.planner`` embeds the current
    value in every plan key, so a live knob switch invalidates every
    cached :class:`~torchmpi_tpu.planner.CollectivePlan` by making it
    unreachable — mutate the active config only through
    :func:`set_config` (direct writes to the :func:`config` object
    bypass the epoch and can replay stale plans)."""
    return _state.config_epoch


def effective_config() -> Config:
    """The active Config when the runtime is initialized, else defaults.

    For trace-time knob reads (``chunk_bytes``, ``pallas_bidirectional``)
    from code that may run outside ``init()`` — direct kernel use, tests —
    so every consumer resolves knobs identically."""
    return _state.config if _state.initialized else Config()


def resolve_blocks(block_a, block_b, field_a: str, field_b: str):
    """Resolve ``None`` kernel-tiling arguments from the active Config —
    the knobs ``benchmarks/autotune.py`` measures per platform.  The one
    resolution point for every Pallas kernel entry (flash forward, the
    custom-VJP training wrappers, ring attention, fused-xent), so the
    autotuned values reach training code, not just forward-only calls."""
    if block_a is None or block_b is None:
        cfg = effective_config()
        if block_a is None:
            block_a = getattr(cfg, field_a)
        if block_b is None:
            block_b = getattr(cfg, field_b)
    return block_a, block_b


def _validate_backend_per_op(table: Dict[str, str]) -> Dict[str, str]:
    """Per-op override tables fail loudly on typos (a silently-ignored key
    would let a user benchmark the wrong implementation)."""
    from . import selector

    avail = selector.available()
    for op, backend in table.items():
        if op not in avail:
            raise ValueError(
                f"backend_per_op: unknown collective {op!r} "
                f"(known: {sorted(avail)})")
        if backend not in ("xla", "auto") and backend not in avail[op]:
            raise ValueError(
                f"backend_per_op[{op!r}]: backend {backend!r} has no "
                f"implementation for this op (available: "
                f"{sorted(avail[op])})")
    return dict(table)  # private copy: never alias the caller's dict


def _tuning_auto_active(cfg: Config) -> bool:
    """Does some backend actually resolve to "auto" (plan-driven)?"""
    if cfg.backend == "auto":
        return True
    return bool(cfg.backend_per_op
                and "auto" in cfg.backend_per_op.values())


def _tuning_opted_in(cfg: Config) -> bool:
    """Did this config ask the tuning subsystem to load a plan?  A plan
    path WITHOUT any "auto" backend still loads (and the decision log
    notes it is inactive) so the misconfiguration is visible."""
    return _tuning_auto_active(cfg) or cfg.tuning_plan_path is not None


def set_config(**kw) -> None:
    """Runtime-switch knobs (reference: the torchmpi_set_* FFI setters).

    Bumps the config epoch and clears the collective plan table
    (``torchmpi_tpu/planner.py``): every planned decision — compiled
    executables, fusion bucketing, selector/tuning backend choices,
    obs/faults enablement — was resolved under the old config and must
    not be replayed (the reference's setters likewise took effect
    immediately).  In-axis collectives inside a USER's jit are cached by
    jax itself and keep their traced-time settings until the user
    retraces.
    """
    _require_init()
    for k, v in kw.items():
        if not hasattr(_state.config, k):
            raise ValueError(f"unknown config field {k!r}")
        if k == "backend_per_op" and v is not None:
            v = _validate_backend_per_op(v)
        if k == "analysis":
            v = _normalize_analysis(v)
            if v is None:
                raise ValueError(
                    "config.analysis must be off|warn|error")
        if k == "obs":
            v = _normalize_obs(v)
            if v is None:
                raise ValueError("config.obs must be off|metrics|trace")
        if k == "faults":
            v = _normalize_faults(v)
        if k == "guard":
            v = _normalize_guard(v)
            if v is None:
                raise ValueError(
                    "config.guard must be off|wire|numeric|full")
        if k == "guard_numeric_policy":
            v = _normalize_guard_policy(v)
            if v is None:
                raise ValueError(
                    "config.guard_numeric_policy must be skip_step|raise")
        if k == "guard_norm_bound":
            v = float(v)
            if v < 0:
                raise ValueError(
                    "config.guard_norm_bound must be >= 0 "
                    "(0 = finite-only)")
        if k == "guard_spike_window":
            v = int(v)
            if v < 2:
                raise ValueError("config.guard_spike_window must be >= 2")
        if k == "guard_spike_threshold":
            v = float(v)
            if v <= 0:
                raise ValueError(
                    "config.guard_spike_threshold must be > 0")
        if k == "watchdog":
            v = _normalize_watchdog(v)
            if v is None:
                raise ValueError(
                    "config.watchdog must be off|warn|break")
        if k in ("watchdog_deadline_s", "watchdog_poll_s"):
            v = float(v)
            if v <= 0:
                raise ValueError(f"config.{k} must be > 0")
        if k == "ckpt_redundancy":
            v = _normalize_ckpt_redundancy(v)
            if v is None:
                raise ValueError(
                    "config.ckpt_redundancy must be off|verify|buddy")
        if k == "ckpt_buddies":
            v = int(v)
            if v < 1:
                raise ValueError("config.ckpt_buddies must be >= 1")
        if k == "ckpt_keep":
            v = int(v)
            if v < 0:
                raise ValueError(
                    "config.ckpt_keep must be >= 0 (0 = keep everything)")
        if k == "elastic":
            v = _normalize_elastic(v)
            if v is None:
                raise ValueError("config.elastic must be off|on")
        if k == "hotstate":
            v = _normalize_hotstate(v)
            if v is None:
                raise ValueError("config.hotstate must be off|on")
        if k in ("hotstate_interval", "hotstate_budget_mb"):
            v = int(v)
            if v < 1:
                raise ValueError(f"config.{k} must be >= 1")
        if k in ("elastic_poll_s", "elastic_deadline_s"):
            v = float(v)
            if v <= 0:
                raise ValueError(f"config.{k} must be > 0")
        if k == "elastic_quorum":
            v = _normalize_elastic_quorum(v)
            if v is None:
                raise ValueError(
                    "config.elastic_quorum must be off|majority")
        if k == "elastic_dir":
            # Same one-home normalization as init: "" means unset.
            v = v or None
        if k == "gradsync_overlap":
            v = _normalize_overlap(v)
            if v is None:
                raise ValueError("config.gradsync_overlap must be off|auto")
        if k == "gradsync_overlap_bytes":
            v = int(v)
            if v < 0:
                raise ValueError(
                    "config.gradsync_overlap_bytes must be >= 0")
        if k == "dcn_compress":
            v = _normalize_dcn_compress(v)
            if v is None:
                raise ValueError(
                    "config.dcn_compress must be off|bf16|int8|fp8")
        if k in ("dcn_compress_min_bytes", "dcn_chunk_bytes"):
            v = int(v)
            if v < 0:
                raise ValueError(f"config.{k} must be >= 0")
        if k == "ps_timeout_s":
            v = float(v)
            if v < 0:
                raise ValueError(
                    "config.ps_timeout_s must be >= 0 (0 disables)")
        if k in ("serving_slots", "serving_replicas"):
            v = int(v)
            if v < 1:
                raise ValueError(f"config.{k} must be >= 1")
        if k == "serving_slot_tokens":
            v = int(v)
            if v < 0:
                raise ValueError(
                    "config.serving_slot_tokens must be >= 0 "
                    "(0 = model max_len)")
        if k == "serving_sample":
            # <= 0 means greedy (config.py), so only the type is pinned.
            v = float(v)
        if k in ("serving_spec_k", "serving_prefill_buckets"):
            v = int(v)
            if v < 0:
                raise ValueError(f"config.{k} must be >= 0 (0 = off)")
        if k in ("serving_prefix_cache", "serving_autoscale"):
            v = int(v)
            if v < 0:
                raise ValueError(f"config.{k} must be >= 0 (0 = off)")
        if k == "serving_slo_ttft_us":
            v = float(v)
            if v < 0:
                raise ValueError(
                    "config.serving_slo_ttft_us must be >= 0 "
                    "(0 = admit everything)")
        if k == "fault_retries":
            v = int(v)
        if k in ("fault_backoff_s", "fault_deadline_s"):
            v = float(v)
        setattr(_state.config, k, v)
    # Every plan key embeds the epoch (torchmpi_tpu/planner.py), so the
    # bump alone already strands every stale CollectivePlan; the
    # clear_cache() below additionally releases their memory.
    _state.config_epoch += 1
    if ("faults" in kw or "fault_retries" in kw or "fault_backoff_s" in kw
            or "fault_deadline_s" in kw):
        if _state.config.faults != "off":
            _faults_activate(_state.config)
        else:
            _faults_deactivate_stale()
    if "obs" in kw or "obs_dir" in kw or "obs_ring_size" in kw:
        if _state.config.obs != "off":
            _obs_activate(_state.config)
        else:
            import sys

            # Turning obs OFF must not import the module it disables.
            mod = sys.modules.get(__package__ + ".obs")
            if mod is not None:
                mod.deactivate()
    if "analysis" in kw and _state.config.analysis != "off":
        # Same arming as init: capture + the ANALYSIS_OUT atexit report.
        from . import analysis

        analysis.arm_runtime_capture()
    if ("watchdog" in kw or "watchdog_deadline_s" in kw
            or "watchdog_poll_s" in kw or "watchdog_dir" in kw):
        if _state.config.watchdog != "off":
            _watchdog_activate(_state.config)
        else:
            # Turning the watchdog OFF must not import the module.
            _watchdog_deactivate_stale()
    from . import collectives, tuning

    collectives.clear_cache()
    # (Re)configure tuning whenever the config opts into auto/planned
    # selection: a changed tuning_plan_path or tuning_rounds takes
    # effect immediately (the reference's setters likewise did), and
    # switching INTO auto at runtime activates the plan DB.  An
    # unchanged path keeps the in-memory entries (they may be
    # unpersistable on a read-only tree) and merges in whatever
    # appeared on disk meanwhile; a changed path reloads outright.
    if _tuning_opted_in(_state.config):
        tuning.configure(_state.config.tuning_plan_path,
                         rounds=_state.config.tuning_rounds,
                         auto_active=_tuning_auto_active(_state.config))


# --- rank/size family -------------------------------------------------------
# TorchMPI's rank was a per-*process* concept (one process per GPU).  Under
# JAX SPMD one process drives many devices, so both granularities are exposed:
# process-level (data loading, logging, PS clients) and device-level (inside
# shard_map, via jax.lax.axis_index).


def rank() -> int:
    """Process rank (reference: ``mpi.rank()``)."""
    return jax.process_index()


def size() -> int:
    """Process count (reference: ``mpi.size()``)."""
    return jax.process_count()


def local_rank() -> int:
    """Rank of this process among processes on the same host.

    Defined (round 1 returned a plausible guess): the launcher that
    co-locates processes exports ``TORCHMPI_TPU_LOCAL_RANK`` (our
    ``launch.py`` does; schedulers can too); absent that, JAX's standard
    deployment is one process per host, so the local rank is 0.  The
    reference used localRank % numDevices for GPU binding; JAX binds
    devices per process itself, so this is informational."""
    v = os.environ.get("TORCHMPI_TPU_LOCAL_RANK")
    return int(v) if v is not None else 0


def device_count() -> int:
    """Total device (chip) count across all processes."""
    _require_init()
    return len(_state.devices)


def local_device_count() -> int:
    return jax.local_device_count()


def barrier(name: str = "torchmpi_tpu_barrier") -> None:
    """Global barrier (reference: ``mpi.barrier()`` -> MPI_Barrier).

    Implemented as a tiny fully-replicated psum across every device — the
    devices *are* the processes' gang, so completion implies every process
    reached the barrier.
    """
    _require_init()
    if _state.config.obs != "off":
        from . import obs

        # Recorded BEFORE the wait: a host stuck in this barrier shows
        # it as the last flight event (obs_tool.py blame anchor).
        obs.record_barrier(name)
    wd = None
    wd_tok = -1
    if _state.config.watchdog != "off":
        # Live hang detection over the gang sync (docs/WATCHDOG.md):
        # a barrier the gang never completes is flagged stalled within
        # watchdog_deadline_s — and any deferred break from a stalled
        # background wait is delivered HERE, at the eager boundary,
        # before this process commits to another gang-wide wait.
        from . import watchdog

        wd = watchdog
        wd.raise_pending()
        wd_tok = wd.begin("runtime.barrier", op=name, peer="gang")

    def _sync():
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(name)
        else:
            jax.block_until_ready(jax.device_put(np.zeros(())))

    try:
        if _state.config.faults != "off":
            from . import faults

            # Injection fires per attempt and the gang sync runs under
            # the site deadline: a wedged peer becomes PeerTimeoutError
            # instead of an unbounded wait (docs/FAULTS.md).
            faults.guarded_barrier(name, _sync)
        else:
            _sync()
    finally:
        if wd is not None:
            wd.end(wd_tok)
    if _state.config.obs != "off":
        from . import obs

        # The completion edge: lets obs_tool blame tell "launched and
        # stuck inside the barrier" from "completed it, never launched
        # the next collective" (docs/OBSERVABILITY.md).
        obs.record_barrier_done(name)


# --- communicator (mesh) stack ---------------------------------------------


def world_mesh() -> Mesh:
    _require_init()
    return _state.mesh_stack[0][1]


def current_mesh() -> Mesh:
    """Innermost pushed communicator (reference: the active communicator the
    collectives resolved against)."""
    _require_init()
    return _state.mesh_stack[-1][1]


def current_mesh_name() -> str:
    _require_init()
    return _state.mesh_stack[-1][0]


def resize_world(devices: Sequence[jax.Device], *,
                 shape: Optional[Dict[str, int]] = None) -> Mesh:
    """Re-form the world mesh over a device subset — the gang-resize
    primitive ``torchmpi_tpu.elastic`` shrinks/grows through
    (docs/ELASTIC.md; the reference analog is tearing down and
    re-creating the communicator tree, PAPER.md: communicators are
    disposable).

    ``shape`` is an ordered axis-name -> size dict over exactly
    ``devices`` (the :func:`push_communicator` convention); ``None``
    builds a 1-D ``(ici,)`` mesh.  Replaces the whole communicator
    stack (pushed communicators are views of the OLD gang — they do
    not survive a membership change) and bumps the config epoch, so
    every cached :class:`~torchmpi_tpu.planner.CollectivePlan` built
    against the old mesh is stranded; ``planner.invalidate()`` then
    releases the stale plans' memory.  The active Config is untouched.
    """
    _require_init()
    devs = list(devices)
    if not devs:
        raise ValueError("resize_world needs at least one device")
    with _state.lock:
        if shape is None:
            mesh = Mesh(np.asarray(devs), (ICI_AXIS,))
        else:
            axes = tuple(shape.keys())
            sizes = tuple(shape.values())
            if int(np.prod(sizes)) != len(devs):
                raise ValueError(
                    f"shape {shape} does not cover {len(devs)} devices")
            mesh = Mesh(np.asarray(devs).reshape(sizes), axes)
        _state.devices = devs
        _state.mesh_stack = [("world", mesh)]
        _state.mesh_cache = {"world": mesh}
        _state.config_epoch += 1
    from . import collectives

    # Routes to planner.invalidate(): drops every plan + cached
    # sharding + legacy executable pinned to the old gang's meshes.
    collectives.clear_cache()
    return mesh


def push_communicator(
    key: str,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    shape: Optional[Dict[str, int]] = None,
) -> Mesh:
    """Push a named communicator scope (reference: user-defined communicator
    splits keyed by a string, SURVEY.md §1 cap.6).

    - ``devices``: explicit subset (1-D mesh named ``ici``) or, with ``shape``,
      reshaped into the given named axes.
    - ``shape``: dict axis-name -> size over the *current* mesh's devices
      (or over ``devices`` when given).
    - Neither: re-push of a cached mesh under ``key`` (must exist).

    Meshes are cached by key, like the reference cached communicators per
    split string.
    """
    _require_init()
    with _state.lock:
        if devices is None and shape is None:
            if key not in _state.mesh_cache:
                raise KeyError(f"no cached communicator {key!r}")
            mesh = _state.mesh_cache[key]
        else:
            devs = list(devices) if devices is not None else list(
                _state.mesh_stack[-1][1].devices.flat
            )
            if shape is None:
                mesh = Mesh(np.asarray(devs), (ICI_AXIS,))
            else:
                axes = tuple(shape.keys())
                sizes = tuple(shape.values())
                if int(np.prod(sizes)) != len(devs):
                    raise ValueError(
                        f"shape {shape} does not cover {len(devs)} devices"
                    )
                mesh = Mesh(np.asarray(devs).reshape(sizes), axes)
            _state.mesh_cache[key] = mesh
        _state.mesh_stack.append((key, mesh))
        return mesh


def pop_communicator() -> None:
    _require_init()
    with _state.lock:
        if len(_state.mesh_stack) <= 1:
            raise RuntimeError("cannot pop the world communicator")
        _state.mesh_stack.pop()


class communicator:
    """Context manager: ``with runtime.communicator("half", shape={...}):``"""

    def __init__(self, key: str, **kw) -> None:
        self._key = key
        self._kw = kw

    def __enter__(self) -> Mesh:
        return push_communicator(self._key, **self._kw)

    def __exit__(self, *exc) -> None:
        pop_communicator()
        return None
