"""Health-routed multi-replica dispatch.

The router spreads sessions across replicas and folds every step
outcome into a per-replica health ledger — the same
``HealthLedger`` / ``decide()`` (ok | degrade | raise) machinery the
fault layer runs on its cross-host surfaces (docs/FAULTS.md).  When the
fault layer is armed, the router uses ITS ledger, so replica
transitions emit the standard ``tm_fault_health_total`` counters and
chaos plans drive the same thresholds; otherwise a private ledger with
the same semantics.

Routing policy (:meth:`Router.pick`): least-loaded among the replicas
whose verdict is ``ok``; ``degrade`` replicas only admit when no
healthy replica has a free slot (shed optional load onto suspects,
never prefer them); ``raise`` (dead) replicas admit nothing and —
handled by the scheduler — drain their in-flight sessions for
re-routing instead of crashing the server.

Recovery feeds back the same way (docs/ELASTIC.md's rejoin, replica
edition): a drained replica whose ledger returns to ``healthy`` — a
probe or a shared-ledger success for the same peer recorded through
:meth:`Router.record` — is re-admitted into the dispatch rotation
(:meth:`Router.readmit`); its slot pool was drained, so it comes back
empty and simply starts taking new admissions.
"""

from __future__ import annotations

import sys
from typing import List, Optional

from ..utils.telemetry import emit
from .engine import ReplicaEngine


def _shared_ledger():
    """The fault layer's ledger when armed (sys.modules lookup keeps the
    decision symmetric with the rest of the library: an armed fault
    layer is necessarily already imported)."""
    mod = sys.modules.get("torchmpi_tpu.faults")
    if mod is not None and mod.active():
        return mod.ledger()
    return None


class Router:
    """Health-aware replica selection over a fixed replica set."""

    def __init__(self, replicas: List[ReplicaEngine], *,
                 ledger=None, suspect_after: int = 2,
                 dead_after: int = 3):
        if not replicas:
            raise ValueError("router needs at least one replica")
        names = [r.name for r in replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"replica names must be unique: {names}")
        self.replicas = list(replicas)
        self._ledger = ledger or _shared_ledger()
        if self._ledger is None:
            from ..faults.health import HealthLedger

            self._ledger = HealthLedger(suspect_after=suspect_after,
                                        dead_after=dead_after)

    # -- health ------------------------------------------------------------

    def record(self, replica: ReplicaEngine, ok: bool) -> str:
        """Fold one step outcome; returns the decide() verdict.  A
        success that brings a DRAINED replica's ledger back to
        ``healthy`` (one success fully resets — the HealthLedger
        contract) re-admits it into the rotation."""
        self._ledger.record(replica.name, ok)
        if ok and replica.dead and \
                self._ledger.state(replica.name) == "healthy":
            self.readmit(replica)
        return self.decide(replica)

    def readmit(self, replica: ReplicaEngine) -> None:
        """Return a healed (previously drained) replica to the
        dispatch rotation: clears its dead flag so ``pick()`` can
        select it again.  Its sessions were re-routed at the drain, so
        it rejoins empty; callers that cannot trust the old process
        should rebuild the engine instead.  A RETIRED replica (scaled
        down on purpose — :meth:`retire`) never comes back this way:
        readmission is for healed failures, not cancelled decisions."""
        if not replica.dead or getattr(replica, "retired", False):
            return
        replica.dead = False
        emit("record_serving", "readmitted", replica=replica.name)

    def decide(self, replica: ReplicaEngine) -> str:
        if replica.dead:
            return "raise"
        return self._ledger.decide(replica.name)

    def mark_dead(self, replica: ReplicaEngine) -> None:
        """Hard failure (the peer is gone — ``InjectedFailure``
        semantics): push the ledger straight past its thresholds so the
        verdict flips to ``raise`` without burning ``dead_after`` ticks
        of a replica that already told us it is dead."""
        for _ in range(max(1, getattr(self._ledger, "dead_after", 1))):
            self._ledger.record(replica.name, ok=False)

    # -- fleet membership --------------------------------------------------

    def add(self, replica: ReplicaEngine) -> None:
        """Register a freshly built replica (autoscale scale-up) into
        the dispatch rotation.  Name uniqueness is the same invariant
        the constructor enforces — per-replica telemetry and ledger
        rows key on it."""
        if any(r.name == replica.name for r in self.replicas):
            raise ValueError(
                f"replica name {replica.name!r} already registered")
        self.replicas.append(replica)

    def retire(self, replica: ReplicaEngine) -> None:
        """Take a replica out of the fleet FOR GOOD (autoscale
        scale-down): dead so ``pick``/``live`` skip it, ``retired`` so
        a later healthy ledger state can never auto-readmit a replica
        the controller deliberately removed.  The caller drains it
        first — retirement loses capacity, never work."""
        replica.dead = True
        replica.retired = True

    # -- selection ---------------------------------------------------------

    def live(self) -> List[ReplicaEngine]:
        return [r for r in self.replicas if not r.dead]

    def pick(self) -> Optional[ReplicaEngine]:
        """Replica for the next admission, or None when nothing can
        take it this tick."""
        ok = [r for r in self.live()
              if self.decide(r) == "ok" and r.has_capacity()]
        if ok:
            return min(ok, key=lambda r: (r.active, r.name))
        degraded = [r for r in self.live()
                    if self.decide(r) == "degrade" and r.has_capacity()]
        if degraded:
            return min(degraded, key=lambda r: (r.active, r.name))
        return None
