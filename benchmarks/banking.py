"""Bank assertable ``*-SUMMARY`` benchmark lines with staleness stamps.

The compare modes of ``collectives_bench.py`` (``--guard-compare``,
``--dcn-compare``, ``--obs-compare``, ``--faults-compare``,
``--watchdog-compare``, ``--overlap-compare``)
and the recovery bench end in one machine-readable
``KIND-SUMMARY {json}`` line that CI greps and asserts — and then the
evidence evaporates with the log.  This module
is the persistence half: ``--bank`` appends each summary to
``SUMMARY_BANK.json`` at the repo root, so a later session (or a reviewer)
can diff today's verdicts against the banked history without re-running
anything.

Staleness discipline: every
record carries its wall-clock stamp, the git commit it measured (when
resolvable), the jax platform (``cpu`` sim vs real ``tpu`` — a sim
number must never be relabeled silicon), and the argv that produced
it.  Consumers compare stamps/commits and treat a mismatch as stale;
nothing here ever overwrites an older record — history is the point.
The bank keeps the newest :data:`KEEP_PER_KIND` records per summary
kind so the file stays reviewable.

Standalone on purpose (stdlib only; jax/git probed best-effort): a
summary must be bankable from any bench entry point without dragging
the bench's stack along.
"""

import json
import os
import subprocess
import sys
import time

KEEP_PER_KIND = 20

round_ = round  # bank_summary's ``round=`` kwarg shadows the builtin

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_PATH = os.path.join(_REPO, "SUMMARY_BANK.json")


def _git_commit():
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_REPO,
            capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        return None


def _platform():
    """``cpu`` / ``tpu`` / ... when jax is already up, else None —
    probed, never imported fresh (banking must not initialize a
    backend as a side effect)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return jax.devices()[0].platform
    except Exception:  # noqa: BLE001 — absence of evidence, recorded as such
        return None


def load_bank(path=None):
    path = path or DEFAULT_PATH
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        bank = json.load(f)
    if not isinstance(bank, dict):
        raise ValueError(f"{path}: bank must be a JSON object "
                         f"(kind -> records)")
    return bank


def bank_summary(kind, summary, *, path=None, argv=None, round=None):
    """Append one ``kind`` (e.g. ``"GUARD-SUMMARY"``) record to the
    bank, newest first, atomically.  Returns the stamped record.

    ``round`` stamps the bench round the record belongs to
    (``collectives_bench --round N`` sets it); when
    omitted it falls back to ``TORCHMPI_TPU_BENCH_ROUND`` so every
    banking call inside one round agrees without threading the number
    through each CLI.  Consumers (``latest`` callers, CI) read it to
    tell this round's verdict from a stale one."""
    if not isinstance(summary, dict):
        raise TypeError(f"summary must be a dict, got {type(summary)}")
    path = path or DEFAULT_PATH
    if round is None:
        env_round = os.environ.get("TORCHMPI_TPU_BENCH_ROUND")
        round = int(env_round) if env_round else None
    rec = {"stamp": time.strftime("%Y%m%d_%H%M%S"),
           "time": round_(time.time(), 3),
           "commit": _git_commit(),
           "platform": _platform(),
           "argv": list(sys.argv[1:] if argv is None else argv),
           "summary": summary}
    if round is not None:
        rec["round"] = int(round)
    bank = load_bank(path)
    rows = bank.setdefault(kind, [])
    rows.insert(0, rec)
    del rows[KEEP_PER_KIND:]
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(bank, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return rec


def latest(kind, *, path=None, platform=None):
    """Newest banked record for ``kind`` (optionally filtered to one
    platform — pass ``"tpu"`` to refuse sim numbers), or None."""
    for rec in load_bank(path).get(kind, []):
        if platform is None or rec.get("platform") == platform:
            return rec
    return None
