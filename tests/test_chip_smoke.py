"""chip_smoke.py's contract, rehearsed on the CPU.

The script is the proof that the main paths start on a TPU; these tests
hold it to what the driver reads: the rehearsal passes at tiny widths, the
default mode fails where there is no TPU and prints no ``"ok": true``, the
four-chip mode runs no one-chip phase, the last line has the contract's
keys and no other, and the argument parser runs before jax is imported.
Every child keeps ``JAX_PLATFORMS=cpu``; one case per phase keeps each
under the tier-1 time budget.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_REPO, "chip_smoke.py")

sys.path.insert(0, _REPO)
import chip_smoke  # noqa: E402 — imports no jax at module level


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """One compile cache for the module's children, outside the checkout."""
    return str(tmp_path_factory.mktemp("jax_cache"))


def _run(args, cache_dir, cwd=_REPO, script=_SCRIPT, xla_flags=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    if xla_flags:
        env["XLA_FLAGS"] = xla_flags
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    return subprocess.run([sys.executable, script, *args],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=cwd)


def _check_contract(out, phases, count):
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    *phase_lines, last = lines
    assert [ln["phase"] for ln in phase_lines] == list(phases)
    for ln in phase_lines:
        assert ln["seconds"] > 0 and ln["checked"]
    # exactly the contract's keys; the platform is reported truthfully
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == count


@pytest.mark.parametrize("phases", ["init,resnet50_dp", "lm_flash_xent",
                                    "serving", "downpour"])
def test_rehearsal_one_chip(cache_dir, phases):
    assert set(phases.split(",")) <= set(chip_smoke.DEFAULT_PHASES)
    out = _run(["--rehearse", "--phases", phases], cache_dir)
    _check_contract(out, phases.split(","), count=1)


@pytest.mark.parametrize("phase", chip_smoke.FOUR_CHIP_PHASES)
def test_rehearsal_four_chips(cache_dir, phase):
    out = _run(["--rehearse", "--chips", "4", "--phases", phase], cache_dir)
    _check_contract(out, [phase], count=4)


def test_default_phases_cover_both_modes_and_do_not_overlap():
    assert set(chip_smoke.DEFAULT_PHASES) | set(
        chip_smoke.FOUR_CHIP_PHASES) == set(chip_smoke.PHASES)
    assert not set(chip_smoke.DEFAULT_PHASES) & set(
        chip_smoke.FOUR_CHIP_PHASES)


def test_four_chip_mode_refuses_one_chip_phases(cache_dir):
    out = _run(["--rehearse", "--chips", "4", "--phases", "init"],
               cache_dir)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and '"phase"' not in out.stdout


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_fails_without_a_tpu(cache_dir, args):
    out = _run(args, cache_dir)
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout
    assert "no TPU" in out.stderr


def test_rehearsal_needs_exactly_the_chips_asked_for(cache_dir):
    # A parent's forced device count must not leak into the rehearsal.
    out = _run(["--rehearse", "--phases", "init"], cache_dir,
               xla_flags="--xla_force_host_platform_device_count=8")
    _check_contract(out, ["init"], count=1)


def test_fails_alone_in_a_directory(cache_dir, tmp_path):
    # The driver also runs the script without the program beside it.
    alone = shutil.copy(_SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(["--rehearse", "--phases", "init"], cache_dir,
               cwd=str(tmp_path), script=str(alone))
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout


def test_no_jax_before_the_arguments_are_parsed():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "a = chip_smoke.parse_args(['--chips', '4', '--seed', '3']); "
            "assert (a.chips, a.seed, a.rehearse) == (4, 3, False); "
            "assert 'jax' not in sys.modules, 'jax imported too early'"
            % _REPO)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]
