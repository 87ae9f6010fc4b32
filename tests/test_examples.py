"""Examples ARE the integration tests (SURVEY.md §5): run a
representative subset end to end at their default, convergence-asserting
settings as part of the pytest suite (slow-marked — skipped by
``-m 'not slow'`` runs).  Each example exits nonzero if its convergence
assertion fails, so subprocess rc is the whole check.
"""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=600):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # examples size their own device counts
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=os.path.join(_REPO, "examples"))
    assert out.returncode == 0, (
        f"{script} failed:\n{out.stdout[-1500:]}\n{out.stderr[-1500:]}")
    return out


@pytest.mark.slow
def test_mnist_allreduce_example():
    # BASELINE config 1-adjacent: the "add 4 lines" data-parallel recipe,
    # default steps, asserts >= 90% accuracy internally.
    _run("mnist_allreduce.py", "--devices", "8")


@pytest.mark.slow
def test_moe_lm_top2_example():
    # Beyond-reference EP path with GShard top-2 combine; asserts the
    # learnable next-token task converges.
    _run("moe_lm.py", "--devices", "8", "--top-k", "2")


@pytest.mark.slow
def test_parallel_serving_example():
    # Dense == TP == PP greedy tokens over the same checkpoint tree.
    _run("parallel_serving.py", "--devices", "8")


@pytest.mark.slow
def test_continuous_serving_example():
    # Continuous-batching server over 2 device-pinned replicas; every
    # request token-exact vs the offline generate path.
    _run("continuous_serving.py", "--devices", "8")


@pytest.mark.slow
def test_lm_generate_example():
    # Serving path: train, then KV-cache decode; asserts the generated
    # continuations follow the learned next-token rule.
    _run("lm_generate.py", "--devices", "1")


@pytest.mark.slow
def test_moe_generate_example():
    # EP serving path: train expert-parallel, decode expert-parallel on
    # the same mesh (generate_parallel); asserts rule-following output.
    _run("moe_generate.py", "--devices", "8", "--dcn", "2")


@pytest.mark.slow
def test_swa_gqa_lm_example():
    # Modern-LM stack: rope + sliding-window + GQA trains and decodes
    # through the kv-heads-only cache; asserts rule-following output.
    _run("swa_gqa_lm.py", "--devices", "1")


@pytest.mark.slow
def test_cifar_zero3_example():
    # ZeRO-3: params live as flat 1/n shards through real training, then
    # unshard for eval; asserts >= 85% accuracy internally.
    _run("cifar_resnet20.py", "--devices", "8", "--zero", "3")


@pytest.mark.slow
def test_mnist_fsdp_example():
    # Annotation-driven FSDP: per-parameter GSPMD shardings, prefetch
    # pipeline placement; asserts convergence AND 1/n persistent layout.
    _run("mnist_fsdp.py", "--devices", "8")


@pytest.mark.slow
@pytest.mark.parametrize("schedule", ["gpipe", "interleaved"])
def test_megatron_pipeline_example(schedule):
    # 2D model parallelism: TP blocks inside pipeline stages (both
    # schedules — their param-indexing paths differ); asserts a 5x loss
    # drop through both axes' collectives at once.
    _run("megatron_pipeline.py", "--devices", "8", "--schedule", schedule)
