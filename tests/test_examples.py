"""Example smoke tests: every example must run end-to-end on the CPU mesh
with tiny settings (the reference treats examples as product surface —
/root/reference/examples — and its CI exercises them in Docker; here each
runs as a subprocess with the standard virtual-device env)."""

import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")
REPO = os.path.dirname(EXAMPLES)


def _run_example(script, *args, timeout=420, devices=8):
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
    })
    p = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script), *args],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert p.returncode == 0, f"{script} failed:\n{p.stdout[-3000:]}\n" \
                              f"{p.stderr[-3000:]}"
    return p.stdout


@pytest.mark.integration
@pytest.mark.parametrize("script,args", [
    ("jax_mnist.py", ("--epochs", "1")),
    ("jax_synthetic_benchmark.py",
     ("--model", "resnet18", "--batch-size", "4", "--num-warmup-batches",
      "1", "--num-batches-per-iter", "1", "--num-iters", "1")),
    ("jax_moe_train.py", ("--steps", "6")),
    ("jax_pipeline_train.py", ("--steps", "10")),
    ("jax_ulysses_long_context.py", ("--seq-len", "256", "--iters", "1")),
    ("jax_checkpoint_resume.py", ()),
    ("jax_serving.py", ("--requests", "8")),
    ("jax_fleet.py", ("--requests", "12")),
    ("jax_generation.py", ("--max-tokens", "8")),
    ("spark_estimator_train.py", ("--epochs", "2", "--torch-streaming")),
    ("tf2_keras_mnist.py", ("--epochs", "1")),
    ("torch_mnist.py", ("--epochs", "1")),
    ("adasum_small_model.py", ()),
    ("torch_synthetic_benchmark.py", ("--num-iters", "2")),
    ("tensorflow2_mnist.py", ("--steps", "30")),
    ("tensorflow1_mnist.py", ("--steps", "60")),
    ("elastic/torch_mnist_elastic.py", ("--epochs", "1")),
])
def test_example_runs(script, args):
    _run_example(script, *args)


@pytest.mark.integration
def test_transformer_train_example():
    out = _run_example("jax_transformer_train.py", "--steps", "4",
                       "--d-model", "32", "--layers", "1")
    assert "loss" in out.lower()
