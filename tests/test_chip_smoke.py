"""chip_smoke.py off the chip.

The script itself must refuse to run without a TPU. Its phase functions
take their sizes as arguments, so the same code that runs full width on
the chip runs here tiny, on the 8-device CPU mesh, with the flash kernel's
``interpret=True`` passed explicitly. (The pipeline and expert phases are
``__graft_entry__``'s, already tiny; ``test_models.py::
test_graft_entry_dryrun`` runs them on this mesh.)
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from horovod_tpu.models import TransformerConfig  # noqa: E402

TINY = TransformerConfig(vocab_size=128, num_layers=2, d_model=32,
                         num_heads=2, head_dim=16, max_seq_len=64,
                         dtype=jnp.float32)


def test_refuses_to_run_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout == ""           # no result line of any kind
    assert "no TPU" in r.stderr and "cpu" in r.stderr


def test_kernel_phase_tiny_interpreted():
    out = chip_smoke.kernel_phase(train_shape=(1, 32, 2, 16),
                                  long_shape=(1, 64, 2, 16),
                                  paged_shape=(5, 2, 2, 16, 8, 3, 16),
                                  dtype=jnp.float32, interpret=True)
    assert out["interpret"] and max(out["rel_err"].values()) < 1e-4
    assert "paged" in out["rel_err"]


def test_trainer_phase_tiny(hvd_world):
    out = chip_smoke.trainer_phase(model_name="resnet18", image_size=32,
                                   batch_per_chip=2, steps=3)
    assert out["loss_last"] < out["loss_first"]
    assert out["param_and_batch_devices"] == 8


def test_server_phase_tiny(hvd_world):
    requests = ((5, 4, False), (20, 3, True), (9, 5, False), (3, 4, True),
                (18, 3, False))
    out = chip_smoke.server_phase(
        cfg=TINY, requests=requests, block_size=8, num_blocks=65,
        max_seqs=4, prefill_chunk=16)
    assert out["requests"] == 5 and out["new_tokens"] == 19
    assert out["greedy_tokens_checked"] == 12
    assert out["worst_logit_gap"] < 1e-4 and out["worst_logprob_gap"] < 1e-4
    assert len(out["params_devices"]) == len(out["kv_pool_devices"]) == 1
    assert out["decode_compiled_kernel"] is False     # off the TPU: gather


def test_ring_train_phase_tiny_interpreted(hvd_world):
    out = chip_smoke.ring_train_phase(jax.devices(), cfg=TINY,
                                      interpret=True)
    assert out["mesh"] == {"dp": 4, "sp": 2}
    assert out["interpret"] and not out["compiled_kernel"]
    assert abs(out["loss_ring"] - out["loss_default_attention"]) < 1e-4
