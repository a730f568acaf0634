"""The persistent compile cache can be placed from outside, and otherwise
sits at one fixed path inside the checkout (horovod_tpu/compile_cache.py)."""

import os
import subprocess
import sys

import jax

from horovod_tpu import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_set_means_the_code_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    assert compile_cache.ensure_compile_cache() == str(tmp_path)
    assert updates == []


def test_unset_means_one_fixed_path_in_the_checkout(tmp_path):
    """Two processes, started from different directories, name the same
    directory — no temp name, pid or timestamp in it."""
    code = ("import jax; "
            "from horovod_tpu.compile_cache import ensure_compile_cache; "
            "print(ensure_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env["PYTHONPATH"] = ROOT
    procs = [subprocess.Popen([sys.executable, "-c", code], cwd=cwd, env=env,
                              stdout=subprocess.PIPE, text=True)
             for cwd in (ROOT, str(tmp_path))]
    outs = [p.communicate(timeout=120)[0].split() for p in procs]
    assert all(p.returncode == 0 for p in procs)
    want = os.path.join(ROOT, ".jax_cache")
    assert outs == [[want, want], [want, want]]
    assert compile_cache.DEFAULT_DIR == want


def test_a_directory_chosen_through_jax_config_is_kept(monkeypatch, tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        assert compile_cache.ensure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_init_places_the_cache(hvd_world):
    assert jax.config.jax_compilation_cache_dir
