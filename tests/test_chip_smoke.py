"""``chip_smoke.py`` on the CPU: its phases at tiny sizes (Pallas kernels in
interpret mode), its four-chip chain on four host devices, and its refusal
to run without a TPU or outside the repository."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kvs_phase_tiny(smoke):
    res = smoke.run_kvs(dict(
        num_buckets=64, ways=4, key_words=2, val_words=8, pool_size=512,
        cache_sets=8, cache_ways=2, load=192, load_batch=64, queues=2,
        per_queue=8, rounds=2))
    assert res["checked"] > 0


def test_tx_phase_tiny(smoke):
    res = smoke.run_tx(dict(num_keys=512, val_words=4, max_ops=3,
                            chain_len=3, log_capacity=64, queues=2,
                            per_queue=4, rounds=2))
    assert res["checked"] == 2 * 2 * 3  # the second entry per queue defers


def test_dlrm_phase_tiny(smoke):
    res = smoke.run_dlrm(dict(num_tables=3, rows=64, dim=8, lookups=4,
                              queues=2, per_queue=4, rounds=2))
    assert res["checked"] == 16


def test_lm_phase_tiny(smoke):
    res = smoke.run_lm(dict(arch="qwen1.5-0.5b", reduced=True, requests=4,
                            prompt_len=8, gen_len=5, page_size=4,
                            admit_per_step=2))
    assert res["checked"] == 5 + 3 + 1 + 5


def _run(args, cwd, **env):
    return subprocess.run([sys.executable, *args], cwd=cwd,
                          capture_output=True, text=True, timeout=600,
                          env={**os.environ, **env})


def test_chain_spmd_phase_on_four_host_devices():
    code = (
        "import importlib.util, jax\n"
        "spec = importlib.util.spec_from_file_location('s', 'chip_smoke.py')\n"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "m.run_chain_spmd(jax.devices()[:4], dict(num_keys=256, val_words=4,"
        " max_ops=3, log_capacity=32, batch=16, rounds=3))\n"
    )
    out = _run(["-c", code], ROOT, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert out.returncode == 0, out.stderr[-3000:]
    assert "bit_equal_to_local_chain=True" in out.stdout


def test_refuses_without_tpu():
    out = _run(["chip_smoke.py"], ROOT, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "[kvs]" not in out.stdout


def test_fails_outside_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _run(["chip_smoke.py"], tmp_path, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.fixture
def cache_dir_config():
    import jax
    was = jax.config.jax_compilation_cache_dir
    yield jax
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_left_where_the_environment_puts_it(
        cache_dir_config, monkeypatch, tmp_path):
    from repro import runtime
    jax = cache_dir_config
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing overridden


def test_compile_cache_defaults_to_the_repository(cache_dir_config,
                                                  monkeypatch):
    from repro import runtime
    jax = cache_dir_config
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
