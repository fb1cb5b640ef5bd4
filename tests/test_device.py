"""One process per chip: the compile cache's directory, the refusal to run
on the CPU where a TPU was expected, and a sweep launcher that never opens
a JAX backend of its own."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import device
from repro.streaming import launcher


@pytest.fixture
def cache_dir_config():
    """Restore the cache directory, and drop the cache object JAX built
    from it, so later compiles in this worker write nowhere new."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    got = device.init_compile_cache()
    assert got == str(device.REPO_CACHE_DIR)
    assert device.REPO_CACHE_DIR.name == ".jax_cache"
    assert (device.REPO_CACHE_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert device.init_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


@pytest.mark.parametrize("platforms,chips,expected", [
    ("", 1, True), ("tpu", 4, True), ("cpu", 1, False), ("", 0, False)])
def test_tpu_expected(monkeypatch, platforms, chips, expected):
    monkeypatch.setattr(device, "tpu_chips_on_host", lambda: chips)
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    assert device.tpu_expected() is expected


def test_cpu_backend_on_a_tpu_host_is_refused(monkeypatch):
    monkeypatch.setattr(device, "tpu_chips_on_host", lambda: 1)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="refusing to run on the CPU"):
        device.require_expected_platform()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert device.require_expected_platform() == "cpu"


def test_launcher_refuses_two_workers_on_a_tpu_host(monkeypatch, tmp_path):
    monkeypatch.setattr(launcher, "tpu_expected", lambda: True)
    monkeypatch.setattr(launcher, "tpu_chips_on_host", lambda: 1)
    with pytest.raises(ValueError, match="n_workers=2"):
        launcher.launch_sweep(covs=None, cases=[], r=2, t_outer=2,
                              seeds=[0, 1], workdir=str(tmp_path),
                              n_workers=2)
    assert not any(tmp_path.iterdir())       # refused before any file


def test_worker_without_its_chip_stops_the_launch(tmp_path):
    """A worker's EXIT_NO_DEVICE ends supervision at once (a retry cannot
    help while another process holds the chip); other exits are retried."""
    log = tmp_path / "worker.log"
    log.write_text("worker: this host has 1 TPU chip(s) but JAX came up on "
                   "'cpu'\n")
    with pytest.raises(RuntimeError, match="found no TPU"):
        launcher._no_device(device.EXIT_NO_DEVICE, str(tmp_path),
                            "worker_s0", str(log))
    launcher._no_device(1, str(tmp_path), "worker_s0", str(log))


def test_launcher_parent_opens_no_backend(tmp_path):
    """A 2-worker sweep: the parent writes NumPy problem files, supervises,
    and merges the published shards without initializing any backend."""
    code = textwrap.dedent(f"""
        import json
        import numpy as np
        from jax._src import xla_bridge
        from repro.streaming.launcher import launch_sweep
        x = np.random.default_rng(0).standard_normal((6, 8, 40))
        covs = (np.einsum("ndm,nem->nde", x, x) / 40).astype(np.float32)
        res = launch_sweep(covs=covs, r=2, t_outer=4, t_c=5, seeds=[0, 1],
                           cases=[{{"topology": {{"kind": "ring", "n": 6}}}}],
                           workdir={str(tmp_path)!r}, n_workers=2)
        print(json.dumps({{"backends": sorted(xla_bridge._backends),
                          "q": list(res.q.shape)}}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["backends"] == []
    assert doc["q"] == [2, 6, 8, 2]                 # (seeds, N, d, r)
