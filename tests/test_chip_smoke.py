"""chip_smoke.py on the CPU: every phase at a tiny size, with its Pallas
kernel in interpret mode, meets the bound the script states; the repaired
ELL kernel equals its oracle; and the script refuses to run off the TPU.
"""
import functools
import importlib.util
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels.ell_spmm import ell_spmm_pallas
from repro.kernels.ref import ell_spmm_ref

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret_kernels(monkeypatch):
    """Route the S-DOT path's kernel calls through the Pallas kernels in
    interpret mode (off TPU the wrappers would pick the jnp oracles)."""
    for name in ("batched_gram_apply", "ell_spmm"):
        fn = getattr(kops, name)
        monkeypatch.setattr(kops, name, functools.partial(
            fn, use_pallas=True, interpret=True))


TINY = {
    "cov": dict(d=32, n_nodes=10, p=0.5, r=3, samples=2000, t_c=50,
                t_o=40),
    "data": dict(d=96, n_nodes=4, p=0.6, r=3, samples=1001, t_c=30,
                 t_o=60),
    "sparse": dict(n_nodes=300, k=6, p=0.1, d=8, r=2, samples_per_node=32,
                   t_c=50, t_o=20),
}


@pytest.mark.parametrize("phase", sorted(TINY))
def test_phase_meets_its_bound(chip_smoke, interpret_kernels, phase):
    res = getattr(chip_smoke, f"phase_{phase}")(0, **TINY[phase])
    assert res["phase"] == phase
    assert res["err"] <= chip_smoke.ERR_MAX[phase], res
    assert res["compile_s"] > 0 and res["run_s"] > 0
    if phase == "sparse":
        assert set(res["ell_vs_oracle"]) == {"float32", "bfloat16"}
        assert max(res["ell_vs_oracle"].values()) <= chip_smoke.ELL_ATOL


def test_four_chip_phase_on_virtual_devices():
    """The node == chip phase on 4 virtual CPU devices (a subprocess:
    the device count is fixed when the backend starts)."""
    code = textwrap.dedent(f"""
        import importlib.util, json
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        res = cs.phase_four_chip(0, d=48, r=3, samples=800, t_o=60)
        print(json.dumps({{"res": res, "bad": cs.check(res)}}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    import json
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["bad"] == [], doc
    assert doc["res"]["n_shard_devices"] == 4


@pytest.mark.parametrize("payload", [None, "bfloat16"])
def test_ell_kernel_matches_oracle_off_block_multiple(payload):
    """Interpret-mode ELL kernel vs ``ell_spmm_ref`` at a row count that is
    not a block multiple; padded rows come back exactly zero."""
    rng = np.random.default_rng(3)
    n, width, k, block = 203, 7, 24, 64
    idx = rng.integers(0, n, (n, width)).astype(np.int32)
    val = rng.standard_normal((n, width)).astype(np.float32)
    diag = rng.standard_normal(n).astype(np.float32)
    z = jnp.asarray(rng.standard_normal((n, k)).astype(np.float32))
    z_src = z if payload is None else z.astype(payload)
    pad = lambda a: kops._pad_to(jnp.asarray(a), 0, block)
    out = ell_spmm_pallas(pad(idx), pad(val), pad(diag), pad(z), z_src,
                          block_rows=block, interpret=True)
    want = ell_spmm_ref(idx, val, diag, z, z_src)
    np.testing.assert_allclose(np.asarray(out[:n]), np.asarray(want),
                               atol=2e-5)
    assert not np.any(np.asarray(out[n:]))
    got = kops.ell_spmm(jnp.asarray(idx), jnp.asarray(val),
                        jnp.asarray(diag), z, payload_dtype=payload,
                        block_rows=block, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_main_refuses_the_cpu(chip_smoke, capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err
    assert captured.out == ""


def test_script_fails_alone_in_a_directory(tmp_path):
    """Without the rest of the repo beside it the script cannot import the
    program, exits non-zero, and prints no result."""
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
