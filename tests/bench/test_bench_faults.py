"""A run of a tiny cell on the CPU, past the harness's look for a chip:
sound, it comes out correct; with the timed path broken underneath in each
way an S-DOT cell can be broken, ``correct`` comes out false. And the
control, the reference's own arithmetic in three bf16 passes put in the
program's place, comes out not correct against the same limit."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, harness, spec
from bench_testcells import TINY_TRAFFIC, write_cell


@pytest.fixture
def fresh_jit():
    """Patched program code is only seen once the jitted programs that
    hold the sound code are dropped; drop them again after the test."""
    yield jax.clear_caches
    jax.clear_caches()


def _run(root, name="tiny.mix", seconds=0.3, traced=False):
    cell = spec.load_cell(name, root)
    return harness.run_cell(cell, 2**32 + 11, seconds, traced,
                            jax.devices()[:cell.chips], time.perf_counter())


def _frozen(build):
    """A step that returns its state unchanged."""
    def frozen(operands, **statics):
        body = build(operands, **statics)

        def step(carry_key, x):
            return carry_key, body(carry_key, x)[1]
        return step
    return frozen


def _half_nodes(apply):
    """Half of the nodes left out, the mean taken over the rest."""
    def half(covs, q):
        z = apply(covs, q)
        keep = (jnp.arange(z.shape[0]) < z.shape[0] // 2)[:, None, None]
        return jnp.where(keep, 2.0 * z, 0.0)
    return half


def _half_samples(apply):
    """Half of each node's samples left out, the mean over the rest."""
    def half(x_stack, q, n_true, **kw):
        m = x_stack.shape[2] // 2
        return apply(x_stack[:, :, :m], q, n_true / 2, **kw)
    return half


def _no_gossip(w, table, z, t_c, t_max):
    """The exchange between nodes left out."""
    return z


def _altered(entry):
    """One node's answer altered where it is produced."""
    def altered(**kw):
        res = entry(**kw)
        q = res.q_nodes
        bump = 1e-3 * jnp.ones_like(q[0])
        res.q_nodes = q.at[0].set(jnp.linalg.qr(q[0] + bump)[0])
        return res
    return altered


def test_sound_run_is_correct(tiny_root):
    out = _run(tiny_root)
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == out["window"]["solves"] > 3
    assert out["window"]["compiles"] == 0
    assert set(out["metrics"]) == {"solve_ms", "solve_p90_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    gap = out["checks"]["subspace_gap"]
    assert 0 < gap["value"] < gap["limit"]


def test_traced_run_is_correct_and_reports_the_window(tiny_root):
    out = _run(tiny_root, seconds=1.0, traced=True)
    assert out["correct"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["metrics"] == {}      # the CPU has no device plane


@pytest.mark.parametrize("fault", ["frozen", "half", "no_gossip", "altered"])
def test_broken_cov_path_is_not_correct(tiny_root, monkeypatch, fresh_jit,
                                        fault):
    from repro.core import sdot as sdot_mod

    if fault == "frozen":
        monkeypatch.setattr(sdot_mod, "_sdot_build_body",
                            _frozen(sdot_mod._sdot_build_body))
    elif fault == "half":
        monkeypatch.setattr(sdot_mod, "local_cov_apply",
                            _half_nodes(sdot_mod.local_cov_apply))
    elif fault == "no_gossip":
        monkeypatch.setattr(sdot_mod, "debiased_gossip", _no_gossip)
    else:
        monkeypatch.setattr(sdot_mod, "sdot", _altered(sdot_mod.sdot))
    fresh_jit()
    out = _run(tiny_root)
    assert not out["correct"]
    assert out["failed"] > 0


@pytest.mark.parametrize("fault", ["half", "no_gossip"])
def test_broken_data_path_is_not_correct(tmp_path, monkeypatch, fresh_jit,
                                         fault):
    from repro.core import sdot as sdot_mod
    from repro.kernels import ops as kops

    root = write_cell(tmp_path, traffic={**TINY_TRAFFIC, "operand": "data"})
    assert _run(root)["correct"]
    if fault == "half":
        monkeypatch.setattr(kops, "batched_gram_apply",
                            _half_samples(kops.batched_gram_apply))
    else:
        monkeypatch.setattr(sdot_mod, "debiased_gossip", _no_gossip)
    fresh_jit()
    assert not _run(root)["correct"]


def test_control_fails_where_the_program_passes(tiny_root):
    """The control in the program's place, judged as a run's solves are
    against the tiny cell's limit: the reference in three bf16 passes
    comes out not correct on every seed where the program comes out
    correct."""
    cell = spec.load_cell("tiny.mix", tiny_root)
    limit = cell.check["subspace_gap_max"]
    for seed in (5, 2**33 + 7):
        solver = harness.setup(cell, seed, jax.devices()[:1])
        q0s = np.stack([data.q_init(seed, k, solver.d, solver.r)
                        for k in range(harness.CHECK_SOLVES)])
        prog = np.stack([np.asarray(solver.solve(q)) for q in q0s])
        ctl = harness.control_solves(solver, q0s)
        make, host = harness.operand_apply(solver.operand, host=True)
        q_ref = harness.reference_solves(solver, q0s, make, host)
        sound = harness.judge(prog, q_ref, limit)
        control = harness.judge(ctl, q_ref, limit)
        assert sound["correct"] and sound["failed"] == 0, sound["gap"]
        assert not control["correct"], control["gap"]
        assert control["failed"] == control["checked"] == len(q0s)
