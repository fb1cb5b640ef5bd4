"""The benchmark's copies of the program's generators and its reference
arithmetic agree with the originals (``chip_smoke.py`` and what it
imports) at one seed; and the command refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import data, reference, spec
from bench_testcells import ROOT

sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


def test_stream_copy_matches_the_program_at_one_seed():
    from repro.data.pipeline import spectrum_matched_stream

    ours = data.spectrum_matched_stream(48, seed=7)
    theirs = spectrum_matched_stream(48, seed=7)
    for step in (0, 3):
        np.testing.assert_array_equal(np.asarray(ours(step, 40)),
                                      np.asarray(theirs(step, 40)))


def test_graph_schedule_and_split_copies_match_the_program():
    from repro.core.consensus import consensus_schedule
    from repro.core.topology import erdos_renyi, local_degree_weights, ring

    for n, p, seed in ((100, 0.05, 1), (20, 0.25, 1)):
        adj = spec.adjacency({"kind": "erdos_renyi", "p": p, "seed": seed},
                             n)
        g = erdos_renyi(n, p, seed=seed)
        np.testing.assert_array_equal(adj, g.adjacency)
        np.testing.assert_allclose(reference.local_degree_weights(adj),
                                   local_degree_weights(g), rtol=0, atol=0)
    np.testing.assert_array_equal(spec.adjacency({"kind": "ring"}, 4),
                                  ring(4).adjacency)
    for kind, slope in (("lin_half", 0.5), ("lin1", 1), ("lin2", 2),
                        ("lin5", 5)):
        for cap in (None, 50):
            np.testing.assert_array_equal(
                data.schedule({"slope": slope, "offset": 1, "cap": cap}, 50),
                consensus_schedule(kind, 50, cap=cap))
    np.testing.assert_array_equal(
        data.schedule({"slope": 0, "offset": 50}, 50),
        consensus_schedule("const", 50, t_max=50))
    assert data.schedule({"slope": 1, "offset": 1, "cap": 50},
                         50).sum() == 1324
    x = np.arange(13_233)[None]
    assert data.split_sizes(13_233, 20) == [
        b.shape[1] for b in chip_smoke._split(x, 20)]


def test_reference_converges_to_chip_smokes_eigenbasis():
    """Run long enough, the float64 S-DOT reference lands on the top-r
    eigenvectors that chip_smoke's reference computes, for every node."""
    d, r, n = 16, 3, 4
    batch = data.spectrum_matched_stream(d, seed=2)
    sizes = data.split_sizes(4_000, n)
    blocks = [np.asarray(batch(i, m), np.float64)
              for i, m in enumerate(sizes)]
    w = reference.local_degree_weights(spec.adjacency({"kind": "ring"}, n))
    q0s = np.stack([data.q_init(2, k, d, r) for k in range(2)])
    ops = reference.Float64
    q = reference.iterate(ops, reference.data_apply(ops, blocks), w, q0s,
                          [60] * 400)
    q_smoke = chip_smoke._ref_basis(blocks, r)
    assert reference.subspace_gap(q, np.broadcast_to(q_smoke, q.shape)) \
        < 1e-9
    assert chip_smoke._mean_err(q_smoke, q[0]) < 1e-15


def test_data_seed_keeps_bits_above_32():
    assert data.data_seed(7) != data.data_seed(7 + 2**32)
    assert 0 <= data.data_seed(2**40 + 3) < 2**32


@pytest.mark.parametrize("workload", ["imagenet_n100.sdot_const50",
                                      "lfw_ring4.sdot_spmd"])
def test_command_without_a_tpu_exits_nonzero_with_no_result(workload):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_control_products_are_three_bf16_passes():
    """Each control product keeps exactly the three bf16 partial products
    (``hi hi + hi lo + lo hi``): well below one pass's error, above f32's."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    a, b = (rng.standard_normal((64, 64)).astype(np.float32)
            for _ in range(2))
    ops = reference.Bf16x3
    (ah, al), (bh, bl) = ops.prep(a), ops.prep(b)
    for part in (ah, al, bh, bl):
        part = np.asarray(part)
        np.testing.assert_array_equal(
            part, np.asarray(jnp.asarray(part).astype(jnp.bfloat16),
                             np.float32))
    got = np.asarray(ops.dot((ah, al), b), np.float64)
    f64 = lambda x: np.asarray(x, np.float64)  # noqa: E731
    three = f64(ah) @ f64(bh) + f64(ah) @ f64(bl) + f64(al) @ f64(bh)
    exact = f64(a) @ f64(b)
    scale = np.abs(exact).max()
    assert np.abs(got - three).max() < 1e-5 * scale
    err = np.abs(got - exact).max() / scale
    one_pass = np.abs(f64(ah) @ f64(bh) - exact).max() / scale
    assert 1e-7 < err < one_pass / 30
