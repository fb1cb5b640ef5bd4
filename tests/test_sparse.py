"""SparseW / ELL-SpMM gossip: representation, kernels, engine seams.

The contract under test: a sparse engine is a drop-in replacement for the
dense einsum engine over the SAME graph and weights — every algorithm in
the zoo (fused and eager), the netfault layer, and chunked resume must
agree with the dense path to f32 tolerance (and the realized fault MASKS
must match exactly, since the sparse round gathers the same pre-sampled
draws at its ELL slots).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology as topo
from repro.core.consensus import DenseConsensus, SparseConsensus, gossip_mix
from repro.core.metrics import CommLedger
from repro.core.sparse import (AUTO_MAX_DENSITY, AUTO_MIN_NODES, SparseW,
                               auto_sparse)
from repro.kernels.ops import ell_spmm, ell_spmm_path
from repro.kernels.ref import ell_spmm_ref, ell_spmm_scan_ref


def _graph(n=24, seed=3):
    return topo.watts_strogatz(n, k=4, p=0.2, seed=seed)


def _principal_angle_f64(q1, q2):
    """Max principal angle between the spans, computed in float64 after
    re-orthonormalization (f32 arccos quantizes angles below ~3e-4)."""
    a = np.linalg.qr(np.asarray(q1, np.float64))[0]
    b = np.linalg.qr(np.asarray(q2, np.float64))[0]
    s = np.linalg.svd(a.T @ b, compute_uv=False)
    return float(np.arccos(np.clip(s, -1.0, 1.0)).max())


# ---------------------------------------------------------------------------
# representation
# ---------------------------------------------------------------------------
def test_from_dense_roundtrip_and_csr():
    g = _graph()
    w = topo.local_degree_weights(g)
    sw = SparseW.from_dense(w, g.adjacency)
    np.testing.assert_allclose(np.asarray(sw.to_dense()), w, atol=1e-7)
    indptr, indices, data = sw.csr()
    assert indptr[-1] == indices.size == data.size
    dense = np.zeros_like(w)
    for i in range(g.n_nodes):
        dense[i, indices[indptr[i]:indptr[i + 1]]] = \
            data[indptr[i]:indptr[i + 1]]
    np.fill_diagonal(dense, np.asarray(sw.diag))
    np.testing.assert_allclose(dense, w, atol=1e-7)
    stats = sw.row_stats()
    assert stats["nnz"] == sw.nnz
    assert stats["row_nnz_max"] == sw.ell_width
    assert 0 < sw.density <= 1


def test_from_dense_rejects_asymmetric():
    w = np.eye(4)
    w[0, 1] = 0.5
    with pytest.raises(ValueError, match="symmetric"):
        SparseW.from_dense(w)


def test_zero_weight_edges_kept_via_adjacency():
    """A real edge whose weight happens to be 0 must stay in the structure
    (fault-model send accounting counts it)."""
    g = topo.ring(6)
    w = topo.local_degree_weights(g).copy()
    w[0, 1] = w[1, 0] = 0.0
    sw = SparseW.from_dense(w, g.adjacency)
    assert sw.nnz == int(g.adjacency.sum()) + 6
    sw2 = SparseW.from_dense(w)          # structure from nonzeros only
    assert sw2.nnz == sw.nnz - 2


def test_mix_matches_dense_and_host():
    g = _graph()
    sw = SparseW.from_graph(g)
    w = np.asarray(sw.to_dense())
    rng = np.random.default_rng(0)
    for shape in [(g.n_nodes,), (g.n_nodes, 7), (g.n_nodes, 3, 2)]:
        z = rng.standard_normal(shape).astype(np.float32)
        want = np.einsum("ij,j...->i...", w, z)
        np.testing.assert_allclose(np.asarray(sw.mix(jnp.asarray(z))), want,
                                   atol=1e-5)
        if z.ndim <= 2:       # mix_host is the matvec/matmat oracle
            np.testing.assert_allclose(sw.mix_host(z), want, atol=1e-5)


def test_stack_and_getitem():
    g1, g2 = topo.ring(10), topo.erdos_renyi(10, 0.4, seed=1)
    s1, s2 = SparseW.from_graph(g1), SparseW.from_graph(g2)
    assert s1.ell_width != s2.ell_width   # forces the widening path
    st = SparseW.stack([s1, s2])
    z = jnp.asarray(np.random.default_rng(1)
                    .standard_normal((10, 4)).astype(np.float32))
    for k, s in enumerate((s1, s2)):
        np.testing.assert_allclose(np.asarray(st[k].mix(z)),
                                   np.asarray(s.mix(z)), atol=1e-6)
    with pytest.raises(ValueError, match="matching"):
        SparseW.stack([s1, SparseW.from_graph(topo.ring(12))])


def test_sparsew_is_pytree():
    sw = SparseW.from_graph(_graph())
    leaves, treedef = jax.tree_util.tree_flatten(sw)
    assert len(leaves) == 4          # ell_idx, ell_val, diag, row_nnz
    sw2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert sw2.n == sw.n and sw2.ell_width == sw.ell_width

    @jax.jit
    def f(w, z):
        return w.mix(z)

    z = jnp.ones((sw.n, 3))
    np.testing.assert_allclose(np.asarray(f(sw, z)), np.asarray(sw.mix(z)),
                               atol=1e-6)


def test_power_iteration_spectral_gap_matches_exact():
    g = _graph(30)
    w = topo.local_degree_weights(g)
    exact = topo.spectral_gap(w, method="exact")
    sw = SparseW.from_dense(w, g.adjacency)
    assert abs(sw.spectral_gap(iters=3000) - exact) < 1e-3
    # the duck-typed seam: spectral_gap(w) accepts the SparseW directly
    assert abs(topo.spectral_gap(sw) - exact) < 1e-3


# ---------------------------------------------------------------------------
# auto-selection policy
# ---------------------------------------------------------------------------
def test_auto_sparse_policy():
    assert auto_sparse(AUTO_MIN_NODES, AUTO_MAX_DENSITY) is True
    assert auto_sparse(AUTO_MIN_NODES - 1, AUTO_MAX_DENSITY) is False
    assert auto_sparse(AUTO_MIN_NODES, AUTO_MAX_DENSITY * 2) is False
    assert auto_sparse(16, 0.9, sparse=True) is True     # explicit wins
    assert auto_sparse(10_000, 0.001, sparse=False) is False


def test_small_dense_engines_stay_dense():
    """The repo's N <= 200 seeded suite must keep the dense einsum."""
    eng = DenseConsensus(topo.erdos_renyi(20, 0.25, seed=0))
    assert not eng.is_sparse
    assert isinstance(eng._w, jnp.ndarray)


# ---------------------------------------------------------------------------
# kernels: pallas (interpret) vs gather vs scan vs dense oracle
# ---------------------------------------------------------------------------
def test_ell_spmm_paths_agree():
    g = _graph(40, seed=9)
    sw = SparseW.from_graph(g)
    w = np.asarray(sw.to_dense())
    z = np.random.default_rng(2).standard_normal((40, 8)).astype(np.float32)
    want = w @ z
    got_gather = ell_spmm(sw.ell_idx, sw.ell_val, sw.diag, jnp.asarray(z),
                          use_pallas=False)
    got_pallas = ell_spmm(sw.ell_idx, sw.ell_val, sw.diag, jnp.asarray(z),
                          use_pallas=True, interpret=True, block_rows=16)
    got_ref = ell_spmm_ref(sw.ell_idx, sw.ell_val, sw.diag, z, z)
    got_scan = ell_spmm_scan_ref(sw.ell_idx, sw.ell_val, sw.diag, z, z)
    for got in (got_gather, got_pallas, got_ref, got_scan):
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_ell_spmm_bf16_quantizes_the_gather_source():
    g = _graph(16)
    sw = SparseW.from_graph(g)
    z = np.random.default_rng(4).standard_normal((16, 5)).astype(np.float32)
    zb = np.asarray(jnp.asarray(z).astype(jnp.bfloat16).astype(jnp.float32))
    # oracle: neighbor messages quantized, own-state diagonal full precision
    want = (np.asarray(sw.diag)[:, None] * z
            + np.einsum("nl,nlk->nk", np.asarray(sw.ell_val),
                        zb[np.asarray(sw.ell_idx)]))
    got = ell_spmm(sw.ell_idx, sw.ell_val, sw.diag, jnp.asarray(z),
                   payload_dtype="bfloat16")
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    assert not np.allclose(np.asarray(got),
                           np.asarray(sw.to_dense()) @ z, atol=1e-6)


def test_ell_spmm_path_policy():
    assert ell_spmm_path(100, 4, 8, use_pallas=True) == "pallas"
    assert ell_spmm_path(100, 4, 8, use_pallas=False) == "fallback_gather"
    # huge gather footprint falls back to the slot scan
    assert ell_spmm_path(1 << 20, 64, 64,
                         use_pallas=False) == "fallback_scan"


@pytest.mark.parametrize("graph", [
    topo.barabasi_albert(40, m=3),       # hub rows pad ELL toward N
    topo.erdos_renyi(40, 0.15),
], ids=["ba_hubs", "er"])
def test_wide_ell_mixes_through_the_gather(graph):
    """However wide the padded ELL gets relative to N, the CPU round is
    the gather fallback, and it is exact against the dense matrix."""
    sw = SparseW.from_graph(graph)
    z = np.random.default_rng(6).standard_normal(
        (graph.n_nodes, 5)).astype(np.float32)
    assert ell_spmm_path(sw.n, sw.ell_width, z.shape[1],
                         use_pallas=False) == "fallback_gather"
    np.testing.assert_allclose(np.asarray(sw.mix(jnp.asarray(z))),
                               np.asarray(sw.to_dense()) @ z, atol=1e-5)


# ---------------------------------------------------------------------------
# engine seams
# ---------------------------------------------------------------------------
def test_gossip_mix_dispatch():
    g = _graph()
    w = jnp.asarray(topo.local_degree_weights(g), jnp.float32)
    sw = SparseW.from_graph(g)
    z = jnp.asarray(np.random.default_rng(5)
                    .standard_normal((g.n_nodes, 3)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(gossip_mix(w, z)),
                               np.asarray(gossip_mix(sw, z)), atol=1e-5)


def test_engine_equivalence_run_and_debiased():
    g = _graph()
    ed = DenseConsensus(g, sparse=False)
    es = SparseConsensus(g)
    assert not ed.is_sparse and es.is_sparse
    z = jnp.asarray(np.random.default_rng(6)
                    .standard_normal((g.n_nodes, 4)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(ed.run(z, 6)),
                               np.asarray(es.run(z, 6)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ed.run_debiased(z, 6)),
                               np.asarray(es.run_debiased(z, 6)), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(ed.debias_table(8)),
                               np.asarray(es.debias_table(8)), atol=1e-6)
    # traceable twin == eager on the sparse engine (same jaxpr per round)
    np.testing.assert_array_equal(
        np.asarray(es.run_debiased_scan(z, jnp.int32(6), t_max=6)),
        np.asarray(es.run_debiased(z, 6)))


def test_bf16_payload_requires_sparse_and_halves_ledger_bytes():
    g = _graph()
    with pytest.raises(ValueError, match="sparse"):
        DenseConsensus(g, sparse=False, payload_dtype="bfloat16")
    z = jnp.asarray(np.random.default_rng(7)
                    .standard_normal((g.n_nodes, 4)).astype(np.float32))
    lf, lb = CommLedger(), CommLedger()
    DenseConsensus(g, sparse=True).run_debiased(z, 4, lf)
    DenseConsensus(g, sparse=True,
                   payload_dtype="bfloat16").run_debiased(z, 4, lb)
    assert lb.payload_bytes == lf.payload_bytes / 2.0
    assert lf.scalars == lb.scalars          # same element count moved


def test_sparse_engine_records_metrics():
    from repro.obs import metrics
    reg = metrics()

    def values():
        return {k: v["value"] for k, v in reg.snapshot().items()
                if k.startswith("gossip_")}

    eng = SparseConsensus(_graph())
    after = values()
    assert after["gossip_sparse_nnz"] == eng._w.nnz
    assert 0 < after["gossip_sparse_density"] <= 1
    assert after["gossip_sparse_ell_width"] == eng._w.ell_width
    # the kernel path depends on the payload width, which the engine does
    # not know: no count claims one (a solve counts it, at its width)
    assert not [k for k in after if k.startswith("gossip_kernel_")]


# ---------------------------------------------------------------------------
# zoo equivalence (fused + eager)
# ---------------------------------------------------------------------------
def _psa_problem(n=20, d=12, r=3, seed=5):
    g = _graph(n, seed=1)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d, 30)).astype(np.float32)
    covs = jnp.asarray(np.einsum("nds,nes->nde", x, x) / 30.0)
    m = np.asarray(covs.mean(0))
    q_true = jnp.asarray(np.linalg.eigh(m)[1][:, ::-1][:, :r].copy())
    return g, covs, q_true, r


@pytest.mark.parametrize("fused", [True, False])
def test_sdot_sparse_vs_dense(fused):
    from repro.core.sdot import sdot
    g, covs, q_true, r = _psa_problem()
    kw = dict(covs=covs, r=r, t_outer=10, t_c=8, q_true=q_true, fused=fused)
    rd = sdot(engine=DenseConsensus(g, sparse=False), **kw)
    rs = sdot(engine=SparseConsensus(g), **kw)
    assert _principal_angle_f64(rd.q_nodes[0], rs.q_nodes[0]) <= 1e-5
    np.testing.assert_allclose(rd.error_trace, rs.error_trace, atol=1e-6)


@pytest.mark.parametrize("name", ["dsa", "dpgd", "deepca", "seq_dist_pm"])
def test_baselines_sparse_vs_dense_fused_and_eager(name):
    from repro.core import baselines as bl
    g, covs, q_true, r = _psa_problem()
    fn = getattr(bl, name)
    kw = (dict(iters_per_vec=4, t_c=8) if name == "seq_dist_pm"
          else dict(t_outer=8))
    for fused in (True, False):
        qd, _ = fn(covs, DenseConsensus(g, sparse=False), r, q_true=q_true,
                   fused=fused, **kw)
        qs, _ = fn(covs, SparseConsensus(g), r, q_true=q_true,
                   fused=fused, **kw)
        np.testing.assert_allclose(np.asarray(qd), np.asarray(qs),
                                   atol=1e-5)


@pytest.mark.parametrize("fused", [True, False])
def test_fdot_sparse_vs_dense(fused):
    from repro.core.fdot import fdot
    rng = np.random.default_rng(8)
    dims = [4, 4, 4, 4, 4]
    r = 3
    blocks = [jnp.asarray(rng.standard_normal((di, 40)).astype(np.float32))
              for di in dims]
    xf = np.concatenate([np.asarray(b) for b in blocks], 0)
    q_true = jnp.asarray(
        np.linalg.eigh(xf @ xf.T / 40)[1][:, ::-1][:, :r].copy())
    g = topo.ring(5)
    kw = dict(data_blocks=blocks, r=r, t_outer=6, t_c=10, q_true=q_true,
              fused=fused)
    rd = fdot(engine=DenseConsensus(g, sparse=False), **kw)
    rs = fdot(engine=SparseConsensus(g), **kw)
    np.testing.assert_allclose(np.asarray(rd.q_full), np.asarray(rs.q_full),
                               atol=1e-5)


def test_bdot_sparse_stacked_engines():
    from repro.core.bdot import bdot
    rng = np.random.default_rng(9)
    r = 3
    dims_i, ns_j = [5, 4, 3], [12, 10, 14]
    grid = [[jnp.asarray(rng.standard_normal((di, nj)).astype(np.float32))
             for nj in ns_j] for di in dims_i]
    xb = np.concatenate(
        [np.concatenate([np.asarray(b) for b in row], 1) for row in grid], 0)
    q_true = jnp.asarray(
        np.linalg.eigh(xb @ xb.T / xb.shape[1])[1][:, ::-1][:, :r].copy())
    gi, gj = topo.ring(3), topo.ring(3)
    kw = dict(blocks=grid, r=r, t_outer=5, t_c=10, q_true=q_true)
    rd = bdot(col_engines=[DenseConsensus(gi, sparse=False)] * 3,
              row_engines=[DenseConsensus(gj, sparse=False)] * 3, **kw)
    rs = bdot(col_engines=[SparseConsensus(gi) for _ in range(3)],
              row_engines=[SparseConsensus(gj) for _ in range(3)], **kw)
    assert _principal_angle_f64(rd.q_full, rs.q_full) <= 1e-5
    # mixed dense/sparse per stage has no batched representation
    with pytest.raises(ValueError, match="mixes sparse and dense"):
        bdot(col_engines=[SparseConsensus(gi), SparseConsensus(gi),
                          DenseConsensus(gi, sparse=False)],
             row_engines=[DenseConsensus(gj, sparse=False)] * 3, **kw)


def test_sweep_rejects_sparse_engines():
    from repro.core.sweep import sdot_sweep
    g, covs, q_true, r = _psa_problem()
    with pytest.raises(ValueError, match="sparse"):
        sdot_sweep(covs=covs, engines=[SparseConsensus(g)],
                   schedules=[np.full(4, 4)], r=r, t_outer=4, t_c=4,
                   seeds=[0], q_true=q_true)


# ---------------------------------------------------------------------------
# netfaults: realized masks match the dense engine exactly
# ---------------------------------------------------------------------------
def _fault_setup():
    from repro.core.netfaults import NetFaultModel
    g = _graph()
    fm = NetFaultModel(p_drop=0.15, p_bad=0.1, p_good=0.5, p_corrupt=0.1,
                       corrupt_mode="nan", crash_windows=((3, 0, 2),))
    return g, fm


def test_faulty_sparse_vs_dense_masks_and_values():
    from repro.core.netfaults import FaultyConsensus
    g, fm = _fault_setup()
    z = np.random.default_rng(0).standard_normal((g.n_nodes, 6, 2)) \
        .astype(np.float32)
    ed = FaultyConsensus(g, fm, seed=7, sparse=False)
    es = FaultyConsensus(g, fm, seed=7, sparse=True)
    node_up = fm.node_up(3, g.n_nodes)
    ld, ls = CommLedger(), CommLedger()
    zd, zs = jnp.asarray(z), jnp.asarray(z)
    for it in range(3):
        zd = ed.run_debiased(zd, 5, ledger=ld, node_up=node_up[it])
        zs = es.run_debiased(zs, 5, ledger=ls, node_up=node_up[it])
    assert ld.p2p == ls.p2p          # identical realized fault masks
    rel = np.max(np.abs(np.asarray(zd) - np.asarray(zs))
                 / (np.abs(np.asarray(zd)) + 1e-3))
    assert rel < 1e-5                # same algebra, reordered reductions


def test_faulty_sparse_eager_matches_fused_bitwise():
    from repro.core.netfaults import FaultyConsensus, realized_debias
    g, fm = _fault_setup()
    z = jnp.asarray(np.random.default_rng(1)
                    .standard_normal((g.n_nodes, 5)).astype(np.float32))
    up = jnp.ones((g.n_nodes,), jnp.float32)
    e1 = FaultyConsensus(g, fm, seed=7, sparse=True)
    e2 = FaultyConsensus(g, fm, seed=7, sparse=True)
    f1, f2 = e1.sample_faults(5), e2.sample_faults(5)
    z_fused = e1.run_debiased(z, 5, faults=f1, node_up=up)
    out = e2.run_rounds_eager(z, up, f2)
    np.testing.assert_array_equal(np.asarray(z_fused),
                                  np.asarray(realized_debias(out[0],
                                                             out[1])))
    # ELL-form Gilbert-Elliott state advanced identically
    np.testing.assert_array_equal(np.asarray(e1._ge), np.asarray(out[2]))


def test_faulty_sparse_engine_guards():
    from repro.core.netfaults import FaultyConsensus
    g, fm = _fault_setup()
    with pytest.raises(ValueError, match="fused"):
        FaultyConsensus(g, fm, sparse=True, fused=False)
    with pytest.raises(ValueError, match="sparse"):
        FaultyConsensus(g, fm, sparse=False, payload_dtype="bfloat16")
    eng = FaultyConsensus(g, fm, sparse=True)
    assert eng._ge.shape == (g.n_nodes, eng._w.ell_width)
    eng.reset()
    assert eng._ge.shape == (g.n_nodes, eng._w.ell_width)


def test_sdot_faulty_sparse_vs_dense():
    """The whole-run fused executor with a sparse faulty engine: the
    (N, L) burst state rides the scan carry transparently."""
    from repro.core.netfaults import FaultyConsensus, NetFaultModel
    from repro.core.sdot import sdot
    g, covs, q_true, r = _psa_problem()
    fm = NetFaultModel(p_drop=0.2, p_bad=0.05, p_good=0.5)
    kw = dict(covs=covs, r=r, t_outer=8, t_c=6, q_true=q_true)
    rd = sdot(engine=FaultyConsensus(g, fm, seed=3, sparse=False), **kw)
    rs = sdot(engine=FaultyConsensus(g, fm, seed=3, sparse=True), **kw)
    np.testing.assert_allclose(rd.error_trace, rs.error_trace, atol=1e-5)
    assert _principal_angle_f64(rd.q_nodes[0], rs.q_nodes[0]) <= 1e-4


# ---------------------------------------------------------------------------
# chunked resume: bit-identical on the sparse engine
# ---------------------------------------------------------------------------
def test_sparse_run_chunked_resume_bit_identical(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    from repro.core.runtime import run_chunked, run_monolithic
    from repro.core.sdot import sdot_program
    g, covs, q_true, r = _psa_problem()

    def program():
        return sdot_program(covs=covs, engine=SparseConsensus(g), r=r,
                            t_outer=9, t_c=6, q_true=q_true)

    mono = run_monolithic(program())
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    run_chunked(program(), mgr, chunk_size=3, max_chunks=2)   # "killed"
    resumed = run_chunked(program(), mgr, chunk_size=3)       # restart
    np.testing.assert_array_equal(np.asarray(mono.q_nodes),
                                  np.asarray(resumed.q_nodes))
    np.testing.assert_array_equal(mono.error_trace, resumed.error_trace)
