"""SPMD tests (shard_map / pjit) — run in subprocesses so the placeholder
device count never leaks into the other tests' jax backend."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_spmd(script: str, n_devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_spmd_consensus_matches_dense_ring():
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.topology import ring
        from repro.core.consensus import DenseConsensus, SpmdConsensus
        n = 8
        mesh = Mesh(np.array(jax.devices()), ("nodes",))
        g = ring(n)
        dense = DenseConsensus(g)
        spmd = SpmdConsensus(mesh, "nodes", graph=g)
        z0 = jnp.asarray(np.random.default_rng(0).standard_normal((n, 6, 3)),
                         jnp.float32)
        for t_c in (1, 5, 20):
            want = dense.run_debiased(z0, t_c)
            got = spmd.build_debiased_sum(t_c)(z0)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)
        print("ring OK")
    """)


def test_spmd_consensus_matches_dense_general_graph():
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.topology import erdos_renyi
        from repro.core.consensus import DenseConsensus, SpmdConsensus
        n = 8
        mesh = Mesh(np.array(jax.devices()), ("nodes",))
        g = erdos_renyi(n, 0.5, seed=3)
        dense = DenseConsensus(g)
        spmd = SpmdConsensus(mesh, "nodes", graph=g)
        z0 = jnp.asarray(np.random.default_rng(1).standard_normal((n, 5, 2)),
                         jnp.float32)
        want = dense.run_debiased(z0, 12)
        got = spmd.build_debiased_sum(12)(z0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("general OK")
    """)


def test_spmd_fused_sdot_matches_dense_fused():
    """Whole-run SPMD S-DOT (one shard_map program: masked collective gossip
    + device debias table inside the outer scan) == the fused DenseConsensus
    executor, on a ring and a general graph, with a varying SA-DOT budget."""
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.topology import erdos_renyi, ring
        from repro.core.consensus import (DenseConsensus, SpmdConsensus,
                                          consensus_schedule)
        from repro.core.sdot import sdot, sdot_spmd
        from repro.core.linalg import eigh_topr
        from repro.data.pipeline import (gaussian_eigengap_data,
                                         partition_samples)
        n, d, r = 8, 16, 3
        x, _, _ = gaussian_eigengap_data(d, n * 400, r, 0.7, seed=0)
        covs = jnp.stack([b @ b.T / b.shape[1]
                          for b in partition_samples(x, n)])
        _, q_true = eigh_topr(covs.sum(0), r)
        mesh = Mesh(np.array(jax.devices()), ("nodes",))
        sched = consensus_schedule("lin2", 12, cap=30)
        for g in (ring(n), erdos_renyi(n, 0.5, seed=3)):
            want = sdot(covs=covs, engine=DenseConsensus(g), r=r, t_outer=12,
                        schedule=sched, q_true=q_true)
            got = sdot_spmd(covs=covs, engine=SpmdConsensus(mesh, "nodes",
                                                            graph=g),
                            r=r, t_outer=12, schedule=sched, q_true=q_true)
            np.testing.assert_allclose(got.error_trace, want.error_trace,
                                       rtol=1e-4, atol=1e-6)
            np.testing.assert_allclose(np.asarray(got.q_nodes),
                                       np.asarray(want.q_nodes), rtol=1e-4,
                                       atol=1e-5)
            assert got.ledger.p2p == want.ledger.p2p
            assert got.ledger.scalars == want.ledger.scalars
        print("spmd fused OK")
    """)


SPMD_REUSE_SETUP = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro import obs
    from repro.core.topology import erdos_renyi, ring
    from repro.core.consensus import DenseConsensus, SpmdConsensus
    from repro.core.sdot import sdot, sdot_spmd
    from repro.core.linalg import eigh_topr
    from repro.data.pipeline import gaussian_eigengap_data, partition_samples
    n, d, r = 8, 16, 3
    x, _, _ = gaussian_eigengap_data(d, n * 400, r, 0.7, seed=0)
    covs = jnp.stack([b @ b.T / b.shape[1] for b in partition_samples(x, n)])
    _, q_true = eigh_topr(covs.sum(0), r)
    mesh = Mesh(np.array(jax.devices()), ("nodes",))

    def misses():            # sdot_spmd.call's jit_miss total so far
        snap = obs.metrics().snapshot()
        return snap.get("sdot_spmd_call_jit_miss_total", {"value": 0})["value"]

    def check(got, g, **kw):  # == the fused DenseConsensus run
        want = sdot(covs=covs, engine=DenseConsensus(g), r=r, **kw)
        np.testing.assert_allclose(np.asarray(got.q_nodes),
                                   np.asarray(want.q_nodes), rtol=1e-4,
                                   atol=1e-5)
        if kw.get("q_true") is not None:
            np.testing.assert_allclose(got.error_trace, want.error_trace,
                                       rtol=1e-4, atol=1e-6)
"""


def test_sdot_spmd_reuses_its_program_on_one_engine():
    """A second call on the same engine traces nothing, misses no jit
    cache and gives bitwise the same nodes as the first."""
    run_spmd(SPMD_REUSE_SETUP + """
    traces = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: traces.append(name)
        if name == "/jax/core/compile/jaxpr_trace_duration" else None)
    eng = SpmdConsensus(mesh, "nodes", graph=ring(n))
    kw = dict(engine=eng, r=r, t_outer=6, schedule=np.array([1, 2, 4, 4, 3, 4]))
    first = sdot_spmd(covs=covs, **kw).q_nodes.block_until_ready()
    n_traced, n_missed = len(traces), misses()
    assert n_traced >= 1 and n_missed == 1, (n_traced, n_missed)
    second = sdot_spmd(covs=covs, **kw).q_nodes.block_until_ready()
    assert len(traces) == n_traced, traces[n_traced:]
    assert misses() == 1
    assert len(eng._spmd_programs) == 1
    np.testing.assert_array_equal(np.asarray(second), np.asarray(first))
    print("reuse OK")
    """)


def test_sdot_spmd_program_is_per_engine():
    """A second engine on the same mesh with another graph builds its own
    program, and it still matches the fused DenseConsensus run."""
    run_spmd(SPMD_REUSE_SETUP + """
    kw = dict(r=r, t_outer=8, t_c=5)
    g1, g2 = ring(n), erdos_renyi(n, 0.5, seed=3)
    e1, e2 = SpmdConsensus(mesh, "nodes", graph=g1), SpmdConsensus(
        mesh, "nodes", graph=g2)
    check(sdot_spmd(covs=covs, engine=e1, **kw), g1, t_outer=8, t_c=5)
    check(sdot_spmd(covs=covs, engine=e2, **kw), g2, t_outer=8, t_c=5)
    assert misses() == 2
    (p1,), (p2,) = e1._spmd_programs.values(), e2._spmd_programs.values()
    assert p1 is not p2
    print("per engine OK")
    """)


def test_sdot_spmd_program_keyed_by_t_max_and_error_trace():
    """Another largest T_c, or q_true given against not given, builds a
    program of its own; a schedule of other values with the same largest
    T_c reuses one. Every call gives the fused DenseConsensus answer."""
    run_spmd(SPMD_REUSE_SETUP + """
    g = ring(n)
    eng = SpmdConsensus(mesh, "nodes", graph=g)
    cases = [(np.array([1, 3, 2, 3, 3]), None, 1),
             (np.array([2, 5, 5, 1, 4]), None, 1),
             (np.array([1, 3, 2, 3, 3]), q_true, 1),
             (np.array([3, 1, 1, 2, 3]), None, 0)]   # same largest T_c
    for sched, qt, missed in cases:
        before = misses()
        got = sdot_spmd(covs=covs, engine=eng, r=r, t_outer=5,
                        schedule=sched, q_true=qt)
        assert misses() - before == missed, (sched, qt is None)
        check(got, g, t_outer=5, schedule=sched, q_true=qt)
    assert sorted(eng._spmd_programs) == [(3, False), (3, True), (5, False)]
    print("keys OK")
    """)


def test_two_level_reduce_exactness():
    """psum intra + enough gossip rounds inter == the true global sum."""
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core.topology import ring
        from repro.core.consensus import SpmdConsensus, two_level_reduce
        devs = np.array(jax.devices()).reshape(4, 2)
        mesh = Mesh(devs, ("pod", "data"))
        spmd = SpmdConsensus(mesh, "pod", graph=ring(4))
        z = jnp.asarray(np.random.default_rng(0).standard_normal((4, 2, 5, 3)),
                        jnp.float32)
        def f(zloc):
            return two_level_reduce(zloc[0, 0], intra_axis="data",
                                    inter=spmd, t_c=60)[None, None]
        out = jax.jit(jax.shard_map(f, mesh=mesh,
                                in_specs=(P("pod", "data", None, None),),
                                out_specs=P("pod", "data", None, None)))(z)
        want = z.sum(axis=(0, 1))
        for i in range(4):
            for j in range(2):
                np.testing.assert_allclose(np.asarray(out[i, j]),
                                           np.asarray(want), rtol=1e-4,
                                           atol=1e-4)
        print("two-level OK")
    """)


def test_psa_train_step_multipod_runs():
    """The paper-integrated train step executes on a 2-pod test mesh and the
    loss/grad-norm stay finite; PSA state keeps its structure."""
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch, reduced_config
        from repro.configs.base import PSAConfig
        from repro.launch.mesh import make_test_mesh
        from repro.models.transformer import init_params
        from repro.optim.adamw import AdamWConfig, adamw_init
        from repro.optim.psa_compress import psa_init
        from repro.train.step import make_psa_train_step
        from repro.data.pipeline import make_lm_batch

        cfg = reduced_config(get_arch("qwen2-7b"))
        mesh = make_test_mesh(multi_pod=True)
        psa = PSAConfig(rank=4, oi_iters=1, gossip_rounds=2)
        opt = AdamWConfig(warmup_steps=1)
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt_state = adamw_init(params, opt)
        psa_state = psa_init(params, psa)
        step_fn, refresh_fn, bspecs = make_psa_train_step(
            cfg, mesh, opt, psa, global_batch=4)
        batch = make_lm_batch(cfg, 0, 0, 4, 8)
        with mesh:
            p, o, ps, m = step_fn(params, opt_state, psa_state, batch)
            assert np.isfinite(float(m["loss"])), m
            ps2 = refresh_fn(p, ps, batch)
            p, o, ps2, m2 = step_fn(p, o, ps2, batch)
            assert np.isfinite(float(m2["loss"]))
        # projector leaves stay orthonormal after refresh
        flat = [l for l in jax.tree.leaves(ps2["proj"]) if l is not None]
        assert flat, "no compressible leaves found"
        print("psa step OK", float(m["loss"]), float(m2["loss"]))
    """)


def test_elastic_checkpoint_reshard():
    """Save under a (4,2) mesh, restore onto a (2,4) mesh — elasticity."""
    run_spmd("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager
        devs = np.array(jax.devices())
        mesh1 = Mesh(devs.reshape(4, 2), ("data", "model"))
        mesh2 = Mesh(devs.reshape(2, 4), ("data", "model"))
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        specs = {"w": P("data", "model")}
        sharded = jax.device_put(tree["w"], NamedSharding(mesh1, specs["w"]))
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, {"w": sharded})
            got, step = mgr.restore({"w": sharded}, mesh=mesh2, specs=specs)
        assert step == 1
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(tree["w"]))
        s = got["w"].sharding
        assert s.mesh.shape["data"] == 2 and s.mesh.shape["model"] == 4
        print("elastic OK")
    """)


def test_sharded_train_step_matches_single_device():
    """pjit-sharded training step == single-device step (same math)."""
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced_config
        from repro.models.transformer import init_params
        from repro.models import sharding as shd
        from repro.train.step import loss_fn
        from repro.data.pipeline import make_lm_batch

        cfg = reduced_config(get_arch("h2o-danube-1.8b"))
        params = init_params(jax.random.PRNGKey(0), cfg)
        batch = make_lm_batch(cfg, 0, 0, 4, 8)
        want = float(loss_fn(params, batch, cfg, remat=False))

        devs = np.array(jax.devices()).reshape(4, 2)
        mesh = Mesh(devs, ("data", "model"))
        pspecs = shd.param_specs(params, cfg, mesh)
        ps = jax.device_put(params, shd.named(mesh, pspecs))
        bspecs = shd.batch_specs(cfg, mesh, 4)
        bs = jax.tree.map(
            lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)),
            batch, bspecs)
        with mesh:
            got = float(jax.jit(
                lambda p, b: loss_fn(p, b, cfg, remat=False))(ps, bs))
        np.testing.assert_allclose(got, want, rtol=1e-4)
        print("sharded==single OK", got, want)
    """)


@pytest.mark.slow
def test_dryrun_production_cell_multipod():
    """One full production-mesh dry-run cell (512 devices) end to end."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "musicgen-medium", "--shape", "decode_32k", "--multipod"],
        capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert '"status": "ok"' in r.stdout
    assert '"n_devices": 512' in r.stdout
