"""The node == chip cell on four virtual CPU devices: a sound run comes
out correct, and each way its timed path (``sdot_spmd``) can be broken
makes ``correct`` false. Runs in a child process, which alone may give
the CPU backend four devices."""
import json
import os
import subprocess
import sys

from bench_testcells import ROOT

CHILD = r'''
import json, sys, tempfile, time, pathlib
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[1] + "/tests/bench"]
import jax, jax.numpy as jnp
from bench_testcells import TINY_CONFIG, TINY_TRAFFIC, write_cell
from bench import harness, spec
from repro.core import sdot as sdot_mod
from repro.core.consensus import SpmdConsensus

root = write_cell(pathlib.Path(tempfile.mkdtemp()), name="ring.spmd",
                  config={**TINY_CONFIG, "n_nodes": 4,
                          "graph": {"kind": "ring"}},
                  traffic={**TINY_TRAFFIC, "entry": "sdot_spmd"}, chips=4)
cell = spec.load_cell("ring.spmd", root)
scan, gossip, entry = jax.lax.scan, SpmdConsensus.gossip_rounds_masked, sdot_mod.sdot_spmd


def frozen_scan(f, init, xs=None, **kw):     # each step keeps its state
    return scan(lambda c, x: (c, f(c, x)[1]), init, xs, **kw)


def half_gossip(self, z, t_c, t_max):        # half the nodes left out
    keep = jax.lax.axis_index(self.axis) < self.n // 2
    return gossip(self, jnp.where(keep, 2.0 * z, 0.0), t_c, t_max)


def altered(**kw):                            # one node's answer altered
    res = entry(**kw)
    q = res.q_nodes
    res.q_nodes = q.at[0].set(jnp.linalg.qr(q[0] + 1e-3)[0])
    return res


faults = {"sound": None,
          "frozen": (jax.lax, "scan", frozen_scan),
          "no_exchange": (SpmdConsensus, "gossip_rounds_masked",
                          lambda self, z, t_c, t_max: z),
          "half": (SpmdConsensus, "gossip_rounds_masked", half_gossip),
          "altered": (sdot_mod, "sdot_spmd", altered)}
out = {}
for name, patch in faults.items():
    if patch:
        setattr(*patch)
    jax.clear_caches()
    res = harness.run_cell(cell, 2**35 + 1, 0.2, False, jax.devices()[:4],
                           time.perf_counter())
    out[name] = [res["correct"], res["checks"]["subspace_gap"]["value"]]
    jax.lax.scan, SpmdConsensus.gossip_rounds_masked = scan, gossip
    sdot_mod.sdot_spmd = entry
print(json.dumps(out))
'''


def test_spmd_cell_sound_and_broken():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", CHILD, str(ROOT)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out.pop("sound")[0] is True
    for fault, (correct, gap) in out.items():
        assert correct is False, (fault, gap)
