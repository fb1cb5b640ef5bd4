#!/usr/bin/env python3
"""Bring-up smoke run: S-DOT on one TPU chip, through the normal entry points.

Each phase builds its data from the repo's seeded generators
(``data/pipeline.py``, ``--seed``), runs ``repro.core.sdot.sdot`` (which
dispatches ``runtime.run_monolithic``) at the sizes of the paper's
real-world rows, and compares the final per-node subspaces with a host
float64 NumPy eigendecomposition of the summed covariance:

  (a) cov mode, Table IX ImageNet row: d=1024, N=100 on ER(p=0.05), r=5,
      const T_c=50, T_o=50; 50,000 samples (500 per node), so the
      (100, 1024, 1024) f32 cov stack (~420 MB) lives on the device;
  (b) raw-data mode through the Pallas ``batched_gram_apply`` kernel, LFW
      row: d=2914, N=20 on ER(p=0.25), r=7, LFW's 13,233 samples split
      over the nodes, T_c=50, T_o=100;
  (c) sparse gossip through the Pallas ELL kernel: Watts-Strogatz N=10,000,
      k=6, p=0.1, d=32, r=4 (payload K = d*r = 128 columns), T_c=50,
      T_o=30; the kernel is also checked against its jnp oracle at this
      shape, with f32 and bf16 payloads.

``--four-chip`` runs only ``sdot_spmd`` with node == chip (N=4 on a
4-device mesh, ring gossip by ppermute, LFW d=2914, r=7), checks that the
cov shards sit on 4 distinct devices, and compares it with the fused
``DenseConsensus`` run of the same W and covs and with the reference.

Usage:  python chip_smoke.py [--seed S] [--four-chip]

Each phase prints one JSON line: kernel path, whether ``tpu_custom_call``
is in the compiled program, compile and run seconds (host clock around
``block_until_ready``), peak device bytes so far, and the final mean
subspace error with its bound. The script exits non-zero, and prints no
result line, when JAX finds no TPU, a bound is missed, or an expected
kernel did not run. Otherwise the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import runtime  # noqa: E402
from repro.core.consensus import DenseConsensus  # noqa: E402
from repro.core.sdot import sdot, sdot_program  # noqa: E402
from repro.core.topology import (erdos_renyi, ring,  # noqa: E402
                                 watts_strogatz)
from repro.data.pipeline import (eigengap_stream,  # noqa: E402
                                 spectrum_matched_stream)
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch.device import init_compile_cache  # noqa: E402

# Bounds on the final mean subspace error (paper eq. 11) against the host
# float64 reference. A float64 NumPy run of the same iteration reaches
# 1.3e-7 for (a), where 50 rounds over ER(100, 0.05) (spectral gap 0.076)
# leave a consensus bias; 5.2e-9 for (b); and 5.1e-5 for (c), whose 50
# rounds barely mix a 10,000-node small world, so each node solves a
# neighbourhood-weighted covariance. The bounds are about ten times the
# floors of (a) and (c); (b) and the four-chip ring, which mixes exactly
# like (b), take (a)'s 1e-6. With f32 matmuls at one bf16 pass, (a) and (b)
# end near 1e-4 and (c) above its bound.
ERR_MAX = {"cov": 1e-6, "data": 1e-6, "sparse": 5e-4, "four_chip": 1e-6}
# Bound on |Pallas ELL - jnp oracle| for one gossip round at phase (c)'s
# shape, f32 and bf16 payloads (f32 sums of 11 products of O(1) values, in
# a different order; both sides gather the same bf16-rounded messages).
ELL_ATOL = 1e-5


def _covs(blocks):
    """(N, d, d) stack of X_i X_i^T / n_i on the device, at full f32
    precision (equal-width blocks; the data, not the code under test)."""
    xs = jnp.asarray(np.stack(blocks))
    return jnp.einsum("ndm,nem->nde", xs, xs,
                      precision=jax.lax.Precision.HIGHEST) / xs.shape[2]


def _ref_basis(blocks, r):
    """Top-r eigenvectors of sum_i X_i X_i^T / n_i, in float64 on the host."""
    y = np.concatenate([np.asarray(x, np.float64) / np.sqrt(x.shape[1])
                        for x in blocks], axis=1)
    _, vecs = np.linalg.eigh(y @ y.T)
    return vecs[:, ::-1][:, :r]


def _mean_err(q_ref, q_nodes):
    """Mean over nodes of eq. (11), in float64 on the host."""
    q = np.asarray(q_nodes, np.float64)
    errs = []
    for qi in q:
        s = np.linalg.svd(q_ref.T @ qi, compute_uv=False)
        errs.append(np.mean(1.0 - np.clip(s, 0.0, 1.0) ** 2))
    return float(np.mean(errs))


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _split(x, n_nodes):
    """Columns of x over n_nodes, sizes differing by at most one."""
    edges = np.linspace(0, x.shape[1], n_nodes + 1).round().astype(int)
    return [x[:, a:b] for a, b in zip(edges[:-1], edges[1:])]


def _solve(kw, q_ref):
    """Compile and run one S-DOT configuration through the entry point."""
    t0 = time.perf_counter()
    compiled = runtime.lower_monolithic(sdot_program(**kw)).compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(sdot(**kw).q_nodes)       # jit dispatch, warm
    t0 = time.perf_counter()
    res = sdot(**kw)
    jax.block_until_ready(res.q_nodes)
    run_s = time.perf_counter() - t0
    return {
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        "compile_s": compile_s,
        "run_s": run_s,
        "peak_bytes": _peak_bytes(),
        "err": _mean_err(q_ref, res.q_nodes),
    }


def phase_cov(seed, *, d=1024, n_nodes=100, p=0.05, r=5, samples=50_000,
              t_c=50, t_o=50):
    """(a) S-DOT on a device-resident (N, d, d) covariance stack."""
    x = spectrum_matched_stream(d, seed=seed)(0, samples)
    blocks = _split(np.asarray(x), n_nodes)
    eng = DenseConsensus(erdos_renyi(n_nodes, p, seed=seed))
    out = _solve(dict(covs=_covs(blocks), engine=eng, r=r, t_outer=t_o,
                      t_c=t_c, seed=seed), _ref_basis(blocks, r))
    return {"phase": "cov", "gossip": "sparse" if eng.is_sparse else "dense",
            **out}


def phase_data(seed, *, d=2914, n_nodes=20, p=0.25, r=7, samples=13_233,
               t_c=50, t_o=100):
    """(b) S-DOT from raw per-node samples (gram-free local apply)."""
    x = spectrum_matched_stream(d, seed=seed)(0, samples)
    blocks = _split(np.asarray(x), n_nodes)
    eng = DenseConsensus(erdos_renyi(n_nodes, p, seed=seed))
    out = _solve(dict(data=[jnp.asarray(b) for b in blocks], engine=eng,
                      r=r, t_outer=t_o, t_c=t_c, seed=seed),
                 _ref_basis(blocks, r))
    return {"phase": "data",
            "gram_path": kops.batched_gram_apply_path(d, r), **out}


def phase_sparse(seed, *, n_nodes=10_000, k=6, p=0.1, d=32, r=4,
                 samples_per_node=64, t_c=50, t_o=30):
    """(c) S-DOT with ELL-SpMM gossip over a large sparse overlay."""
    batch, _, _ = eigengap_stream(d, r, 0.5, seed=seed)
    blocks = _split(np.asarray(batch(0, n_nodes * samples_per_node)),
                    n_nodes)
    eng = DenseConsensus(watts_strogatz(n_nodes, k=k, p=p, seed=seed))
    if not eng.is_sparse:
        raise ValueError(f"N={n_nodes} did not select sparse gossip")
    sw = eng._w
    z = jax.random.normal(jax.random.PRNGKey(seed), (n_nodes, d * r))
    ell_err = {}
    for payload in (None, "bfloat16"):
        got = kops.ell_spmm(sw.ell_idx, sw.ell_val, sw.diag, z,
                            payload_dtype=payload)
        want = kref.ell_spmm_ref(sw.ell_idx, sw.ell_val, sw.diag, z,
                                 z if payload is None else z.astype(payload))
        ell_err[payload or "float32"] = float(jnp.max(jnp.abs(got - want)))
    out = _solve(dict(covs=_covs(blocks), engine=eng, r=r, t_outer=t_o,
                      t_c=t_c, seed=seed), _ref_basis(blocks, r))
    return {"phase": "sparse", "ell_width": sw.ell_width,
            "ell_spmm_path": kops.ell_spmm_path(n_nodes, sw.ell_width,
                                                d * r),
            "ell_vs_oracle": ell_err, **out}


def phase_four_chip(seed, *, d=2914, n_nodes=4, r=7, samples=13_233,
                    t_c=50, t_o=100):
    """node == chip: ``sdot_spmd`` over a 4-device mesh vs the fused
    single-device run of the same W and covs."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.consensus import SpmdConsensus
    from repro.core.sdot import sdot_spmd

    x = spectrum_matched_stream(d, seed=seed)(0, samples)
    blocks = _split(np.asarray(x), n_nodes)
    covs = jnp.concatenate([_covs([b]) for b in blocks])
    mesh = Mesh(np.array(jax.devices()[:n_nodes]), ("node",))
    covs_sh = jax.device_put(covs, NamedSharding(mesh, P("node")))
    shard_devices = {s.device for s in covs_sh.addressable_shards}
    g = ring(n_nodes)
    spmd = SpmdConsensus(mesh, "node", graph=g)
    kw = dict(r=r, t_outer=t_o, t_c=t_c, seed=seed)
    t0 = time.perf_counter()
    jax.block_until_ready(sdot_spmd(covs=covs_sh, engine=spmd,
                                    **kw).q_nodes)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = sdot_spmd(covs=covs_sh, engine=spmd, **kw)
    jax.block_until_ready(res.q_nodes)
    run_s = time.perf_counter() - t0
    dense = sdot(covs=covs, engine=DenseConsensus(g, weights=spmd.weights),
                 **kw)
    q_ref = _ref_basis(blocks, r)
    return {"phase": "four_chip", "n_shard_devices": len(shard_devices),
            "first_call_s": first_s, "run_s": run_s,
            "peak_bytes": _peak_bytes(),
            "spmd_vs_dense": float(np.max(np.abs(
                np.asarray(res.q_nodes) - np.asarray(dense.q_nodes)))),
            "dense_err": _mean_err(q_ref, dense.q_nodes),
            "err": _mean_err(q_ref, res.q_nodes)}


def check(res) -> list:
    """Failures of one phase result, as messages (empty when it passed)."""
    bad = []
    bound = ERR_MAX[res["phase"]]
    res["err_max"] = bound
    if not res["err"] <= bound:
        bad.append(f"error {res['err']:.3e} above {bound:.0e}")
    if res["phase"] == "data" and not (res["gram_path"] == "pallas"
                                       and res["tpu_custom_call"]):
        bad.append(f"Pallas gram kernel did not run ({res['gram_path']})")
    if res["phase"] == "sparse":
        if not (res["ell_spmm_path"] == "pallas" and res["tpu_custom_call"]):
            bad.append(f"Pallas ELL kernel did not run "
                       f"({res['ell_spmm_path']})")
        for payload, err in res["ell_vs_oracle"].items():
            if not err <= ELL_ATOL:
                bad.append(f"ELL kernel ({payload} payload) off its oracle "
                           f"by {err:.3e}")
    if res["phase"] == "four_chip":
        if res["n_shard_devices"] != 4:
            bad.append(f"cov shards on {res['n_shard_devices']} devices")
        if not res["dense_err"] <= bound:
            bad.append(f"dense fused error {res['dense_err']:.3e}")
        if not res["spmd_vs_dense"] <= 1e-4:
            bad.append(f"SPMD run off the dense fused run by "
                       f"{res['spmd_vs_dense']:.3e}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the node == chip sdot_spmd phase on 4 "
                         "devices")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found platform '{platform}', not 'tpu'; "
              f"nothing was run", file=sys.stderr)
        return 1
    if args.four_chip and len(devices) < 4:
        print(f"chip_smoke: --four-chip needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    init_compile_cache()

    phases = ([phase_four_chip] if args.four_chip
              else [phase_cov, phase_data, phase_sparse])
    failed = []
    for phase in phases:
        res = phase(args.seed)
        bad = check(res)
        res["ok"] = not bad
        print(json.dumps(res), flush=True)
        failed += [f"{res['phase']}: {b}" for b in bad]
    if failed:
        print("chip_smoke: FAILED\n  " + "\n  ".join(failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
