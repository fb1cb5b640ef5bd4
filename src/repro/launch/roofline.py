import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

# Delta-scaled roofline extraction (single-pod mesh), one JSON per cell.
#
# Why not read the dry-run numbers directly? cost_analysis() counts a
# lax.scan body ONCE regardless of trip count, so any scan-over-layers cost
# is a ~1/n_groups undercount (and collectives inside the scan likewise).
# Here each cell is compiled twice, UNROLLED, at full width but with 1 and 2
# layer-groups:
#
#     cost(G) = outside + G * body    (exactly, since every group is
#                                      structurally identical)
#  => body = cost(2) - cost(1),  total = cost(1) + (G - 1) * body.
#
# The extrapolation is exact for FLOPs and collective bytes; for HBM bytes it
# is exact modulo XLA fusing across the group boundary (second-order). The
# full-depth compile in launch/dryrun.py remains the proof that the sharding
# and memory plan hold at depth; this module supplies the roofline numerators.
"""Roofline driver — see header comment above the docstring for method.

Usage:
  python -m repro.launch.roofline --arch qwen2-7b --shape train_4k
  python -m repro.launch.roofline --all --out experiments/roofline
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs import SHAPES, get_arch, valid_cells
from ..configs.base import ModelConfig, ShapeConfig
from ..launch.dryrun import abstract_state, input_specs, model_flops
from ..launch.hlo_analysis import collective_bytes, roofline_terms
from ..launch.mesh import HW, make_production_mesh
from ..optim.adamw import AdamWConfig, adamw_update


def _cfg_groups(cfg: ModelConfig, g: int) -> ModelConfig:
    return dataclasses.replace(cfg, n_layers=len(cfg.block_pattern) * g)


def _compile_cost(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                  remat: bool, constrain_acts: bool = True,
                  q_chunk: int = 1024) -> Dict[str, float]:
    """Lower+compile one UNROLLED variant; return per-device cost numbers."""
    from ..models import sharding as shd
    from ..models.transformer import decode_step, forward
    from ..train.step import loss_fn

    opt = AdamWConfig()
    abs_state = abstract_state(cfg, shape, mesh, opt)
    ins = input_specs(cfg, shape, mesh)
    aspecs = shd.activation_specs(cfg, mesh, shape.global_batch) \
        if constrain_acts else None

    if shape.kind == "train":
        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, batch, cfg, remat=remat, unroll_layers=True,
                act_specs=aspecs)
            return adamw_update(grads, opt_state, params, opt)

        with mesh:
            lowered = jax.jit(train_step).lower(
                abs_state["params"], abs_state["opt"], ins)
    elif shape.kind == "prefill":
        def prefill(params, batch):
            return forward(params, batch, cfg, remat=False, unroll_layers=True,
                           act_specs=aspecs)

        with mesh:
            lowered = jax.jit(prefill).lower(abs_state["params"], ins)
    else:
        def serve(params, state, tokens):
            return decode_step(params, state, tokens, cfg, unroll_layers=True,
                               act_specs=aspecs)

        with mesh:
            lowered = jax.jit(serve).lower(
                abs_state["params"], abs_state["decode_state"], ins["tokens"])

    compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text(), mesh.size)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "wire": coll.wire_bytes,
        "coll_by_kind": coll.by_kind,
        "coll_count": coll.count,
    }


def run_cell(arch: str, shape_id: str, *, remat: bool = True,
             constrain_acts: bool = True, mesh_shape: str | None = None,
             kv_quant: bool = False,
             out_path: str | None = None) -> Dict[str, Any]:
    cfg = get_arch(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    shape = SHAPES[shape_id]
    if shape_id == "long_500k" and not cfg.subquadratic:
        res = {"arch": arch, "shape": shape_id, "status": "skipped"}
        if out_path:
            json.dump(res, open(out_path, "w"), indent=1)
        return res

    if mesh_shape:
        dims = tuple(int(t) for t in mesh_shape.split(","))
        assert len(dims) == 2 and dims[0] * dims[1] == 256, mesh_shape
        mesh = jax.make_mesh(dims, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=False)
    g_total = cfg.n_groups
    t0 = time.time()
    c1 = _compile_cost(_cfg_groups(cfg, 1), shape, mesh, remat=remat,
                       constrain_acts=constrain_acts)
    c2 = _compile_cost(_cfg_groups(cfg, 2), shape, mesh, remat=remat,
                       constrain_acts=constrain_acts)

    def extrap(key):
        body = c2[key] - c1[key]
        return c1[key] + (g_total - 1) * body

    flops_dev = extrap("flops")
    bytes_dev = extrap("bytes")
    wire_dev = extrap("wire")
    coll_kind = {k: c1["coll_by_kind"].get(k, 0.0) +
                 (g_total - 1) * (c2["coll_by_kind"].get(k, 0.0)
                                  - c1["coll_by_kind"].get(k, 0.0))
                 for k in set(c1["coll_by_kind"]) | set(c2["coll_by_kind"])}

    mf = model_flops(cfg, shape)
    total_flops = flops_dev * mesh.size
    terms = roofline_terms(flops_per_dev=flops_dev, bytes_per_dev=bytes_dev,
                           wire_bytes_per_dev=wire_dev, hw=HW)
    # roofline fraction: useful model FLOPs per chip-second at the bound
    mfu_at_bound = (mf / mesh.size / HW.PEAK_FLOPS_BF16) / terms["bound_s"] \
        if terms["bound_s"] else None
    res = {
        "arch": arch, "shape": shape_id, "status": "ok",
        "n_devices": mesh.size, "n_groups": g_total,
        "elapsed_s": round(time.time() - t0, 1),
        "flops_per_dev": flops_dev, "bytes_per_dev": bytes_dev,
        "wire_bytes_per_dev": wire_dev,
        "collectives_by_kind": coll_kind,
        "model_flops": mf,
        "useful_flops_frac": mf / total_flops if total_flops else None,
        "roofline": terms,
        "mfu_at_bound": mfu_at_bound,
    }
    if out_path:
        json.dump(res, open(out_path, "w"), indent=1)
    return res


def _run_all(out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    failures = []
    for cell in valid_cells():
        tag = f"{cell['arch']}__{cell['shape']}"
        out = os.path.join(out_dir, tag + ".json")
        if os.path.exists(out):
            print(f"[skip cached] {tag}")
            continue
        cmd = [sys.executable, "-m", "repro.launch.roofline",
               "--arch", cell["arch"], "--shape", cell["shape"], "--out", out]
        print(f"[run] {tag}", flush=True)
        r = subprocess.run(cmd, capture_output=True, text=True)
        if r.returncode != 0:
            failures.append((tag, r.stderr[-1500:]))
            print(f"[FAIL] {tag}\n{r.stderr[-1500:]}", flush=True)
    print(f"done; {len(failures)} failures")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--remat", default="full", choices=["full", "names", "none"])
    ap.add_argument("--no-act-constraints", action="store_true",
                    help="baseline mode: no activation sharding constraints")
    ap.add_argument("--mesh-shape", default=None,
                    help="alternative single-pod logical shape, e.g. 64,4")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (decode cells)")
    ap.add_argument("--out")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()
    if args.all:
        _run_all(args.out or "experiments/roofline")
        return
    res = run_cell(args.arch, args.shape,
                   remat={"full": True, "names": "names", "none": False}[args.remat],
                   constrain_acts=not args.no_act_constraints,
                   mesh_shape=args.mesh_shape, kv_quant=args.kv_quant,
                   out_path=args.out)
    print(json.dumps(res, indent=1, default=str))


if __name__ == "__main__":
    main()
