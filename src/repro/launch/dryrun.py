import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
#
# The two lines above MUST run before any jax import — jax locks the device
# count at first backend init, and the production meshes need 512 host
# placeholder devices. (Tests/benchmarks never import this module, so they
# see the real single CPU device.)
"""Dry-run driver (see module header comment; docstring kept below the
XLA_FLAGS lines deliberately).

Per cell this proves, without hardware:
  * the sharding config is coherent (lower+compile succeeds, no sharding
    mismatch, no unsupported collective),
  * the memory plan fits (memory_analysis),
  * and it yields the roofline terms (cost_analysis + collective parsing).

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --multipod
  python -m repro.launch.dryrun --all --out experiments/dryrun   (subprocesses)
"""

import argparse
import json
import subprocess
import sys
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, get_arch, get_psa_config, valid_cells
from ..configs.base import ModelConfig, ShapeConfig
from ..models import sharding as shd
from ..models.transformer import init_decode_state, init_params
from ..optim.adamw import AdamWConfig, adamw_init
from ..optim.psa_compress import psa_init
from .hlo_analysis import collective_bytes, roofline_terms
from .mesh import HW, make_production_mesh

__all__ = ["input_specs", "abstract_state", "run_cell"]


def _sds(tree, mesh, specs):
    """ShapeDtypeStructs with shardings attached (no allocation)."""
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
        tree, specs, is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    bspecs = shd.batch_specs(cfg, mesh, b)
    if shape.kind == "train":
        tshape = (b, s, cfg.n_codebooks) if cfg.frontend == "audio_codec" else (b, s)
        out = {
            "tokens": jax.ShapeDtypeStruct(
                tshape, jnp.int32, sharding=NamedSharding(mesh, bspecs["tokens"])),
            "labels": jax.ShapeDtypeStruct(
                tshape, jnp.int32, sharding=NamedSharding(mesh, bspecs["labels"])),
        }
        if cfg.frontend == "vlm_patches":
            out["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_prefix_tokens, cfg.d_model), jnp.float32,
                sharding=NamedSharding(mesh, bspecs["patch_embeds"]))
        return out
    if shape.kind == "prefill":
        tshape = (b, s, cfg.n_codebooks) if cfg.frontend == "audio_codec" else (b, s)
        out = {"tokens": jax.ShapeDtypeStruct(
            tshape, jnp.int32, sharding=NamedSharding(mesh, bspecs["tokens"]))}
        if cfg.frontend == "vlm_patches":
            out["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_prefix_tokens, cfg.d_model), jnp.float32,
                sharding=NamedSharding(mesh, bspecs["patch_embeds"]))
        return out
    # decode: one new token against a seq_len-deep cache
    tshape = (b, 1, cfg.n_codebooks) if cfg.frontend == "audio_codec" else (b, 1)
    return {"tokens": jax.ShapeDtypeStruct(
        tshape, jnp.int32, sharding=NamedSharding(mesh, bspecs["tokens"]))}


def abstract_state(cfg: ModelConfig, shape: ShapeConfig, mesh, opt: AdamWConfig,
                   *, psa=None):
    """Abstract (ShapeDtypeStruct) params / optimizer / decode state."""
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_shape = jax.eval_shape(lambda k: init_params(k, cfg), key)
    pspecs = shd.param_specs(params_shape, cfg, mesh)
    params_sds = _sds(params_shape, mesh, pspecs)
    out = {"params": params_sds, "pspecs": pspecs}
    if shape.kind == "train":
        opt_shape = jax.eval_shape(lambda p: adamw_init(p, opt), params_shape)
        ospecs = {"m": pspecs, "v": pspecs, "step": P()}
        out["opt"] = _sds(opt_shape, mesh, ospecs)
        if psa is not None:
            psa_shape = jax.eval_shape(
                lambda p: psa_init(p, psa), params_shape)
            # projectors / EF buffers are pod-replicated (P() everywhere)
            psa_specs = jax.tree.map(
                lambda l: P(*([None] * l.ndim)) if l is not None else None,
                psa_shape, is_leaf=lambda x: x is None or hasattr(x, "shape"))
            out["psa"] = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=NamedSharding(mesh, s))
                if a is not None else None,
                psa_shape, psa_specs,
                is_leaf=lambda x: x is None or hasattr(x, "shape"))
    else:
        cache_len = shape.seq_len
        st_shape = jax.eval_shape(
            lambda: init_decode_state(cfg, shape.global_batch, cache_len))
        st_specs = shd.decode_state_specs(st_shape, cfg, mesh, shape.global_batch)
        out["decode_state"] = _sds(st_shape, mesh, st_specs)
    return out


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6 N D (train) / 2 N D (prefill & decode), N = active params."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch     # one token per sequence


def run_cell(arch: str, shape_id: str, *, multi_pod: bool, psa: bool = False,
             use_pallas: bool = False, remat: bool = True,
             constrain_acts: bool = True,
             out_path: str | None = None) -> Dict[str, Any]:
    from ..train.step import loss_fn, make_psa_train_step  # late import
    from ..models.transformer import decode_step
    from ..optim.adamw import adamw_update

    cfg = get_arch(arch)
    shape = SHAPES[shape_id]
    if shape_id == "long_500k" and not cfg.subquadratic:
        res = {"arch": arch, "shape": shape_id, "multi_pod": multi_pod,
               "status": "skipped",
               "reason": "full-attention arch: 500k decode cache infeasible"}
        if out_path:
            json.dump(res, open(out_path, "w"), indent=1)
        return res

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    opt = AdamWConfig(moment_dtype="bfloat16" if cfg.param_count() > 2e11 else "float32")
    psa_cfg = get_psa_config() if psa else None
    abs_state = abstract_state(cfg, shape, mesh, opt, psa=psa_cfg)
    ins = input_specs(cfg, shape, mesh)
    aspecs = shd.activation_specs(cfg, mesh, shape.global_batch) \
        if constrain_acts else None

    t0 = time.time()
    if shape.kind == "train":
        if psa:
            step_fn, _, _ = make_psa_train_step(
                cfg, mesh, opt, psa_cfg, global_batch=shape.global_batch,
                use_pallas=use_pallas, remat=remat)
            lowered = step_fn.lower(abs_state["params"], abs_state["opt"],
                                    abs_state["psa"], ins)
        else:
            def train_step(params, opt_state, batch):
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, batch, cfg, use_pallas=use_pallas, remat=remat,
                    act_specs=aspecs)
                new_p, new_o, gn = adamw_update(grads, opt_state, params, opt)
                return new_p, new_o, {"loss": loss, "grad_norm": gn}

            with mesh:
                lowered = jax.jit(train_step).lower(
                    abs_state["params"], abs_state["opt"], ins)
    elif shape.kind == "prefill":
        from ..models.transformer import forward

        def prefill(params, batch):
            return forward(params, batch, cfg, use_pallas=use_pallas,
                           remat=False, act_specs=aspecs)

        with mesh:
            lowered = jax.jit(prefill).lower(abs_state["params"], ins)
    else:
        def serve_step(params, state, tokens):
            return decode_step(params, state, tokens, cfg, act_specs=aspecs)

        with mesh:
            lowered = jax.jit(serve_step).lower(
                abs_state["params"], abs_state["decode_state"], ins["tokens"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ca = compiled.cost_analysis() or {}
    flops_dev = float(ca.get("flops", 0.0))
    bytes_dev = float(ca.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_info = {"error": str(e)}

    hlo = compiled.as_text()
    coll = collective_bytes(hlo, n_dev)
    pod_split = None
    if multi_pod:
        from .hlo_analysis import cross_pod_bytes
        pod_split = cross_pod_bytes(hlo, n_dev, 256)
    mf = model_flops(cfg, shape)
    total_flops = flops_dev * n_dev
    terms = roofline_terms(flops_per_dev=flops_dev, bytes_per_dev=bytes_dev,
                           wire_bytes_per_dev=coll.wire_bytes, hw=HW)
    res = {
        "arch": arch, "shape": shape_id, "multi_pod": multi_pod, "psa": psa,
        "status": "ok", "n_devices": n_dev,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops_per_dev": flops_dev, "bytes_per_dev": bytes_dev,
        "total_flops": total_flops,
        "model_flops": mf,
        "useful_flops_frac": mf / total_flops if total_flops else None,
        "collectives": {"wire_bytes_per_dev": coll.wire_bytes,
                        "by_kind": coll.by_kind, "count": coll.count,
                        "pod_split": pod_split},
        "memory": mem_info,
        "roofline": terms,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    if out_path:
        json.dump(res, open(out_path, "w"), indent=1)
    return res


def _run_all(out_dir: str, multi_pod_also: bool = True):
    import os as _os
    _os.makedirs(out_dir, exist_ok=True)
    cells = valid_cells()
    meshes = [False, True] if multi_pod_also else [False]
    failures = []
    for cell in cells:
        for mp in meshes:
            tag = f"{cell['arch']}__{cell['shape']}__{'mp' if mp else 'sp'}"
            out = _os.path.join(out_dir, tag + ".json")
            if _os.path.exists(out):
                print(f"[skip cached] {tag}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", cell["arch"], "--shape", cell["shape"],
                   "--out", out] + (["--multipod"] if mp else [])
            print(f"[run] {tag}", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                failures.append((tag, r.stderr[-2000:]))
                print(f"[FAIL] {tag}\n{r.stderr[-2000:]}", flush=True)
    print(f"done; {len(failures)} failures")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--psa", action="store_true",
                    help="PSA-compressed cross-pod gradient reduction")
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()
    if args.all:
        _run_all(args.out or "experiments/dryrun")
        return
    res = run_cell(args.arch, args.shape, multi_pod=args.multipod, psa=args.psa,
                   use_pallas=args.pallas, remat=not args.no_remat,
                   out_path=args.out)
    slim = {k: v for k, v in res.items() if k not in ("memory",)}
    print(json.dumps(slim, indent=1, default=str))
    if res.get("memory"):
        print("memory_analysis:", res["memory"])


if __name__ == "__main__":
    main()
