"""Train / serve step factories.

``make_train_step``      — standard SPMD step (pjit; XLA inserts the gradient
                           all-reduce over every data axis).
``make_psa_train_step``  — the paper-integrated step: gradients are reduced
                           *within* a pod by XLA (auto axes) but *across* pods
                           through PSA subspace compression (manual "pod"
                           axis inside shard_map). See optim/psa_compress.py.
``make_serve_step``      — one-token decode with KV/recurrent caches.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..configs.base import ModelConfig, PSAConfig
from ..models import sharding as shd
from ..models.transformer import decode_step, forward
from ..optim.adamw import AdamWConfig, adamw_init, adamw_update
from ..optim.psa_compress import compress_grads, psa_refresh

__all__ = ["loss_fn", "make_train_step", "make_psa_train_step", "make_serve_step"]


def loss_fn(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
            use_pallas: bool = False, remat: bool = True,
            unroll_layers: bool = False, act_specs=None) -> jnp.ndarray:
    """Mean next-token cross entropy (fp32 log-softmax; vocab may be sharded)."""
    logits = forward(params, batch, cfg, use_pallas=use_pallas, remat=remat,
                     unroll_layers=unroll_layers, act_specs=act_specs)
    logits = logits.astype(jnp.float32)
    labels = batch["labels"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    # gold-logit extraction as a masked sum: fuses into the reduction (no
    # (b,s,V) one-hot materialized) and partitions cleanly over sharded vocab
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1)
    return jnp.mean(logz - gold)


def _train_step(params, opt_state, batch, cfg: ModelConfig, opt: AdamWConfig,
                use_pallas: bool, remat: bool, act_specs=None):
    loss, grads = jax.value_and_grad(loss_fn)(
        params, batch, cfg, use_pallas=use_pallas, remat=remat,
        act_specs=act_specs)
    new_params, new_opt, gnorm = adamw_update(grads, opt_state, params, opt)
    metrics = {"loss": loss, "grad_norm": gnorm}
    return new_params, new_opt, metrics


def make_train_step(cfg: ModelConfig, mesh: Mesh, opt: AdamWConfig, *,
                    global_batch: int, use_pallas: bool = False,
                    remat: bool = True, donate: bool = True):
    """jit'd (params, opt_state, batch) -> (params, opt_state, metrics)."""
    aspecs = shd.activation_specs(cfg, mesh, global_batch)
    step = functools.partial(_train_step, cfg=cfg, opt=opt,
                             use_pallas=use_pallas, remat=remat,
                             act_specs=aspecs)
    # shardings: params/opt by rules; batch by batch_specs; metrics replicated
    bspecs = shd.batch_specs(cfg, mesh, global_batch)

    jit_kwargs = dict(donate_argnums=(0, 1) if donate else ())
    return jax.jit(step, **jit_kwargs), bspecs


def make_psa_train_step(cfg: ModelConfig, mesh: Mesh, opt: AdamWConfig,
                        psa: PSAConfig, *, global_batch: int,
                        use_pallas: bool = False, remat: bool = True):
    """Train step with PSA-compressed cross-pod gradient reduction.

    Per-pod gradients are computed inside shard_map with the "pod" axis
    MANUAL (each pod sees its own batch shard) and "data"/"model" AUTO (XLA
    keeps partitioning the model math). Cross-pod traffic is the projected
    U = P^T G plus the uncompressed small leaves — the paper's S-DOT
    consensus doing the reduction.

    The token-embedding GATHER (and its scatter VJP) runs OUTSIDE the
    manual region: XLA's SPMD partitioner cannot partition gathers inside a
    shard_map auto sub-mesh at production scale (CHECK-crash at 512 devices,
    iota device-group expansion). The inner region differentiates the model
    from the embeddings; the embedding-table gradient is assembled outside
    from the returned activation cotangent, where the pod axis is auto and
    the scatter partitions normally.
    """
    if "pod" not in mesh.axis_names:
        raise ValueError("PSA train step needs a multi-pod mesh ('pod' axis)")
    pod_axis = "pod"
    bspecs = shd.batch_specs(cfg, mesh, global_batch)
    n_pods = mesh.shape[pod_axis]
    # inside the shard_map body "pod" is manual — constraints may only name
    # the auto axes, and the batch is the per-pod shard
    aspecs = shd.activation_specs(
        cfg, mesh, max(global_batch // n_pods, 1), dp=("data",))
    from ..models.transformer import embed_inputs

    def local_loss(p, x, labels):
        batch = {"inputs_embeds": x, "labels": labels}
        return loss_fn(p, batch, cfg, use_pallas=use_pallas, remat=remat,
                       act_specs=aspecs)

    def inner_grads(params, psa_state, x, labels):
        """shard_map body: per-pod grads -> PSA-reduced grads + x cotangent."""
        loss, (gp, gx) = jax.value_and_grad(local_loss, argnums=(0, 1))(
            params, x, labels)
        gp = dict(gp)
        g_emb_in = gp.pop("embed")      # zero unless embeddings are tied
        proj = {k: v for k, v in psa_state["proj"].items() if k != "embed"}
        ef = {k: v for k, v in psa_state["ef"].items() if k != "embed"}
        red, new_ef = compress_grads(gp, {"proj": proj, "ef": ef}, psa,
                                     pod_axis=pod_axis)
        if cfg.tie_embeddings:          # logits matmul contributes inside
            g_emb_in = (jax.lax.psum(g_emb_in.astype(jnp.float32), pod_axis)
                        / n_pods).astype(g_emb_in.dtype)
        red["embed"] = g_emb_in
        new_ef["embed"] = None
        loss = jax.lax.pmean(loss, pod_axis)
        return loss, red, new_ef, gx

    def inner_refresh(params, psa_state, x, labels):
        """shard_map body for the refresh pass: S-DOT subspace update from
        pod-local gradients, gossip over the pod ring inside the manual
        region (paper Alg. 1 with nodes == pods)."""
        grads = jax.grad(local_loss)(params, x, labels)
        return psa_refresh(grads, psa_state, psa, pod_axis=pod_axis)

    rep = P()
    batch_dims = 3 if cfg.frontend == "audio_codec" else 2
    lbl_spec = bspecs["labels"]
    lbl_pod = P(pod_axis, *lbl_spec[1:]) if lbl_spec[0] is not None else lbl_spec
    x_pod = P(pod_axis if lbl_spec[0] is not None else None, None, None)

    inner_sm = jax.shard_map(
        inner_grads, mesh=mesh, axis_names={pod_axis}, check_vma=False,
        in_specs=(rep, rep, x_pod, lbl_pod),
        out_specs=(rep, rep, rep, x_pod))
    refresh_sm = jax.shard_map(
        inner_refresh, mesh=mesh, axis_names={pod_axis}, check_vma=False,
        in_specs=(rep, rep, x_pod, lbl_pod),
        out_specs=rep)

    def _embed_grad(params, batch, gx):
        """Embedding-table gradient via the gather VJP, in the AUTO region.

        gx is each pod's d(pod-mean loss)/dx; the global loss is the pod
        mean, so the table gradient is scatter(gx) / n_pods.
        """
        _, vjp = jax.vjp(lambda e: embed_inputs(
            {**params, "embed": e}, batch, cfg), params["embed"])
        (g_embed,) = vjp(gx.astype(params["embed"].dtype))
        return g_embed / n_pods

    def step(params, opt_state, psa_state, batch):
        x = embed_inputs(params, batch, cfg)          # gather: auto region
        loss, red, new_ef, gx = inner_sm(params, psa_state, x,
                                         batch["labels"])
        red = dict(red)
        red["embed"] = red["embed"] + _embed_grad(params, batch, gx)
        new_params, new_opt, gnorm = adamw_update(red, opt_state, params, opt)
        new_psa = {"proj": psa_state["proj"], "ef": new_ef}
        return new_params, new_opt, new_psa, {"loss": loss, "grad_norm": gnorm}

    def refresh(params, psa_state, batch):
        x = embed_inputs(params, batch, cfg)
        return refresh_sm(params, psa_state, x, batch["labels"])

    return jax.jit(step), jax.jit(refresh), bspecs


def make_serve_step(cfg: ModelConfig, mesh: Mesh, *, global_batch: int):
    """jit'd (params, state, tokens) -> (logits, state): one decode step."""

    def serve(params, state, tokens):
        return decode_step(params, state, tokens, cfg)

    return jax.jit(serve, donate_argnums=(1,))
