"""GQA attention: blockwise-XLA path (portable), Pallas path (TPU), KV cache.

The model default is ``blockwise_attention`` — a pure-XLA online-softmax
attention double-scanned over query/key chunks. It never materializes the
(s x s) logits, so its HLO byte traffic matches a flash kernel (this is what
the dry-run rooflines measure), it compiles on any backend, and its chunk
sizes mirror the Pallas BlockSpecs. On TPU the Pallas kernel in
``repro.kernels`` is selected with ``use_pallas=True``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops as kops
from .layers import init_dense, rope

__all__ = ["init_attn", "apply_attn", "init_kv_cache", "blockwise_attention"]

_NEG = -1e30


def blockwise_attention(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None, q_chunk: int = 1024,
                        k_chunk: int = 1024, q_offset: int = 0) -> jnp.ndarray:
    """Online-softmax attention, scanned over chunks. q: (b,h,sq,hd)."""
    b, h, sq, hd = q.shape
    skv = k.shape[2]
    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, skv)
    assert sq % q_chunk == 0 and skv % k_chunk == 0
    nq, nk = sq // q_chunk, skv // k_chunk
    scale = hd ** -0.5

    if nq == 1 and nk == 1:
        # single-chunk fast path: same ops as one (q_step, k_step) pass, no
        # scans — less dispatch for short sequences
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(skv)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((sq, skv), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(mask[None, None], logits, _NEG)
        m = jnp.maximum(_NEG, logits.max(-1, keepdims=True))
        p = jnp.where(mask[None, None], jnp.exp(logits - m), 0.0)
        l = p.sum(-1, keepdims=True)
        l = jnp.where(l == 0.0, 1.0, l)
        acc = jnp.einsum("bhqk,bhkd->bhqd", p, v,
                         preferred_element_type=jnp.float32)
        return (acc / l).astype(q.dtype)

    qb = q.reshape(b, h, nq, q_chunk, hd).transpose(2, 0, 1, 3, 4)
    kb = k.reshape(b, h, nk, k_chunk, hd).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nk, k_chunk, hd).transpose(2, 0, 1, 3, 4)

    # Chunk indices ride in the scan CARRY (counters), which compute the
    # same positions as scanning over an iota.
    def q_step(qi, qblk):
        qpos = qi * q_chunk + jnp.arange(q_chunk) + q_offset      # (qc,)
        init = (jnp.int32(0),
                jnp.full((b, h, q_chunk, 1), _NEG, jnp.float32),
                jnp.zeros((b, h, q_chunk, 1), jnp.float32),
                jnp.zeros((b, h, q_chunk, hd), jnp.float32))

        def k_step(carry, kv_blk):
            ki, m, l, acc = carry
            kblk, vblk = kv_blk
            kpos = ki * k_chunk + jnp.arange(k_chunk)              # (kc,)
            logits = jnp.einsum("bhqd,bhkd->bhqk", qblk, kblk,
                                preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((q_chunk, k_chunk), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            logits = jnp.where(mask[None, None], logits, _NEG)
            m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
            p = jnp.where(mask[None, None], jnp.exp(logits - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + p.sum(-1, keepdims=True)
            acc_new = alpha * acc + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vblk, preferred_element_type=jnp.float32)
            return (ki + 1, m_new, l_new, acc_new), None

        (_, m, l, acc), _ = jax.lax.scan(k_step, init, (kb, vb))
        l = jnp.where(l == 0.0, 1.0, l)
        return qi + 1, (acc / l).astype(q.dtype)

    _, outs = jax.lax.scan(q_step, jnp.int32(0), qb)
    return outs.transpose(1, 2, 0, 3, 4).reshape(b, h, sq, hd)


def init_attn(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.hd
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = cfg.jnp_dtype
    p = {
        "wq": init_dense(ks[0], d, nq * hd, dt),
        "wk": init_dense(ks[1], d, nkv * hd, dt),
        "wv": init_dense(ks[2], d, nkv * hd, dt),
        "wo": init_dense(ks[3], nq * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
    return p


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int):
    """Stacked-over-layers ring-buffer KV cache for attention layers.

    With ``cfg.kv_quant`` entries are int8 with a per-(token, head) absmax
    scale — half the capacity and read traffic of bf16.
    """
    hd = cfg.hd
    shape = (n_layers, batch, cfg.n_kv_heads, max_len, hd)
    if cfg.kv_quant:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
            "v_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, cfg.jnp_dtype),
        "v": jnp.zeros(shape, cfg.jnp_dtype),
    }


def _quantize_kv(x):
    """(b, kv, 1, hd) -> int8 values + f32 absmax scale."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale * 127.0),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _project_qkv(p, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    hd = cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def apply_attn(p, x, cfg: ModelConfig, *, window: Optional[int] = None,
               positions=None, cache=None, cache_index=None,
               use_pallas: bool = False, q_chunk: int = 1024,
               k_chunk: int = 1024, act_specs=None):
    """Full-sequence path (cache is None) or single-step decode path.

    Decode: x is (b, 1, d); cache = {"k","v"} slabs (b, nkv, S, hd) for THIS
    layer; cache_index = current length (traced scalar). Returns (out, cache).
    """
    b, s, _ = x.shape
    if positions is None:
        if cache is None:
            positions = jnp.arange(s)[None].repeat(b, 0)
        else:
            positions = jnp.full((b, 1), cache_index, dtype=jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions)

    if cache is None:
        rep = cfg.n_heads // cfg.n_kv_heads
        kf = jnp.repeat(k, rep, axis=1)
        vf = jnp.repeat(v, rep, axis=1)
        if act_specs is not None and act_specs.get("attn_q") is not None:
            q = jax.lax.with_sharding_constraint(q, act_specs["attn_q"])
            kf = jax.lax.with_sharding_constraint(kf, act_specs["attn_kv"])
            vf = jax.lax.with_sharding_constraint(vf, act_specs["attn_kv"])
        if use_pallas:
            out = kops.flash_attention(q, kf, vf, causal=True, window=window)
        else:
            out = blockwise_attention(q, kf, vf, causal=True, window=window,
                                      q_chunk=q_chunk, k_chunk=k_chunk)
        new_cache = None
    else:
        max_len = cache["k"].shape[2]
        # ring-buffer position (SWA uses max_len == window)
        slot = jnp.mod(cache_index, max_len)
        if cfg.kv_quant:
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            ck = jax.lax.dynamic_update_slice(cache["k"], kq, (0, 0, slot, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], vq, (0, 0, slot, 0))
            cks = jax.lax.dynamic_update_slice(cache["k_scale"], ks,
                                               (0, 0, slot, 0))
            cvs = jax.lax.dynamic_update_slice(cache["v_scale"], vs,
                                               (0, 0, slot, 0))
            kd = ck.astype(jnp.float32) * cks / 127.0
            vd = cv.astype(jnp.float32) * cvs / 127.0
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
        else:
            kd = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, slot, 0))
            vd = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, slot, 0))
            new_cache = {"k": kd, "v": vd}
        rep = cfg.n_heads // cfg.n_kv_heads
        kf = jnp.repeat(kd, rep, axis=1)
        vf = jnp.repeat(vd, rep, axis=1)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            kf.astype(jnp.float32)) * (cfg.hd ** -0.5)
        # valid = filled slots only (ring semantics: all slots < min(idx+1, S))
        filled = jnp.minimum(cache_index + 1, max_len)
        valid = jnp.arange(max_len)[None, None, None, :] < filled
        logits = jnp.where(valid, logits, _NEG)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf.astype(jnp.float32))
        out = out.astype(x.dtype)

    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
    return out @ p["wo"], new_cache
