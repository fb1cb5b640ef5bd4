"""Numerical linear algebra helpers tuned for the MXU.

CholeskyQR2 replaces Householder QR everywhere in this codebase: it consists
of three matmuls + one tiny (r x r) Cholesky, which maps to the TPU MXU
whereas Householder is sequential. Two passes restore the orthogonality lost
to squaring the condition number.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["PRECISION", "cholesky_qr", "cholesky_qr2", "orthonormal_init",
           "eigh_topr"]

# Precision of every f32 matmul on the S-DOT path: the gossip, the local
# apply (cov einsum and the Pallas gram kernel), CholeskyQR2 and the error
# trace. HIGHEST is full f32 (several bf16 passes on the TPU MXU). At
# DEFAULT a TPU rounds f32 operands to bf16, and CholeskyQR2 squares the
# condition number of what it rounds: on a TPU v5e the paper's ImageNet and
# LFW rows (chip_smoke.py) then ended at subspace errors of 1.4e-4 and
# 1.3e-4, against a float64 floor of 1.3e-7 and 5.2e-9. The CPU computes
# f32 either way.
PRECISION = jax.lax.Precision.HIGHEST


def cholesky_qr(v: jnp.ndarray, eps: float = 0.0):
    """One CholeskyQR pass: V = Q R with Q^T Q ~= I.

    Gram is computed in float32 at minimum for stability.
    """
    acc = jnp.promote_types(v.dtype, jnp.float32)
    g = jnp.matmul(v.astype(acc).T, v.astype(acc), precision=PRECISION)
    if eps:
        g = g + eps * jnp.eye(g.shape[0], dtype=acc)
    r = jnp.linalg.cholesky(g).T  # upper triangular
    q = jax.scipy.linalg.solve_triangular(r.T, v.astype(acc).T, lower=True).T
    return q.astype(v.dtype), r.astype(v.dtype)


def cholesky_qr2(v: jnp.ndarray, eps: float = 1e-12):
    """CholeskyQR2: two passes; orthogonality error ~ machine eps."""
    q1, r1 = cholesky_qr(v, eps=eps)
    q2, r2 = cholesky_qr(q1, eps=0.0)
    return q2, jnp.matmul(r2, r1, precision=PRECISION)


def orthonormal_init(key, d: int, r: int, dtype=jnp.float32) -> jnp.ndarray:
    """Random d x r matrix with orthonormal columns (Q_init of Alg. 1/2)."""
    a = jax.random.normal(key, (d, r), dtype=jnp.float32)
    q, _ = jnp.linalg.qr(a)
    return q.astype(dtype)


def eigh_topr(m: jnp.ndarray, r: int):
    """Top-r eigenpairs of a symmetric matrix (ground truth for tests)."""
    vals, vecs = jnp.linalg.eigh(m)
    order = jnp.argsort(vals)[::-1]
    vals = vals[order][:r]
    vecs = vecs[:, order][:, :r]
    return vals, vecs
