"""Plain S-DOT (Alg. 1 of arXiv:2103.06406), written from the paper and
independent of the program: the reference that decides ``correct``, and
the same arithmetic at a lower precision as its control.

One outer iteration, for every node i at once:

    Z_i = M_i Q_i                      local apply (M_i = X_i X_i^T / n_i)
    Z   = W^{t_c} Z                    t_c gossip rounds
    V_i = Z_i / max([W^{t_c} e_1]_i, 1e-6)    debias
    Q_i = qr(V_i), R with a positive diagonal

K solves of one cell share M and W, so they run together: their iterates
sit side by side as K*r columns, and one pass over M serves them all.

``Float64`` runs it in NumPy float64 on the host; ``Bf16x3`` runs it in
JAX with every product of the apply and the gossip taken in three bf16
passes with f32 sums, which is what ``Precision.HIGH`` means on a TPU and
the step below the program's stated ``HIGHEST``. Written out, it computes
the same on a CPU, so its test holds there too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["local_degree_weights", "Float64", "Bf16x3", "iterate",
           "cov_apply", "data_apply", "subspace_gap"]


def local_degree_weights(adj: np.ndarray) -> np.ndarray:
    """w_ij = 1 / (1 + max(deg_i, deg_j)) on edges, w_ii = 1 - sum_j w_ij
    (Xiao & Boyd's local-degree weights, as the paper uses)."""
    deg = adj.sum(axis=1)
    w = np.where(adj > 0, 1.0 / (1.0 + np.maximum(deg[:, None],
                                                 deg[None, :])), 0.0)
    np.fill_diagonal(w, 0.0)
    np.fill_diagonal(w, 1.0 - w.sum(axis=1))
    return w


class Float64:
    """Host NumPy in float64."""
    xp, dtype = np, np.float64

    @staticmethod
    def prep(a):
        return np.asarray(a, np.float64)

    @staticmethod
    def dot(a, b):
        return np.matmul(a, b)

    @staticmethod
    def dot_t(a, b):
        return np.matmul(np.swapaxes(a, -1, -2), b)

    @staticmethod
    def qr(v):
        q, r = np.linalg.qr(v)
        s = np.sign(np.diagonal(r, axis1=-2, axis2=-1))
        return q * np.where(s == 0, 1.0, s)[..., None, :]

    @staticmethod
    def host(a):
        return a


def _bf16(a):
    """``a`` rounded to bf16, kept in f32. ``reduce_precision`` is an op the
    compiler must keep; a round trip through a bf16 array it may drop as
    excess precision, and on a TPU it does, which leaves one pass."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _mm(a, b):
    # operands hold bf16 values, so each product is exact; sums in f32
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


class Bf16x3:
    """JAX f32 with three-pass bf16 products: a = hi + lo, both bf16
    values, and a b ~ hi_a hi_b + hi_a lo_b + lo_a hi_b, summed in f32.
    ``prep`` splits an operand once; the QR stays at full f32."""
    xp, dtype = jnp, jnp.float32

    @staticmethod
    def prep(a):
        a = jnp.asarray(a, jnp.float32)
        hi = _bf16(a)
        return hi, _bf16(a - hi)

    @staticmethod
    def dot(a, b):
        (ah, al), (bh, bl) = a, Bf16x3.prep(b)
        return _mm(ah, bh) + (_mm(ah, bl) + _mm(al, bh))

    @staticmethod
    def dot_t(a, b):
        t = tuple(jnp.swapaxes(p, -1, -2) for p in a)
        return Bf16x3.dot(t, b)

    @staticmethod
    def qr(v):
        """Gram-Schmidt, twice over, in f32 sums of elementwise products
        (no dot, so no backend picks their precision); R's diagonal is the
        columns' norms, so positive."""
        cols = []
        for j in range(v.shape[-1]):
            x = v[..., j]
            for _ in range(2):
                for c in cols:
                    x = x - jnp.sum(c * x, axis=-1, keepdims=True) * c
            cols.append(x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)))
        return jnp.stack(cols, axis=-1)

    @staticmethod
    def host(a):
        return np.asarray(a, np.float64)


def cov_apply(ops, covs):
    """Z_i = M_i Q_i from the (N, d, d) covariance stack, as the pair
    ``(fn, operands)`` with ``fn(operands, q)``."""
    return (lambda m, q: ops.dot(m, q)), ops.prep(covs)


def data_apply(ops, blocks):
    """Z_i = X_i (X_i^T Q_i) / n_i from each node's raw (d, n_i) block."""
    ns = [x.shape[1] for x in blocks]

    def fn(xs, q):
        return ops.xp.stack([ops.dot(x, ops.dot_t(x, q[i])) / n
                             for i, (x, n) in enumerate(zip(xs, ns))])
    return fn, [ops.prep(x) for x in blocks]


def iterate(ops, apply, w: np.ndarray, q0s: np.ndarray,
            sched) -> np.ndarray:
    """Alg. 1 from each of the K initial iterates ``q0s`` (K, d, r), shared
    by all nodes; returns every node's final iterate, (K, N, d, r)."""
    xp = ops.xp
    fn, operands = apply
    n = w.shape[0]
    k, d, r = q0s.shape
    q0 = np.transpose(q0s, (1, 0, 2)).reshape(d, k * r)
    q = xp.broadcast_to(xp.asarray(q0, ops.dtype)[None], (n, d, k * r))
    powers = {}

    def step(operands, q, wt, p):
        z = fn(operands, q)
        z = ops.dot(wt, z.reshape(n, d * k * r)).reshape(n, d, k * r)
        v = z / p[:, None, None]
        v = v.reshape(n, d, k, r).transpose(0, 2, 1, 3)
        return ops.qr(v).transpose(0, 2, 1, 3).reshape(n, d, k * r)

    if xp is jnp:
        step = jax.jit(step)
    for t in sched:
        if t not in powers:
            wt = np.linalg.matrix_power(np.asarray(w, np.float64), int(t))
            powers[t] = (ops.prep(wt),
                         xp.asarray(np.maximum(wt[0], 1e-6), ops.dtype))
        q = step(operands, q, *powers[t])
    return ops.host(q).reshape(n, d, k, r).transpose(2, 0, 1, 3)


def subspace_gap(q_prog, q_ref) -> float:
    """Largest sine of a principal angle between a node's subspace in the
    program and in the reference, over every node of every solve:
    ||Q_p - Q_ref Q_ref^T Q_p||_2, in float64."""
    qp = np.asarray(q_prog, np.float64)
    qr = np.asarray(q_ref, np.float64)
    res = qp - qr @ (np.swapaxes(qr, -1, -2) @ qp)
    return float(np.max(np.linalg.norm(res, ord=2, axis=(-2, -1))))
