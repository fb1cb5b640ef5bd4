"""The benchmark's own inputs: synthetic data, schedules and initial
iterates, all made from a seed. Networks are made by ``bench/graphs``.

The generators are copies of the program's (``data/pipeline.py``'s
spectrum-matched stream, ``core/consensus.py``'s schedules), kept here so
that a change to the program cannot change what the benchmark feeds it. A
test holds each copy equal to its original at one seed.

Node i's samples are step i of the stream, ``batch(i, n_i)``, so each block
is drawn on the device by itself and no full sample matrix is ever held.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["data_seed", "spectrum_matched_stream", "split_sizes",
           "schedule", "q_init", "cov_stack", "data_blocks", "host_covs"]

HIGHEST = jax.lax.Precision.HIGHEST


def data_seed(seed: int) -> int:
    """A 32-bit seed for the generators from a run's ``--seed``, which may
    exceed what ``jax.random.PRNGKey`` keeps (it drops bits above 32)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def _spectrum_factor(rng, d: int, alpha: float) -> np.ndarray:
    """Power-law factor L with L L^T spectrum lambda_i ~ i^-alpha."""
    evals = np.arange(1, d + 1, dtype=np.float64) ** (-alpha)
    u = np.linalg.qr(rng.standard_normal((d, d)))[0]
    return u * np.sqrt(evals)


def spectrum_matched_stream(d: int, seed: int = 0, alpha: float = 1.2):
    """``batch(step, m) -> (d, m)``: samples of a power-law population
    covariance (a stand-in for natural-image data), a pure function of
    ``(seed, step)``. ``batch.factor`` and ``batch.base`` (the key) let a
    jitted builder take both as arguments, so that its program is the same
    for every seed and is found in the compile cache."""
    rng = np.random.default_rng(seed)
    factor = jnp.asarray(_spectrum_factor(rng, d, alpha), jnp.float32)
    base = jax.random.PRNGKey(seed)

    def batch(step, m: int, factor=factor, base=base) -> jnp.ndarray:
        key = jax.random.fold_in(base, step)
        return factor @ jax.random.normal(key, (factor.shape[0], m),
                                          jnp.float32)

    batch.factor, batch.base = factor, base
    return batch


def split_sizes(samples: int, n_nodes: int) -> list:
    """Samples per node, differing by at most one (column ranges of a
    ``linspace`` split, as the paper's partition of a dataset)."""
    edges = np.linspace(0, samples, n_nodes + 1).round().astype(int)
    return [int(b - a) for a, b in zip(edges[:-1], edges[1:])]


def schedule(spec: dict, t_outer: int) -> np.ndarray:
    """Consensus rounds per outer iteration t = 1..T_o: ceil(slope t +
    offset), clipped at ``cap`` if given. S-DOT's constant T_c is slope 0;
    the paper's SA-DOT schedules are slope 0.5, 1, 2 or 5 with offset 1
    (``core/consensus.consensus_schedule``'s ``lin_half``, ``lin1``,
    ``lin2``, ``lin5``)."""
    t = np.arange(1, t_outer + 1, dtype=np.float64)
    out = np.ceil(spec["slope"] * t + spec["offset"])
    if spec.get("cap") is not None:
        out = np.minimum(out, spec["cap"])
    return out.astype(np.int64)


def q_init(seed: int, index: int, d: int, r: int) -> np.ndarray:
    """Solve ``index``'s shared initial iterate: orthonormal (d, r), drawn
    on the host from (seed, index), so no two solves of a run share one."""
    rng = np.random.default_rng([seed, index % 2**64])
    q, _ = np.linalg.qr(rng.standard_normal((d, r)))
    return q.astype(np.float32)


def cov_stack(batch, sizes) -> jnp.ndarray:
    """(N, d, d) stack of X_i X_i^T / n_i, one node at a time on the device
    (equal ``sizes``), so no more than one node's samples are live."""
    n = sizes[0]
    if any(s != n for s in sizes):
        raise ValueError("cov_stack needs equal per-node sample counts")

    @jax.jit
    def build(factor, base):
        def one(i):
            x = batch(i, n, factor=factor, base=base)
            return jnp.matmul(x, x.T, precision=HIGHEST) / n
        return jax.lax.map(one, jnp.arange(len(sizes)))

    return build(batch.factor, batch.base)


def data_blocks(batch, sizes) -> list:
    """Node i's raw (d, n_i) samples, one node per call, so that no more
    than one node's draw is in flight beside the blocks made so far."""

    @functools.partial(jax.jit, static_argnums=3)
    def build(factor, base, i, n):
        return batch(i, n, factor=factor, base=base)

    out = []
    for i, n in enumerate(sizes):
        out.append(build(batch.factor, batch.base, np.int32(i), n))
        out[-1].block_until_ready()
    return out


def host_covs(d: int, seed: int, alpha: float, sizes):
    """Node i's (d, d) covariance X_i X_i^T / n_i in float32, made in NumPy
    on the host one node at a time: the stream's population (the same
    factor), with node i's samples drawn from (seed, i)."""
    factor = _spectrum_factor(np.random.default_rng(seed), d,
                              alpha).astype(np.float32)
    for i, n in enumerate(sizes):
        g = np.random.default_rng([seed, i]).standard_normal(
            (d, n), dtype=np.float32)
        x = factor @ g
        yield x @ x.T / np.float32(n)
