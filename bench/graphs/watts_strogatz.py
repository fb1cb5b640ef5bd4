"""Graph ``watts_strogatz``: a k-nearest-neighbour ring lattice with each
edge rewired to a uniform random endpoint with probability p, resampled
until connected. A copy of ``core/topology.watts_strogatz``'s draw; the
configuration gives ``k``, ``p`` and the ``seed``, so every run of a cell
mixes over the same network. A draw keeps exactly n k / 2 edges."""
import numpy as np


def _connected(adj: np.ndarray) -> bool:
    n = adj.shape[0]
    seen = np.zeros(n, dtype=bool)
    stack = [0]
    seen[0] = True
    while stack:
        u = stack.pop()
        for v in np.nonzero(adj[u])[0]:
            if not seen[v]:
                seen[v] = True
                stack.append(int(v))
    return bool(seen.all())


def adjacency(graph: dict, n: int) -> np.ndarray:
    k, p = graph["k"], graph["p"]
    if k % 2 or k < 2 or k >= n:
        raise ValueError(f"need an even k with 2 <= k < n, got k={k}, n={n}")
    rng = np.random.default_rng(graph["seed"])
    idx = np.arange(n)
    for _ in range(1000):
        adj = np.zeros((n, n))
        for off in range(1, k // 2 + 1):
            adj[idx, (idx + off) % n] = 1.0
            adj[(idx + off) % n, idx] = 1.0
        for off in range(1, k // 2 + 1):
            for u in range(n):
                if rng.random() >= p:
                    continue
                candidates = np.nonzero(adj[u] == 0)[0]
                candidates = candidates[candidates != u]
                if candidates.size == 0:
                    continue
                v_old, v_new = (u + off) % n, int(rng.choice(candidates))
                adj[u, v_old] = adj[v_old, u] = 0.0
                adj[u, v_new] = adj[v_new, u] = 1.0
        if _connected(adj):
            return adj
    raise RuntimeError(f"no connected WS graph (n={n}, k={k}, p={p})")
