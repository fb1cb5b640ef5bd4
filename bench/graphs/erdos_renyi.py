"""Graph ``erdos_renyi``: G(n, p), resampled until connected (as in the
paper). A copy of ``core/topology.erdos_renyi``'s draw; the configuration
gives ``p`` and the ``seed``, so every run of a cell mixes over the same
network."""
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
    rng = np.random.default_rng(graph["seed"])
    for _ in range(10_000):
        upper = rng.random((n, n)) < graph["p"]
        adj = np.triu(upper, k=1)
        adj = (adj | adj.T).astype(np.float64)
        if _connected(adj):
            return adj
    raise RuntimeError(f"no connected ER graph (n={n}, p={graph['p']})")
