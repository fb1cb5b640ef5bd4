"""Graph ``ring``: node i linked to i - 1 and i + 1 (mod n). A copy of
``core/topology.ring``."""
import numpy as np


def adjacency(graph: dict, n: int) -> np.ndarray:
    adj = np.zeros((n, n))
    idx = np.arange(n)
    adj[idx, (idx + 1) % n] = 1.0
    adj[(idx + 1) % n, idx] = 1.0
    return adj
