"""Entry ``sdot_spmd``: one node per chip, each node's (d, d) covariance
on its own chip, solved by ``repro.core.sdot.sdot_spmd`` with
``SpmdConsensus`` (``ppermute``) gossip.

The covariances are made on the host (``data.host_covs``) and copied to
their chips, so that a chip holds nothing of set-up beside its own
covariance and the peak of its memory is the solve's."""
import numpy as np

from bench import data


def build(config, traffic, seed, graph, devices, **kw):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import sdot as sdot_mod
    from repro.core.consensus import SpmdConsensus

    d, n_nodes = config["d"], config["n_nodes"]
    if traffic["operand"] != "cov":
        raise ValueError("sdot_spmd takes a covariance per node")
    if len(devices) != n_nodes:
        raise ValueError(f"sdot_spmd places one node per chip: "
                         f"{n_nodes} nodes, {len(devices)} chips")
    mesh = Mesh(np.array(devices), ("node",))
    engine = SpmdConsensus(mesh, "node", graph=graph)
    sizes = data.split_sizes(config["samples"], n_nodes)
    shards = [jax.device_put(c[None], dev) for c, dev in zip(
        data.host_covs(d, data.data_seed(seed), config["alpha"], sizes),
        devices)]
    covs = jax.make_array_from_single_device_arrays(
        (n_nodes, d, d), NamedSharding(mesh, P("node")), shards)
    operand = {"covs": covs}

    def solve(q0):
        return sdot_mod.sdot_spmd(covs=covs, engine=engine, q_init=q0,
                                  **kw).q_nodes
    return solve, operand
