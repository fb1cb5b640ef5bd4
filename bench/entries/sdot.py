"""Entry ``sdot``: one chip, every node's operand in one stack, solved by
``repro.core.sdot.sdot`` (``runtime.run_monolithic`` underneath) with
``DenseConsensus`` gossip. The traffic mix's ``operand`` is ``cov`` (an
(N, d, d) covariance stack) or ``data`` (each node's raw (d, n_i) block,
the Pallas gram kernel's path)."""
from bench import data


def build(config, traffic, seed, graph, devices, **kw):
    """``(solve, operand)``: ``solve(q0) -> q_nodes`` on the device, and
    the input the program was given, for the reference."""
    from repro.core import sdot as sdot_mod
    from repro.core.consensus import DenseConsensus

    batch = data.spectrum_matched_stream(config["d"], data.data_seed(seed),
                                         config["alpha"])
    sizes = data.split_sizes(config["samples"], config["n_nodes"])
    engine = DenseConsensus(graph)
    if traffic["operand"] == "cov":
        operand = {"covs": data.cov_stack(batch, sizes)}
    elif traffic["operand"] == "data":
        operand = {"data": data.data_blocks(batch, sizes)}
    else:
        raise ValueError(f"unknown operand {traffic['operand']!r}")

    def solve(q0):
        return sdot_mod.sdot(**operand, engine=engine, q_init=q0,
                             **kw).q_nodes
    return solve, operand
