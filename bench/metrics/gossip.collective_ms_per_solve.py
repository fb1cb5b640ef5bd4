"""Device time of the collectives that carry gossip between chips
(``SpmdConsensus``'s ppermute rounds lower to collective-permute), per
solve, in ms: summed over the collective ops' events on a chip, the
highest over the chips."""

COLLECTIVES = ("collective-permute", "all-gather", "all-reduce",
               "reduce-scatter", "all-to-all")


def read(view):
    red = view.reduced
    if len(red.devices) < 2 or not red.solves:
        return None
    per_chip = [sum(ns for name, ns in dv.op_ns.items()
                    if name.startswith(COLLECTIVES))
                for dv in red.devices]
    if not any(per_chip):
        return None
    return max(per_chip) / 1e6 / red.solves
