"""Auction iterations per solver round (program counters
``auction.iterations`` / ``sim.rounds``)."""


def read(o):
    if not o.counters or not o.counters.get("sim.rounds"):
        return None
    return o.counters.get("auction.iterations", 0.0) / o.counters["sim.rounds"]
