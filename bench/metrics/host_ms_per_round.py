"""Host round: self time of the simulator's ``sim.round`` spans less the
solver's ``solver.*`` span inside each, summed over the window, per
round (program spans)."""


def read(o):
    if o.spans is None:
        return None
    rounds = [s for s in o.spans if s.name == "sim.round"]
    if not rounds:
        return None
    solver = sum(s.dur_ns for s in o.spans if s.name.startswith("solver."))
    return (sum(s.dur_ns for s in rounds) - solver) / len(rounds) / 1e6
