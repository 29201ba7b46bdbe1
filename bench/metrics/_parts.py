"""Shared arithmetic of the readers that split a per-layer time into its
parts: one program span summed over the window, divided by the same count
as the whole it belongs to, so that the parts add up to that whole.

- solver parts (``round_program.upload`` / ``.dispatch`` / ``.sync`` /
  ``.fetch``) divide by the number of ``solver.*`` spans, as
  ``solve_ms_per_round`` does;
- host-round parts (``sim.select``, ``sim.build_state``,
  ``round_program.stack``, ``sim.apply``) divide by the number of
  ``sim.round`` spans, as ``host_ms_per_round`` does. A what-if round
  stacks inside its ``solver.*`` span; the cells run no what-if rounds.
"""


def per_round(o, name, parent):
    """Milliseconds of the ``name`` spans over the window, per span whose
    name ``parent`` accepts; None when either kind is absent."""
    if o.spans is None:
        return None
    n = sum(1 for s in o.spans if parent(s.name))
    part = [s.dur_ns for s in o.spans if s.name == name]
    if not n or not part:
        return None
    return sum(part) / n / 1e6


def solver(o, name):
    return per_round(o, name, lambda s: s.startswith("solver."))


def host_round(o, name):
    return per_round(o, name, lambda s: s == "sim.round")
