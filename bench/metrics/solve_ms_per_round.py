"""Solver: the backend's ``solver.*`` span (upload, dispatch, device time
and the result sync), per solver round (program spans)."""


def read(o):
    if o.spans is None:
        return None
    solver = [s for s in o.spans if s.name.startswith("solver.")]
    if not solver:
        return None
    return sum(s.dur_ns for s in solver) / len(solver) / 1e6
