"""Simulated seconds replayed over wall seconds of the whole window."""


def read(o):
    if o.run.mode != "replay" or o.run.window_s <= 0:
        return None
    return o.run.sim_s / o.run.window_s
