"""Device idle share (%) of the traced window: 1 - union of the device's
op intervals over the window (device trace)."""


def read(o):
    if o.device is None:
        return None
    return 100.0 * o.device["idle_share"]
