"""Set-up: process start to the window's start (host clock), compiles,
plane, trace, fill and warm-up included."""


def read(o):
    return o.setup_s
