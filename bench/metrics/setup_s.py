"""Seconds from process start until the window opens: imports, device
start-up, weights, engine, autotune, compilation or cache loads, warm-up
and the set-up the traffic needs."""


def read(rec):
    return rec.setup_s
