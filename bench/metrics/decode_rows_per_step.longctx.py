"""Scheduler: decode rows per engine step, averaged over the window's
steps."""


def read(rec):
    steps = rec.window.steps
    return sum(s.n_decode for s in steps) / len(steps) if steps else None
