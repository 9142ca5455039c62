"""Kernel: the least time the paged-attention kernel needs for the
traced slice's calls (per call the larger of needed bytes over HBM
bandwidth and needed operations over the bf16 peak; bytes are K/V up to
each row's valid length plus q and out, every layer), over the kernel's
summed device time, in percent."""


def read(rec):
    if rec.trace is None or rec.peaks is None or not rec.trace["kernel_s"]:
        return None
    steps = rec.traced_steps()
    if not steps:
        return None
    floor = sum(rec.kernel_floor_s(s)[0] for s in steps)
    return 100.0 * floor / rec.trace["kernel_s"]
