"""Model step: the operations that the traced slice's tokens need
(``costs.chunk_flops`` at each row's real context), over the slice's
seconds times the chip's bf16 peak, in percent."""


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    steps = rec.traced_steps()
    if not steps:
        return None
    flops = sum(rec.step_flops(s) for s in steps)
    return 100.0 * flops / (rec.trace["window_s"] * rec.peaks["bf16_flops"])
