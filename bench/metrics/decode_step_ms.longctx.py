"""Model step: device milliseconds per run of the jitted ``decode_step``
program, in the traced slice."""
from trace_reduce import module_time


def read(rec):
    if rec.trace is None:
        return None
    calls, secs = module_time(rec.trace, "decode_step")
    return 1e3 * secs / calls if calls else None
