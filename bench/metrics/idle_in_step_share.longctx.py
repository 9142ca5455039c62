"""Device: the share of the traced slice in which no operation runs on
the device while the host is inside a ``serve_step`` span, in percent:
the part of ``idle_share`` that the engine's host code causes
(``program_trace``)."""
import program_trace


def read(rec):
    return program_trace.value(rec, "idle_in_step_share")
