"""Engine: host milliseconds per engine step spent launching its
programs, the ``serve.admit``, ``serve.decode`` and ``serve.prefill``
spans (building arguments, putting them on the device, dispatching),
averaged over the ``serve_step`` spans of the traced slice
(``program_trace``)."""
import program_trace


def read(rec):
    return program_trace.value(rec, "launch_host_ms")
