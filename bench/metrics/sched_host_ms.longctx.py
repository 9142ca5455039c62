"""Scheduler: host milliseconds per engine step spent planning and
committing, the self time of the program's ``serve.plan`` and
``serve.commit`` spans, averaged over the ``serve_step`` spans of the
traced slice (``program_trace``)."""
import program_trace


def read(rec):
    return program_trace.value(rec, "sched_host_ms")
