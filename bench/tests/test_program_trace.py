"""``bench/program_trace.py`` on traces recorded on a TPU v5e.

    JAX_PLATFORMS=cpu python -m pytest bench/tests

``phi3_longctx_spans_1s.xplane.pb`` is one second of a traced run of
``phi3-medium-14b-d10.longctx`` whose engine writes its own spans; the
reduction must give the values that run printed.  The older
``phi3_longctx_1s.xplane.pb`` predates the spans: every value is None
there, as on a program without them.
"""
from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import program_trace  # noqa: E402
import trace_reduce  # noqa: E402

DATA = BENCH / "tests" / "data"
SPANS = str(DATA / "phi3_longctx_spans_1s.xplane.pb")
NO_SPANS = str(DATA / "phi3_longctx_1s.xplane.pb")

# what the traced run printed (TPU v5 lite, seed 2300001421, 1 s slice)
PRINTED = {"sched_host_ms": 0.47135241666666666,
           "launch_host_ms": 3.8087291666666676,
           "idle_in_step_share": 1.5091994113500526}


def test_values_the_chip_run_printed():
    got = program_trace.metrics(SPANS)
    for name, want in PRINTED.items():
        assert got[name] == pytest.approx(want, rel=1e-9), name


def test_phases_fit_their_steps_and_idle_in_steps_fits_idle():
    red = program_trace.reduce_spans(SPANS)
    assert red["n_steps"] > 0
    assert 0 < red["sched_host_s"] + red["launch_host_s"] <= red["step_s"]
    idle = 1.0 - trace_reduce.reduce_trace(SPANS)["busy_s"] / red["window_s"]
    assert 0 < red["idle_in_step_s"] / red["window_s"] <= idle


def test_device_clock_is_shifted_onto_the_hosts():
    """Unshifted, the trace has device programs starting before the host
    began to enqueue them; the shift is the least that undoes it."""
    shift, = program_trace.reduce_spans(SPANS)["clock_shift_s"]
    assert 0 < shift < 0.005


def test_every_program_run_follows_its_own_dispatch():
    red = program_trace.reduce_spans(SPANS)
    for module, join in red["dispatch"].items():
        assert join["runs"] > 0, module
        assert join["matched"] == join["runs"] == join["dispatches"], join
    decode = red["dispatch"]["jit_decode_step"]["steps"]
    assert decode == sorted(set(decode))         # one decode run per step


def test_a_trace_without_the_programs_spans_gives_none():
    assert program_trace.reduce_spans(NO_SPANS) is None
    assert program_trace.metrics(NO_SPANS) is None
