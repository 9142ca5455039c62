"""repro.perf — the repo's single counter-calibrated measurement surface.

The paper's methodology is a pipeline: calibrate performance counters on
programs with *known* counts, classify each channel reliable/unreliable
at 5% tolerance, then use only validated channels to explain application
performance.  This package is that pipeline as an API:

  measure.py    the ONE warm-up + ``block_until_ready`` + interleaved-
                repeat wall-clock implementation (medians over interleaved
                repeats — CPU wall time on this class of box swings ±50%
                between processes, so rivals are timed round-robin and
                compared by median).  Every timing loop in ``benchmarks/``
                and ``core/`` goes through ``measure()``; every
                instrumentation timestamp (serve engine steps, trainer
                straggler watchdog) goes through ``now()``.

  channels.py   the XLA cost channels (``cost_analysis()`` flops / bytes /
                transcendentals + the HLO op histogram) gated *at read
                time* by the Table-1 calibration verdicts: an unreliable
                channel returns the caller-supplied analytic model value
                tagged ``source="model"`` instead of a silently-wrong
                counter — the paper's treatment of its broken "vector ins"
                event.

  report.py     the canonical ``Report`` JSON schema every benchmark
                emits (``benchmarks/common.save_result``), making
                ``benchmarks/results/`` one machine-checkable format
                (``python -m repro.perf --validate ...``).
"""
from repro.perf.channels import (  # noqa: F401
    Calibration,
    ChannelValue,
    Channels,
    calibrate,
    channels_for,
    default_calibration,
)
# NOTE: the measure() *function* is deliberately not re-exported here —
# it would shadow the repro.perf.measure submodule attribute.  Import it
# as `from repro.perf.measure import measure`.
from repro.perf.measure import Measurement, now  # noqa: F401
from repro.perf.report import (  # noqa: F401
    Report,
    make_report,
    validate,
)
