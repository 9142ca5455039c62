"""What the engine's step says about itself: span names in the profiler's
trace, and one public record per executed step.

Spans are ``jax.profiler`` annotations, so they land in the profiler's
own trace on the clock of its device planes; with the profiler off each
costs about a microsecond.  ``ContinuousBatchingEngine.step`` opens one
``SERVE_STEP`` per step that has work, with its phases inside it in this
order: ``PLAN``, then on a step that admits ``ADMIT``, then ``DECODE``
when there are decode rows, one ``PREFILL`` per prefill row, and
``COMMIT``.  ``FLUSH`` nests wherever results are read back.

``StepEvent`` is the public record of a step that ran a plan
(``engine.last_event``): what it ran, whose tokens it committed, and the
device array to wait on for them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

# the whole of ContinuousBatchingEngine.step(): a StepTraceAnnotation
# whose ``step_num`` is the engine's step index.  A step whose plan comes
# back empty while work is queued (every row waits for pages) keeps the
# span, since it is opened before planning, but carries no step_num.
SERVE_STEP = "serve_step"
# the drafter's proposals and Scheduler.next_plan: admission, page
# growth, preemption and the step's host arrays
PLAN = "serve.plan"
# admission only: output-row recycling and the reset, prefix-copy and
# context-install programs of the slots entering this step
ADMIT = "serve.admit"
# building the decode program's arguments and dispatching it
DECODE = "serve.decode"
# one prefill row's arguments and dispatch (one span per row)
PREFILL = "serve.prefill"
# the EOS readback (when eos_id is set), Scheduler.commit, speculative
# feedback, stats and this step's StepEvent
COMMIT = "serve.commit"
# _flush_results: the device-to-host read of finished requests' tokens
FLUSH = "serve.flush"

#: the phases of a step, in the order they run inside SERVE_STEP
PHASES = (PLAN, ADMIT, DECODE, PREFILL, COMMIT)
#: every span name the engine writes
SPANS = (SERVE_STEP,) + PHASES + (FLUSH,)


@dataclasses.dataclass(frozen=True)
class StepEvent:
    """One executed step.  Counts are taken where the work happens:
    rows as planned, tokens as committed."""
    step: int                          # the engine's step index
    n_decode: int                      # decode rows run
    decode_pos: Tuple[int, ...]        # each decode row's first position
    # prefill rows: (first position, valid tokens, completes the prompt)
    prefills: Tuple[Tuple[int, int, bool], ...]
    # (rid, tokens committed) of every request that sampled this step
    sampled: Tuple[Tuple[int, int], ...]
    admitted: Tuple[int, ...]          # rids first scheduled this step
    finished: Tuple[int, ...]          # rids finished by this step
    # rids sent back to the queue since the previous event (planning
    # preempts, and a step whose plan comes back empty can too)
    preempted: Tuple[int, ...]
    # the device array this step's last program writes: once it is
    # ready, so are the step's tokens.  The next step donates it.
    ready: Any

    @property
    def n_prefill_tokens(self) -> int:
        return sum(n for _, n, _ in self.prefills)
