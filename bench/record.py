"""What one run hands to the metric readers, and the arithmetic they
share.  A reader that finds nothing to read returns None, and the run
leaves that metric out."""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from costs import Shapes, chunk_flops, paged_attn_call


def percentile(values, p: float):
    """Empirical percentile (0..100) as ``repro.serve.slo.percentile``
    takes it; None on an empty sample."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), p))


@dataclass
class Record:
    cell: str
    config: dict
    shapes: Shapes
    peaks: dict
    window: object                 # serve_loop.Window
    setup_s: float
    trace: dict | None = None      # trace_reduce.reduce_trace output
    extra: dict = field(default_factory=dict)

    # -- the window's requests ---------------------------------------------
    def due_in_window(self):
        w = self.window
        return [e for e in w.events.values() if w.open_s <= e.due_s < w.close_s]

    def ttfts(self):
        """TTFT from the due time of every request due in the window; one
        that never got a first token counts up to the last instant served."""
        end = self.extra.get("served_until_s", self.window.close_s)
        return [(e.token_s[0] if e.token_s else end) - e.due_s
                for e in self.due_in_window()]

    def tbt_gaps(self):
        w = self.window
        out = []
        for e in w.events.values():
            ts = [t for t in e.token_s if w.open_s <= t <= w.close_s]
            out += list(np.diff(ts))
        return out

    def tokens_in_window(self) -> int:
        w = self.window
        return sum(1 for e in w.events.values() for t in e.token_s
                   if w.open_s <= t <= w.close_s)

    # -- the traced steps --------------------------------------------------
    def traced_steps(self):
        w = self.window
        if w.trace_span is None:
            return []
        lo, hi = w.trace_span
        return [s for s in w.steps if s.start_s >= lo and s.done_s <= hi]

    def step_flops(self, step) -> float:
        s = self.shapes
        fl = sum(chunk_flops(s, p, 1, True) for p in step.decode_pos)
        fl += sum(chunk_flops(s, p0, n, samples) for p0, n, samples in step.prefills)
        return fl

    def kernel_floor_s(self, step) -> tuple:
        """Least time the paged kernel needs for ``step`` on this chip,
        summed over its calls (every layer, every row), and the seconds
        that the memory bound and the compute bound give."""
        s, pk = self.shapes, self.peaks
        mem = comp = floor = 0.0
        rows = [(p, 1) for p in step.decode_pos] + [(p0, n) for p0, n, _ in step.prefills]
        for p0, n in rows:
            fl, by = paged_attn_call(s, p0, n)
            m, c = by / pk["hbm_bytes_per_s"], fl / pk["bf16_flops"]
            mem += m * s.n_layers
            comp += c * s.n_layers
            floor += max(m, c) * s.n_layers
        return floor, mem, comp
