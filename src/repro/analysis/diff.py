"""Layer 3 — the differential gate: live fingerprints vs committed
baselines.

``python -m repro.analysis --diff`` collects the pinned programs' live
fingerprints (``repro.analysis.fingerprint.collect_fingerprints``),
loads the checked-in baselines from ``src/repro/analysis/baselines/``
(one ``<target>.json`` per pinned program), and turns every regression
into a typed, waivable :class:`~repro.analysis.findings.Finding` on the
``<diff:<target>>`` pseudo-path:

``new-gather`` (error)
    gather or scatter ops appeared in — or grew on — a program whose
    baseline pinned fewer (each kind is compared on its own).  The headline drift: the paged decode path
    is gather-free by construction (PR 6) and must stay that way.

``flops-inflation`` (warning)
    counter flops or bytes grew beyond tolerance (default 5%) vs the
    baseline — the program is doing materially more work for the same
    shapes.

``lost-donation`` (error)
    a donating program's input/output aliasing dropped to zero — the
    donated buffer is silently copied every step.

``new-finding-class`` (warning)
    a trace-lint rule now fires on a program it was clean on.

``layout-change`` (warning)
    input dtypes or sharding layout changed vs the baseline.

``missing-baseline`` (error)
    a pinned program has no committed baseline; the CLI maps an unwaived
    one to exit 2 (usage: run ``--update-baselines`` and commit).

This module is **stdlib-only**: collection lives in
``repro.analysis.fingerprint`` (jax) and is imported lazily through
:func:`collect_fingerprints`, which tests monkeypatch to feed synthetic
fingerprints.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding
from repro.analysis.registry import DIFF_RULES

#: committed baselines live next to this module, one JSON per target
BASELINE_DIR = os.path.join(os.path.dirname(__file__), "baselines")

#: relative growth in counter flops/bytes tolerated before
#: ``flops-inflation`` fires (constant folding and fusion jitter the
#: totals a little across minor jax versions; 5% is structural change)
FLOPS_TOLERANCE = 0.05


def collect_fingerprints(targets: Optional[Sequence[str]] = None
                         ) -> Dict[str, Dict[str, Any]]:
    """Live fingerprints of the pinned programs (lazy jax import —
    monkeypatch THIS name to feed synthetic fingerprints in tests)."""
    from repro.analysis import fingerprint
    return fingerprint.collect_fingerprints(targets)


def pinned_targets() -> Tuple[str, ...]:
    from repro.analysis import fingerprint
    return fingerprint.TARGETS


# ---------------------------------------------------------------------------
# baseline IO
# ---------------------------------------------------------------------------
def baseline_path(name: str, baseline_dir: Optional[str] = None) -> str:
    return os.path.join(baseline_dir or BASELINE_DIR, f"{name}.json")


def load_baselines(baseline_dir: Optional[str] = None
                   ) -> Dict[str, Dict[str, Any]]:
    """Every committed baseline ({target: fingerprint})."""
    d = baseline_dir or BASELINE_DIR
    out: Dict[str, Dict[str, Any]] = {}
    if not os.path.isdir(d):
        return out
    for fname in sorted(os.listdir(d)):
        if not fname.endswith(".json"):
            continue
        with open(os.path.join(d, fname)) as fh:
            out[fname[:-len(".json")]] = json.load(fh)
    return out


def save_baselines(fingerprints: Dict[str, Dict[str, Any]],
                   baseline_dir: Optional[str] = None) -> List[str]:
    """Write one ``<target>.json`` per fingerprint (sorted keys, stable
    bytes); returns the written paths."""
    d = baseline_dir or BASELINE_DIR
    os.makedirs(d, exist_ok=True)
    paths = []
    for name in sorted(fingerprints):
        path = baseline_path(name, d)
        with open(path, "w") as fh:
            json.dump(fingerprints[name], fh, indent=2, sort_keys=True)
            fh.write("\n")
        paths.append(path)
    return paths


# ---------------------------------------------------------------------------
# the drift rules
# ---------------------------------------------------------------------------
def _finding(rule: str, name: str, message: str,
             context: Optional[Dict[str, Any]] = None) -> Finding:
    return Finding(rule, DIFF_RULES[rule].severity, f"<diff:{name}>", 0,
                   message, context=context)


def diff_fingerprint(name: str, base: Dict[str, Any],
                     live: Dict[str, Any], *,
                     flops_tolerance: float = FLOPS_TOLERANCE
                     ) -> List[Finding]:
    """Every drift finding for one pinned program."""
    findings: List[Finding] = []

    for kind in ("gather", "scatter"):
        b_n = int(base.get(f"{kind}_ops", 0))
        l_n = int(live.get(f"{kind}_ops", 0))
        if l_n > b_n:
            findings.append(_finding(
                "new-gather", name,
                f"program {name}: {l_n} {kind} op(s) vs {b_n} in the "
                "baseline — a mispriced access pattern crept back into "
                "a pinned program",
                context={"kind": kind, "baseline": b_n, "live": l_n}))

    b_cnt = base.get("counters", {}) or {}
    l_cnt = live.get("counters", {}) or {}
    for ch in ("flops", "bytes"):
        b = float(b_cnt.get(ch, 0.0))
        l = float(l_cnt.get(ch, 0.0))
        if b > 0 and l > b * (1.0 + flops_tolerance):
            findings.append(_finding(
                "flops-inflation", name,
                f"program {name}: counter {ch} grew {l / b - 1.0:+.1%} "
                f"({b:.3g} -> {l:.3g}), beyond the "
                f"{flops_tolerance:.0%} tolerance "
                f"(verdict: {l_cnt.get('verdict')})",
                context={"channel": ch, "baseline": b, "live": l,
                         "tolerance": flops_tolerance}))

    if (base.get("donated") and int(base.get("alias_pairs", 0)) > 0
            and int(live.get("alias_pairs", 0)) == 0):
        findings.append(_finding(
            "lost-donation", name,
            f"program {name}: baseline had "
            f"{base['alias_pairs']} input/output alias pair(s), live has "
            "none — the donated buffers are copied every call",
            context={"baseline": int(base["alias_pairs"]), "live": 0}))

    new_rules = sorted(set(live.get("finding_rules", ()))
                       - set(base.get("finding_rules", ())))
    if new_rules:
        findings.append(_finding(
            "new-finding-class", name,
            f"program {name}: trace rule(s) {new_rules} now fire on a "
            "program the baseline had clean of them",
            context={"new_rules": new_rules,
                     "baseline_rules":
                         sorted(base.get("finding_rules", ()))}))

    b_dtypes = sorted(base.get("input_dtypes", ()))
    l_dtypes = sorted(live.get("input_dtypes", ()))
    if b_dtypes != l_dtypes:
        findings.append(_finding(
            "layout-change", name,
            f"program {name}: input dtypes changed "
            f"{b_dtypes} -> {l_dtypes}",
            context={"baseline": b_dtypes, "live": l_dtypes}))
    elif base.get("sharding") != live.get("sharding"):
        findings.append(_finding(
            "layout-change", name,
            f"program {name}: sharding layout changed vs the baseline",
            context={"baseline": base.get("sharding"),
                     "live": live.get("sharding")}))

    return findings


def diff_all(live: Dict[str, Dict[str, Any]],
             baselines: Dict[str, Dict[str, Any]], *,
             flops_tolerance: float = FLOPS_TOLERANCE) -> List[Finding]:
    """Drift findings across every live program (sorted by target).

    A live program without a baseline is a ``missing-baseline`` error
    (the CLI maps an unwaived one to exit 2).  Baselines without a live
    program are ignored here — retired targets are deleted with the
    code change that retires them.
    """
    findings: List[Finding] = []
    for name in sorted(live):
        base = baselines.get(name)
        if base is None:
            findings.append(_finding(
                "missing-baseline", name,
                f"pinned program {name} has no committed baseline under "
                f"{os.path.relpath(BASELINE_DIR)} — run "
                "`python -m repro.analysis --update-baselines` and "
                "commit the JSON"))
            continue
        findings.extend(diff_fingerprint(
            name, base, live[name], flops_tolerance=flops_tolerance))
    return findings
