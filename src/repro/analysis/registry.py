"""One discoverable rule catalog across every analysis layer.

``python -m repro.analysis --rules`` used to list the source rules only;
this module is the fix: the trace-lint, fingerprint-diff, and schedcheck
rule tables live (or are re-exported) here, keyed by layer, so the CLI
can print the whole registry without importing jax (the trace layer's
*implementation* stays in ``repro.analysis.trace``, which does import
jax — only the rule metadata lives here).

Layers:

``source``      ``repro.analysis.lint`` — AST rules over the scan set.
``trace``       ``repro.analysis.trace`` — compiled-program rules.
``diff``        ``repro.analysis.diff`` — fingerprint drift rules
                (``python -m repro.analysis --diff`` against the
                committed ``src/repro/analysis/baselines/*.json``).
``schedcheck``  ``repro.analysis.schedcheck`` — serve shadow-state
                transition rules (``ContinuousBatchingEngine(check=True)``).

Stdlib-only, like every module the CLI imports eagerly.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from repro.analysis.lint import SOURCE_RULES, Rule

#: compiled-program rules — implemented by ``repro.analysis.trace``
#: (which imports this table so the ids/docs exist in exactly one place)
TRACE_RULES: Dict[str, Rule] = {r.rule: r for r in (
    Rule("hot-gather", "warning",
         "gather access in the compiled module"),
    Rule("predication-density", "warning",
         "select density above threshold (predication-heavy lowering)"),
    Rule("scan-counter-blindness", "error",
         "while-lowered scan invalidates counter channels"),
    Rule("f32-upcast", "warning",
         "bf16/f16 program compiled to mostly-f32 instructions"),
    Rule("host-callback", "error",
         "host callback inside the compiled program"),
    Rule("missed-donation", "error",
         "donate_argnums requested but nothing aliased"),
)}

#: fingerprint drift rules — implemented by ``repro.analysis.diff``
DIFF_RULES: Dict[str, Rule] = {r.rule: r for r in (
    Rule("new-gather", "error",
         "gather/scatter ops appeared in (or grew on) a pinned program"),
    Rule("flops-inflation", "warning",
         "counter flops/bytes grew beyond tolerance vs the baseline"),
    Rule("lost-donation", "error",
         "input/output aliasing dropped from a donating program"),
    Rule("new-finding-class", "warning",
         "a trace-lint rule fires on a program it was clean on"),
    Rule("layout-change", "warning",
         "input dtypes / sharding layout changed vs the baseline"),
    Rule("missing-baseline", "error",
         "a pinned program has no committed baseline (run "
         "--update-baselines)"),
)}

#: serve shadow-state transition rules — ``repro.analysis.schedcheck``
SCHED_RULES: Dict[str, Rule] = {r.rule: r for r in (
    Rule("refcount-conservation", "error",
         "page refcounts != slot/prefix owner count (sum over shard)"),
    Rule("double-free", "error",
         "page freed below zero shadow references"),
    Rule("page-leak", "error",
         "allocated pages with no owner survive a drain"),
    Rule("slot-double-bind", "error",
         "one slot bound to two rids (or one rid to two slots)"),
    Rule("prefix-double-claim", "error",
         "a prefix-pool page claimed twice by one entry/slot"),
    Rule("illegal-admission", "error",
         "admission into an occupied/excluded/foreign-shard slot"),
    Rule("illegal-preemption", "error",
         "preemption victim older than the stalled request or off-shard"),
)}

#: (layer name, rule table) in reporting order
LAYERS: Tuple[Tuple[str, Dict[str, Rule]], ...] = (
    ("source", SOURCE_RULES),
    ("trace", TRACE_RULES),
    ("diff", DIFF_RULES),
    ("schedcheck", SCHED_RULES),
)


def all_rules() -> List[Tuple[str, Rule]]:
    """Every (layer, rule) pair, layer order then rule id."""
    out: List[Tuple[str, Rule]] = []
    for layer, table in LAYERS:
        out.extend((layer, table[k]) for k in sorted(table))
    return out
