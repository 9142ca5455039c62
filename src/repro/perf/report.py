"""The canonical benchmark Report schema.

Every artifact under ``benchmarks/results/*.json`` is one serialized
:class:`Report`: benchmark name, rows, optional channel summary, the
calibration reliability verdicts the rows were read under, the hardware
ceiling the model columns refer to, and environment metadata — one
machine-checkable shape for every figure/table plus the serve benchmark.

``benchmarks/common.save_result`` writes it; this module validates it:

    PYTHONPATH=src python -m repro.perf --validate benchmarks/results

exits non-zero when any top-level JSON in the directory fails the schema
(the ``scripts/ci.sh --bench-smoke`` gate).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional

from repro.core.costmodel import TPU_V5E, HWSpec

SCHEMA = "repro.perf.report"
SCHEMA_VERSION = 1


def environment_meta() -> Dict[str, Any]:
    import platform

    import jax

    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "python": platform.python_version(),
        "platform": platform.platform(),
    }


def hw_meta(hw: HWSpec = TPU_V5E) -> Dict[str, Any]:
    return {"name": hw.name, "peak_flops_bf16": hw.peak_flops_bf16,
            "hbm_bw": hw.hbm_bw, "ici_bw": hw.ici_bw}


@dataclasses.dataclass
class Report:
    benchmark: str
    rows: List[Dict[str, Any]]
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    reliability: Dict[str, bool] = dataclasses.field(default_factory=dict)
    channels: Optional[Dict[str, Any]] = None
    hw: Dict[str, Any] = dataclasses.field(default_factory=hw_meta)
    environment: Dict[str, Any] = dataclasses.field(
        default_factory=environment_meta)
    created_unix: float = dataclasses.field(default_factory=time.time)

    def to_payload(self) -> Dict[str, Any]:
        return {
            "schema": SCHEMA,
            "schema_version": SCHEMA_VERSION,
            "benchmark": self.benchmark,
            "created_unix": self.created_unix,
            "environment": self.environment,
            "hw": self.hw,
            "meta": self.meta,
            "reliability": self.reliability,
            "channels": self.channels,
            "rows": self.rows,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_payload(), indent=2, default=str)

    @classmethod
    def from_payload(cls, payload: Dict[str, Any]) -> "Report":
        errors = validate(payload)
        if errors:
            raise ValueError(f"invalid Report payload: {errors}")
        return cls(benchmark=payload["benchmark"], rows=payload["rows"],
                   meta=payload["meta"], reliability=payload["reliability"],
                   channels=payload.get("channels"), hw=payload["hw"],
                   environment=payload["environment"],
                   created_unix=payload["created_unix"])


def make_report(benchmark: str, rows: List[Dict[str, Any]], *,
                meta: Optional[Dict[str, Any]] = None,
                reliability: Optional[Dict[str, bool]] = None,
                channels: Optional[Dict[str, Any]] = None,
                hw: HWSpec = TPU_V5E) -> Report:
    return Report(benchmark=benchmark, rows=list(rows), meta=dict(meta or {}),
                  reliability=dict(reliability or {}), channels=channels,
                  hw=hw_meta(hw))


_REQUIRED = {
    "schema": str,
    "schema_version": int,
    "benchmark": str,
    "created_unix": (int, float),
    "environment": dict,
    "hw": dict,
    "meta": dict,
    "reliability": dict,
    "rows": list,
}
_HW_KEYS = ("name", "peak_flops_bf16", "hbm_bw")

# open-loop serving rows (serve_bench --open-loop) carry a "latency"
# block produced by repro.serve.slo.latency_summary; when present it
# must be the full telemetry surface, not a partial dict
_LATENCY_KEYS = ("requests", "completed", "goodput_tok_s", "makespan_s",
                 "queue_depth")
_LATENCY_DISTS = ("ttft_s", "tbt_s", "e2e_s", "queue_wait_s")
_DIST_KEYS = ("p50", "p90", "p99", "mean", "max", "n")
_SLO_KEYS = ("ttft_s", "tbt_s", "attainment", "good_requests")

# serve_bench meta carries the trace-lint analysis block per traced
# engine (``engine.analysis_meta``); each program record must carry the
# canonical compile-drift fingerprint (``repro.analysis.fingerprint``)
# so the artifact pins program *shape* next to the measured numbers —
# the same dict ``python -m repro.analysis --diff`` gates on
_FINGERPRINT_KEYS = ("version", "label", "op_histogram", "total_ops",
                     "gather_ops", "while_bodies", "input_dtypes",
                     "donated", "alias_pairs", "counters", "finding_rules")


def _validate_latency(lat: Any, where: str, errors: List[str]) -> None:
    if not isinstance(lat, dict):
        errors.append(f"{where} is {type(lat).__name__}, expected object")
        return
    for key in _LATENCY_KEYS:
        if key not in lat:
            errors.append(f"{where} missing key {key!r}")
    for dist in _LATENCY_DISTS:
        blk = lat.get(dist)
        if not isinstance(blk, dict):
            errors.append(f"{where}[{dist!r}] missing or not an object")
            continue
        for key in _DIST_KEYS:
            if not isinstance(blk.get(key), (int, float)):
                errors.append(
                    f"{where}[{dist!r}][{key!r}] missing or non-numeric")
    slo = lat.get("slo")
    if slo is not None:
        if not isinstance(slo, dict):
            errors.append(f"{where}['slo'] is not an object")
        else:
            for key in _SLO_KEYS:
                if not isinstance(slo.get(key), (int, float)):
                    errors.append(
                        f"{where}['slo'][{key!r}] missing or non-numeric")


def _validate_analysis(block: Any, where: str, errors: List[str]) -> None:
    """An analysis block's traced programs must each carry a complete
    fingerprint dict (missing keys mean the artifact cannot back the
    compile-drift gate)."""
    if not isinstance(block, dict):
        return
    programs = block.get("programs")
    if not isinstance(programs, dict):
        return
    for label, prog in programs.items():
        loc = f"{where}['programs'][{label!r}]"
        if not isinstance(prog, dict):
            errors.append(f"{loc} is not an object")
            continue
        fp = prog.get("fingerprint")
        if not isinstance(fp, dict):
            errors.append(f"{loc} missing its 'fingerprint' block")
            continue
        for key in _FINGERPRINT_KEYS:
            if key not in fp:
                errors.append(f"{loc}['fingerprint'] missing key {key!r}")
        cnt = fp.get("counters")
        if not isinstance(cnt, dict) or "verdict" not in cnt:
            errors.append(
                f"{loc}['fingerprint']['counters'] missing 'verdict'")


def validate(payload: Any) -> List[str]:
    """Schema check; returns a list of error strings (empty = valid)."""
    errors: List[str] = []
    if not isinstance(payload, dict):
        return [f"payload is {type(payload).__name__}, expected object"]
    for key, typ in _REQUIRED.items():
        if key not in payload:
            errors.append(f"missing required key {key!r}")
        elif not isinstance(payload[key], typ):
            errors.append(
                f"key {key!r} is {type(payload[key]).__name__}, "
                f"expected {typ}")
    if errors:
        return errors
    if payload["schema"] != SCHEMA:
        errors.append(f"schema is {payload['schema']!r}, expected {SCHEMA!r}")
    if payload["schema_version"] > SCHEMA_VERSION:
        errors.append(
            f"schema_version {payload['schema_version']} is newer than "
            f"this reader ({SCHEMA_VERSION})")
    for i, row in enumerate(payload["rows"]):
        if not isinstance(row, dict):
            errors.append(f"rows[{i}] is {type(row).__name__}, "
                          "expected object")
        elif "latency" in row:
            _validate_latency(row["latency"], f"rows[{i}]['latency']",
                              errors)
    meta = payload["meta"]
    _validate_analysis(meta.get("analysis"), "meta['analysis']", errors)
    paged = meta.get("paged")
    if isinstance(paged, dict) and isinstance(paged.get("engines"), dict):
        for name, blk in paged["engines"].items():
            _validate_analysis(
                blk, f"meta['paged']['engines'][{name!r}]", errors)
    for ch, verdict in payload["reliability"].items():
        if not isinstance(verdict, bool):
            errors.append(f"reliability[{ch!r}] is not a bool")
    for key in _HW_KEYS:
        if key not in payload["hw"]:
            errors.append(f"hw missing key {key!r}")
    ch = payload.get("channels")
    if ch is not None and not isinstance(ch, dict):
        errors.append(f"channels is {type(ch).__name__}, expected object")
    return errors


def validate_path(path: pathlib.Path) -> List[str]:
    try:
        payload = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [f"unreadable JSON: {e}"]
    return validate(payload)


def main(argv: Optional[List[str]] = None) -> int:
    # reporting/exit contract shared with `python -m repro.analysis`:
    # offending files print as `FAIL <path>` + indented `  - ` lines,
    # clean files print nothing, the last line is a
    # `<clean>/<scanned> files clean` summary; exit 0 = clean,
    # 1 = findings, 2 = usage error / nothing to scan.
    args = [a for a in (argv if argv is not None else sys.argv[1:])
            if a != "--validate"]
    if not args:
        print("usage: python -m repro.perf --validate "
              "<file.json | results-dir> ...")
        return 2
    files: List[pathlib.Path] = []
    for a in args:
        p = pathlib.Path(a)
        # directories: top-level JSONs only — nested dirs (e.g. the
        # dry-run artifacts under results/dryrun/) are other formats
        files.extend(sorted(p.glob("*.json")) if p.is_dir() else [p])
    if not files:
        print("no JSON files to validate")
        return 2
    n_bad = 0
    for f in files:
        errors = validate_path(f)
        if errors:
            n_bad += 1
            print(f"FAIL {f}")
            for e in errors:
                print(f"  - {e}")
    print(f"{len(files) - n_bad}/{len(files)} files clean")
    return 1 if n_bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
