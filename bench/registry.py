"""Finds what belongs to a cell by name: the configuration file, the
traffic mix, the fixed rate, and the code a file names (the mix's
generator and distributions, the configuration's reference, each metric's
reader).  Nothing here knows a cell, a configuration, a mix, a reference
or a metric by name; a new one is added by adding its files and its
entries in BENCHMARK.json."""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
import re

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                     f"{[w['name'] for w in bench['workloads']]}")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"unknown config {name!r}")


def load_config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / config_entry(bench, name)["file"]).read_text())


def load_traffic(mix: str, root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{mix}.json").read_text())


def load_rate(cell: str, root: pathlib.Path = ROOT) -> dict:
    """The fixed offered load of an open-loop cell (``bench/rates``)."""
    return json.loads((root / "bench" / "rates" / f"{cell}.json").read_text())


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: end-to-end ones
    without the trace, per-layer ones with it."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


@functools.lru_cache(maxsize=None)
def module(kind: str, name: str, root: pathlib.Path = ROOT):
    """The module ``bench/<kind>/<name>.py``, loaded once: a metric
    reader (``metrics``), a traffic generator (``generators``), a
    distribution of lengths or gaps (``distributions``) or a plain
    reference (``reference``)."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """``read(record) -> float | None`` from ``bench/metrics/<name>.py``."""
    return module("metrics", name, root).read
