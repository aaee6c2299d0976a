"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own under ``bench/``, named after
it, so a later change adds a cell by adding files and entries:

    bench/configs/<config>.json    a deployment: spec, backend, model shapes
    bench/traffic/<traffic>.json   a traffic mix: its kind and parameters
    bench/cells/<workload>.json    optional: parameters one cell sets for
                                   its mix (an open loop's rate)
    bench/metrics/<metric>.py      the reader of one per-layer metric,
                                   ``read(run) -> float | None``
    bench/peaks.json               device peaks keyed by ``device_kind``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

from .traffic import validate

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of the benchmark with its files resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return load_json(path)


def _listed(metric: dict, workload: str) -> Optional[bool]:
    names = metric.get("workloads")
    return None if names is None else workload in names


def resolve(bench: dict, workload: str, base: Path = BENCH_DIR) -> Cell:
    """The cell named ``workload``, its config, mix and metrics (the data
    files are looked up under ``base``)."""
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise ValueError(f"no workload {workload!r} in the benchmark; "
                         f"known: {sorted(entries)}")
    w = entries[workload]
    config = load_json(base / "configs" / f"{w['config']}.json")
    traffic = load_json(base / "traffic" / f"{w['traffic']}.json")
    own = base / "cells" / f"{workload}.json"
    if own.exists():
        traffic = {**traffic, **load_json(own)}
    e2e = [m for m in bench["end_to_end"] if _listed(m, workload) in (None, True)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _listed(m, workload)
                 or (_listed(m, workload) is None and m["moves"] in e2e_names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=validate(traffic), end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_peaks(kind: str) -> Dict[str, float]:
    """The peaks of one device kind; a kind not in the table is an error."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]
