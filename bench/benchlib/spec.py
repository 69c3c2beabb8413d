"""Find a cell and everything it names, by name, in files of their own.

    BENCHMARK.json                the cells and their metrics
    bench/workloads/<cell>.json   config, traffic, engine sizes, check limit
    bench/configs/<config>.json   sizes as run, source, cut, reference module
    bench/traffic/<mix>.json      parameters of the one traffic generator
    bench/metrics/<metric>.py     one reader per metric
    bench/reference/<module>.py   plain float32 reference of a block family
    bench/peaks.json              chip peaks by device_kind

Adding a cell, a configuration, a mix or a metric adds a file; no file
that is there needs an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(ValueError):
    """A cell, file or name that the benchmark cannot resolve."""


def _check_name(name: str) -> str:
    if not _NAME.match(name):
        raise SpecError(f"not a valid name: {name!r}")
    return name


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return load_json(bench_dir / "configs" / f"{_check_name(name)}.json")


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return load_json(bench_dir / "traffic" / f"{_check_name(name)}.json")


def workload(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return load_json(bench_dir / "workloads" / f"{_check_name(name)}.json")


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """Peaks of one chip. A kind the table lacks is an error, not a
    default."""
    table = load_json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device_kind {device_kind!r}; "
                        f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def _load_module(path: Path, modname: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_module(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    path = bench_dir / "metrics" / f"{_check_name(name)}.py"
    return _load_module(path, "bench_metric_" + re.sub(r"\W", "_", name))


def reference_module(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    path = bench_dir / "reference" / f"{_check_name(name)}.py"
    return _load_module(path, "bench_reference_" + re.sub(r"\W", "_", name))


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    name: str
    unit: str


@dataclasses.dataclass
class Cell:
    """One entry of BENCHMARK.json's workloads, with all it names."""

    name: str
    chips: int
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[MetricSpec]
    per_layer: List[MetricSpec]


def _metrics_for(entries: List[dict], cell: str) -> List[MetricSpec]:
    """The metrics a cell reports: those with no "workloads" list, and
    those whose list names it."""
    return [MetricSpec(m["name"], m["unit"]) for m in entries
            if cell in m.get("workloads", [cell])]


def cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve a cell of BENCHMARK.json and every file it names."""
    bench = benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{sorted(entries)}")
    entry = entries[name]
    bench_dir = root / "bench"
    wl = workload(name, bench_dir)
    for key in ("config", "traffic"):
        if wl[key] != entry[key]:
            raise SpecError(f"{name}: bench/workloads says {key} "
                            f"{wl[key]!r}, BENCHMARK.json {entry[key]!r}")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        workload=wl,
        config=config(entry["config"], bench_dir),
        traffic=traffic(entry["traffic"], bench_dir),
        end_to_end=_metrics_for(bench["end_to_end"], name),
        per_layer=_metrics_for(bench["per_layer"], name),
    )
