"""Discovery by name.  The harness holds no table of cells, configurations,
traffic mixes or metrics: ``BENCHMARK.json`` names them and each lives in a
file of its own under ``benchmark/``, found by that name.

    configs/<config>.json              sizes, source, guarantees, runner kind
    configs/<config>_reference.py      the configuration's plain reference
    traffic/<mix>.json                 parameters of one traffic mix
    layer_metrics/<metric>.py          one reader: read(obs) -> number | None
    runners/<kind>.py                  what executes a configuration
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                     f"(has {[c['name'] for c in spec['workloads']]})")


def load_config(spec: dict, name: str) -> dict:
    for entry in spec["configs"]:
        if entry["name"] == name:
            with open(ROOT / entry["file"]) as f:
                return json.load(f)
    raise SystemExit(f"benchmark: no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _module(path: Path, modname: str):
    if not path.exists():
        raise SystemExit(f"benchmark: {path.relative_to(ROOT)} is missing")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_runner(kind: str):
    return _module(BENCH / "runners" / f"{kind}.py", f"benchmark_runner_{kind}")


def load_reference(config_name: str):
    return _module(BENCH / "configs" / f"{config_name}_reference.py",
                   f"benchmark_reference_{config_name}")


def load_reader(metric: str):
    return _module(BENCH / "layer_metrics" / f"{metric}.py",
                   "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"))


def _applies(metric: dict, cell: str, reports) -> bool:
    """A metric with a ``workloads`` key belongs to the cells it lists;
    without one, to every cell that reports what it belongs with."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reports(metric)


def end_to_end_for(spec: dict, cell: str) -> list:
    return [m for m in spec["end_to_end"] if _applies(m, cell, lambda m: True)]


def per_layer_for(spec: dict, cell: str) -> list:
    e2e = {m["name"] for m in end_to_end_for(spec, cell)}
    return [m for m in spec["per_layer"]
            if _applies(m, cell, lambda m: m["moves"] in e2e)]


def read_layer_metrics(spec: dict, cell: str, obs: dict) -> dict:
    """Each per-layer metric of ``cell`` through its own reader.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in per_layer_for(spec, cell):
        value = load_reader(m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
