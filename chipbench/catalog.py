"""Find the benchmark's parts by the names ``BENCHMARK.json`` gives them."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

#: The benchmark's own directory; ``BENCHMARK.json`` sits one level up.
HERE = Path(__file__).resolve().parent


def load_benchmark(path: Path = HERE.parent / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def config(bench: dict, name: str, base: Path = HERE) -> dict:
    """The configuration's file, as ``BENCHMARK.json`` names it."""
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(base.parent / c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def part(kind: str, name: str, base: Path = HERE) -> dict:
    """``<base>/<kind>/<name>.json``: a traffic mix or a cell's limits."""
    return load_json(base / kind / f"{name}.json")


def module(kind: str, name: str, base: Path = HERE):
    """``<base>/<kind>/<name>.py``, imported: an entry, a reference or a
    metric reader."""
    path = base / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not Path(path).is_file():
        raise FileNotFoundError(f"{path} is missing")
    with open(path) as f:
        return json.load(f)


def metrics_for(bench: dict, cell_name: str, trace: bool) -> list:
    """The metric entries a run of this cell reports: its end-to-end metrics
    with ``trace`` off, its per-layer metrics with it on."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]
