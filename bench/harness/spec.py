"""The benchmark's data: BENCHMARK.json, one file per configuration, one per
traffic mix, one reader per per-layer metric, one module per model family.
Every lookup is by the name that BENCHMARK.json or the configuration file
gives, so a later change adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
TRAFFIC_DIR = os.path.join(BENCH_DIR, "traffic")
METRICS_DIR = os.path.join(BENCH_DIR, "metrics")
FAMILIES_DIR = os.path.join(BENCH_DIR, "families")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    model: Dict            # the configuration file as run
    traffic: Dict          # the traffic mix file
    end_to_end: List[Dict]  # metric entries this cell reports
    per_layer: List[Dict]


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_traffic(name: str) -> Dict:
    with open(os.path.join(TRAFFIC_DIR, name + ".json")) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        model = json.load(f)
    family(model)          # an unknown architecture fails before any set-up
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    layer = [m for m in bench["per_layer"] if _reports(m, name)]
    return Cell(name, w["config"], w["traffic"], int(w["chips"]), model,
                load_traffic(w["traffic"]), e2e, layer)


def metric_reader(name: str) -> Callable:
    """``read(run) -> float | None`` from ``metrics/<name>.py``."""
    path = os.path.join(METRICS_DIR, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


_FAMILIES: Dict[str, ModuleType] = {}


def family(model: Dict) -> ModuleType:
    """The family module ``families/<architectures[0]>.py`` of a
    configuration file, loaded once: its ``program_fields``, ``shapes``,
    ``Reference`` and work counts.  A file that names no architecture, or
    one with no module, is an error; there is no default family."""
    arch = (model.get("architectures") or [None])[0]
    path = os.path.join(FAMILIES_DIR, f"{arch}.py")
    if path in _FAMILIES:
        return _FAMILIES[path]
    if arch is None or not os.path.isfile(path):
        names = sorted(f[:-3] for f in os.listdir(FAMILIES_DIR)
                       if f.endswith(".py"))
        raise KeyError(f"configuration {model.get('registry')!r} names "
                       f"architecture {arch!r}: no family module {path}; "
                       f"known: {names}")
    spec = importlib.util.spec_from_file_location("bench_family_" + arch,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _FAMILIES[path] = mod
    return mod


def program_config(model: Dict, capacity: Optional[int] = None):
    """The program's registered configuration for ``model``, after checking
    that every size it runs at is the one the file states (the fields its
    family's ``program_fields`` names)."""
    from repro.configs import get_config
    want = family(model).program_fields(model, capacity)
    pc = get_config(model["registry"])
    got = {k: getattr(pc, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise ValueError(f"program config {pc.name} differs from the "
                         f"benchmark's file (program, file): {bad}")
    return pc
