"""The benchmark's data: BENCHMARK.json, one file per configuration, one per
traffic mix, one reader per per-layer metric.  Every lookup is by the name
that BENCHMARK.json gives, so a later change adds files and entries and
edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
TRAFFIC_DIR = os.path.join(BENCH_DIR, "traffic")
METRICS_DIR = os.path.join(BENCH_DIR, "metrics")


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


def program_config(model: Dict, capacity: Optional[int] = None):
    """The program's registered configuration for ``model``, after checking
    that every size it runs at is the one the file states."""
    from repro.configs import get_config
    pc = get_config(model["registry"])
    want = {
        "d_model": model["hidden_size"], "d_ff": model["intermediate_size"],
        "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "head_dim": model["head_dim"], "n_layers": model["num_hidden_layers"],
        "vocab_size": model["vocab_size"],
        "rope_theta": model["rope_theta"],
        "tie_embeddings": model["tie_word_embeddings"],
        "mlp_act": model["hidden_act"], "mlp_gated": True,
        "norm_type": "rmsnorm", "qkv_bias": False, "window": None,
        "family": "dense", "causal": True, "dtype": model["torch_dtype"],
        "logit_softcap": None, "final_softcap": None,
        "scale_embeddings": False, "block_pattern": ("attn",),
    }
    got = {k: getattr(pc, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise ValueError(f"program config {pc.name} differs from the "
                         f"benchmark's file (program, file): {bad}")
    window = model.get("sliding_window")
    if window is not None and capacity is not None and capacity > window:
        raise ValueError(f"capacity {capacity} exceeds the published "
                         f"sliding window {window}, which the program does "
                         "not implement")
    return pc
