"""Finds a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, so a later PR adds a cell or a
metric by adding files and entries and edits nothing that exists:

    configs/<config>.json     sizes as run, source, reduced, assumed
    traffic/<traffic>.json    the mix (serve) or the job (train)
    workloads/<cell>.json     which runner, engine or mesh sizes, warm-up
    metrics/<metric>.json     which reader takes it, with arguments
    readers/<reader>.py       read(ctx, **args) -> number or None
    runners/<runner>.py       run(cell, opts) -> result dict
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str) -> Dict[str, Any]:
    """Everything one cell needs, gathered from its files."""
    bench = benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    workload = load_json(os.path.join(HERE, "workloads", name + ".json"))
    return {
        "name": name,
        "chips": entry["chips"],
        "config_name": entry["config"],
        "config": load_json(os.path.join(ROOT, configs[entry["config"]]["file"])),
        "traffic_name": entry["traffic"],
        "traffic": load_json(
            os.path.join(HERE, "traffic", entry["traffic"] + ".json")),
        "workload": workload,
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
    }


def metric_file(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(HERE, "metrics", name + ".json"))


def overlay(base: Dict[str, Any], patch: Dict[str, Any]) -> Dict[str, Any]:
    """``base`` with ``patch`` laid over it, nested objects merged."""
    out = dict(base)
    for key, value in patch.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = overlay(out[key], value)
        else:
            out[key] = value
    return out


def rehearsal(cell_: Dict[str, Any]) -> Dict[str, Any]:
    """The cell at the tiny sizes its files give under ``rehearse``: a
    CPU run of the same control flow, which measures nothing."""
    out = dict(cell_)
    for part in ("config", "traffic", "workload"):
        out[part] = overlay(cell_[part], cell_[part].get("rehearse", {}))
    return out


def llama_config(config: Dict[str, Any], **extra):
    """The program's config object from a published ``config.json``'s
    keys (the names Hugging Face uses)."""
    from ray_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        **extra)


def names(entries: List[Dict[str, Any]]) -> List[str]:
    return [e["name"] for e in entries]
