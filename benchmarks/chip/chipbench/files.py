"""Finds a cell's files by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; each lives in a file of
its own under this benchmark's directory, and each per-layer metric is a
reader of its own under `metrics/`. A configuration names its plain
reference (`reference/`) and its block module (`blocks/`). Adding a cell, a configuration, a mix
or a metric adds files and entries; nothing here names one of them.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]     # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                          # the checkout
SPEC_FILE = ROOT / "BENCHMARK.json"


def load_spec(path: Path = SPEC_FILE) -> dict:
    return json.loads(Path(path).read_text())


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in spec['workloads']]}")


def config_entry(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_config(spec: dict, name: str) -> dict:
    """The configuration file named by the spec's entry, as it is run."""
    cfg = json.loads((ROOT / config_entry(spec, name)["file"]).read_text())
    if cfg["name"] != name:
        raise ValueError(f"{name}: its file names itself {cfg['name']!r}")
    return cfg


def load_traffic(name: str) -> dict:
    path = BENCH_DIR / "traffic" / f"{name}.json"
    mix = json.loads(path.read_text())
    if mix["name"] != name:
        raise ValueError(f"{path}: names itself {mix['name']!r}")
    return mix


def load_module(kind: str, name: str):
    """benchmarks/chip/<kind>/<name>.py as a module (names may hold dots,
    so the module is loaded from its file, not imported by name)."""
    path = BENCH_DIR / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_block(cfg: dict):
    """The configuration's block module, `blocks/<its "block">.py`
    (`dense` where it names none): the program's tree, the window's work
    and the CPU tests' cut of that kind of layer stack."""
    return load_module("blocks", cfg.get("block", "dense"))


def metrics_for(spec: dict, cell: str, section: str) -> list[dict]:
    """The metrics of `section` ('end_to_end' or 'per_layer') this cell
    reports: those with no `workloads` key, and those that list it."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]
