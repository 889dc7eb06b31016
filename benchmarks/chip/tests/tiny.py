"""Tiny cells for CPU runs of the harness: the configurations' shapes of
block at toy widths, through the same files, runner and reference.

Besides the benchmark's cells, `phi3-medium-14b.chat-shared` runs from
files kept in `tests/data` (a cell left out of the benchmark, PERF.md), so
the reference's GQA, SwiGLU, RMSNorm and RoPE paths stay tested against
the program."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from chipbench import files

DATA = Path(__file__).resolve().parent / "data"
TINY_MODEL = dict(n_layers=2, d_model=2048, head_dim=64, d_ff=2048, vocab=2048)
TINY_SERVING = dict(slots=4, max_len=512, pool_pages=24)
# the compared number's limit at this size, from CPU readings of these
# cells on three seeds: the program 0 to 1.1e-4, the control 0.021 to 2.8
TINY_CHECK = {"logit_gap_mean": 0.005}
DATA_CELLS = ["phi3-medium-14b.chat-shared"]


def load_data(name: str) -> dict:
    return json.loads((DATA / f"{name}.json").read_text())


def tiny_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(spec, cell, config, mix) of a BENCHMARK.json cell or of one of
    DATA_CELLS, cut to a size a CPU test holds: every width shrunk, heads
    kept in their ratio."""
    spec = files.load_spec()
    if name in DATA_CELLS:
        config, mix_name = name.split(".", 1)
        cell = {"name": name, "config": config, "traffic": mix_name,
                "chips": 1}
        cfg, mix = load_data(config), load_data(mix_name)
    else:
        cell = files.workload(spec, name)
        cfg = copy.deepcopy(files.load_config(spec, cell["config"]))
        mix = copy.deepcopy(files.load_traffic(cell["traffic"]))
    m = cfg["model"]
    group = m["n_heads"] // m["n_kv_heads"]
    m.update(TINY_MODEL, n_heads=4, n_kv_heads=4 // min(group, 4))
    cfg["serving"].update(TINY_SERVING)
    cfg["check"] = dict(TINY_CHECK)
    mix["warmup_s"] = 1.0
    if mix["arrivals"]["kind"] == "poisson":
        mix["arrivals"]["rate"] = 2.0
    else:
        mix["arrivals"].update(depth_per_slot=1, pool_per_s=60)
    for key in ("prompt", "user"):
        if key in mix:
            mix[key].update(lo=8, hi=150)
            if "median" in mix[key]:
                mix[key]["median"] = 40
    if "system" in mix:
        mix["system"].update(lo=128, hi=200)
    mix["output"].update(lo=32, hi=96)
    if "median" in mix["output"]:
        mix["output"]["median"] = 48
    return spec, cell, cfg, mix
