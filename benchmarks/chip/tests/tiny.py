"""Tiny cells for CPU runs of the harness: the configurations' shapes of
block at toy widths (each block module's `tiny` cut), through the same
files, runner and reference.

Besides the benchmark's cells, two run from files kept in `tests/data`:
`phi3-medium-14b.chat-shared` (a cell left out of the benchmark, PERF.md),
so the reference's GQA, SwiGLU, RMSNorm and RoPE paths stay tested against
the program, and `moe-window.long-prompts`, a fixture of the program's
`moe` family (8 experts, top-2, a 64-position window, prompts past it)
that runs through `blocks/moe.py` and `reference/moe_decoder.py` and
nothing else of its own."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from chipbench import files

DATA = Path(__file__).resolve().parent / "data"
TINY_SERVING = dict(slots=4, max_len=512, pool_pages=24)
# the compared number's limit at this size, from CPU readings of these
# cells on three seeds: the program 0 to 1.1e-4, the control 0.021 to 2.8;
# three requests compared, which a 3 s tiny window always delivers
TINY_CHECK = {"checked_requests": 3, "logit_gap_mean": 0.005}
DATA_CELLS = ["phi3-medium-14b.chat-shared", "moe-window.long-prompts"]


def load_data(name: str) -> dict:
    return json.loads((DATA / f"{name}.json").read_text())


def tiny_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(spec, cell, config, mix) of a BENCHMARK.json cell or of one of
    DATA_CELLS, cut to a size a CPU test holds by its block module."""
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
    cfg["model"] = files.load_block(cfg).tiny(cfg["model"])
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
