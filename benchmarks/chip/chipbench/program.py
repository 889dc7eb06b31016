"""The system under test: a frozen `ServingEngine` over the configuration.

This is the only module of the benchmark that imports the program, apart
from the block modules (`blocks/<name>.py`, whose `params_fn` hands it the
packed tree).
"""
from __future__ import annotations

import dataclasses
import sys

from chipbench.files import ROOT

SRC = ROOT / "src"


def import_program() -> None:
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"the program's sources are not in this checkout "
                         f"({SRC})")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def model_config(cfg: dict):
    """The program's ModelConfig: every key of the configuration's "model"
    that is one of its fields (keys only the reference reads, such as
    `layernorm_eps`, are left out)."""
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in cfg["model"].items() if k in fields}
    return ModelConfig(name=cfg["name"], source=cfg["source"], **kw)


def build_engine(cfg: dict, params, seed: int):
    """The engine a deployment runs: packed weights, 1-bit paged KV pool,
    chunked admission, prefix cache; no invariant audits."""
    from repro.serving.engine import ServingEngine
    s = cfg["serving"]
    return ServingEngine(
        model_config(cfg), params, max_len=s["max_len"], freeze=True,
        slots=s["slots"], seed=seed % (1 << 31), kv_bits=cfg["model"]["kv_bits"],
        prefill_chunk=s["prefill_chunk"], page_size=s["page_size"],
        pool_pages=s["pool_pages"], prefix_cache=s["prefix_cache"],
        interleave_steps=s["interleave_steps"], check_invariants=False)
