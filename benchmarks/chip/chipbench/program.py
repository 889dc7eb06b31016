"""The system under test: a frozen `ServingEngine` over the configuration.

This is the only module of the benchmark that imports the program, apart
from `weights.build_program_params` (which hands it the packed tree).
"""
from __future__ import annotations

import sys

from chipbench.files import ROOT

SRC = ROOT / "src"
# keys of a configuration's "model" section that ModelConfig takes
MODEL_KEYS = ("family", "n_layers", "d_model", "n_heads", "n_kv_heads",
              "head_dim", "d_ff", "vocab", "mlp", "norm", "pos", "rope_theta",
              "quant", "dtype", "kv_bits")


def import_program() -> None:
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"the program's sources are not in this checkout "
                         f"({SRC})")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    m = cfg["model"]
    return ModelConfig(name=cfg["name"], source=cfg["source"],
                       **{k: m[k] for k in MODEL_KEYS if k in m})


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
