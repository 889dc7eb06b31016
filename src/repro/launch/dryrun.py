"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
GSPMD-partitions, and compiles on the production mesh, and extract the
memory / FLOP / collective numbers the roofline analysis consumes.

MUST be run as its own process (the XLA flag set immediately below is
latched at first jax init — that is why it precedes every other import,
including repro's).

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  python -m repro.launch.dryrun --all            # every cell, single-pod
  python -m repro.launch.dryrun --all --multi-pod
Results are appended as JSON lines to --out (default dryrun_results.jsonl).
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, list_archs
from repro.launch.mesh import make_production_mesh, batch_axes
from repro.launch.shardings import (
    batch_shardings, cache_shardings, param_shardings,
)
from repro.launch.specs import (
    abstract_opt_state, abstract_params, input_specs,
)
from repro.models.api import get_model
from repro.roofline.hlo import parse_collectives
from repro.train.step import default_optimizer, make_decode_step, \
    make_prefill_step, make_train_step


def _mem_dict(mem) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    return out


def pick_accum(cfg, shape) -> int:
    """Gradient-accumulation factor for train cells, sized so activations
    fit v5e HBM (16 GB): large models halve/quarter the microbatch."""
    n = cfg.n_params()
    if n > 3e10:
        return 4
    if n > 8e9:
        return 2
    return 1


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             hlo_dir: str | None = None, overrides: dict | None = None,
             accum: int | None = None, seq_shard: bool = True,
             verbose: bool = True) -> dict:
    """Lower + compile one (arch x shape) cell on the production mesh."""
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.scaled(**overrides)
    if shape_name not in cfg.shapes:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "SKIP",
                "reason": "long_500k needs sub-quadratic attention "
                          "(DESIGN.md §Arch-applicability)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = get_model(cfg)
    shape = SHAPES[shape_name]
    if accum is None:
        accum = pick_accum(cfg, shape) if shape.kind == "train" else 1
    t0 = time.time()

    params_sds = abstract_params(cfg, model)
    p_sh = param_shardings(mesh, params_sds)
    specs = input_specs(cfg, model, shape_name)

    from repro.launch.shardctx import activation_sharding
    with mesh, activation_sharding(mesh, global_batch=shape.global_batch,
                                   seq_shard=seq_shard):
        if shape.kind == "train":
            opt = default_optimizer(cfg)
            opt_sds = abstract_opt_state(opt, params_sds)
            # optimizer state mirrors param shardings; scalars replicated
            o_sh = _opt_shardings(mesh, opt_sds, params_sds, p_sh)
            b_sh = batch_shardings(mesh, specs["batch"])
            step = make_train_step(model, opt, accum=accum,
                                   grad_shardings=p_sh)
            fn = lambda p, o, b: step(p, o, b, None)
            jfn = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh),
                          out_shardings=(p_sh, o_sh, None),
                          donate_argnums=(0, 1))
            lowered = jfn.lower(params_sds, opt_sds, specs["batch"])
        elif shape.kind == "prefill":
            b_sh = batch_shardings(mesh, specs["batch"])
            step = make_prefill_step(model, max_len=shape.seq_len)
            jfn = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jfn.lower(params_sds, specs["batch"])
        else:  # decode
            from repro.launch.shardings import div_batch_axes
            step = make_decode_step(model)
            ba = div_batch_axes(mesh, shape.global_batch)
            tok_sh = NamedSharding(mesh, P(ba))
            c_sh = cache_shardings(mesh, specs["cache"], cfg.family,
                                   shape.global_batch)
            # per-slot (B,) positions shard with the batch, like tokens
            pos_sh = NamedSharding(mesh, P(ba))
            jfn = jax.jit(step, in_shardings=(p_sh, tok_sh, c_sh, pos_sh),
                          out_shardings=(None, c_sh), donate_argnums=(2,))
            lowered = jfn.lower(params_sds, specs["token"], specs["cache"],
                                specs["pos"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis()
    mem = _mem_dict(compiled.memory_analysis())
    hlo = compiled.as_text()
    from repro.roofline.hlo import analyze
    corrected = analyze(hlo)   # scan-corrected flops/bytes/collectives
    coll = corrected["collectives"]
    if hlo_dir:
        Path(hlo_dir).mkdir(parents=True, exist_ok=True)
        tag = f"{arch}_{shape_name}{'_mp' if multi_pod else ''}"
        (Path(hlo_dir) / f"{tag}.hlo.txt").write_text(hlo)

    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "OK",
        "accum": accum, "seq_shard": seq_shard,
        "n_devices": mesh.devices.size,
        "flops": float(corrected["flops"]),
        "bytes_accessed": float(corrected["hbm_bytes"]),
        "flops_xla_raw": float(cost.get("flops", -1.0)),
        "bytes_xla_raw": float(cost.get("bytes accessed", -1.0)),
        "collectives": coll,
        "memory": mem,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} mesh="
              f"{'2x16x16' if multi_pod else '16x16'} OK "
              f"flops/dev={result['flops']:.3e} "
              f"coll={coll['total_bytes']/1e6:.1f}MB "
              f"temp={mem.get('temp_size_in_bytes', 0)/1e9:.2f}GB "
              f"args={mem.get('argument_size_in_bytes', 0)/1e9:.2f}GB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops={result['flops']:.4e} "
              f"bytes={result['bytes_accessed']:.4e}")
    return result


def _opt_shardings(mesh, opt_sds, params_sds, p_sh):
    """Optimizer states (m/u/v trees mirror params; step scalars replicated)."""
    flat_p, _ = jax.tree_util.tree_flatten(params_sds)
    flat_psh, _ = jax.tree_util.tree_flatten(p_sh)
    shard_by_shape = {}
    for sds, sh in zip(flat_p, flat_psh):
        shard_by_shape.setdefault((tuple(sds.shape)), sh)

    def one(leaf):
        sh = shard_by_shape.get(tuple(leaf.shape))
        return sh if sh is not None else NamedSharding(mesh, P())

    return jax.tree.map(one, opt_sds)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.jsonl")
    ap.add_argument("--hlo-dir", default=None)
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    for a in archs:
        shape_names = [args.shape] if args.shape else list(SHAPES)
        for s in shape_names:
            cells.append((a, s))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    out = Path(args.out)
    n_fail = 0
    for mp in meshes:
        for arch, shape in cells:
            try:
                res = run_cell(arch, shape, multi_pod=mp,
                               hlo_dir=args.hlo_dir)
            except Exception as e:  # a failed cell is a bug — surface it
                traceback.print_exc()
                res = {"arch": arch, "shape": shape, "multi_pod": mp,
                       "status": "FAIL", "error": f"{type(e).__name__}: {e}"}
                n_fail += 1
            with out.open("a") as f:
                f.write(json.dumps(res) + "\n")
    print(f"done; {n_fail} failures -> {out}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
