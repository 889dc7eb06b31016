#!/usr/bin/env python3
"""Chip benchmark of packed 1-bit serving: one cell, one run.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (an entry of BENCHMARK.json's `workloads`) names a configuration
file (`configs/<name>.json`) and a traffic mix (`traffic/<name>.json`);
the run serves that traffic open-loop through the program's
`ServingEngine` -> `Scheduler.submit`/`poll` on one TPU, then checks what
the window delivered against the plain reference (`reference/`). With
`--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics (`metrics/<name>.py`), read from a
profiler trace of part of the window and from the scheduler's counters.

Progress and the compared numbers go to standard error; the last line of
standard output is one JSON object. A machine without a TPU, or with
fewer chips than the cell asks for, gets an error and no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import files, program  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str) -> None:
    log(f"bench: {msg}")
    sys.exit(1)


def configure_jax():
    """JAX with its persistent compilation cache at a fixed directory of
    this checkout, whatever the environment names: the two sides of a
    comparison share no cache."""
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(files.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)   # no eviction
    return jax


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the control (the reference in float8, one "
                         "precision below the configuration's bfloat16) in "
                         "the program's place for the check, so that "
                         "`correct` comes out false; for setting limits, "
                         "never in a benchmark run")
    args = ap.parse_args(argv)

    spec = files.load_spec()
    cell = files.workload(spec, args.workload)
    cfg = files.load_config(spec, cell["config"])
    mix = files.load_traffic(cell["traffic"])
    program.import_program()
    jax = configure_jax()
    devs = jax.devices()
    if devs[0].platform != "tpu" or jax.default_backend() != "tpu":
        fail(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < cell["chips"]:
        fail(f"the cell asks for {cell['chips']} chips, JAX finds "
             f"{len(devs)}")
    log(f"device: {devs[0].device_kind} x {len(devs)}, jax {jax.__version__}")
    log(f"cell {cell['name']}: config {cfg['name']}, traffic {mix['name']}, "
        f"seed {args.seed}, window {args.seconds} s, trace {args.trace}")

    from chipbench import runner
    result = runner.run_cell(spec, cell, cfg, mix, args.seed, args.seconds,
                             bool(args.trace), T_PROCESS, log,
                             control=bool(args.control))
    log(f"correct: {result['correct']}")
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
