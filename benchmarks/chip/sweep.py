#!/usr/bin/env python3
"""Finds a cell's knee on the chip: the highest offered rate it sustains.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        [--seconds S] [--fractions 0.6,0.8,1.0] [--out DIR (.sweep)]

Each step is one whole run of the cell (`runner.run_cell`, as the
benchmark makes it) with the mix's arrivals replaced. First a backlog (the
queue kept at 2 x slots), whose delivered request rate bounds the knee;
then Poisson arrivals at each fraction of that rate. The knee is the
highest rate at which no request failed, every request due in the window
was delivered while the client served on past its end, and no more
requests were outstanding at the window's end than at its start. A
fixed-rate cell runs at 0.8 of it; the rate is written into the mix file
by hand. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import configure_jax, fail, log  # noqa: E402
from chipbench import files, program  # noqa: E402


def sustained(row: dict) -> bool:
    return (row["failed"] == 0 and row["undelivered"] == 0
            and row["outstanding_end"] <= row["outstanding_start"])


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window per rate (default: run_seconds)")
    ap.add_argument("--fractions", default="0.6,0.8,1.0")
    ap.add_argument("--out", default=".sweep")
    args = ap.parse_args(argv)

    spec = files.load_spec()
    cell = files.workload(spec, args.workload)
    cfg = files.load_config(spec, cell["config"])
    mix = files.load_traffic(cell["traffic"])
    seconds = args.seconds or spec["run_seconds"]
    program.import_program()
    jax = configure_jax()
    if jax.devices()[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {jax.devices()[0].platform}")
    from chipbench import runner

    def step(arrivals: dict, **tags) -> dict:
        m2 = copy.deepcopy(mix)
        m2["arrivals"] = arrivals
        res = runner.run_cell(spec, cell, cfg, m2, args.seed, seconds, False,
                              time.perf_counter(), log)
        row = {**tags, **res["window"], "failed": res["failed"],
               "correct": res["correct"],
               "delivered_per_s": res["window"]["delivered_in_window"]
               / seconds,
               **{k: v["value"] for k, v in res["metrics"].items()}}
        log(f"sweep: {json.dumps(row)}")
        return row

    rows = [step({"kind": "backlog", "depth_per_slot": 2, "pool_per_s": 3},
                 kind="backlog")]
    cap = rows[0]["delivered_per_s"]
    if mix["arrivals"]["kind"] == "poisson":
        for f in [float(x) for x in args.fractions.split(",")]:
            rows.append(step({"kind": "poisson", "rate": f * cap},
                             kind="poisson", fraction=f, rate=f * cap))
        knee = max((r["rate"] for r in rows[1:] if sustained(r)),
                   default=None)
        log(f"knee: {knee}; cell rate at 0.8: "
            f"{None if knee is None else 0.8 * knee}")
        rows.append({"kind": "knee", "rate": knee})
    out_dir = files.ROOT / args.out
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell['name']}.json").write_text(json.dumps(rows, indent=1))
    print(json.dumps(rows[-1]))


if __name__ == "__main__":
    main()
