"""Whole runs of every cell on the CPU at a tiny size: the harness drives
the program open-loop, reads its metrics and decides `correct` against the
plain reference. With a served token altered, or with the control (the
reference one precision lower) in the program's place, `correct` comes
out false."""
import time

import jax.numpy as jnp
import pytest

from chipbench import files, runner
from tiny import DATA_CELLS, tiny_cell

CELLS = [w["name"] for w in files.load_spec()["workloads"]] + DATA_CELLS
SEED = 2**31 + 17


def run(cell, **kw):
    spec, c, cfg, mix = tiny_cell(cell)
    return runner.run_cell(spec, c, cfg, mix, SEED, 3.0, False,
                           time.perf_counter(), lambda m: None, **kw), cfg


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(cell):
    res, cfg = run(cell)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    spec = files.load_spec()
    want = {m["name"] for m in files.metrics_for(spec, cell, "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_is_not_correct(cell):
    """The tokens the control puts first, read against the reference's
    logits at the same positions, fail a compared number."""
    res, cfg = run(cell, control=True)
    assert not res["correct"], res["compared"]
    assert res["compared"]["failed_requests"]["value"] == 0
    over = [k for k, c in res["compared"].items() if c["value"] > c["limit"]]
    assert over and set(over) <= set(cfg["check"]), res["compared"]


def test_token_altered_where_produced_fails(monkeypatch):
    import repro.serving.scheduler as sched_mod
    orig = sched_mod.sample_tokens

    def altered(logits, keys, temps):
        tok = orig(logits, keys, temps)
        return jnp.where(jnp.arange(tok.shape[0]) % 2 == 0,
                         (tok + 1) % logits.shape[-1], tok)
    monkeypatch.setattr(sched_mod, "sample_tokens", altered)
    res, _ = run(CELLS[0])
    assert not res["correct"]


def test_sample_holds_the_longest_and_the_rest_from_the_seed():
    recs = [{"rid": i, "n": n} for i, n in enumerate([5, 9, 7, 9, 3, 8])]
    a = runner._sample(recs, SEED, 4)
    assert len(a) == 4 and len({r["rid"] for r in a}) == 4
    assert a[0]["rid"] == 1                  # longest, ties to the lower rid
    assert a == runner._sample(list(reversed(recs)), SEED, 4)
    assert runner._sample(recs, SEED, 10) == sorted(
        recs, key=lambda r: (-r["n"], r["rid"]))


def test_fewer_requests_than_the_check_names_is_not_correct():
    spec, c, cfg, mix = tiny_cell(CELLS[0])
    cfg["check"]["checked_requests"] = 1000
    res = runner.run_cell(spec, c, cfg, mix, SEED, 3.0, False,
                          time.perf_counter(), lambda m: None)
    assert not res["correct"]
    got = res["compared"]["checked_requests"]
    assert 0 < got["value"] < got["limit"] == 1000
