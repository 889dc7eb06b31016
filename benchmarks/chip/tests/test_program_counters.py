"""The program's own counters against the harness's count of the same
work, the readers of the per-layer metrics built on them, and kernel
classification by name against classification by shape on traces
recorded on a TPU v5e."""
import json
import re
import time
from pathlib import Path

import pytest

from chipbench import files, probe, program, runner, trace, weights
from tiny import tiny_cell

DATA = Path(__file__).resolve().parent / "data"
FIXTURES = [DATA / "v5e_trace.json", DATA / "v5e_named_trace.json"]
SEED = 2**31 + 29
WINDOW_S = 3.0
# (stats key, WindowWork field) of the same meaning
SAME = [("decode_tokens", "decoded"), ("decode_keys", "decode_keys"),
        ("chunks", "chunks"), ("prefill_tokens", "chunk_rows"),
        ("chunk_keys", "chunk_keys"), ("chunk_kv", "chunk_kv"),
        ("first_tokens", "firsts")]
NEW = ["decode_step_ms", "prefill_chunk_ms", "poll_host_ms",
       "glue_share.serve"]


def _reader(name):
    return files.load_module("metrics", name).read


@pytest.fixture(scope="module")
def window():
    """A window of the benchmark's cell at a tiny size, served as the
    runner serves it: the snapshots at its edges and its records."""
    _, _, cfg, mix = tiny_cell(files.load_spec()["workloads"][0]["name"])
    m, s = cfg["model"], cfg["serving"]
    params_fn = files.load_block(cfg).params_fn(m)
    eng = program.build_engine(
        cfg, weights.build_program_params(params_fn, SEED), SEED)
    sched = eng.scheduler()
    client = runner.Client(sched, time.perf_counter, spans=False)
    runner.compile_pass(client, mix, cfg, SEED)
    for phase, span in ((0, 1.0), (1, WINDOW_S)):    # warm-up, window
        if phase:
            a = probe.snapshot(sched, client.clk())
        t = client.clk()
        client.run_poisson(runner._phase_reqs(mix, m["vocab"], SEED, phase,
                                              span, s["slots"]),
                           t, t + span, phase)
    b = probe.snapshot(sched, client.clk())
    return a, b, list(client.records.values()), s["prefill_chunk"]


def test_counters_equal_the_harness_count(window):
    a, b, recs, chunk = window
    reqs = {r["rid"]: {"plen": r["plen"], "n": r["n"],
                       **({"cached": r["cached"]} if r["done"] else {})}
            for r in recs}
    delivered = {r["rid"] for r in recs
                 if r["done"] is not None and a["t"] <= r["done"] <= b["t"]}
    w = probe.window_work(a, b, reqs, delivered, chunk)
    assert w.steps > 0 and w.chunks > 0 and w.firsts > 0
    for key, field in SAME:
        assert b["stats"][key] - a["stats"][key] == getattr(w, field), key


def test_readers_give_numbers(window):
    a, b, _, _ = window
    ctx = {"snapshots": {"start": a, "end": b}, "trace": None}
    got = {n: _reader(n)(ctx) for n in NEW}
    assert got.pop("glue_share.serve") is None        # nothing traced
    assert all(v > 0 for v in got.values()), got
    fixture = json.loads(FIXTURES[0].read_text())
    tr = trace.reduce(fixture, fixture.pop("window_s"))
    glue = _reader("glue_share.serve")({"trace": tr})
    assert 0 < glue < 100
    kernels = sum(tr["kernel_s"].values())
    assert glue == pytest.approx(100 * (1 - kernels / tr["busy_s"]))


def test_readers_of_an_older_program_read_nothing(window):
    """A program without the new counters (the parent's) leaves the
    metrics that need them out, and does not raise."""
    a, b, _, _ = window
    old = [dict(s, stats={k: v for k, v in s["stats"].items()
                          if k not in ("polls", "poll_s", "chunks")})
           for s in (a, b)]
    ctx = {"snapshots": dict(zip(("start", "end"), old)), "trace": None}
    assert _reader("prefill_chunk_ms")(ctx) is None
    assert _reader("poll_host_ms")(ctx) is None
    assert _reader("decode_step_ms")(ctx) > 0


def _by_shape(name):
    if "= (s32[2," in name:
        return "attn"                      # the pair of int32 limb sums
    return "gemm" if "tpu_custom_call" in name else None


def _by_name(name):
    """By the op's own name, the HLO text before ` = `: its operands may
    name the kernel that produced them."""
    for kind, pat in (("attn", r"%packed_attention"),
                      ("gemm", r"%binary_gemm")):
        if re.match(pat, name):
            return kind
    return None


def _kernel_s(ops, kind_of):
    out = {"attn": 0.0, "gemm": 0.0}
    for name, _, d in ops:
        kind = None if name.startswith(trace.CONTAINERS) else kind_of(name)
        if kind:
            out[kind] += d
    return out


@pytest.mark.parametrize("path", FIXTURES, ids=lambda p: p.stem)
def test_kernels_by_name_and_by_shape_agree(path):
    """On the trace recorded before the kernels had names, the harness's
    rule (`trace.kind_of`) is the shape rule; on the named trace, every
    kernel is named and the names give the shape rule's seconds."""
    ev = json.loads(path.read_text())
    for ops in ev["devices"].values():
        shape = _kernel_s(ops, _by_shape)
        assert shape["attn"] > 0 and shape["gemm"] > 0
        if path.stem == "v5e_trace":
            assert _kernel_s(ops, trace.kind_of) == shape
        else:
            assert _kernel_s(ops, _by_name) == shape
            custom = [n for n, _, _ in ops if "tpu_custom_call" in n]
            assert custom and all(_by_name(n) for n in custom)
