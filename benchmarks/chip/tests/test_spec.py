"""BENCHMARK.json against the benchmark's contract, and every cell's
files found by name."""
import re

import pytest

from chipbench import files

SPEC = files.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_keys_and_sizes():
    assert set(SPEC) == KEYS["top"]
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        assert 1 <= len(SPEC[section])
        for e in SPEC[section]:
            extra = {"workloads"} if section in ("end_to_end",
                                                 "per_layer") else set()
            assert KEYS[section] <= set(e) <= KEYS[section] | extra, e
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["paths"]) <= 16
    assert all(PATH.match(p) and ".." not in p and not p.startswith("/")
               for p in SPEC["paths"])
    assert len(SPEC["command"]) <= 32 and all(_text(w)
                                              for w in SPEC["command"])
    assert len(files.SPEC_FILE.read_bytes()) <= 64 * 1024


def test_names_units_and_text():
    names = [e["name"] for s in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[s]]
    assert all(NAME.match(n) for n in names)
    for s in ("workloads", "end_to_end", "per_layer", "configs"):
        assert len({e["name"] for e in SPEC[s]}) == len(SPEC[s])
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert _text(w["why"]) and w["chips"] in (1, 4)
    for c in SPEC["configs"]:
        assert _text(c["why"]) and _text(c["source"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert _text(m["layer"])
    pairs = {(w["config"], w["traffic"]) for w in SPEC["workloads"]}
    assert len(pairs) == len(SPEC["workloads"])
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_load_by_name(cell):
    w = files.workload(SPEC, cell)
    cfg = files.load_config(SPEC, w["config"])
    mix = files.load_traffic(w["traffic"])
    files.load_module("reference", cfg["reference"])
    e2e = files.metrics_for(SPEC, cell, "end_to_end")
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    per = files.metrics_for(SPEC, cell, "per_layer")
    assert per
    for m in e2e + per:
        if m["name"] != "setup_s":
            assert callable(files.load_module("metrics", m["name"]).read)
    for m in per:
        assert m["moves"] in names, (cell, m["name"])
    assert cfg["serving"]["max_len"] >= \
        __import__("chipbench.traffic").traffic.longest_request(mix)


def test_configs_used_and_files_under_paths():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    fs = [c["file"] for c in SPEC["configs"]]
    assert len(set(fs)) == len(fs)
    assert all(any(f.startswith(p + "/") for p in SPEC["paths"]) for f in fs)
    for c in SPEC["configs"]:
        assert c["reduced"] == files.load_config(SPEC, c["name"])["reduced"]


def test_per_layer_moves_reported_where_listed():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        target = e2e[m["moves"]]
        cells = m.get("workloads", [w["name"] for w in SPEC["workloads"]])
        for c in cells:
            assert "workloads" not in target or c in target["workloads"]
