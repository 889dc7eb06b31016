"""The `decode_page_share` reader on a snapshot pair recorded at the edges
of a tiny paged scheduler's work, and on a program without its counter."""
import jax
import numpy as np
import pytest

from chipbench import files, probe
from repro.configs.smoke import smoke_config
from repro.models.api import get_model
from repro.serving.engine import Request
from repro.serving.scheduler import Scheduler

SLOTS, MAX_LEN, PAGE, CHUNK = 2, 32, 4, 4
MIX = [(5, 6), (9, 5)]          # (prompt length, tokens asked), no eos


def _read(a, b):
    ctx = {"snapshots": {"start": a, "end": b}, "slots": SLOTS,
           "config": {"serving": {"max_len": MAX_LEN, "page_size": PAGE}},
           "trace": None}
    return files.load_module("metrics", "decode_page_share").read(ctx)


@pytest.fixture(scope="module")
def snaps():
    cfg = smoke_config("musicgen-large")
    m = get_model(cfg)
    sched = Scheduler(cfg, m, m.init(jax.random.PRNGKey(0)), n_slots=SLOTS,
                      max_len=MAX_LEN, prefill_chunk=CHUNK,
                      interleave_steps=2, page_size=PAGE)
    rng = np.random.default_rng(0)
    for plen, n in MIX:
        sched.submit(Request(rng.integers(0, cfg.vocab, plen,
                                          dtype=np.int32), n))
    a = probe.snapshot(sched, 0.0)
    while not sched.idle:
        sched.poll()
    return a, probe.snapshot(sched, 1.0)


def test_reads_the_share_of_the_tables_walked(snaps):
    a, b = snaps
    pages = sum(-(-(plen + j) // PAGE) for plen, n in MIX for j in range(1, n))
    steps = b["steps"] - a["steps"]
    assert steps > 0
    got = _read(a, b)
    assert got == pytest.approx(100 * pages / (steps * SLOTS * MAX_LEN
                                               // PAGE))
    assert 0 < got < 100


def test_reads_none_without_the_counter_or_the_steps(snaps):
    a, b = snaps
    old = [dict(s, stats={k: v for k, v in s["stats"].items()
                          if k != "decode_pages"}) for s in snaps]
    assert _read(*old) is None
    assert _read(a, a) is None
