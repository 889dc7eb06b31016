"""The scheduler's own tracing: its `stats` counters match a hand count of
the work a known request mix makes, `Completion.queue_s` is the wait
before a request's first prefill call, and a profiler trace holds the
`serve.*` host spans nested in `serve.poll`, carrying request ids."""
import jax
import numpy as np
import pytest

from repro.configs.smoke import smoke_config
from repro.models.api import get_model
from repro.serving.engine import Request
from repro.serving.scheduler import Scheduler

# (prompt length, tokens asked); greedy, no eos: every request decodes all
MIX = [(5, 4), (9, 3)]
CHUNK = 4


@pytest.fixture(scope="module")
def model():
    cfg = smoke_config("musicgen-large")
    m = get_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _serve(model, prefill_chunk, mix=MIX, page_size=None):
    cfg, m, params = model
    sched = Scheduler(cfg, m, params, n_slots=2, max_len=32,
                      prefill_chunk=prefill_chunk, interleave_steps=2,
                      page_size=page_size)
    rng = np.random.default_rng(0)
    rids = [sched.submit(Request(rng.integers(0, cfg.vocab, plen,
                                              dtype=np.int32), n))
            for plen, n in mix]
    comps, polls = {}, 0
    while not sched.idle:
        comps.update((c.rid, c) for c in sched.poll())
        polls += 1
    return sched, [comps[r] for r in rids], polls


# Hand count of the rounds. Chunked (chunk 4, interleave 2): round 1 runs
# chunk 0 of request 0; round 2 its final chunk, then a burst bounded to 2
# steps; round 3 chunk 0 of request 1, then a burst that stops after 1 step
# when request 0 finishes; round 4 chunk 1 of request 1; round 5 its final
# chunk, then a burst of 2 steps. Whole-prompt: round 1 admits both and
# decodes 2 steps until request 1 finishes; round 2 decodes the last step
# of request 0.
ROUNDS = {CHUNK: dict(polls=5, bursts=3, steps=5,
                      chunk_kv=(4 + 5) + (4 + 8 + 9)),
          None: dict(polls=2, bursts=2, steps=3, chunk_kv=5 + 9)}


@pytest.mark.parametrize("prefill_chunk", [CHUNK, None])
def test_counters_match_a_hand_count(model, prefill_chunk):
    sched, comps, polls = _serve(model, prefill_chunk)
    want = ROUNDS[prefill_chunk]
    st = sched.stats
    assert polls == want["polls"] == st["polls"]
    assert st["bursts"] == want["bursts"]
    assert sched.decode_steps() == want["steps"]
    n_chunks = [-(-plen // prefill_chunk) if prefill_chunk else 1
                for plen, _ in MIX]
    assert st["chunks"] == sum(n_chunks)
    assert st["prefill_tokens"] == sum(plen for plen, _ in MIX)
    assert st["chunk_keys"] == sum(plen * (plen + 1) // 2 for plen, _ in MIX)
    assert st["chunk_kv"] == want["chunk_kv"]
    assert st["first_tokens"] == len(MIX)
    # token j of an L-token prompt (j >= 1 comes from a decode step)
    # attends L + j keys
    assert st["decode_tokens"] == sum(n - 1 for _, n in MIX)
    assert st["decode_keys"] == sum(plen + j for plen, n in MIX
                                    for j in range(1, n))
    assert [c.tokens.size for c in comps] == [n for _, n in MIX]
    assert st["poll_s"] >= st["prefill_s"] + st["decode_s"] > 0


@pytest.mark.parametrize("page_size", [None, 4, 8])
def test_decode_pages_count_the_pages_the_decode_rows_hold(model, page_size):
    """Paged, token j of an L-token prompt comes from a decode step whose
    row holds ceil((L + j) / page_size) table pages: the pages the paged
    attention kernel walks for it. Unpaged there are no pages to count."""
    sched, comps, _ = _serve(model, CHUNK, page_size=page_size)
    assert [c.tokens.size for c in comps] == [n for _, n in MIX]
    if page_size is None:
        assert "decode_pages" not in sched.stats
        return
    assert sched.stats["decode_pages"] == sum(
        -(-(plen + j) // page_size) for plen, n in MIX for j in range(1, n))


def test_queue_wait_is_the_wait_before_the_first_prefill_call(model):
    """Request 1 is admitted behind request 0, whose chunks run first (one
    chunk per round, head admission first): it waits at least request 0's
    own prefill compute before its first chunk starts."""
    _, (c0, c1), _ = _serve(model, CHUNK)
    for c in (c0, c1):
        assert c.status == "completed"
        assert 0.0 <= c.queue_s <= c.ttft_wall
        assert c.ttft <= c.ttft_wall - c.queue_s
    assert c1.queue_s >= c0.ttft > 0.0


def test_profiler_trace_holds_the_scheduler_spans(model, tmp_path):
    from jax.profiler import ProfileData
    _serve(model, CHUNK)                         # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        sched, comps, _ = _serve(model, CHUNK)
    pb = sorted(tmp_path.rglob("*.xplane.pb"))
    assert pb, "the profiler wrote no trace"
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for plane in ProfileData.from_file(str(pb[-1])).planes
             if plane.name.startswith("/host:CPU")
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")]
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    polls = by["serve.poll"]
    assert len(polls) == sched.stats["polls"]
    assert len(by["serve.chunk"]) == sched.stats["chunks"]
    assert len(by["serve.burst"]) == sched.stats["bursts"]
    for name in ("serve.admit", "serve.chunk", "serve.burst", "serve.itl",
                 "serve.harvest"):
        for _, a, b, _ in by[name]:
            assert any(pa <= a and b <= pb_ for _, pa, pb_, _ in polls), name
    rids = {c.rid for c in comps}
    assert {s[3]["rid"] for s in by["serve.chunk"]} == rids
    assert {s[3]["rid"] for s in by["serve.admit"]} == rids
    assert sorted(s[3]["chunk"] for s in by["serve.chunk"]) == [0, 0, 1, 1, 2]
