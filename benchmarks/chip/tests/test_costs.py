"""Operations and bytes from shapes, against counts by hand at the two
configurations' published widths, and the window's work from snapshots."""
import pytest

from chipbench import costs, files, probe
from tiny import load_data

SPEC = files.load_spec()
MUSIC = files.load_config(SPEC, "musicgen-large")["model"]
PHI3 = load_data("phi3-medium-14b")["model"]
V5E = costs.peaks("TPU v5 lite")


@pytest.mark.parametrize("m,weights_per_layer", [
    # wq wk wv wo: 4 x 2048^2; up, down: 2 x 2048 x 8192
    (MUSIC, 4 * 2048 * 2048 + 2 * 2048 * 8192),
    # wq, wo: 5120^2; wk, wv: 5120 x 1280; gate, up, down: 5120 x 17920
    (PHI3, 2 * 5120 * 5120 + 2 * 5120 * 1280 + 3 * 5120 * 17920),
])
def test_decode_step_gemms(m, weights_per_layer):
    w = costs.block_gemms(m, rows=64, calls=1)
    n = weights_per_layer * m["n_layers"]
    assert w.ops == 2 * 64 * n
    acts = sum(64 * (k / 8 + nn * 2) for k, nn in costs.gemm_shapes(m))
    assert w.bytes == pytest.approx(n / 8 + acts * m["n_layers"])


def test_musicgen_decode_step_is_compute_bound():
    """2.42 G weights x 64 rows: 0.79 ms at the int8 peak against 0.37 ms
    for the 302 MB of packed weights."""
    t, bound = costs.least_time(costs.block_gemms(MUSIC, 64, 1), V5E)
    assert bound == "compute" and t == pytest.approx(0.787e-3, rel=0.01)
    t1, bound1 = costs.least_time(costs.block_gemms(MUSIC, 1, 1), V5E)
    assert bound1 == "memory" and t1 == pytest.approx(0.369e-3, rel=0.01)


def test_phi3_weights_and_head():
    w = costs.block_gemms(PHI3, 64, 1)
    assert w.ops / 128 == pytest.approx(13.63e9, rel=0.001)
    h = costs.head(PHI3, 64, 1)
    assert h.ops == 2 * 64 * 5120 * 32064
    assert h.bytes == 5120 * 32064 * 2 + 64 * (5120 + 32064) * 2


def test_attention_counts():
    # token j of an L-token prompt attends L + j keys
    assert costs.decode_keys(100, 1, 4) == 101 + 102 + 103
    assert costs.decode_keys(100, 4, 4) == 0
    assert costs.chunk_keys(0, 3) == 1 + 2 + 3
    assert costs.chunk_keys(128, 130) == 129 + 130
    a = costs.attention(PHI3, key_sum=10, queries=2, kv_read=10)
    assert a.ops == 40 * 4 * 10 * 40 * 128
    assert a.bytes == 40 * (10 * 10 * 128 / 8 * 2 + 2 * 40 * 128 * 2 * 2)


@pytest.mark.parametrize("window", [0, 1, 5, 64, 130, 4096])
def test_windowed_keys_are_brute_force_sums(window):
    """A query at position p scores min(p + 1, window) keys (every p + 1
    at window 0); a chunk reads the positions some query of it sees."""
    def cap(n):
        return min(n, window) if window else n
    for plen, first, last in [(100, 1, 4), (5, 1, 40), (60, 7, 9),
                              (200, 10, 30), (3, 4, 4)]:
        assert costs.decode_keys(plen, first, last, window) == \
            sum(cap(plen + j) for j in range(first, last))
    for lo, hi in [(0, 3), (0, 128), (128, 130), (60, 190), (256, 300)]:
        assert costs.chunk_keys(lo, hi, window) == \
            sum(cap(p + 1) for p in range(lo, hi))
        seen = {k for p in range(lo, hi) for k in range(p + 1)
                if not window or k > p - window}
        assert costs.chunk_reads(lo, hi, window) == len(seen)


def test_window_zero_is_the_old_count():
    for plen, first, last in [(100, 1, 4), (5, 1, 40), (3, 4, 4),
                              (7, 5, 2)]:
        n = last - first
        old = n * plen + (first + last - 1) * n // 2 if n > 0 else 0
        assert costs.decode_keys(plen, first, last) == old
    for lo, hi in [(0, 3), (128, 130), (60, 190)]:
        assert costs.chunk_keys(lo, hi) == sum(range(lo + 1, hi + 1))
        assert costs.chunk_reads(lo, hi) == hi


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")


def test_window_work_from_snapshots():
    a = {"steps": 100, "running": {1: 10}, "admitting": {2: (1, 0)},
         "cached": {}}
    b = {"steps": 120, "running": {2: 5, 3: 2},
         "admitting": {4: (1, 128)}, "cached": {3: 0}}
    reqs = {1: {"plen": 200, "n": 30, "cached": 0},
            2: {"plen": 300, "n": 50}, 3: {"plen": 100, "n": 40},
            4: {"plen": 400, "n": 10}}
    w = probe.window_work(a, b, reqs, delivered={1}, chunk=128)
    assert w.steps == 20
    assert w.decoded == 20 + 4 + 1
    assert w.decode_keys == (costs.decode_keys(200, 10, 30)
                             + costs.decode_keys(300, 1, 5)
                             + costs.decode_keys(100, 1, 2))
    assert w.firsts == 2
    # rid 2: chunks 1-2 of 3; rid 3: its one chunk; rid 4: chunk 0 after
    # a 128-token prefix hit
    assert w.chunks == 4 and w.chunk_rows == 128 + 44 + 100 + 128
    assert w.chunk_kv == 256 + 300 + 100 + 256
    # the totals are the sums of what each request and chunk did
    assert sorted(w.decodes) == [(100, 1, 2), (200, 10, 30), (300, 1, 5)]
    assert sorted(w.spans) == [(0, 100), (128, 256), (128, 256),
                               (256, 300)]
    assert w.keys() == (w.decode_keys, w.chunk_keys, w.chunk_kv)
    assert w.keys(64) == (
        sum(costs.decode_keys(*d, 64) for d in w.decodes),
        sum(costs.chunk_keys(*c, 64) for c in w.spans),
        sum(costs.chunk_reads(*c, 64) for c in w.spans))
    assert w.keys(64)[0] == 64 * (20 + 4 + 1)
