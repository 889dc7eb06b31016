"""Traffic generation: the same seed gives the same requests, every seed
the same work, and every length stays in its range."""
import numpy as np
import pytest

from chipbench import files, traffic
from tiny import load_data

MIXES = ["music-gen", "chat-shared"]


def _mix(name):
    """A benchmark mix, or the chat mix kept with the tests (PERF.md)."""
    try:
        return files.load_traffic(name)
    except FileNotFoundError:
        return load_data(name)


def _phase(name, seed, phase=1, span=40.0):
    mix = _mix(name)
    return mix, traffic.phase(mix, 2048, seed, phase, span, n_backlog=300)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    _, a = _phase(name, 2**31 + 99)
    _, b = _phase(name, 2**31 + 99)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due, x.system) == (y.max_new, y.due, y.system)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_work(name):
    """Other seeds refill the requests with other tokens; lengths, system
    prompt picks and arrival times stay, in the same order."""
    _, a = _phase(name, 1)
    _, b = _phase(name, 2)
    assert [(r.prompt.size, r.max_new, r.due, r.system) for r in a] == \
        [(r.prompt.size, r.max_new, r.due, r.system) for r in b]
    assert any(not np.array_equal(x.prompt[-8:], y.prompt[-8:])
               for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range(name):
    mix, reqs = _phase(name, 5)
    out = mix["output"]
    assert all(out["lo"] <= r.max_new <= out["hi"] for r in reqs)
    if "system" in mix:
        s, u = mix["system"], mix["user"]
        assert all(s["lo"] + u["lo"] <= r.prompt.size
                   <= s["hi"] + u["hi"] for r in reqs)
    else:
        p = mix["prompt"]
        assert all(p["lo"] <= r.prompt.size <= p["hi"] for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 2048 for r in reqs)


def test_poisson_arrivals_fill_the_span():
    mix, reqs = _phase("music-gen", 3, span=40.0)
    dues = np.array([r.due for r in reqs])
    assert len(reqs) == round(mix["arrivals"]["rate"] * 40.0)
    assert dues[0] == 0.0 and np.all(np.diff(dues) > 0) and dues[-1] < 40.0


def test_system_prompts_shared_across_phases():
    """Warm-up and window draw from the same system prompts, so the
    window meets a warm prefix cache; the popular ones come most."""
    mix = _mix("chat-shared")
    mix["arrivals"]["rate"] = 2.0      # enough requests to count picks
    warm = traffic.phase(mix, 32064, 8, 0, 30.0)
    win = traffic.phase(mix, 32064, 8, 1, 30.0)
    systems = traffic.system_prompts(mix, 32064, 8)
    for r in warm + win:
        s = systems[r.system]
        assert np.array_equal(r.prompt[:s.size], s)
    picks = np.bincount([r.system for r in win], minlength=8)
    assert picks[0] == picks.max() and picks[0] > 2 * picks[-1]


def test_lognormal_quantiles():
    d = {"dist": "lognormal", "median": 160, "sigma": 0.8, "lo": 16,
         "hi": 512}
    x = traffic.lengths(d, 1001)
    assert x[500] == 160 and x.min() >= 16 and x.max() <= 512
    assert np.all(np.diff(x) >= 0)


def test_mix_longer_than_positions_refused():
    mix = _mix("chat-shared")
    with pytest.raises(ValueError):
        traffic.check(mix, 2048)
    traffic.check(mix, 4096)
