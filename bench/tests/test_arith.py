"""Percentiles, interval arithmetic, the traffic generator and the
end-to-end readers' window arithmetic."""
import types

import numpy as np
import pytest

from bench import harness, stats, traffic
from bench.tests import smoke


def test_percentile_matches_linear_interpolation():
    v = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(v, 50) == 3.0
    assert stats.percentile(v, 95) == pytest.approx(4.8)
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_union_and_merge():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert stats.union_length(iv) == pytest.approx(3.0)
    assert stats.merge(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert stats.union_length([]) == 0.0


@pytest.mark.parametrize("kind", ["poisson", "bursty"])
def test_generator_fixes_count_and_lengths(kind):
    mix = dict(smoke.MIX)
    mix["arrivals"] = {"kind": kind, "rate": 10.0, "period_s": 4,
                       "burst_s": 1, "burst_factor": 4}
    a = traffic.generate(mix, 8.0, 256, 5)
    b = traffic.generate(mix, 8.0, 256, 2**31 + 12345)
    win_a = [x for x in a if x.in_window]
    assert len(win_a) == 80
    # the run seed draws the prompt tokens, the mix the schedule
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new_tokens) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in b]
    # another schedule seed: the same gaps and lengths, in another order
    c = traffic.generate(dict(mix, schedule_seed=2), 8.0, 256, 5)
    win_c = [x for x in c if x.in_window]
    assert len(win_c) == 80
    assert sorted(len(x.prompt) for x in win_a) == \
        sorted(len(x.prompt) for x in win_c)
    assert sorted(x.max_new_tokens for x in a) == \
        sorted(x.max_new_tokens for x in c)
    assert [x.due_s for x in a] != [x.due_s for x in c]
    if kind == "poisson":
        ga = np.sort(np.diff([x.due_s for x in win_a]))
        gc = np.sort(np.diff([x.due_s for x in win_c]))
        assert np.mean(np.abs(ga - gc)) < 0.05 * np.mean(ga)
    assert all(-mix["lead_in_s"] <= x.due_s < 8.0 for x in a)
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)
    assert traffic.generate(mix, 8.0, 256, 5) == a
    if kind == "bursty":        # a quarter of the time holds 4/7 of arrivals
        burst = sum(1 for x in win_a if x.due_s % 4 < 1)
        assert 0.4 < burst / len(win_a) < 0.75


def test_seeds_of_any_size():
    w, t = harness.derived_seeds(2**40 + 3)
    assert 0 <= w < 2**31 and t >= 0
    assert harness.derived_seeds(7) != harness.derived_seeds(8)


def _rec(requests, seconds=10.0):
    return types.SimpleNamespace(requests=requests, seconds=seconds,
                                 setup_s=1.5)


def _read(name, rec):
    mod = harness.load_module(harness.HERE / "metrics" / f"{name}.py",
                              f"bench.metrics.{name}")
    return mod.read(rec)


def test_end_to_end_readers_use_delivery_stamps_in_the_window():
    reqs = [
        # due in the window: first token 0.2 s after due; gaps 0.1 s
        {"in_window": True, "due": 1.0, "times": [1.2, 1.3, 1.4]},
        # due in the window, first token after the window closed
        {"in_window": True, "due": 9.9, "times": [10.4, 10.5]},
        # lead-in request: no TTFT sample, but its in-window tokens count
        {"in_window": False, "due": -1.0, "times": [-0.5, 0.5, 0.9]},
        # never served
        {"in_window": True, "due": 5.0, "times": []},
    ]
    rec = _rec(reqs)
    assert _read("ttft_p75_ms", rec) == pytest.approx(
        np.percentile([200.0, 500.0], 75))
    # gaps whose later delivery lies in [0, 10): 0.1, 0.1, 1.0, 0.4
    assert _read("itl_p95_ms", rec) == pytest.approx(
        np.percentile([100.0, 100.0, 1000.0, 400.0], 95))
    # tokens delivered in [0, 10): 3 + 0 + 2
    assert _read("output_tokens_per_s", rec) == pytest.approx(0.5)
    assert _read("setup_s", rec) == 1.5
    assert _read("ttft_p75_ms", _rec([])) is None
