"""The schedule gives every seed the same work, stretch by stretch."""

import collections

import numpy as np
import pytest

from traffic import load, schedule

SEEDS = [1, 2**33 + 7, 4000000401]


def strata(reqs, traffic, seconds):
    """Per stratum of the window: the sorted prompt lengths and budgets."""
    n = round(seconds / traffic["arrival"]["stratum_s"])
    out = collections.defaultdict(list)
    for r in reqs:
        if r.in_window:
            out[min(n - 1, int(r.due / (seconds / n)))].append(
                (len(r.prompt), r.budget))
    return {k: (sorted(p for p, _ in v), sorted(b for _, b in v))
            for k, v in out.items()}


@pytest.mark.parametrize("name", ["code-chat", "chat-overload"])
def test_every_seed_gets_the_same_work_per_stratum(name):
    traffic = load(name)
    runs = [schedule(traffic, s, 51.0, 1000) for s in SEEDS]
    want = strata(runs[0], traffic, 51.0)
    assert len(want) == round(51.0 / traffic["arrival"]["stratum_s"])
    for reqs in runs[1:]:
        assert strata(reqs, traffic, 51.0) == want
    orders = {tuple(r.budget for r in reqs) for reqs in runs}
    assert len(orders) == len(SEEDS)          # the seed still orders them


def test_rate_and_window():
    traffic = load("code-chat")
    reqs = schedule(traffic, 5, 51.0, 1000)
    inside = [r for r in reqs if r.in_window]
    assert len(inside) == round(traffic["arrival"]["rate_rps"] * 51.0)
    assert all(0.0 <= r.due < 51.0 for r in inside)
    assert all(r.due >= 51.0 for r in reqs if not r.in_window)
    dues = np.array([r.due for r in reqs])
    assert np.all(np.diff(dues) > 0)
