"""The statistics check: the exact bootstrap distribution of a median,
and the numbers that hold the program's change results to it."""
from dataclasses import dataclass, replace

import numpy as np
import pytest

from bench.check import median_atoms, stats_numbers
from bench.entries import Entries
from repro.core.stats import detect_change

LIMITS = {c["name"]: Entries().config(c["name"])["limits"]
          for c in Entries().spec["configs"]}


@dataclass
class Pair:
    benchmark: str
    v1_seconds: float
    v2_seconds: float


@pytest.mark.parametrize("n", [9, 12, 45, 44])
def test_median_atoms_match_resampling(n):
    rng = np.random.default_rng(n)
    d = np.round(rng.normal(size=n), 1)          # ties on purpose
    values, probs = median_atoms(d)
    assert probs.min() >= 0 and abs(probs.sum() - 1) < 1e-12
    medians = np.median(d[rng.integers(0, n, (100_000, n))], axis=1)
    for x in np.quantile(medians, [0.01, 0.1, 0.5, 0.9, 0.99]):
        assert abs((medians <= x).mean()
                   - probs[values <= x + 1e-12].sum()) < 4e-3


def _window(seed, n=45, k=12, **kw):
    """`k` change results of the program over `n` pairs each, with the
    pairs they came from."""
    rng = np.random.default_rng(seed)
    runs = []
    for i in range(k):
        v1 = rng.lognormal(-3, 0.02, n)
        v2 = v1 * (1 + rng.normal(0.005, 0.01, n))
        c = detect_change("b", v1, v2, seed=int(rng.integers(2**31)),
                          min_results=10, **kw)
        runs.append(([Pair("b", a, b) for a, b in zip(v1, v2)], {"b": c}))
    return runs


@pytest.mark.parametrize("seed", range(4))
def test_sound_change_results_pass_whatever_their_random_stream(seed):
    faults, surprise = stats_numbers(_window(seed), min_results=10)
    assert faults == 0
    assert surprise < min(lim["ci_end_surprise"] for lim in LIMITS.values())


def test_planted_statistics_faults_are_caught():
    runs = _window(7)
    limit = max(lim["ci_end_surprise"] for lim in LIMITS.values())
    moved = [(p, {b: replace(c, ci_high=c.ci_high + 1.0)
                  for b, c in ch.items()}) for p, ch in runs]
    assert stats_numbers(moved, min_results=10)[1] > limit
    flipped = [(p, {b: replace(c, changed=not c.changed)
                    for b, c in ch.items()}) for p, ch in runs]
    assert stats_numbers(flipped, min_results=10)[0] == len(runs)
    halved = [(p, {"b": detect_change(
        "b", [x.v1_seconds for x in p[::2]], [x.v2_seconds for x in p[::2]],
        seed=1, min_results=10)}) for p, _ in runs]
    assert stats_numbers(halved, min_results=10)[0] == len(runs)
    missing = [(p, {}) for p, _ in runs]
    assert stats_numbers(missing, min_results=10)[0] == len(runs)
