"""Vectorized statistics engine: batched == scalar bit-for-bit, empty-input
guards, the cached bootstrap draws, and the streaming dirty-set."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import stats
from repro.core.duet import DuetPair
from repro.core.results import StreamingAnalyzer, analyze
from repro.core.stats import (bootstrap_median_ci, bootstrap_median_ci_batch,
                              detect_change, detect_changes_batch,
                              _boot_draw, _window_medians)


def _seed_reference_ci(x, confidence=0.99, n_boot=1000, seed=0):
    """The pre-vectorization implementation, verbatim: fresh RNG + index
    draw, dense resample medians, np.quantile outward interpolation."""
    x = np.asarray(x, dtype=np.float64)
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(x), size=(n_boot, len(x)))
    medians = np.median(x[idx], axis=1)
    alpha = (1.0 - confidence) / 2.0
    lo = np.quantile(medians, alpha, method="lower")
    hi = np.quantile(medians, 1.0 - alpha, method="higher")
    return float(np.median(x)), float(lo), float(hi)


def _tuples_equal(a, b):
    return all((np.isnan(p) and np.isnan(q)) or p == q for p, q in zip(a, b))


# ------------------------------------------------------- scalar == seed
@pytest.mark.parametrize("n", [1, 2, 3, 10, 31, 45, 200, 257])
@pytest.mark.parametrize("seed", [0, 7])
def test_scalar_ci_matches_seed_reference(n, seed):
    rng = np.random.default_rng(n * 31 + seed)
    for x in (rng.normal(0, 1, n), np.round(rng.normal(0, 1, n), 1),
              np.full(n, 0.5)):
        assert bootstrap_median_ci(x, seed=seed) == \
            _seed_reference_ci(x, seed=seed)


def test_scalar_ci_matches_seed_reference_nonfinite():
    x = np.linspace(-1, 1, 20)
    for bad in (np.nan, np.inf, -np.inf):
        y = x.copy()
        y[3] = bad
        assert _tuples_equal(bootstrap_median_ci(y, seed=2),
                             _seed_reference_ci(y, seed=2))


def test_empty_input_guards():
    assert bootstrap_median_ci(np.array([])) == pytest.approx(
        (np.nan,) * 3, nan_ok=True)
    # min_results=0 used to crash in rng.integers(0, 0, ...)
    assert detect_change("b", np.array([]), np.array([]),
                         min_results=0) is None
    m, lo, hi = bootstrap_median_ci_batch([np.array([]), np.ones(12)])
    assert np.isnan(m[0]) and np.isnan(lo[0]) and np.isnan(hi[0])
    assert np.isfinite(m[1])
    assert detect_changes_batch([("b", np.array([]), np.array([]))],
                                min_results=0) == {}


# ------------------------------------------------------- batched == loop
def _ragged_suite(seed, k, max_n):
    rng = np.random.default_rng(seed)
    items = []
    for i in range(k):
        n = int(rng.integers(1, max_n + 1))
        v1 = rng.lognormal(0.0, 0.05, n)
        v2 = v1 * float(rng.uniform(0.85, 1.2)) * rng.lognormal(0.0, 0.03, n)
        items.append((f"b{i}", v1, v2))
    return items


@pytest.mark.parametrize("confidence", [0.99, 0.95, 0.5])
@pytest.mark.parametrize("seed", [0, 1, 12345])
def test_detect_changes_batch_equals_loop(confidence, seed):
    items = _ragged_suite(seed + 17, k=25, max_n=90)
    loop = {}
    for name, v1, v2 in items:
        res = detect_change(name, v1, v2, confidence=confidence, seed=seed,
                            min_results=5)
        if res is not None:
            loop[name] = res
    batch = detect_changes_batch(items, confidence=confidence, seed=seed,
                                 min_results=5)
    assert batch == loop
    assert list(batch) == list(loop)          # insertion order preserved


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=10**6),
       st.integers(min_value=1, max_value=12),
       st.integers(min_value=1, max_value=60),
       st.sampled_from([0.99, 0.9, 0.75]))
def test_property_batch_equals_loop(seed, k, max_n, confidence):
    """Property (ISSUE satellite): detect_changes_batch == per-benchmark
    detect_change loop bit-for-bit across ragged suite shapes,
    confidences, and seeds."""
    items = _ragged_suite(seed, k=k, max_n=max_n)
    loop = {}
    for name, v1, v2 in items:
        res = detect_change(name, v1, v2, confidence=confidence,
                            seed=seed % 997, min_results=3)
        if res is not None:
            loop[name] = res
    assert detect_changes_batch(items, confidence=confidence,
                                seed=seed % 997, min_results=3) == loop


def test_window_fallback_rows_are_exact():
    """pad=0 forces every resample row through the out-of-window fallback;
    results must not change."""
    rng = np.random.default_rng(11)
    block = np.stack([rng.normal(0, 1, 30) for _ in range(4)])
    draw = _boot_draw(30, 1000, 7)
    ref = np.stack([np.median(row[draw.idx], axis=1) for row in block])
    assert np.array_equal(_window_medians(block, draw)[0], ref)
    assert np.array_equal(_window_medians(block, draw, pad=0)[0], ref)


def test_boot_draw_cache_reuses_and_bounds():
    stats._boot_cache.clear()
    d1 = _boot_draw(40, 1000, 3)
    assert _boot_draw(40, 1000, 3) is d1          # hit
    assert _boot_draw(40, 1000, 4) is not d1      # seed in the key
    for i in range(stats._BOOT_CACHE_MAX + 5):
        _boot_draw(10 + i, 64, 0)
    assert len(stats._boot_cache) <= stats._BOOT_CACHE_MAX


# -------------------------------------------------- streaming dirty-set
def _pair_stream(seed, n_bench, n_pairs):
    rng = np.random.default_rng(seed)
    pairs = []
    for i in range(n_bench):
        effect = float(rng.uniform(0.9, 1.15))
        v1 = rng.lognormal(0.0, 0.05, n_pairs)
        v2 = v1 * effect * rng.lognormal(0.0, 0.03, n_pairs)
        pairs += [DuetPair(benchmark=f"b{i}", v1_seconds=float(a),
                           v2_seconds=float(b))
                  for a, b in zip(v1, v2)]
    order = rng.permutation(len(pairs))
    return [pairs[int(j)] for j in order]


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=10**6),
       st.integers(min_value=1, max_value=5),
       st.integers(min_value=1, max_value=30))
def test_property_streaming_dirty_set_equals_batch(seed, n_bench, n_pairs):
    """Property (ISSUE satellite): the ring-buffer + dirty-set analyzer,
    with interim result()/results() queries exercising partial
    recomputation, equals batch analyze() bit-for-bit."""
    stream = _pair_stream(seed, n_bench, n_pairs)
    an = StreamingAnalyzer(seed=seed % 991, min_results=4)
    for i, p in enumerate(stream):
        an.add_pair(p)
        if i % 3 == 0:
            an.result(p.benchmark)
        if i % 7 == 0:
            an.results(an.benchmarks)          # batched partial recompute
    assert an.analyze() == analyze(stream, seed=seed % 991, min_results=4)


def test_streaming_results_batch_query():
    stream = _pair_stream(3, 3, 20)
    an = StreamingAnalyzer(seed=5, min_results=4)
    an.add_pairs(stream)
    res = an.results(["b0", "b1", "b2", "ghost"])
    assert res["ghost"] is None
    for name in ("b0", "b1", "b2"):
        assert res[name] == detect_change(
            name,
            np.array([p.v1_seconds for p in stream if p.benchmark == name]),
            np.array([p.v2_seconds for p in stream if p.benchmark == name]),
            seed=5, min_results=4)
        assert an.result(name) is res[name]    # cache hit, same object


# ------------------------------------------------------------ jax kernel
def test_jax_kernel_agrees_with_numpy():
    rng = np.random.default_rng(0)
    arrays = [rng.normal(0, 1, n) for n in (45, 45, 128, 31, 10)]
    m0, l0, h0 = bootstrap_median_ci_batch(arrays, seed=3)
    m1, l1, h1 = bootstrap_median_ci_batch(arrays, seed=3, backend="jax")
    assert np.allclose(m0, m1, rtol=1e-5, atol=1e-6)
    assert np.allclose(l0, l1, rtol=1e-5, atol=1e-6)
    assert np.allclose(h0, h1, rtol=1e-5, atol=1e-6)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        bootstrap_median_ci_batch([np.ones(5)], backend="cuda")
