"""The window function, called in-process at the suite's CPU test shapes:
the result has the contract's shape, and ``correct`` comes out false
when the timed path is broken underneath, or when the float8 control
takes the program's place."""
import json
import shutil

import jax
import pytest

from bench.entries import ROOT, Entries
from bench.window import run_window

kernel_bench = pytest.importorskip(
    "benchmarks.kernel_bench",
    reason="benchmarks package needs the repo root on sys.path")

from repro.cb import get_suite  # noqa: E402
from repro.core.duet import DuetRunnable  # noqa: E402
from repro.core.timing import make_timed  # noqa: E402

SPEC = Entries().spec
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**31 + 3
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """Keep the process's compile cache as the other tests have it."""
    monkeypatch.setattr("repro.core.timing.use_compile_cache",
                        lambda: "off")


@pytest.fixture(scope="module")
def suite():
    return get_suite("kernels", small=True)


@pytest.fixture(scope="module")
def short_root(tmp_path_factory):
    """A copy of the benchmark whose configurations plan 4 calls of 3
    pairs per benchmark, so a commit takes a second on the CPU."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    for c in SPEC["configs"]:
        path = root / c["file"]
        cfg = json.loads(path.read_text())
        cfg["deployment"]["pipeline"]["n_calls"] = 4
        path.write_text(json.dumps(cfg))
    return root


@pytest.mark.parametrize("cell", CELLS)
def test_window_returns_the_contract_line(cell, suite):
    r = run_window(cell, SEED, 0.1, False, small=True, suite=suite)
    assert list(r)[:5] == RESULT_KEYS and list(r)[-1] == "checks"
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    names = {m["name"]: m["unit"] for m in SPEC["end_to_end"]
             if cell in m.get("workloads", [cell])}
    assert {k: v["unit"] for k, v in r["metrics"].items()} == names
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert r["device"]["platform"] == jax.devices()[0].platform
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]
    json.dumps(r, allow_nan=False)


def test_traced_window_reports_per_layer_metrics(short_root, suite):
    cell = CELLS[0]
    r = run_window(cell, SEED + 1, 0.1, True, root=short_root, small=True,
                   suite=suite)
    assert r["correct"] is True, r["checks"]
    per_layer = {m["name"] for m in SPEC["per_layer"]
                 if cell in m.get("workloads", [cell])}
    # the host spans are read on any platform; device metrics need a TPU
    assert {"pipeline_host_ms", "stats_ms", "engine_gap_ms",
            "ci_width_pp"} <= set(r["metrics"]) <= per_layer
    assert r["device"]["window_s"] > 0
    assert r["metrics"]["stats_ms"]["value"] > 0


def _altered_output(suite, monkeypatch):
    """Version 2 of the first duet returns a changed output from its
    compiled program."""
    name = sorted(suite._build())[0]
    spec = {s.name: s for s in kernel_bench.duet_specs(small=True)}[name]
    args = spec.make_args()
    bad = jax.jit(lambda *a: spec.v2(*a).at[0].multiply(1.5)).lower(
        *args).compile()
    duet = suite._build()[name]
    monkeypatch.setitem(suite._duets, name, DuetRunnable(
        name, duet.v1, make_timed(bad, *args)))


def _half_batch(suite, monkeypatch):
    from repro.cb import registry
    analyze = registry.analyze
    monkeypatch.setattr(registry, "analyze",
                        lambda pairs, **kw: analyze(list(pairs)[::2], **kw))


def _verdict_altered(suite, monkeypatch):
    from dataclasses import replace

    from repro.cb import registry
    analyze = registry.analyze

    def flipped(pairs, **kw):
        return {b: replace(c, changed=not c.changed)
                for b, c in analyze(pairs, **kw).items()}
    monkeypatch.setattr(registry, "analyze", flipped)


def _ci_end_moved(suite, monkeypatch):
    """Each interval's upper end moved by 1 pp and ten widths: beyond
    what any bootstrap of the pairs reports."""
    from dataclasses import replace

    from repro.cb import registry
    analyze = registry.analyze

    def moved(pairs, **kw):
        return {b: replace(c, ci_high=c.ci_high + 1.0 + 10 * c.ci_size)
                for b, c in analyze(pairs, **kw).items()}
    monkeypatch.setattr(registry, "analyze", moved)


def _history_unchanged(suite, monkeypatch):
    from repro.cb.history import HistoryStore
    monkeypatch.setattr(HistoryStore, "append", lambda self, records: None)


@pytest.mark.parametrize("fault", [_altered_output, _half_batch,
                                   _verdict_altered, _ci_end_moved,
                                   _history_unchanged],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_timed_path_is_not_correct(fault, short_root, monkeypatch):
    suite = get_suite("kernels", small=True)
    fault(suite, monkeypatch)
    r = run_window(CELLS[0], SEED, 0.1, False, root=short_root, small=True,
                   suite=suite)
    assert r["correct"] is False
    assert any(c["value"] == "inf" or c["value"] > c["limit"]
               for c in r["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_the_float8_control_fails_the_limits(cell, short_root, suite):
    """The control takes the program's place, and the same checks that
    decide every run's ``correct`` find it not correct: each duet's gap
    over its limit, while the program's own numbers keep theirs."""
    r = run_window(cell, SEED, 0.1, False, root=short_root, small=True,
                   suite=suite, control=True)
    assert r["correct"] is False
    assert list(r)[-2:] == ["program_checks", "checks"]
    gaps = [k for k in r["checks"] if k.endswith("_gap")]
    assert gaps and all(r["checks"][k]["value"] > r["checks"][k]["limit"]
                        for k in gaps)
    assert all(c["value"] <= c["limit"]
               for c in r["program_checks"].values()), r["program_checks"]


def test_duet_inputs_come_from_the_seed():
    """The suite builds its duets on inputs made from the seed: the same
    seed gives the same inputs, another seed others, and the RMSNorm
    weight is not zero."""
    from bench.hooks import seeded_inputs

    def inputs(seed):
        with seeded_inputs(Entries(), seed):
            specs = kernel_bench.duet_specs(small=True)
        return {s.name: s.make_args() for s in specs}

    a, b, c = inputs(SEED), inputs(SEED), inputs(SEED + 1)
    for name, args in a.items():
        for x, y, z in zip(args, b[name], c[name]):
            assert x.shape == z.shape and x.dtype == z.dtype
            assert bool((x == y).all())
        assert not all(bool((x == z).all()) for x, z in zip(args, c[name]))
    assert float(abs(a["rmsnorm_fused_vs_unfused"][1]).max()) > 0.1
