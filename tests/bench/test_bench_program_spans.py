"""The program's own spans on the trace: they nest as the served path
does on a CPU run of the small kernel suite, ``cb.cli --trace`` records
them, and the readers of the timing harness's numbers give, on a
hand-made window, the answers worked out by hand."""
import glob
import json

import jax
import pytest
from jax.profiler import TraceAnnotation

from bench.entries import Entries
from bench.program_spans import read_program_spans
from bench.tracing import MetricContext, TraceView, read_xplane

kernel_bench = pytest.importorskip(
    "benchmarks.kernel_bench",
    reason="benchmarks package needs the repo root on sys.path")

from repro.cb import Pipeline, PipelineConfig, get_suite  # noqa: E402
from repro.cb.cli import main as cli_main  # noqa: E402
from repro.core.duet import DuetRunnable  # noqa: E402
from repro.obs.spans import compiles  # noqa: E402

ENTRIES = Entries()
NEW_METRICS = ("harness_unmeasured_pct", "harness_rounds_per_version",
               "dispatch_gap_us", "window_compiles")

#: A 2000-ns window: one commit (100..1900) of one pair.  Version v1
#: (210..800) warms up, runs a 1-call round and a 4-call round; v2
#: (810..1390) warms up and measures at once.  The device clock runs
#: 5 ns early at v1's first round: its execution starts before the round.
#: A version span after the window (2100..2200) is not the window's.
HAND2 = {
    "host": [["bench.window", 0, 2000]],
    "program": [
        ["cb.commit", 100, 1900],
        ["cb.prepare", 110, 150],
        ["cb.suite_run", 160, 1700],
        ["engine.run", 170, 1600],
        ["engine.execute", 180, 1500],
        ["duet.pair", 200, 1400],
        ["duet.v1/d", 210, 800],
        ["timing.warmup", 215, 300],
        ["timing.round", 310, 400],
        ["timing.round", 410, 790],
        ["duet.v2/d", 810, 1390],
        ["timing.warmup", 815, 900],
        ["timing.round", 910, 1380],
        ["cb.analyze", 1620, 1690],
        ["cb.finalize", 1710, 1800],
        ["cb.detect", 1910, 1990],
        ["duet.v1/d", 2100, 2200],
        ["timing.round", 2110, 2190],
    ],
    "chips": [{"modules": [
        ["p1", 230, 290],                                   # warm-up
        ["p1", 305, 395],                                   # round 1
        ["p1", 420, 500], ["p1", 505, 585], ["p1", 590, 670],
        ["p1", 675, 755],                                   # round 2
        ["p2", 820, 890],                                   # warm-up
        ["p2", 920, 1370],                                  # v2's round
    ]}],
}


def program_context(events, compiles_seen=None):
    view = TraceView(events)
    view.program = events["program"]
    ctx = MetricContext(view, {}, None)
    if compiles_seen is not None:
        ctx.compiles = compiles_seen
    return ctx


def read(name, ctx):
    return ENTRIES.metric(name).read(ctx)


def test_harness_metrics_on_the_hand_made_window():
    ctx = program_context(HAND2, compiles_seen=0)
    # versions 590 + 580 ns; measured rounds 380 + 470 ns
    assert read("harness_unmeasured_pct", ctx) == pytest.approx(
        100 * (1170 - 850) / 1170)
    # v1 two rounds, v2 one
    assert read("harness_rounds_per_version", ctx) == pytest.approx(1.5)
    # round 1: 90 - 90; round 2: 380 - 4 x 80; v2's: 470 - 450; the
    # warm-ups' executions lie in no round: 80 ns over 6 executions
    assert read("dispatch_gap_us", ctx) == pytest.approx(80 / 6 * 1e-3)
    assert read("window_compiles", ctx) == 0


def test_new_readers_read_nothing_where_the_harness_keeps_no_program_spans():
    ctx = MetricContext(TraceView({"host": HAND2["host"],
                                   "chips": HAND2["chips"]}), {}, None)
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name
    no_chip = program_context(dict(HAND2, chips=[]))
    assert read("dispatch_gap_us", no_chip) is None
    assert read("harness_rounds_per_version", no_chip) == pytest.approx(1.5)


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def parents(spans, child, parent):
    """Each span called `child` with the spans called `parent` (or with a
    name starting with `parent`, for a tuple) that hold it."""
    match = ((lambda n: n.startswith(parent)) if isinstance(parent, tuple)
             else (lambda n: n == parent))
    outers = [s for s in spans if match(s[0])]
    return [(c, [o for o in outers if inside(c, o)])
            for c in spans if c[0] == child]


@pytest.fixture(scope="module")
def traced_commit(tmp_path_factory):
    """A commit of the small kernel suite, 2 calls a benchmark, traced."""
    suite = get_suite("kernels", small=True)
    suite._build()
    cfg = PipelineConfig(suite="kernels", provider="local", mode="selective",
                         n_calls=2, repeats_per_call=1, parallelism=1,
                         min_results=2)
    pipe = Pipeline(suite, cfg)
    base, head = kernel_bench.kernel_commits()
    pipe.run_commit(base)
    trace_dir = tmp_path_factory.mktemp("trace")
    before = compiles()
    with jax.profiler.trace(str(trace_dir)):
        with TraceAnnotation("bench.window"):
            run = pipe.run_commit(head)
    after = compiles()
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return run, path, after[0] - before[0]


def test_the_served_path_nests_on_the_profiler_clock(traced_commit):
    run, path, _ = traced_commit
    spans = read_program_spans(path)
    names = [s[0] for s in spans]
    duets = sorted(kernel_bench._FP_MODULES)
    assert run.ran == duets
    for name in ("cb.commit", "cb.prepare", "cb.suite_run", "engine.run",
                 "cb.analyze", "cb.finalize"):
        assert names.count(name) == 1, name
    chain = [("cb.prepare", "cb.commit"), ("cb.suite_run", "cb.commit"),
             ("cb.finalize", "cb.commit"), ("engine.run", "cb.suite_run"),
             ("cb.analyze", "cb.suite_run"),
             ("engine.execute", "engine.run"),
             ("duet.pair", "engine.execute"),
             ("timing.warmup", ("duet.v1/", "duet.v2/")),
             ("timing.round", ("duet.v1/", "duet.v2/"))]
    for d in duets:
        chain += [(f"duet.v1/{d}", "duet.pair"), (f"duet.v2/{d}", "duet.pair")]
    for child, parent in chain:
        held = parents(spans, child, parent)
        assert held and all(len(o) == 1 for _, o in held), (child, parent)
    assert names.count("engine.execute") == names.count("duet.pair") == 6
    # one warm-up and at least one round in every version
    versions = [s for s in spans if s[0].startswith(("duet.v1/",
                                                     "duet.v2/"))]
    assert len(versions) == 12
    for v in versions:
        inner = [s[0] for s in spans if inside(s, v) and s is not v]
        assert inner.count("timing.warmup") == 1
        assert inner.count("timing.round") >= 1


def test_the_harness_readers_on_a_cpu_trace(traced_commit):
    _, path, window_compiles = traced_commit
    events = read_xplane(path)
    events["program"] = read_program_spans(path)
    ctx = program_context(events, compiles_seen=window_compiles)
    assert 0 < read("harness_unmeasured_pct", ctx) < 100
    assert read("harness_rounds_per_version", ctx) >= 1
    assert read("dispatch_gap_us", ctx) is None       # no device plane
    # the versions call executables compiled before the window
    assert read("window_compiles", ctx) == 0


def test_the_cli_records_a_profiler_trace_for_the_kernel_suite(
        monkeypatch, tmp_path, capsys):
    def build(self):
        return {b: DuetRunnable(b, lambda: 1e-3, lambda: 1.1e-3)
                for b in kernel_bench._FP_MODULES}

    monkeypatch.setattr(kernel_bench.KernelSuite, "_build", build)
    out = tmp_path / "trace"
    rc = cli_main(["--suite", "kernels", "--mode", "selective",
                   "--n-calls", "3", "--repeats", "1", "--parallelism", "1",
                   "--trace", str(out)])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.splitlines()[0])
    assert summary["failed"] == []
    (path,) = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    names = [s[0] for s in read_program_spans(path)]
    # the baseline commit and the head, then the stream's scan
    assert names.count("cb.commit") == 2 and names.count("cb.detect") == 1
    assert names.count("duet.pair") == 3 * len(kernel_bench._FP_MODULES)
    assert glob.glob(f"{out}/**/perfetto_trace.json.gz", recursive=True)
