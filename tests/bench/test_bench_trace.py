"""The reduction from trace events to per-layer metrics: on a hand-made
window whose answers are worked out by hand, and on a small trace
recorded on a TPU v5e, checked against a brute-force recount."""
import gzip
import json
from pathlib import Path

import pytest

from bench.entries import Entries
from bench.tracing import MetricContext, TraceView, union_ns
from repro.core.stats import ChangeResult

RECORDED = Path(__file__).parent / "data" / "recorded_trace.json.gz"
ENTRIES = Entries()

#: A 1000-ns window: one commit (100..900) whose suite run (150..700)
#: holds one engine run, one invocation, one pair and its two versions,
#: and its analysis (620..690); the detector runs at 700..880.
HAND = {
    "host": [
        ["bench.window", 0, 1000],
        ["bench.commit", 100, 900],
        ["bench.suite_run", 150, 700],
        ["bench.engine", 160, 600],
        ["bench.execute", 200, 560],
        ["bench.pair", 210, 550],
        ["bench.v1/d", 220, 380],
        ["bench.v2/d", 390, 540],
        ["bench.analyze", 620, 690],
        ["bench.detect", 700, 880],
    ],
    "chips": [{"modules": [["jit_a", 230, 300], ["jit_a", 310, 370],
                           ["jit_b", 400, 500]]}],
}


def hand_context(peaks=None, programs=None):
    return MetricContext(TraceView(HAND), programs or {}, peaks)


def test_union_merges_overlaps_and_clips_to_the_window():
    assert union_ns([(5, 10), (0, 3), (8, 12), (20, 30)], 1, 25) == [
        (1, 3), (5, 12), (20, 25)]


def test_busy_idle_and_gaps_on_the_hand_made_window():
    view = TraceView(HAND)
    # programs run 230..300, 310..370, 400..500: 70 + 60 + 100 ns
    assert view.busy_s() == pytest.approx(230e-9)
    assert view.window_s == pytest.approx(1000e-9)
    gaps = dict(view.idle_gaps())
    # 0..230: middle 115 lies in the commit only; 300..310 in bench.v1/d;
    # 370..400: middle 385 in bench.pair; 500..1000: middle 750 in detect
    assert gaps == pytest.approx({"bench.commit": 230e-9,
                                  "bench.v1/d": 10e-9,
                                  "bench.pair": 30e-9,
                                  "bench.detect": 500e-9})
    assert dict(view.device_ops()) == pytest.approx(
        {"jit_a as bench.v1/d": 130e-9, "jit_b as bench.v2/d": 100e-9})
    assert [m[0] for m in view.executions("bench.v1/d")] == ["jit_a",
                                                              "jit_a"]
    assert [m[0] for m in view.executions("bench.v2/d")] == ["jit_b"]


def test_programs_are_attributed_across_a_clock_offset():
    """The device's clock runs 15 ns early: jit_a's first run starts
    before its span, jit_b's before v2's; each program still belongs to
    the version whose span it overlaps most."""
    shifted = dict(HAND, chips=[{"modules": [
        [n, s - 15, e - 15] for n, s, e in HAND["chips"][0]["modules"]]}])
    view = TraceView(shifted)
    assert view.programs() == {"jit_a": "bench.v1/d", "jit_b": "bench.v2/d"}
    assert len(view.executions("bench.v1/d")) == 2
    assert len(view.executions("bench.v2/d")) == 1


def test_layer_metrics_on_the_hand_made_window():
    ctx = hand_context(
        peaks={"flops_per_s": 1e9, "bytes_per_s": 1e9},
        programs={"a": ("d", "v1", 20, 10), "b": ("d", "v2", 10, 50)})
    read = lambda name: ENTRIES.metric(name).read(ctx)
    assert read("pipeline_host_ms") == pytest.approx((800 - 550) * 1e-6)
    assert read("stats_ms") == pytest.approx(70 * 1e-6)
    assert read("engine_gap_ms") == pytest.approx((440 - 360) * 1e-6)
    assert read("harness_calls_per_pair") == 3.0
    assert read("device_idle_pct") == pytest.approx(77.0)
    # a: 2 runs of at least 20 ns in 130 ns; b: 1 run of 50 ns in 100 ns
    assert ctx.roofline_pct("a") == pytest.approx(100 * 40 / 130)
    assert ctx.roofline_pct("b") == pytest.approx(50.0)
    assert ctx.roofline_pct("missing") is None
    assert hand_context().roofline_pct("a") is None
    assert read("ci_width_pp") is None          # no verdict measured
    ctx.changes = [ChangeResult("d", 45, -1.0, -1.5, -0.5, True, -1),
                   ChangeResult("d", 45, 0.1, -0.2, 0.2, False, 0)]
    assert read("ci_width_pp") == pytest.approx(0.7)


def test_a_window_without_device_events_reports_no_device_metric():
    view = TraceView({"host": HAND["host"], "chips": []})
    ctx = MetricContext(view, {}, None)
    assert view.busy_s() is None
    assert ENTRIES.metric("device_idle_pct").read(ctx) is None
    assert ENTRIES.metric("harness_calls_per_pair").read(ctx) is None
    assert ENTRIES.metric("stats_ms").read(ctx) == pytest.approx(70e-6)


def brute_busy_ns(events, lo, hi):
    """Busy time by walking every interval endpoint in order."""
    points = sorted([(max(s, lo), 1) for _, s, e in events if e > lo
                     and s < hi] + [(min(e, hi), -1) for _, s, e in events
                                    if e > lo and s < hi])
    busy, depth, last = 0, 0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def brute_programs(events):
    """Program name -> the version span it overlaps most, pair by pair."""
    spans = [s for s in events["host"]
             if s[0].startswith(("bench.v1/", "bench.v2/"))]
    overlap = {}
    for name, s, e in events["chips"][0]["modules"]:
        for label, vs, ve in spans:
            if min(e, ve) > max(s, vs):
                key = (name, label)
                overlap[key] = overlap.get(key, 0) + min(e, ve) - max(s, vs)
    best = {}
    for (name, label), ns in overlap.items():
        if ns > best.get(name, (0, ""))[0]:
            best[name] = (ns, label)
    return {n: label for n, (_, label) in best.items()}


def test_recorded_v5e_trace():
    with gzip.open(RECORDED, "rt") as f:
        events = json.load(f)
    view = TraceView(events)
    chip = events["chips"][0]
    assert view.busy_s() == pytest.approx(
        brute_busy_ns(chip["modules"], view.w0, view.w1) * 1e-9)
    assert 0 < view.busy_s() < view.window_s
    gaps = view.idle_gaps()
    assert sum(s for _, s in gaps) == pytest.approx(
        view.window_s - view.busy_s())
    programs = brute_programs(events)
    assert view.programs() == programs
    # four versions, four distinct programs
    assert sorted(programs.values()) == sorted(
        {s[0] for s in events["host"]
         if s[0].startswith(("bench.v1/", "bench.v2/"))})
    for name, label in programs.items():
        runs = view.executions(label)
        assert runs and {m[0] for m in runs} == {name}
        assert len(runs) == sum(m[0] == name for m in chip["modules"])
    device = dict(view.device_ops())
    assert sum(device.values()) == pytest.approx(view.busy_s())
