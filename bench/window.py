"""One run of one cell: set-up, the measured window, the checks, the
result line.

The window drives the service's served path in one process: for each
commit of the generated stream, ``Pipeline.run_commit`` and then
``RegressionDetector.scan`` over the history so far, as a CI service does
for every verdict.  The loop is closed (one CI runner holds the chip, and
the next commit is submitted when the previous verdict is out) and made of
whole commits: it ends at the verdict of the last commit that started
before `seconds` ran out.
"""
from __future__ import annotations

import contextlib
import glob
import math
import shutil
import tempfile
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import check, hooks
from bench.entries import ROOT, Entries
from bench.stream import commit_stream
from bench.tracing import MetricContext, TraceView, read_xplane

#: Entries kept in each list of the result's ``breakdown``.
BREAKDOWN_TOP = 10


def run_window(cell_name: str, seed: int, seconds: float, trace: bool, *,
               root: Path = ROOT, small: bool = False, suite=None,
               t_start: Optional[float] = None, control: bool = False,
               recorder: Optional[hooks.Recorder] = None,
               setup_marks: Optional[dict] = None) -> dict:
    """Run `cell_name` once and return the result line as a dict: with
    `trace`, the cell's per-layer metrics, read from a profiler trace of
    the window's second commit; else its end-to-end metrics.

    The suite builds its duets on inputs made from `seed`; `suite`, a
    suite object already built, keeps the inputs it was built on.
    `small` runs the suite at its CPU test shapes (and skips the check of
    the configuration's widths).  `t_start` is when the process started
    (set-up counts from there); `setup_marks` adds seconds of set-up
    measured before the call to the result's ``setup``.  With `control`,
    the float8 control's outputs take the place of the program's before
    the checks decide ``correct``; the program's own numbers are kept
    under ``program_checks``.  `recorder`, where given, is filled with
    what the window produced."""
    from repro.cb import Pipeline, PipelineConfig, get_suite
    from repro.cb.detect import RegressionDetector
    from repro.core.timing import use_compile_cache
    import benchmarks.kernel_bench  # noqa: F401  registers the suite

    t_start = time.perf_counter() if t_start is None else t_start
    entries = Entries(root)
    cell = entries.cell(cell_name)
    cfg = entries.config(cell["config"])
    dep = cfg["deployment"]
    benchmarks = dep["benchmarks"]

    cache_dir = use_compile_cache()
    if suite is None:
        suite = get_suite(dep["suite"], small=small)
    t_build = time.perf_counter()
    with hooks.seeded_inputs(entries, seed):
        duets = suite._build()
    build_s = time.perf_counter() - t_build
    compile_s = sum(sum(c.compile_seconds) for c in suite.checks.values())

    pcfg = PipelineConfig(suite=dep["suite"], seed=seed, **dep["pipeline"])
    pipeline = Pipeline(suite, pcfg)
    detector = RegressionDetector(pcfg.detector)
    stream = commit_stream(entries.traffic(cell["traffic"]), benchmarks,
                           seed)
    parent = next(stream)
    pipeline.run_commit(parent)                 # the baseline: no run
    setup_s = time.perf_counter() - t_start

    rec = hooks.Recorder() if recorder is None else recorder
    commits, verdict_s = [], []
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    with hooks.installed(suite, {b: duets[b] for b in benchmarks}, rec):
        t0 = time.perf_counter()
        # the traced run traces its second commit (the first after one
        # untraced), and only that: the device writes millions of events
        # a commit; it runs on to that commit whatever `seconds` says
        while (time.perf_counter() - t0 < seconds
               or (trace and len(commits) < 2)):
            commit = next(stream)
            traced = trace and len(commits) == 1
            if traced:
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=_options())
            try:
                with (TraceAnnotation("bench.window") if traced
                      else contextlib.nullcontext()):
                    ts = time.perf_counter()
                    with TraceAnnotation("bench.commit"):
                        run = pipeline.run_commit(commit)
                        with TraceAnnotation("bench.detect"):
                            detector.scan(pipeline.history,
                                          provider=pcfg.provider,
                                          mode=pcfg.mode)
                    verdict_s.append(time.perf_counter() - ts)
            finally:
                if traced:
                    jax.profiler.stop_trace()
            commits.append((commit, parent, run))
            parent = commit
        window_s = time.perf_counter() - t0

    device = _device()
    checks = _checks(entries, cfg, rec.outputs, rec, commits, pipeline,
                     pcfg, small)
    if control:
        program_checks = checks
        checks = _checks(entries, cfg,
                         check.control_outputs(entries, cfg, rec.outputs),
                         rec, commits, pipeline, pcfg, small)
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": sum(r.report.invocations_done
                         + r.report.invocations_failed
                         for r in rec.suite_runs),
        "failed": sum(_failed_invocations(r.report) for r in rec.suite_runs),
    }
    if trace:
        try:
            events = read_xplane(_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        view = TraceView(events)
        result["metrics"] = _per_layer(entries, cell_name, rec, view,
                                       device["kind"], small)
        device["busy_s"] = view.busy_s() or 0.0
        device["window_s"] = view.window_s
        result["device"] = device
        if view.chips:
            result["breakdown"] = {
                "device_ops": [list(x) for x in
                               view.device_ops()[:BREAKDOWN_TOP]],
                "idle_gaps": [list(x) for x in
                              view.idle_gaps()[:BREAKDOWN_TOP]]}
    else:
        result["metrics"] = _end_to_end(entries, cell_name, rec, verdict_s,
                                        window_s, setup_s)
        result["device"] = device
    result["setup"] = {**(setup_marks or {}), "build_s": build_s,
                       "compile_s": compile_s, "compile_cache": cache_dir,
                       "commits": len(commits), "window_s": window_s}
    if control:
        result["program_checks"] = printable(program_checks)
    result["checks"] = printable(checks)
    return result


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _xplane(trace_dir: str) -> str:
    found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, "
                           f"found {found}")
    return found[0]


def _device() -> dict:
    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def _failed_invocations(report) -> int:
    """Failed invocations, plus every invocation of a failed benchmark."""
    done_of_failed = len({(p.benchmark, p.call_index) for p in report.pairs
                          if p.benchmark in report.failed_benchmarks})
    return report.invocations_failed + done_of_failed


def _checks(entries, cfg, outputs, rec, commits, pipeline, pcfg,
            small) -> dict:
    limits = cfg["limits"]
    numbers = dict(check.duet_gaps(entries, cfg, outputs))
    if not small:
        numbers["width_faults"] = check.width_faults(entries, cfg, outputs)
    numbers["stats_faults"], numbers["ci_end_surprise"] = \
        check.stats_numbers([(r.report.pairs, r.changes)
                             for r in rec.suite_runs],
                            min_results=pcfg.min_results)
    numbers["pipeline_faults"] = check.pipeline_faults(
        commits, rec.suite_runs, pipeline.history)
    if not commits:
        numbers["pipeline_faults"] = math.inf   # nothing was checked
    return {k: {"value": float(v), "limit": limits[k]}
            for k, v in numbers.items()}


def printable(checks: dict) -> dict:
    """`checks` with each number JSON can hold: infinity as a string."""
    return {k: {"value": c["value"] if math.isfinite(c["value"])
                else str(c["value"]), "limit": c["limit"]}
            for k, c in checks.items()}


def _end_to_end(entries, cell_name, rec, verdict_s, window_s,
                setup_s) -> dict:
    values = {
        "verdict_s": float(np.mean(verdict_s)) if verdict_s else None,
        "pairs_per_s": sum(len(r.report.pairs) for r in rec.suite_runs)
        / window_s,
        "pair_p95_ms": float(np.percentile(rec.pair_walls, 95)) * 1e3
        if rec.pair_walls else None,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in entries.metrics_for(cell_name, "end_to_end")
            if values.get(m["name"]) is not None}


def _per_layer(entries, cell_name, rec, view, device_kind, small) -> dict:
    programs = {}
    for (duet, version), (args, _) in rec.outputs.items():
        work = entries.work(duet)
        flops, moved = work.work([(a.shape, a.dtype) for a in args])
        programs[work.VERSIONS[version]] = (duet, version, flops, moved)
    try:
        peaks = entries.peaks(device_kind)
    except LookupError:
        if not small:
            raise
        peaks = None                # a CPU test run has no peaks
    changes = [c for r in rec.suite_runs for c in r.changes.values()]
    ctx = MetricContext(view, programs, peaks, changes)
    out = {}
    for m in entries.metrics_for(cell_name, "per_layer"):
        value = entries.metric(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
