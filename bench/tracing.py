"""From the profiler's trace to the per-layer metrics.

`read_xplane` keeps what the metrics read from an ``.xplane.pb`` file:
the benchmark's host spans (``bench.*``) and, for each TPU, the program
executions (line "XLA Modules"), on the trace's one clock in nanoseconds.
The device counts as busy while a program executes.  Its operations
(line "XLA Ops") are not read: the SSD recurrence alone writes millions of
them, and on traces of both cells their union differs from that of the
programs by under 0.1 %.  `TraceView` answers the questions the metric
readers ask of those events.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."


def read_xplane(path: str) -> dict:
    """The events of one trace file as plain lists (JSON-ready)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host: List[list] = []
    chips: List[dict] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.end_ns]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
        elif _TPU_PLANE.match(plane.name):
            chips.append({"modules": [[e.name, e.start_ns, e.end_ns]
                                      for line in plane.lines
                                      if line.name == "XLA Modules"
                                      for e in line.events]})
    host.sort(key=lambda e: e[1])
    return {"host": host, "chips": chips}


def union_ns(intervals: Iterable[Tuple[float, float]], lo: float,
             hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


class TraceView:
    """The events of one traced window.  The window is the benchmark's
    ``bench.window`` span."""

    def __init__(self, events: dict):
        self.host: List[Interval] = [tuple(e) for e in events["host"]]
        self.chips = events["chips"]
        windows = self.spans("bench.window")
        if len(windows) != 1:
            raise ValueError(f"expected one bench.window span, got "
                             f"{len(windows)}")
        _, self.w0, self.w1 = windows[0]
        self._modules = [sorted((tuple(m) for m in chip["modules"]),
                                key=lambda m: m[1])
                         for chip in self.chips]
        self._programs: Dict[int, Dict[str, str]] = {}

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def spans(self, name: str) -> List[Interval]:
        return [s for s in self.host if s[0] == name]

    def inside(self, name: str, outer: Interval) -> List[Interval]:
        """Spans called `name` that lie within `outer`."""
        return [s for s in self.host if s[0] == name
                and s[1] >= outer[1] and s[2] <= outer[2]]

    def busy(self, chip: int) -> List[Tuple[float, float]]:
        """Intervals of the window in which a program ran on `chip`."""
        return union_ns(((e[1], e[2]) for e in self._modules[chip]),
                        self.w0, self.w1)

    def busy_s(self) -> Optional[float]:
        """Busy seconds in the window, averaged over the chips traced."""
        if not self.chips:
            return None
        return sum(sum(e - s for s, e in self.busy(c))
                   for c in range(len(self.chips))) * 1e-9 / len(self.chips)

    def programs(self, chip: int = 0) -> Dict[str, str]:
        """Each program executed on `chip` (by the name the trace gives
        it, which carries a hash of the program) -> the version span
        (``bench.v1/<duet>`` or ``bench.v2/<duet>``) that its executions
        overlap most.  Device and host clocks in a trace can disagree by
        a fraction of a millisecond, so a short execution near a span's
        edge can start outside it; the program as a whole cannot."""
        if chip not in self._programs:
            versions = sorted((s for s in self.host
                               if s[0].startswith(("bench.v1/",
                                                   "bench.v2/"))),
                              key=lambda s: s[1])
            starts = [s[1] for s in versions]
            overlap: Dict[Tuple[str, str], float] = defaultdict(float)
            for name, s, e in self._modules[chip]:
                i = bisect.bisect_left(starts, e) - 1
                while i >= 0 and versions[i][2] > s:
                    label, vs, ve = versions[i]
                    overlap[(name, label)] += min(e, ve) - max(s, vs)
                    i -= 1
            best: Dict[str, Tuple[float, str]] = {}
            for (name, label), ns in overlap.items():
                if ns > best.get(name, (0.0, ""))[0]:
                    best[name] = (ns, label)
            self._programs[chip] = {n: lb for n, (_, lb) in best.items()}
        return self._programs[chip]

    def executions(self, span_name: str, chip: int = 0
                   ) -> List[Interval]:
        """Executions on `chip`, within the window, of the programs that
        the version span `span_name` runs (see `programs`)."""
        if chip >= len(self._modules):
            return []
        names = {n for n, label in self.programs(chip).items()
                 if label == span_name}
        return [m for m in self._modules[chip] if m[0] in names
                and m[2] > self.w0 and m[1] < self.w1]

    def idle_gaps(self, chip: int = 0) -> List[Tuple[str, float]]:
        """Idle seconds of `chip` in the window, summed by what the host
        was doing: the innermost benchmark span around each gap's middle."""
        gaps: List[Tuple[float, float]] = []
        cursor = self.w0
        for s, e in self.busy(chip) + [(self.w1, self.w1)]:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        by_label: Dict[str, float] = defaultdict(float)
        labels = self.labels_at([(s + e) / 2 for s, e in gaps])
        for label, (s, e) in zip(labels, gaps):
            by_label[label] += (e - s) * 1e-9
        return sorted(by_label.items(), key=lambda kv: -kv[1])

    def labels_at(self, times: List[float]) -> List[str]:
        """For ascending `times`, the name of the latest-started host span
        still open at each (one sweep over the spans)."""
        out: List[str] = []
        active: List[Interval] = []
        i = 0
        for t in times:
            while i < len(self.host) and self.host[i][1] <= t:
                active.append(self.host[i])
                i += 1
            while active and active[-1][2] < t:
                active.pop()
            out.append(active[-1][0] if active else "outside any span")
        return out

    def device_ops(self, chip: int = 0) -> List[Tuple[str, float]]:
        """Seconds per program on `chip` within the window, each named as
        the trace prints it and by the version it runs for."""
        total: Dict[str, float] = defaultdict(float)
        programs = self.programs(chip)
        for name, s, e in self._modules[chip]:
            if e > self.w0 and s < self.w1:
                label = programs.get(name, "no version span")
                total[f"{name} as {label}"] += (min(e, self.w1)
                                                - max(s, self.w0)) * 1e-9
        return sorted(total.items(), key=lambda kv: -kv[1])


class MetricContext:
    """What a per-layer metric reader gets: the trace of the traced commit,
    the work of each duet program, the chip's peaks, and the change
    results the run's window measured."""

    def __init__(self, view: TraceView, programs: Dict[str, tuple],
                 peaks: Optional[dict], changes: Sequence = ()):
        self.view = view
        self.programs = programs    # name -> (duet, version, flops, bytes)
        self.peaks = peaks
        self.changes = list(changes)

    def commit_ms(self) -> Optional[float]:
        """Mean milliseconds of the window's commits (submission to
        verdict)."""
        commits = self.view.spans("bench.commit")
        if not commits:
            return None
        return sum(c[2] - c[1] for c in commits) / len(commits) * 1e-6

    def within_commits_ms(self, name: str) -> Optional[float]:
        """Mean over the window's commits of the milliseconds spent in
        spans called `name` inside each."""
        commits = self.view.spans("bench.commit")
        if not commits:
            return None
        return sum(s[2] - s[1] for c in commits
                   for s in self.view.inside(name, c)) / len(commits) * 1e-6

    def roofline_pct(self, program: str) -> Optional[float]:
        """Least time the chip needs for the program's work over its
        device time, in percent; None where the trace has no execution."""
        if self.peaks is None or program not in self.programs:
            return None
        duet, version, flops, moved = self.programs[program]
        runs = self.view.executions(f"bench.{version}/{duet}")
        device_ns = sum(e - s for _, s, e in runs)
        if not runs or device_ns <= 0:
            return None
        least_s = max(flops / self.peaks["flops_per_s"],
                      moved / self.peaks["bytes_per_s"])
        return 100.0 * len(runs) * least_s / (device_ns * 1e-9)
