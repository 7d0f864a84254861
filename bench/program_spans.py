"""The program's own spans on the trace, and the per-layer numbers only
they give.

The program traces its realtime path on the profiler's clock
(``repro.obs.spans``): ``cb.*`` (commit, prepare, suite run, analysis,
finalize, detection), ``engine.*`` (engine run, each invocation's
attempt), ``duet.*`` (pair, ``duet.v1/<duet>`` and ``duet.v2/<duet>``) and
``timing.*`` (the harness's warm-up call and each calibration round, the
last round of a version being its measured pass).  `read_program_spans`
keeps them from an ``.xplane.pb`` file, apart from the benchmark's own
``bench.*`` spans, as ``[name, start_ns, end_ns]``.

The metric readers (``bench/metrics/harness_unmeasured_pct.py``,
``harness_rounds_per_version.py``, ``dispatch_gap_us.py``,
``window_compiles.py``) read ``ctx.view.program``, these spans within the
traced window, and ``ctx.compiles``, the program's count of XLA compiles
(``repro.obs.spans.compiles``) over the traced commit.  Where the harness
provides neither, each reads nothing.
"""
from __future__ import annotations

import bisect
from typing import Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

PREFIXES = ("cb.", "engine.", "duet.", "timing.")
VERSION_PREFIXES = ("duet.v1/", "duet.v2/")


def read_program_spans(path: str) -> List[list]:
    """The program's spans in one trace file, by start."""
    from jax.profiler import ProfileData
    spans = [[e.name, e.start_ns, e.end_ns]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIXES)]
    spans.sort(key=lambda e: e[1])
    return spans


def program_spans(ctx) -> Optional[List[Interval]]:
    """The program's spans within the traced window, or None where the
    harness keeps none."""
    spans = getattr(ctx.view, "program", None)
    if spans is None:
        return None
    view = ctx.view
    return [tuple(s) for s in spans if s[1] >= view.w0 and s[2] <= view.w1]


def _within(spans: Sequence[Interval], name: str,
            outer: Interval) -> List[Interval]:
    return [s for s in spans if s[0] == name
            and s[1] >= outer[1] and s[2] <= outer[2]]


def version_rounds(spans: Sequence[Interval]
                   ) -> List[Tuple[Interval, List[Interval]]]:
    """Each version span with the calibration rounds inside it, by
    start."""
    versions = [s for s in spans if s[0].startswith(VERSION_PREFIXES)]
    return [(v, sorted(_within(spans, "timing.round", v),
                       key=lambda r: r[1]))
            for v in versions]


def unmeasured_pct(spans: Sequence[Interval]) -> Optional[float]:
    """Percent of the time in version spans outside each version's last
    (measured) round: warm-up and calibration."""
    total = measured = 0.0
    for v, rounds in version_rounds(spans):
        total += v[2] - v[1]
        if rounds:
            measured += rounds[-1][2] - rounds[-1][1]
    if total <= 0:
        return None
    return 100.0 * (total - measured) / total


def rounds_per_version(spans: Sequence[Interval]) -> Optional[float]:
    """Calibration rounds, the measured one included, per version
    span."""
    pairs = version_rounds(spans)
    if not pairs:
        return None
    return sum(len(rounds) for _, rounds in pairs) / len(pairs)


def dispatch_gap_us(spans: Sequence[Interval],
                    modules: Iterable[Sequence]) -> Optional[float]:
    """Host time per program execution inside calibration rounds that the
    device did not spend executing: (round time - device time of the
    executions assigned to the round) / those executions, in
    microseconds, pooled over the rounds.  An execution is assigned to
    the round that holds its midpoint, so a clock offset between host and
    device moves only executions at a round's edges."""
    rounds = sorted((s for s in spans if s[0] == "timing.round"),
                    key=lambda r: r[1])
    starts = [r[1] for r in rounds]
    held = {}                       # round index -> (device ns, executions)
    for _, s, e in (m[:3] for m in modules):
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid <= rounds[i][2]:
            ns, n = held.get(i, (0.0, 0))
            held[i] = (ns + e - s, n + 1)
    runs = sum(n for _, n in held.values())
    if not runs:
        return None
    gap_ns = sum(rounds[i][2] - rounds[i][1] - ns
                 for i, (ns, _) in held.items())
    return gap_ns / runs * 1e-3
