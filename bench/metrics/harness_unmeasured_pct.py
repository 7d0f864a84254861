"""Timing harness (core/timing.py): the share of the time in version
spans (``duet.v1/<duet>``, ``duet.v2/<duet>``) spent warming up and
calibrating rather than in each version's last, measured round."""
from bench.program_spans import program_spans, unmeasured_pct


def read(ctx):
    spans = program_spans(ctx)
    return None if spans is None else unmeasured_pct(spans)
