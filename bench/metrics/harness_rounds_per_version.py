"""Timing harness (core/timing.py): calibration rounds (``timing.round``
spans), the measured one included, per version span."""
from bench.program_spans import program_spans, rounds_per_version


def read(ctx):
    spans = program_spans(ctx)
    return None if spans is None else rounds_per_version(spans)
