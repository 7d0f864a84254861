"""Timing harness, host dispatch: in calibration rounds, the round's time
the device spent outside the round's program executions, per execution,
in microseconds (`bench.program_spans.dispatch_gap_us`)."""
from bench.program_spans import dispatch_gap_us, program_spans


def read(ctx):
    spans = program_spans(ctx)
    if spans is None or not ctx.view.chips:
        return None
    return dispatch_gap_us(spans, ctx.view.chips[0]["modules"])
