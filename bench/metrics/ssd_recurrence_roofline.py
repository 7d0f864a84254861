"""Duet program `ssd_recurrence`: the least time its work needs on this chip (see
bench/work) over its device time in the trace, in percent."""


def read(ctx):
    return ctx.roofline_pct("ssd_recurrence")
