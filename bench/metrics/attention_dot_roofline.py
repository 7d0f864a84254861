"""Duet program `attention_dot`: the least time its work needs on this chip (see
bench/work) over its device time in the trace, in percent."""


def read(ctx):
    return ctx.roofline_pct("attention_dot")
