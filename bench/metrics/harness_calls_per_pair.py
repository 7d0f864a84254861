"""Timing harness (core/timing.py): program executions on the device per
duet pair, warm-up, calibration and measured calls together."""


def read(ctx):
    view = ctx.view
    pairs = view.spans("bench.pair")
    calls = sum(len(view.executions(f"bench.{version}/{duet}"))
                for duet, version, _, _ in ctx.programs.values())
    if not pairs or not calls:
        return None
    return calls / len(pairs)
