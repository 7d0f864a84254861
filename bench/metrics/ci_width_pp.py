"""Statistics layer (core/stats.py): mean width, in percentage points, of
the CI of the median relative difference over the run's measured verdicts
(``ChangeResult.ci_size``; verdicts served from the cache are left out).
It guards sensitivity: a harness that delivers more pairs per second by
measuring more noisily widens it."""


def read(ctx):
    if not ctx.changes:
        return None
    return sum(c.ci_size for c in ctx.changes) / len(ctx.changes)
