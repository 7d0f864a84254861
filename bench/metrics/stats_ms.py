"""Statistics layer (core/results.analyze, core/stats.py): milliseconds
per commit in the bootstrap analysis of the commit's duet pairs."""


def read(ctx):
    return ctx.within_commits_ms("bench.analyze")
