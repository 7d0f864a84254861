"""Pipeline layer (cb/pipeline.py, select.py, cache.py, history.py,
detect.py): host milliseconds per commit outside the suite run, i.e.
selection, cache, history and the detector scan."""


def read(ctx):
    commit, suite = ctx.commit_ms(), ctx.within_commits_ms("bench.suite_run")
    return None if commit is None else commit - suite
