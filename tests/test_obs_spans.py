"""The wall-clock lane (repro.obs.spans): spans record only inside a
profiler session, the compile counter counts XLA backend compiles, and a
pipeline traces each commit on exactly one clock: the profiler's for a
realtime suite, the virtual one for a simulated suite."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.cb import Pipeline, PipelineConfig, SyntheticSuite
from repro.cb.commits import Commit
from repro.cb.registry import BenchmarkSuite, run_plan
from repro.core import rmit
from repro.core.duet import DuetRunnable
from repro.faas.backends import LocalDuetBackend
from repro.obs import Observability, use_obs
from repro.obs.spans import compiles, span


def host_spans(trace_dir, prefix):
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


class HostSuite(BenchmarkSuite):
    """A realtime suite of two host duets that return fixed timings."""
    name = "host"
    realtime = True

    def benchmark_names(self):
        return ["a", "b"]

    def run(self, benchmarks, commit, *, seed=0, min_results=2,
            observer=None, n_calls=3, repeats_per_call=1, **_):
        duets = {b: DuetRunnable(b, lambda: 1e-3, lambda: 1.1e-3)
                 for b in benchmarks}
        plan = rmit.make_plan(sorted(duets), n_calls=n_calls,
                              repeats_per_call=repeats_per_call, seed=seed)
        return run_plan(LocalDuetBackend(duets), plan, parallelism=1,
                        seed=seed, min_results=min_results,
                        observer=observer)


def two_commits(names):
    base = Commit(commit_id="c0", index=0, parent=None, timestamp_s=0.0,
                  fingerprints={b: "x" for b in names})
    head = Commit(commit_id="c1", index=1, parent="c0", timestamp_s=0.0,
                  fingerprints={b: "y" for b in names},
                  touched=tuple(names))
    return base, head


def test_a_fresh_jit_counts_one_compile_and_an_aot_call_none():
    x = jnp.arange(4.0)
    salt = float(int.from_bytes(os.urandom(3), "little"))  # no cache hit
    n0, s0 = compiles()
    jax.jit(lambda v: v * salt + 1.0)(x).block_until_ready()
    n1, s1 = compiles()
    assert n1 == n0 + 1 and s1 > s0
    compiled = jax.jit(lambda v: v - salt).lower(x).compile()
    n2, _ = compiles()
    assert n2 == n1 + 1
    for _ in range(3):
        compiled(x).block_until_ready()
    assert compiles()[0] == n2


def test_spans_record_only_inside_a_profiler_session(tmp_path):
    with span("timing.round", inner=3):
        pass                                   # no session: nothing kept
    with jax.profiler.trace(str(tmp_path)):
        with span("timing.round", inner=4):
            with span("duet.v1/x"):
                pass
    assert sorted(host_spans(tmp_path, ("timing.", "duet."))) == [
        "duet.v1/x", "timing.round"]


def test_a_realtime_commit_is_traced_on_the_profiler_clock_only(tmp_path):
    suite = HostSuite()
    cfg = PipelineConfig(suite="host", provider="local", mode="selective",
                         n_calls=3, repeats_per_call=1, parallelism=1,
                         min_results=2)
    obs = Observability.recording()
    with use_obs(obs):
        pipe = Pipeline(suite, cfg)
        base, head = two_commits(suite.benchmark_names())
        pipe.run_commit(base)
        with jax.profiler.trace(str(tmp_path)):
            run = pipe.run_commit(head)
    assert run.ran == ["a", "b"]
    assert [ev for ev in obs.tracer.events() if ev[2] == "commit"] == []
    assert pipe._obs_clock == 0.0
    names = host_spans(tmp_path, ("cb.", "engine.", "duet."))
    for name in ("cb.commit", "cb.prepare", "cb.suite_run", "cb.analyze",
                 "cb.finalize", "engine.run"):
        assert names.count(name) == 1, name
    assert names.count("engine.execute") == 6
    assert names.count("duet.pair") == 6
    assert names.count("duet.v1/a") == names.count("duet.v2/b") == 3


def test_a_simulated_commit_keeps_its_virtual_span_and_no_cb_spans(
        tmp_path):
    suite = SyntheticSuite()
    names = suite.benchmark_names()[:2]
    cfg = PipelineConfig(provider="lambda", mode="selective", n_calls=4,
                         repeats_per_call=1, parallelism=8, min_results=2)
    obs = Observability.recording()
    with use_obs(obs):
        pipe = Pipeline(suite, cfg)
        base, head = two_commits(names)
        pipe.run_commit(base)
        with jax.profiler.trace(str(tmp_path)):
            run = pipe.run_commit(head)
    commits = [ev for ev in obs.tracer.events() if ev[2] == "commit"]
    assert [ev[1] for ev in commits] == ["c1"]
    assert pipe._obs_clock == pytest.approx(run.wall_seconds)
    assert host_spans(tmp_path, ("cb.commit", "cb.prepare", "cb.suite_run",
                                 "cb.finalize")) == []
