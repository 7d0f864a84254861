"""The repo's real kernel duets behind the suite registry: fingerprints
from actual sources, and an end-to-end pipeline run with real timings."""
import pytest

kernel_bench = pytest.importorskip(
    "benchmarks.kernel_bench",
    reason="benchmarks package needs the repo root on sys.path")

from repro.cb import (Pipeline, PipelineConfig, available_suites,  # noqa: E402
                      get_suite)
from repro.cb.cli import EXIT_FAILED  # noqa: E402
from repro.cb.cli import main as cli_main  # noqa: E402
from repro.cb.history import SOURCE_RUN  # noqa: E402
from repro.core.duet import DuetRunnable  # noqa: E402


def test_kernel_suite_is_registered():
    assert "kernels" in available_suites()
    suite = get_suite("kernels", small=True)
    assert suite.benchmark_names() == sorted(kernel_bench._FP_MODULES)


def test_kernel_fingerprints_track_sources():
    fps = kernel_bench.kernel_fingerprints()
    assert set(fps) == set(kernel_bench._FP_MODULES)
    assert fps == kernel_bench.kernel_fingerprints()    # stable
    assert len(set(fps.values())) == len(fps)           # per-benchmark


def test_kernel_commits_change_every_benchmark():
    base, head = kernel_bench.kernel_commits()
    assert base.index == 0 and head.parent == base.commit_id
    for b in base.fingerprints:
        assert base.fingerprints[b] != head.fingerprints[b]


@pytest.mark.slow
def test_pipeline_runs_real_kernels_end_to_end():
    commits = kernel_bench.kernel_commits()
    cfg = PipelineConfig(suite="kernels", provider="local", mode="selective",
                         n_calls=5, repeats_per_call=1, parallelism=1,
                         min_results=4)
    pipe = Pipeline(get_suite("kernels", small=True), cfg)
    rep = pipe.run_stream(commits)
    run = rep.commits[0]
    assert set(run.ran) == set(kernel_bench._FP_MODULES)
    assert run.invocations == 5 * len(run.ran)
    # real timings flowed through engine -> analysis -> history
    assert all(c.n_pairs == 5 for c in run.changes.values())
    recs = [r for r in pipe.history.records() if r.source == SOURCE_RUN]
    assert len(recs) == len(run.ran)
    assert all(r.invocations == 5 and r.billed_seconds > 0 for r in recs)


@pytest.fixture
def raising_kernel_suite(monkeypatch):
    """The kernel suite with every duet's second version raising, as a
    program the chip refuses or that runs out of memory would."""
    def boom():
        raise RuntimeError("injected: version failed")

    def build(self):
        return {b: DuetRunnable(b, lambda: 1e-3, boom)
                for b in kernel_bench._FP_MODULES}

    monkeypatch.setattr(kernel_bench.KernelSuite, "_build", build)


def test_failed_benchmark_fails_the_cli(raising_kernel_suite, capsys):
    import json
    rc = cli_main(["--suite", "kernels", "--mode", "selective",
                   "--n-calls", "3", "--repeats", "1", "--parallelism", "1"])
    assert rc == EXIT_FAILED
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[0])
    assert summary["failed"] == [f"head:{b}"
                                 for b in sorted(kernel_bench._FP_MODULES)]


def test_failed_benchmark_fails_the_table_harness(raising_kernel_suite,
                                                  capsys):
    from benchmarks.run import main as run_main
    with pytest.raises(SystemExit) as exc:
        run_main(["--tables", "kernel_duets"])
    assert exc.value.code == EXIT_FAILED
    out = capsys.readouterr()
    assert "kernel_duets,-1," in out.out
    assert "tables that raised: kernel_duets" in out.err
