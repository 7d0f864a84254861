"""`chip_smoke.py` off the chip: it refuses a CPU, and its phases' control
flow holds at the kernel suite's small widths (Pallas in interpret mode)."""
import jax
import pytest

from repro.core.timing import CHECKOUT_CACHE_DIR, use_compile_cache

chip_smoke = pytest.importorskip(
    "chip_smoke", reason="chip_smoke.py needs the repo root on sys.path")


@pytest.mark.parametrize("env_dir", [None, "named"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """An environment's cache directory is left to JAX; without one the
    cache is the checkout's fixed directory."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        expected = str(CHECKOUT_CACHE_DIR)
    else:
        expected = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", expected)
    try:
        assert use_compile_cache() == expected
        configured = jax.config.jax_compilation_cache_dir
        if env_dir is None:
            assert configured == expected
        else:
            assert configured == saved["jax_compilation_cache_dir"]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main() == 1
    cap = capsys.readouterr()
    assert "no TPU found" in cap.err
    assert '"ok"' not in cap.out


def test_phases_pass_at_small_widths(capsys):
    assert chip_smoke.kernel_phase(small=True) > 0
    pairs, compile_s = chip_smoke.pipeline_phase(small=True)
    assert compile_s > 0
    assert chip_smoke.stats_phase(pairs) > 0
    out = capsys.readouterr().out
    assert out.count("kernel: flash") == 2
    assert out.count("pipeline: ") == 4
    assert out.count("statistics: ") == 3
