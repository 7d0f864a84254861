"""Bring-up check: the per-commit kernel-duet pipeline on one TPU chip.

    python chip_smoke.py

Everything runs in this one process, on ``jax.devices()[0]``:

1. device      the first device must be a TPU; there is no CPU fallback.
2. kernel      the Pallas flash-attention kernel at internlm2-1.8b widths
               (B=1, S=4096, H=16, K=8, hd=128, bf16), global causal and
               window 1024: the program must hold a ``tpu_custom_call``
               (compiled for the chip, not interpreted) and agree with
               ``attention_dot`` within `FLASH_TOL`.
3. pipeline    ``Pipeline(get_suite("kernels"), ...).run_stream(
               kernel_commits())``, the call ``python -m repro.cb.cli
               --suite kernels`` makes: every duet runs at the suite's
               widths, none fails, each delivers at least ``min_results``
               pairs, and each duet's two versions agree in output.
4. statistics  the device bootstrap over the pipeline's pairs agrees with
               the NumPy path to float tolerance.

Earlier lines give each phase's wall seconds and its cold compile seconds
(compilation measured apart from execution).  The last line is one JSON
object, printed only when every phase passed; any failure exits non-zero.
The compile cache is set up by `repro.core.timing.use_compile_cache`.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: Flash kernel vs `attention_dot`, allclose atol = rtol: bf16 inputs and
#: outputs (8 mantissa bits, one rounding is 2**-8 relative); the kernel
#: accumulates in f32 and the reference rounds its probabilities to bf16.
FLASH_TOL = 2e-2
#: Device bootstrap vs NumPy: the device computes in float32.
STATS_RTOL, STATS_ATOL = 1e-5, 1e-6


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_phase():
    import jax
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU found: the first JAX device is {dev.platform} "
          f"({dev.device_kind}); this check runs on a TPU only")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def kernel_phase(*, small: bool = False) -> float:
    """Flash attention vs the materialized-scores reference; returns the
    compile seconds of the kernel programs."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import TRAIN_4K, get_config
    from repro.kernels import ops
    from repro.models.attention import attention_dot

    lm = get_config("internlm2-1.8b")
    if small:
        B, S, H, K, hd, window = 1, 256, 4, 2, 64, 64
    else:
        B, S, H, K, hd = (1, TRAIN_4K.seq_len, lm.num_heads,
                          lm.num_kv_heads, lm.head_dim)
        window = 1024
    dtype = jnp.dtype(lm.dtype)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, S, H, hd), dtype)
    k = jax.random.normal(kk, (B, S, K, hd), dtype)
    v = jax.random.normal(kv, (B, S, K, hd), dtype)
    compile_s = 0.0
    for w in (None, window):
        label = "global" if w is None else f"window {w}"
        lowered = ops.flash_attention.lower(q, k, v, causal=True, window=w)
        mosaic = "tpu_custom_call" in lowered.as_text()
        check(small or mosaic,
              f"flash ({label}): no tpu_custom_call in the program; the "
              f"kernel was not compiled for the chip")
        t0 = time.perf_counter()
        prog = lowered.compile()
        compile_s += time.perf_counter() - t0
        out = np.asarray(prog(q, k, v), np.float32)
        ref = jax.jit(functools.partial(attention_dot, causal=True,
                                        window=w))(q, k, v)
        ref = np.asarray(ref, np.float32)
        err = float(np.max(np.abs(out - ref)))
        print(f"kernel: flash {label} [{B},{S},{H}/{K},{hd}] {dtype}: "
              f"tpu_custom_call={mosaic} max|flash - dot|={err!r} "
              f"(tol {FLASH_TOL})")
        check(bool(np.isfinite(out).all()), f"flash ({label}): non-finite")
        check(np.allclose(out, ref, atol=FLASH_TOL, rtol=FLASH_TOL),
              f"flash ({label}) disagrees with attention_dot: max abs "
              f"error {err!r} over tolerance {FLASH_TOL}")
    return compile_s


def pipeline_phase(*, small: bool = False):
    """The CLI's kernel-suite path; returns (pairs, compile seconds)."""
    from benchmarks.kernel_bench import kernel_commits
    from repro.cb import Pipeline, PipelineConfig, get_suite

    suite = get_suite("kernels", small=small)
    # keep the engine report of each suite run, for its duet pairs
    runs = []
    suite_run = suite.run

    def recording_run(*args, **kwargs):
        runs.append(suite_run(*args, **kwargs))
        return runs[-1]

    suite.run = recording_run
    cfg = PipelineConfig(suite="kernels", provider="local", mode="selective",
                         parallelism=1)
    rep = Pipeline(suite, cfg).run_stream(kernel_commits())
    names = suite.benchmark_names()
    check(len(rep.commits) == 1 and len(runs) == 1,
          f"expected one measured commit, got {len(rep.commits)}")
    run = rep.commits[0]
    check(not run.failed, f"failed benchmarks: {run.failed}")
    check(sorted(run.ran) == names,
          f"ran {sorted(run.ran)}, expected every benchmark {names}")
    for b in names:
        c = run.changes.get(b)
        chk = suite.checks[b]
        check(c is not None and c.n_pairs >= cfg.min_results,
              f"{b}: {c.n_pairs if c else 0} pairs, fewer than "
              f"min_results={cfg.min_results}")
        verdict = "changed" if c.changed else "no change"
        print(f"pipeline: {b}: {verdict}, median diff "
              f"{c.median_diff_pct!r}% CI [{c.ci_low!r}, {c.ci_high!r}] "
              f"n={c.n_pairs}; outputs agree max|v1 - v2|="
              f"{chk.max_abs_err!r} (bound {chk.bound!r}); compile s "
              f"{chk.compile_seconds[0]!r} / {chk.compile_seconds[1]!r}")
    compile_s = sum(sum(c.compile_seconds) for c in suite.checks.values())
    print(f"pipeline: {run.invocations} invocations, engine wall "
          f"{run.wall_seconds!r} s")
    return runs[0].report.pairs, compile_s


def stats_phase(pairs) -> float:
    """Device bootstrap vs NumPy on the pipeline's pairs; returns the
    seconds of the first (compiling) device call."""
    import jax
    import numpy as np

    from repro.core.duet import collect_pairs
    from repro.core.stats import bootstrap_median_ci_batch, relative_diffs

    grouped = collect_pairs(pairs)
    diffs = [relative_diffs(np.asarray(v1), np.asarray(v2))
             for v1, v2 in (grouped[b] for b in sorted(grouped))]
    ref = bootstrap_median_ci_batch(diffs, seed=0)
    t0 = time.perf_counter()
    dev = bootstrap_median_ci_batch(diffs, seed=0, backend="jax")
    first_s = time.perf_counter() - t0
    for name, a, b in zip(("median", "ci low", "ci high"), ref, dev):
        err = float(np.max(np.abs(a - b)))
        print(f"statistics: {name} over {len(diffs)} benchmarks on "
              f"{jax.devices()[0].platform}: max|device - numpy|={err!r}")
        check(np.allclose(a, b, rtol=STATS_RTOL, atol=STATS_ATOL),
              f"device bootstrap {name} disagrees with NumPy: {err!r}")
    return first_s


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


def main() -> int:
    try:
        device, wall = timed(device_phase)
        print(f"phase device: wall {wall!r} s")
        sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
        from repro.core.timing import use_compile_cache
        print(f"compile cache: {use_compile_cache()}")
        kernel_c, wall = timed(kernel_phase)
        print(f"phase kernel: wall {wall!r} s, compile {kernel_c!r} s")
        (pairs, pipe_c), wall = timed(pipeline_phase)
        print(f"phase pipeline: wall {wall!r} s, compile {pipe_c!r} s")
        stats_c, wall = timed(stats_phase, pairs)
        print(f"phase statistics: wall {wall!r} s, first device call "
              f"{stats_c!r} s")
        print(f"compile seconds (kernel + pipeline duets): "
              f"{kernel_c + pipe_c!r}")
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
