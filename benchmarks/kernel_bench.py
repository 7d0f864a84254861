"""Real-timing microbenchmarks of the substrate's hot layers.

These are the *actual* microbenchmark suite that ElastiBench accelerates for
this framework: jnp reference vs optimized implementations, timed with the
calibrated duet harness.  The normal suite runs at the published widths of
the repo's configs (internlm2-1.8b attention and norm, mamba2-1.3b SSD) in
their bf16 dtype; ``small=True`` shrinks the shapes to a CPU test size.
Before any timing each duet's two programs are compiled ahead of time and
run once, and their outputs must agree to a tolerance set by the dtype.
"""
from __future__ import annotations

import importlib
import inspect
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cb.commits import Commit, code_digest
from repro.cb.registry import (BenchmarkSuite, SuiteRunResult,
                               register_suite, run_plan)
from repro.configs.base import TRAIN_4K, get_config
from repro.core import rmit
from repro.core.duet import DuetRunnable
from repro.core.timing import block, make_timed
from repro.faas.backends import LocalDuetBackend

#: Agreement between a duet's two outputs, by output dtype:
#: max |v1 - v2| <= tol * (1 + max |v2|).  The bound scales with the output
#: because both versions sum many terms, and on a TPU an f32 matmul at
#: default precision rounds its inputs to bf16: an element near zero can
#: carry the absolute error of its large neighbours.  bf16 keeps 8 mantissa
#: bits (one rounding is 2**-8 relative), so a few ulps.
OUTPUT_TOL = {jnp.dtype(jnp.float32): 1e-3, jnp.dtype(jnp.bfloat16): 2e-2}


@dataclass(frozen=True)
class DuetSpec:
    """One duet as programs plus the shapes of their shared arguments:
    enough to compile it for a device that is not attached, or to make
    its inputs from a fixed seed and run it."""
    name: str
    v1: Callable
    v2: Callable
    shapes: Tuple[jax.ShapeDtypeStruct, ...]
    make_args: Callable[[], tuple]


def _normal_args(seed: int, shapes) -> tuple:
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return tuple(jax.random.normal(k, s.shape, s.dtype)
                 for k, s in zip(keys, shapes))


def attention_spec(*, B: int, S: int, H: int, K: int, hd: int, dtype,
                   chunk: int) -> DuetSpec:
    """Materialized-scores GQA attention vs the online-softmax chunked scan;
    q [B,S,H,hd], k/v [B,S,K,hd]."""
    from repro.models.attention import attention_chunked, attention_dot
    shapes = (jax.ShapeDtypeStruct((B, S, H, hd), dtype),
              jax.ShapeDtypeStruct((B, S, K, hd), dtype),
              jax.ShapeDtypeStruct((B, S, K, hd), dtype))
    return DuetSpec(
        "attention_dot_vs_chunked",
        lambda q, k, v: attention_dot(q, k, v, causal=True),
        lambda q, k, v: attention_chunked(q, k, v, causal=True, chunk=chunk),
        shapes, lambda: _normal_args(0, shapes))


def ssd_spec(*, B: int, S: int, H: int, P: int, N: int, G: int, dtype,
             chunk: int) -> DuetSpec:
    """Exact SSD recurrence vs the chunked block decomposition; x
    [B,S,H,P], dt [B,S,H], A [H], B/C [B,S,G,N] (model layout)."""
    from repro.kernels.ref import ssd_ref
    from repro.models.ssm import ssd_chunked
    shapes = (jax.ShapeDtypeStruct((B, S, H, P), dtype),
              jax.ShapeDtypeStruct((B, S, H), jnp.float32),
              jax.ShapeDtypeStruct((H,), jnp.float32),
              jax.ShapeDtypeStruct((B, S, G, N), dtype),
              jax.ShapeDtypeStruct((B, S, G, N), dtype))

    def make_args():
        x, dt, a, bi, ci = _normal_args(1, shapes)
        return x, jax.nn.softplus(dt), -jnp.exp(a * 0.5), bi * 0.5, ci * 0.5

    def recur(x, dt, A, Bi, Ci):
        h = lambda t: jnp.moveaxis(t, 1, 2)
        return jnp.moveaxis(ssd_ref(h(x), h(dt), A, h(Bi), h(Ci))[0], 2, 1)

    return DuetSpec(
        "ssd_recurrence_vs_chunked", recur,
        lambda x, dt, A, Bi, Ci: ssd_chunked(x, dt, A, Bi, Ci,
                                             chunk=chunk)[0],
        shapes, make_args)


def rmsnorm_spec(*, T: int, D: int, dtype) -> DuetSpec:
    """Hand-written RMSNorm vs the model's fused `rms_norm`; x [T,D]."""
    from repro.models.layers import rms_norm
    shapes = (jax.ShapeDtypeStruct((T, D), dtype),
              jax.ShapeDtypeStruct((D,), dtype))

    def unfused(x, w):
        x32 = x.astype(jnp.float32)
        y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6)
        return (y * (1 + w.astype(jnp.float32))).astype(x.dtype)

    return DuetSpec(
        "rmsnorm_fused_vs_unfused", unfused,
        lambda x, w: rms_norm(x, w, 1e-6), shapes,
        lambda: (_normal_args(2, shapes[:1])[0], jnp.zeros((D,), dtype)))


def duet_specs(*, small: bool = False) -> List[DuetSpec]:
    """The suite's duets.  Full size takes internlm2-1.8b's attention and
    norm widths and mamba2-1.3b's SSD widths, at the train_4k sequence
    length and the configs' dtype."""
    if small:
        f32 = jnp.float32
        return [attention_spec(B=1, S=64, H=4, K=4, hd=64, dtype=f32,
                               chunk=64),
                ssd_spec(B=1, S=128, H=4, P=16, N=16, G=1, dtype=f32,
                         chunk=64),
                rmsnorm_spec(T=512, D=128, dtype=f32)]
    lm = get_config("internlm2-1.8b")
    ssm_cfg = get_config("mamba2-1.3b")
    s = ssm_cfg.ssm
    S = TRAIN_4K.seq_len
    return [attention_spec(B=1, S=S, H=lm.num_heads, K=lm.num_kv_heads,
                           hd=lm.head_dim, dtype=jnp.dtype(lm.dtype),
                           chunk=lm.attention_chunk),
            ssd_spec(B=1, S=S, H=s.n_heads(ssm_cfg.d_model), P=s.head_dim,
                     N=s.d_state, G=s.n_groups,
                     dtype=jnp.dtype(ssm_cfg.dtype), chunk=s.chunk_size),
            rmsnorm_spec(T=S, D=lm.d_model, dtype=jnp.dtype(lm.dtype))]


@dataclass
class DuetCheck:
    """What building one duet measured: the cold compile seconds of each
    version and the largest output disagreement against its bound."""
    compile_seconds: Tuple[float, float]
    max_abs_err: float
    bound: float


def build_duet(spec: DuetSpec) -> Tuple[DuetRunnable, DuetCheck]:
    """Compile both versions, run each once on the same inputs, and fail
    unless the outputs agree; the timed versions then call the compiled
    executables, so no compilation falls inside a measurement."""
    args = spec.make_args()
    progs, compile_s = [], []
    for fn in (spec.v1, spec.v2):
        t0 = time.perf_counter()
        progs.append(jax.jit(fn).lower(*args).compile())
        compile_s.append(time.perf_counter() - t0)
    o1, o2 = (np.asarray(block(p(*args)), np.float32) for p in progs)
    out_dtype = jax.eval_shape(spec.v1, *spec.shapes).dtype
    if o1.shape != o2.shape:
        raise AssertionError(f"{spec.name}: output shapes differ "
                             f"{o1.shape} vs {o2.shape}")
    err = float(np.max(np.abs(o1 - o2)))
    bound = OUTPUT_TOL[jnp.dtype(out_dtype)] * (1.0 + np.max(np.abs(o2)))
    if not err <= bound:                    # NaN fails too
        raise AssertionError(
            f"{spec.name}: versions disagree: max |v1 - v2| = {err!r} over "
            f"the {out_dtype} bound {bound!r}")
    return (DuetRunnable(spec.name, make_timed(progs[0], *args),
                         make_timed(progs[1], *args)),
            DuetCheck(tuple(compile_s), err, float(bound)))


# ------------------------------------------------ registry-backed real suite
# which source modules implement each duet: editing any of them changes the
# benchmark's code fingerprint, which is what drives pipeline selection
_FP_MODULES = {
    "attention_dot_vs_chunked": ("repro.models.attention",),
    "ssd_recurrence_vs_chunked": ("repro.kernels.ref", "repro.models.ssm"),
    "rmsnorm_fused_vs_unfused": ("repro.models.layers",),
}


def kernel_fingerprints() -> Dict[str, str]:
    """Content digests of the *actual* implementation sources."""
    fps = {}
    for bench, mods in _FP_MODULES.items():
        fps[bench] = code_digest(*(
            inspect.getsource(importlib.import_module(m)) for m in mods))
    return fps


def kernel_commits() -> List[Commit]:
    """Two-version stream for the working tree: the reference
    implementations as the baseline, the optimized implementations as the
    head commit.  Every benchmark's fingerprint differs between the two, so
    the pipeline selects and really measures all of them."""
    fps = kernel_fingerprints()
    base = {b: code_digest("reference", fp) for b, fp in fps.items()}
    head = {b: code_digest("optimized", fp) for b, fp in fps.items()}
    return [
        Commit(commit_id="reference", index=0, parent=None, timestamp_s=0.0,
               fingerprints=base),
        Commit(commit_id="head", index=1, parent="reference", timestamp_s=0.0,
               fingerprints=head, touched=tuple(sorted(head))),
    ]


class KernelSuite(BenchmarkSuite):
    """The repo's own JAX/Pallas kernel duets behind the same registry
    interface as the synthetic suite — the pipeline runs a real workload
    end-to-end with real host timings (``small=True`` shrinks the shapes
    for CI)."""

    name = "kernels"
    realtime = True

    def __init__(self, *, small: bool = False):
        self.small = bool(small)
        self._duets: Optional[Dict[str, DuetRunnable]] = None
        self.checks: Dict[str, DuetCheck] = {}

    def _build(self) -> Dict[str, DuetRunnable]:
        if self._duets is None:
            duets = {}
            for spec in duet_specs(small=self.small):
                duets[spec.name], self.checks[spec.name] = build_duet(spec)
            self._duets = duets
        return self._duets

    def benchmark_names(self) -> List[str]:
        return sorted(_FP_MODULES)

    def run(self, benchmarks: List[str], commit: Commit, *,
            provider: str = "local", n_calls: int = 12,
            repeats_per_call: int = 1, parallelism: int = 1,
            memory_mb: int = 0, seed: int = 0, min_results: int = 10,
            adaptive: bool = False, chaos=None,
            observer=None, engine=None) -> SuiteRunResult:
        if chaos is not None:
            raise ValueError("fault injection wraps virtual-time backends; "
                             "the kernel suite runs real host timings")
        duets = {b: self._build()[b] for b in benchmarks}
        plan = rmit.make_plan(sorted(duets), n_calls=n_calls,
                              repeats_per_call=repeats_per_call, seed=seed)
        backend = LocalDuetBackend(duets, benchmark_timeout_s=60.0)
        # real duets on one device: wide parallelism would have the
        # versions contend with each other instead of measuring them
        return run_plan(backend, plan,
                        parallelism=max(1, min(parallelism, 2)),
                        seed=seed, min_results=min_results,
                        adaptive=adaptive, observer=observer,
                        engine=engine)


register_suite("kernels", KernelSuite, replace_existing=True)


def table_kernel_duets():
    """Duet-benchmark the real JAX implementations through the suite's
    normal path on one device (parallelism 1: correctness of the pipeline,
    not fleet timing).  Raises when any benchmark failed."""
    t0 = time.perf_counter()
    suite = KernelSuite()
    names = suite.benchmark_names()
    res = suite.run(names, kernel_commits()[1], n_calls=12, parallelism=1,
                    seed=3, min_results=10)
    if res.report.failed_benchmarks:
        raise RuntimeError(f"failed benchmarks: "
                           f"{res.report.failed_benchmarks}")
    harness_us = (time.perf_counter() - t0) * 1e6
    rows = {}
    for name, c in sorted(res.changes.items()):
        rows[name] = {
            "median_diff_pct": round(c.median_diff_pct, 2),
            "ci": [round(c.ci_low, 2), round(c.ci_high, 2)],
            "changed": c.changed, "n": c.n_pairs,
        }
    rows["wall_s"] = round(res.report.wall_seconds, 1)
    rows["invocations"] = res.report.invocations_done
    return "kernel_duets_real", harness_us, rows
