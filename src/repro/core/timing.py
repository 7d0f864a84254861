"""Timing source for real (non-simulated) benchmark execution.

The TPU/JAX adaptation of Go's benchmark harness: a jitted program is
timed around block_until_ready with perf_counter, after a calibration
phase that picks an inner-repeat count so one measurement takes at least
``min_measure_s`` (Go's -benchtime analogue).  The warm-up call and each
calibration round are spans on the profiler's clock (``timing.warmup``,
``timing.round``; ``repro.obs.spans``), the last round of a call being the
measured one.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

import jax

from repro.obs.spans import span

#: Persistent compile cache of a checkout when the environment names none.
#: A fixed path: the directory is part of what a cache entry is found by.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, where set, is
    left to JAX; otherwise the cache lives in `CHECKOUT_CACHE_DIR`.  Every
    program is cached, however quickly it compiled.  Call before the
    first compilation: JAX fixes the cache when it first compiles."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


@dataclass
class Timing:
    seconds_per_call: float
    inner_repeats: int


def block(x):
    return jax.block_until_ready(x)


def time_fn(fn: Callable[[], object], *, min_measure_s: float = 0.02,
            max_inner: int = 1000) -> Timing:
    """Calibrated timing of `fn` (which must block on its own result)."""
    with span("timing.warmup"):
        fn()

    inner = 1
    while True:
        with span("timing.round", inner=inner):
            t0 = time.perf_counter()
            for _ in range(inner):
                fn()
            dt = time.perf_counter() - t0
        if dt >= min_measure_s or inner >= max_inner:
            return Timing(seconds_per_call=dt / inner, inner_repeats=inner)
        inner = min(max_inner, max(inner * 2,
                                   int(inner * min_measure_s / max(dt, 1e-9))))


def make_timed(fn: Callable, *args, **kwargs) -> Callable[[], float]:
    """Package fn(*args) into a zero-arg timed callable returning seconds
    (duet 'version' interface)."""
    def run() -> float:
        t = time_fn(lambda: block(fn(*args, **kwargs)))
        return t.seconds_per_call
    return run
