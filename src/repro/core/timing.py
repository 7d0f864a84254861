"""Timing source for real (non-simulated) benchmark execution.

The TPU/JAX adaptation of Go's benchmark harness: a jitted program is
timed around block_until_ready with perf_counter, after a calibration
phase that picks an inner-repeat count so one measurement takes at least
``min_measure_s`` (Go's -benchtime analogue).  The warm-up call and each
calibration round are spans on the profiler's clock (``timing.warmup``,
``timing.round``; ``repro.obs.spans``), the last round of a call being the
measured one.

A timed program (`make_timed`) calibrates once: it stores the inner count
the calibration loop settles on at the per-call time of its last measured
round (`settled_inner`), and a later invocation runs its first round at
that count, which measures at once unless the program got faster.  A
measured round still lasts at least ``min_measure_s`` (or runs
``max_inner`` calls), with as many calls as a fresh calibration of a
program of that speed.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Optional

import jax

from repro.obs.spans import count_timed, span

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
            max_inner: int = 1000,
            stored_inner: Optional[int] = None) -> Timing:
    """Calibrated timing of `fn` (which must block on its own result).

    `stored_inner` is a count stored for the same program by an earlier
    call (`make_timed`): the first round runs at it (capped at
    `max_inner`), and the loop grows from there only if that round falls
    short.  None calibrates afresh from 1.  The round's span says which
    (meta ``stored``), and the call counts once in
    ``repro.obs.spans.timed_invocations``: a hit if it measured in one
    round at its stored count, else a calibration."""
    with span("timing.warmup"):
        fn()

    inner = 1 if stored_inner is None else min(max_inner, stored_inner)
    stored = stored_inner is not None
    while True:
        with span("timing.round", inner=inner, stored=stored):
            t0 = time.perf_counter()
            for _ in range(inner):
                fn()
            dt = time.perf_counter() - t0
        if dt >= min_measure_s or inner >= max_inner:
            count_timed(hit=stored)   # the first round, at the stored count
            return Timing(seconds_per_call=dt / inner, inner_repeats=inner)
        inner = _grow(inner, dt, min_measure_s, max_inner)
        stored = False


def _grow(inner: int, dt: float, min_measure_s: float, max_inner: int) -> int:
    """The calibration loop's next inner count after a round of `inner`
    calls took `dt` and fell short of `min_measure_s`."""
    return min(max_inner, max(inner * 2,
                              int(inner * min_measure_s / max(dt, 1e-9))))


def settled_inner(seconds_per_call: float, *, min_measure_s: float = 0.02,
                  max_inner: int = 1000) -> int:
    """The inner count `time_fn`'s loop ends on, from 1, for a program
    whose every call takes `seconds_per_call`: the loop replayed on that
    time.  A count taken from a long measured round, not from the loop's
    noisy one-call first round, so programs of one speed settle on one
    count."""
    inner = 1
    while inner * seconds_per_call < min_measure_s and inner < max_inner:
        inner = _grow(inner, inner * seconds_per_call, min_measure_s,
                      max_inner)
    return inner


def make_timed(fn: Callable, *args, **kwargs) -> Callable[[], float]:
    """Package fn(*args) into a zero-arg timed callable returning seconds
    (duet 'version' interface).  The callable stores the count the loop
    settles on at its last measured round's per-call time
    (`settled_inner`) and starts each later invocation there.  Invocations
    may run on several threads at once: each reads the count once and
    replaces it whole."""
    inner: Optional[int] = None

    def run() -> float:
        nonlocal inner
        t = time_fn(lambda: block(fn(*args, **kwargs)), stored_inner=inner)
        inner = settled_inner(t.seconds_per_call)
        return t.seconds_per_call
    return run
