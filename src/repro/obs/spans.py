"""The wall-clock lane: spans and counters for real executions.

Simulated runs trace on the virtual clock (``RecordingTracer``).  Real
executions trace here, on the clock of JAX's profiler, which the device's
program executions share: a span is a ``jax.profiler.TraceAnnotation``, so
it records only while a profiler session is active (``jax.profiler.trace``)
and lands in the same trace as the programs it dispatches.  There is no
switch: the session is the switch.  Off, a span costs about a microsecond,
so spans mark phases (a commit, an invocation, a duet pair, one pass of
the timing harness), never single program calls inside a loop.

A process that has not imported JAX cannot hold a profiler session, so
there a span is a shared no-op and this module imports nothing: the
simulated layers that place spans stay free of JAX.

The spans, from the outside in::

    cb.commit          Pipeline.run_commit (realtime suites), meta commit
      cb.prepare       selection and cache lookups
      cb.suite_run     the suite's run of the selected benchmarks
        engine.run     ExecutionEngine._run_realtime
          engine.execute   one attempt of one invocation (worker thread),
                           meta benchmark, attempt
            duet.pair      DuetRunnable.run_pair
              duet.v1/<benchmark>, duet.v2/<benchmark>   each version
                timing.warmup    time_fn's warm-up call
                timing.round     one pass of time_fn's calibration loop,
                                 meta inner, and stored (the round ran at
                                 the program's stored count); a version's
                                 last is measured
        cb.analyze     the change analysis of the run's pairs
      cb.finalize      history records and cache fills
    cb.detect          RegressionDetector.scan

`compiles` answers "which step recompiled": it counts XLA backend
compiles and their seconds for the whole process, through a
``jax.monitoring`` listener that runs only when a compile ends.  Read it
before and after a step.  The listener is registered when this module
is imported into a process that has imported JAX, else by the first span
or reading after JAX is; compiles before that are not counted.

`timed_invocations` answers "did the harness calibrate": it counts the
timing harness's invocations (``repro.core.timing.time_fn``) as hits,
which measured in one round at their program's stored inner count, and
calibrations, which did not (a program's first invocation, or one whose
stored count fell short).  Read it before and after a step, like
`compiles`.
"""
from __future__ import annotations

import contextlib
import sys
import threading
from typing import Tuple

#: The ``jax.monitoring`` duration event of one XLA backend compile.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_OFF = contextlib.nullcontext()
_lock = threading.Lock()
_compiles = [0, 0.0]            # count, seconds
_timed = [0, 0]                 # hits, calibrations
_listening = False


def span(name: str, **meta):
    """A context manager that records `name` (with `meta` as its
    arguments) on the profiler's clock while a session is active."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _OFF
    if not _listening:
        _listen()
    return profiler.TraceAnnotation(name, **meta)


def compiles() -> Tuple[int, float]:
    """XLA backend compiles counted in this process so far, and their
    seconds."""
    if not _listening and "jax" in sys.modules:
        _listen()
    with _lock:
        return _compiles[0], _compiles[1]


def timed_invocations() -> Tuple[int, int]:
    """Timing-harness invocations counted in this process so far: hits
    and calibrations."""
    with _lock:
        return _timed[0], _timed[1]


def count_timed(*, hit: bool) -> None:
    """Count one timing-harness invocation (`timed_invocations`)."""
    with _lock:
        _timed[0 if hit else 1] += 1


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == COMPILE_EVENT:
        with _lock:
            _compiles[0] += 1
            _compiles[1] += seconds


def _listen() -> None:
    global _listening
    import jax.monitoring
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


if "jax" in sys.modules:
    _listen()
