"""JAX-jitted batched median-bootstrap kernel (optional analysis backend).

The statistics engine's NumPy path (core/stats.py) is the default and the
golden-tested reference; this module lets the same batched analysis run on
the accelerator that executes the workloads — on TPU the gather + per-row
median + quantile pipeline compiles to one fused Mosaic/XLA program, on
CPU it JIT-compiles to a multi-threaded XLA executable.

The resampling scheme is shared with the NumPy engine: the caller passes
the cached ``(n_boot, n)`` index matrix from `stats._boot_draw`, so both
backends bootstrap the *same* resamples.  Numerical results agree with the
NumPy path to float tolerance (XLA defaults to float32 unless x64 is
enabled); bit-for-bit replay of seed behavior stays the NumPy path's job.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("lo_idx", "hi_idx"))
def _boot_median_ci_block(block, idx, *, lo_idx: int, hi_idx: int):
    """(k, n) same-length diff block + (n_boot, n) index matrix ->
    (med, lo, hi), each (k,).

    One fused program: gather every resample, per-row medians, then
    the conservative outward quantiles as order statistics of the
    sorted bootstrap-median distribution (`lo_idx`/`hi_idx` replicate
    ``np.quantile(..., method="lower"/"higher")``)."""
    resamples = block[:, idx]                   # (k, n_boot, n)
    boots = jnp.median(resamples, axis=2)       # (k, n_boot)
    boots = jnp.sort(boots, axis=1)
    return (jnp.median(block, axis=1),
            boots[:, lo_idx], boots[:, hi_idx])


def bootstrap_median_ci_batch_jax(arrays: Sequence[np.ndarray], *,
                                  confidence: float = 0.99,
                                  n_boot: int = 1000,
                                  seed: int = 0) -> tuple:
    """Accelerator twin of `stats.bootstrap_median_ci_batch`.

    Same grouping-by-length batching and the same cached index matrices;
    returns (med, lo, hi) NumPy float arrays aligned with `arrays` (NaN
    for empty inputs)."""
    from repro.core.stats import _boot_draw

    k = len(arrays)
    med = np.full(k, np.nan)
    lo = np.full(k, np.nan)
    hi = np.full(k, np.nan)
    alpha = (1.0 - confidence) / 2.0
    lo_idx = int(np.floor(alpha * (n_boot - 1)))
    hi_idx = int(np.ceil((1.0 - alpha) * (n_boot - 1)))

    by_len: dict = {}
    for i, a in enumerate(arrays):
        a = np.asarray(a, dtype=np.float64)
        if not len(a):
            continue
        if not np.isfinite(a).all():
            # jnp.sort pushes NaN medians to the end, which would turn
            # NaN CIs into finite ones — keep NaN/inf semantics identical
            # to the reference by deferring to the NumPy path
            from repro.core.stats import bootstrap_median_ci
            med[i], lo[i], hi[i] = bootstrap_median_ci(
                a, confidence=confidence, n_boot=n_boot, seed=seed)
            continue
        by_len.setdefault(len(a), []).append((i, a))
    for n, items in by_len.items():
        pos = np.array([i for i, _ in items])
        block = np.stack([a for _, a in items])
        idx = _boot_draw(n, n_boot, seed).idx
        m, l, h = _boot_median_ci_block(jnp.asarray(block), jnp.asarray(idx),
                                        lo_idx=lo_idx, hi_idx=hi_idx)
        med[pos] = np.asarray(m, dtype=np.float64)
        lo[pos] = np.asarray(l, dtype=np.float64)
        hi[pos] = np.asarray(h, dtype=np.float64)
    return med, lo, hi
