"""What decides ``correct``: each number compared, beside its limit.

- Duet programs: the first output of each version's compiled program in
  the window, against the duet's plain float32 reference on the same
  inputs: ``max |out - ref| / max |ref|``.
- Statistics: every change result of the window against that run's own
  pairs.  The pair count, median and verdict exactly (``stats_faults``);
  each CI end against the exact bootstrap distribution of the median,
  as the improbability that a bootstrap of N_BOOT resamples reports it
  (``ci_end_surprise``), so a sound rewrite with any random stream reads
  as sound.
- Pipeline: faults counted in what the pipeline delivered: a verdict that
  is not the one measured (or, served from the cache, not the one first
  measured for that fingerprint pair), and any commit without exactly one
  history record per benchmark.  Exact.
"""
from __future__ import annotations

import math
from collections import Counter
from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np
from scipy.stats import binom

#: The service's statistics (paper section 6.1): a 99 % percentile
#: bootstrap CI of the median relative difference, 1,000 resamples.
CONFIDENCE = 0.99
N_BOOT = 1000
#: Relative rounding allowed where two sums of the same pairs agree.
ROUNDING = 1e-9


def gap(out, ref) -> float:
    """``max |out - ref| / max |ref|`` in float32 on the device."""
    out = jnp.asarray(out).astype(jnp.float32)
    ref = jnp.asarray(ref).astype(jnp.float32)
    if out.shape != ref.shape:
        return math.inf
    g = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    return g if math.isfinite(g) else math.inf


def duet_gaps(entries, cfg: dict, outputs: Dict[tuple, tuple]
              ) -> Dict[str, float]:
    """``<program>_gap`` for both versions of every configured duet."""
    gaps: Dict[str, float] = {}
    for duet in cfg["deployment"]["benchmarks"]:
        versions = entries.work(duet).VERSIONS
        ref_mod = entries.reference(duet)
        refs: Dict[int, object] = {}
        for version, program in versions.items():
            got = outputs.get((duet, version))
            if got is None:                # the window never ran it
                gaps[f"{program}_gap"] = math.inf
                continue
            args, out = got
            key = id(args[0])
            if key not in refs:
                refs[key] = ref_mod.reference(args, cfg)
            gaps[f"{program}_gap"] = gap(out, refs[key])
    return gaps


def control_outputs(entries, cfg: dict, outputs: Dict[tuple, tuple]
                    ) -> Dict[tuple, tuple]:
    """`outputs` with each replaced by the duet's control: the reference
    computed in float8 on the same arguments."""
    return {(duet, version): (args, entries.reference(duet).control(args,
                                                                    cfg))
            for (duet, version), (args, _) in outputs.items()}


def width_faults(entries, cfg: dict, outputs: Dict[tuple, tuple]) -> int:
    """Arguments whose shape or dtype is not what the configuration's
    widths give the duet."""
    faults = 0
    for (duet, _), (args, _) in outputs.items():
        want = [(tuple(s), str(jnp.dtype(t)))
                for s, t in entries.work(duet).shapes(cfg)]
        have = [(tuple(a.shape), str(a.dtype)) for a in args]
        faults += want != have
    return faults


def median_atoms(d) -> tuple:
    """The exact distribution of the median of one bootstrap resample of
    `d` (n draws with replacement, ``np.median`` of them): its values and
    their probabilities.

    With the distinct values u_1 < ... < u_K of `d` and P_k the share of
    `d` at or below u_k, the count of draws at or below u_k is
    Binomial(n, P_k).  For odd n = 2m + 1 the median is draw m + 1 in
    order; for even n = 2m it is the mean of draws m and m + 1, which fall
    on u_i and u_j (i <= j) with a probability in closed form."""
    u, counts = np.unique(np.asarray(d, np.float64), return_counts=True)
    n = int(counts.sum())
    P = np.concatenate([[0.0], np.cumsum(counts) / n])
    P[-1] = 1.0
    hi, lo = P[1:], P[:-1]
    m = n // 2
    if n % 2:
        return u, np.diff(binom.sf(m, n, P))
    c = math.comb(n, m)
    same = (binom.sf(m, n, hi) - binom.sf(m - 1, n, lo)
            + c * lo ** m * (1.0 - hi) ** (n - m))
    # draw m on u_i, and the first draw above it on u_j
    pair = np.outer(c * (hi ** m - lo ** m),
                    (1.0 - lo) ** (n - m) - (1.0 - hi) ** (n - m))
    i, j = np.triu_indices(len(u), 1)
    return (np.concatenate([u, (u[i] + u[j]) * 0.5]),
            np.concatenate([same, pair[i, j]]))


def end_surprise(end: float, rank: int, values, probs, tol: float
                 ) -> float:
    """``-log10`` of the two-sided probability that the `rank`-th smallest
    of N_BOOT bootstrap medians lies at least as far out as `end`."""
    if not math.isfinite(end):
        return math.inf
    at_or_below = float(probs[values <= end + tol].sum())
    below = float(probs[values < end - tol].sum())
    p = 2.0 * min(binom.sf(rank - 1, N_BOOT, min(at_or_below, 1.0)),
                  binom.cdf(rank - 1, N_BOOT, min(below, 1.0)))
    return -math.log10(p) if 0.0 < p < 1.0 else (0.0 if p else math.inf)


def stats_numbers(runs, *, min_results: int) -> Tuple[int, float]:
    """(faults, surprise) over `runs`, pairs of (the run's pairs, the
    program's change results).

    Faults, exact: a benchmark with `min_results` pairs or more and no
    result, or a result for one with fewer; a result whose pair count or
    median (to rounding) is not that of the run's own pairs; a verdict or
    direction that its own interval and median do not give.  Surprise:
    the largest `end_surprise` of a CI end, so that any percentile
    bootstrap of N_BOOT resamples at CONFIDENCE reads as sound, whatever
    its random stream."""
    alpha = (1.0 - CONFIDENCE) / 2.0
    ranks = (math.floor(alpha * (N_BOOT - 1)) + 1,
             math.ceil((1.0 - alpha) * (N_BOOT - 1)) + 1)
    faults, worst = 0, 0.0
    for pairs, changes in runs:
        grouped: Dict[str, tuple] = {}
        for p in pairs:
            v1s, v2s = grouped.setdefault(p.benchmark, ([], []))
            v1s.append(p.v1_seconds)
            v2s.append(p.v2_seconds)
        due = {b for b, (v1, _) in grouped.items() if len(v1) >= min_results}
        faults += len(due ^ set(changes))
        for b in due & set(changes):
            v1, v2 = (np.asarray(v, np.float64) for v in grouped[b])
            d = (v2 - v1) / v1 * 100.0
            c = changes[b]
            med = float(np.median(d))
            tol = ROUNDING * max(1.0, float(np.max(np.abs(d))))
            changed = c.ci_low > 0 or c.ci_high < 0
            faults += (c.n_pairs != len(d)
                       or not abs(c.median_diff_pct - med) <= tol
                       or c.changed != changed
                       or c.direction != (0 if not changed
                                          else (1 if c.median_diff_pct > 0
                                                else -1)))
            values, probs = median_atoms(d)
            for end, rank in zip((c.ci_low, c.ci_high), ranks):
                worst = max(worst, end_surprise(end, rank, values, probs,
                                                tol))
    return faults, worst


def pipeline_faults(commits: List[tuple], suite_runs: List,
                    history) -> int:
    """`commits` holds (commit, parent, CommitRun) for each commit of the
    window, in order; the first parent is the stream's baseline."""
    faults = 0
    measured = iter(suite_runs)
    first: Dict[tuple, object] = {}
    for commit, parent, run in commits:
        changes = next(measured).changes if run.ran or run.revalidated \
            else {}
        for b in run.ran + run.revalidated + run.cache_hits:
            key = (b, parent.fingerprints[b], commit.fingerprints[b])
            if b in run.cache_hits:
                faults += key not in first or run.changes.get(b) != first[key]
            else:
                first.setdefault(key, changes.get(b))
                faults += run.changes.get(b) != changes.get(b)
    records = Counter((r.commit_id, r.benchmark) for r in history.records())
    kept = {(r.commit_id, r.benchmark): r for r in history.records()}
    stream = [commits[0][1]] + [c for c, _, _ in commits] if commits else []
    delivered = {c.commit_id: run.changes for c, _, run in commits}
    for commit in stream:
        for b, fp in commit.fingerprints.items():
            r = kept.get((commit.commit_id, b))
            c = delivered.get(commit.commit_id, {}).get(b)
            faults += (records[(commit.commit_id, b)] != 1
                       or r.fingerprint != fp
                       or r.median_diff_pct != (c.median_diff_pct if c
                                                else None))
    return faults
