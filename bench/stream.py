"""The commit stream one CI runner submits, generated from a traffic mix.

A traffic mix is data (``bench/traffic/<name>.json``):

- ``touch_probability``: chance that a commit touches each benchmark;
- ``min_touched``: at least this many benchmarks are touched per commit;
- ``reland_share``: share of touched benchmarks that take back a
  fingerprint the benchmark had before (a revert or a re-land), so the
  result cache can serve the pair; the rest get a fresh fingerprint.

The stream is deterministic in ``seed``.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Sequence

import numpy as np

from repro.cb.commits import Commit, code_digest


def commit_stream(traffic: dict, benchmarks: Sequence[str],
                  seed: int) -> Iterator[Commit]:
    """Endless stream: index 0 is the baseline, then one commit after
    another, each the child of the one before."""
    names = sorted(benchmarks)
    rng = np.random.default_rng([seed, 0x5eed])
    p = float(traffic["touch_probability"])
    min_touched = int(traffic["min_touched"])
    reland = float(traffic["reland_share"])
    seen: Dict[str, List[str]] = {b: [] for b in names}
    fps = {b: code_digest("bench", seed, b, 0) for b in names}
    for b in names:
        seen[b].append(fps[b])
    yield Commit(commit_id="c00000", index=0, parent=None, timestamp_s=0.0,
                 fingerprints=dict(fps))
    index = 0
    while True:
        index += 1
        touched = [b for b in names if rng.random() < p]
        while len(touched) < min(min_touched, len(names)):
            extra = str(rng.choice([b for b in names if b not in touched]))
            touched.append(extra)
        for b in sorted(touched):
            earlier = [f for f in seen[b] if f != fps[b]]
            if earlier and rng.random() < reland:
                fps[b] = earlier[int(rng.integers(len(earlier)))]
            else:
                fps[b] = code_digest("bench", seed, b, index)
                seen[b].append(fps[b])
        yield Commit(commit_id=f"c{index:05d}", index=index,
                     parent=f"c{index - 1:05d}", timestamp_s=0.0,
                     fingerprints=dict(fps), touched=tuple(sorted(touched)))
