"""The commit stream the benchmark's CI runner submits."""
import itertools

from bench.entries import Entries
from bench.stream import commit_stream

EVERY_COMMIT = Entries().traffic("every-commit")
NAMES = ["attention_dot_vs_chunked", "rmsnorm_fused_vs_unfused"]


def take(traffic, seed, n, names=NAMES):
    return list(itertools.islice(commit_stream(traffic, names, seed), n))


def test_stream_is_deterministic_per_seed():
    seed = 2**31 + 11
    a, b = take(EVERY_COMMIT, seed, 8), take(EVERY_COMMIT, seed, 8)
    assert a == b
    other = take(EVERY_COMMIT, seed + 1, 8)
    assert [c.fingerprints for c in a] != [c.fingerprints for c in other]


def test_every_commit_touches_every_benchmark_with_fresh_fingerprints():
    commits = take(EVERY_COMMIT, 5, 12)
    base = commits[0]
    assert base.parent is None and base.index == 0
    assert sorted(base.fingerprints) == NAMES
    seen = {fp for fp in base.fingerprints.values()}
    for parent, commit in zip(commits, commits[1:]):
        assert commit.parent == parent.commit_id
        assert commit.index == parent.index + 1
        assert list(commit.touched) == NAMES
        for b in NAMES:
            assert commit.fingerprints[b] not in seen
            seen.add(commit.fingerprints[b])


def test_relanding_traffic_reuses_earlier_fingerprints():
    traffic = {"touch_probability": 0.5, "min_touched": 1,
               "reland_share": 0.5}
    commits = take(traffic, 9, 60)
    relanded = 0
    history = {b: [commits[0].fingerprints[b]] for b in NAMES}
    for parent, commit in zip(commits, commits[1:]):
        assert len(commit.touched) >= 1
        for b in NAMES:
            fp = commit.fingerprints[b]
            if b not in commit.touched:
                assert fp == parent.fingerprints[b]
                continue
            assert fp != parent.fingerprints[b]
            relanded += fp in history[b]
            history[b].append(fp)
    assert relanded > 5
