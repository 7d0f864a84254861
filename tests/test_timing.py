"""The timing harness (repro.core.timing) on a controlled clock: a timed
program calibrates once, later invocations measure in one round at the
stored inner count, the count stored is the one the calibration loop
settles on at the last measured per-call time, within `max_inner`, and
the hit and calibration counter counts each invocation once."""
import contextlib
import sys
import threading
import time
from fractions import Fraction
from types import SimpleNamespace

import pytest

from repro.core import timing
from repro.obs.spans import timed_invocations

MIN_S = 0.02            # time_fn's default min_measure_s
MAX_INNER = 1000        # and max_inner


class Stand:
    """A program whose every call advances a fake clock by `per_call`
    seconds, and a record of the harness's spans with the calls made
    inside each.  The clock counts in exact fractions, so a round of n
    calls takes n * per_call to the last bit."""

    def __init__(self, monkeypatch, per_call):
        self.per_call = per_call
        self.now = Fraction(0)
        self.spans = []                  # [name, meta, calls]
        monkeypatch.setattr(timing, "time",
                            SimpleNamespace(perf_counter=lambda: self.now))
        monkeypatch.setattr(timing, "span", self.span)
        monkeypatch.setattr(timing, "block", lambda x: x)

    @contextlib.contextmanager
    def span(self, name, **meta):
        self.spans.append([name, meta, 0])
        yield

    @property
    def per_call(self):
        return self._step

    @per_call.setter
    def per_call(self, seconds):
        self._step = Fraction(seconds)

    def __call__(self):
        self.now += self.per_call
        self.spans[-1][2] += 1

    def rounds(self):
        return [(meta, calls) for name, meta, calls in self.spans
                if name == "timing.round"]

    def invoke(self, timed):
        """One invocation of `timed`: its spans, and the counter's
        (hits, calibrations) it added."""
        self.spans = []
        h0, c0 = timed_invocations()
        seconds = timed()
        h1, c1 = timed_invocations()
        assert seconds == self.per_call
        return self.spans, (h1 - h0, c1 - c0)


def fresh_count(monkeypatch, per_call):
    """The inner count a fresh `time_fn` measures the stand-in at."""
    stand = Stand(monkeypatch, per_call)
    return timing.time_fn(stand).inner_repeats


# host seconds per call: attention (5.2 ms), rmsnorm (0.69 ms), chunked SSD
# (1.28 ms), recurrence (22.7 ms), a program that reaches max_inner, and
# one just short of the target at its first count
PER_CALL = [0.0052, 0.00069, 0.00128, 0.0227, 1e-6, 0.0199]


@pytest.mark.parametrize("per_call", PER_CALL)
def test_a_second_invocation_measures_in_one_round_at_the_stored_count(
        monkeypatch, per_call):
    stand = Stand(monkeypatch, per_call)
    timed = timing.make_timed(stand)
    _, counted = stand.invoke(timed)
    assert counted == (0, 1)
    assert [meta["stored"] for meta, _ in stand.rounds()] == (
        [False] * len(stand.rounds()))
    stored = stand.rounds()[-1][1]
    assert stored == timing.settled_inner(per_call)
    for _ in range(3):
        spans, counted = stand.invoke(timed)
        assert [name for name, _, _ in spans] == ["timing.warmup",
                                                  "timing.round"]
        assert spans[0][2] == 1                       # one warm-up call
        assert stand.rounds() == [({"inner": stored, "stored": True},
                                   stored)]
        assert stored * stand.per_call >= MIN_S or stored == MAX_INNER
        assert counted == (1, 0)


def test_today_s_calibration_rounds_for_the_cells_programs(monkeypatch):
    """The fresh loop on a program of constant speed: attention runs
    rounds of 1, 3 and 6 calls, rmsnorm 1, 28 and 56, the chunked SSD 1,
    15 and 30, the recurrence 1, and `settled_inner` replays it to the
    same count.  (On the chip a round's one-call first round runs at
    another speed than later calls, so the loop lands elsewhere in some
    versions; `settled_inner` of a long round's per-call time does not.)"""
    counts = {}
    for per_call in (0.0052, 0.00069, 0.00128, 0.0227):
        stand = Stand(monkeypatch, per_call)
        timing.time_fn(stand)
        counts[per_call] = [calls for _, calls in stand.rounds()]
        assert timing.settled_inner(per_call) == counts[per_call][-1]
    assert counts == {0.0052: [1, 3, 6], 0.00069: [1, 28, 56],
                      0.00128: [1, 15, 30], 0.0227: [1]}


@pytest.mark.parametrize("per_call,settled", [
    (0.00069, 56), (0.0007, 56), (0.00072, 54), (0.00066, 60),
    (0.00128, 30), (0.00124, 32), (0.00125, 16), (0.0052, 6), (0.0227, 1), (0.02, 1),
    (0.0199, 2), (0.011, 2), (0.0099, 4), (3e-5, 1000), (1e-6, 1000),
    (0.0, 1000),
])
def test_a_settled_count_moves_with_the_per_call_time_alone(
        per_call, settled):
    """The count a program settles on is a step function of its per-call
    time: programs within a few per cent of each other settle within a
    few per cent of one count (rmsnorm's two versions, the attention
    pair), never on a count whose round falls short, never past
    `max_inner`.  (0.00125 s: 16 calls reach the target exactly.)"""
    assert timing.settled_inner(per_call) == settled
    assert settled * per_call >= MIN_S or settled == MAX_INNER


@pytest.mark.parametrize("first,later", [
    (0.0052, 0.0026),        # twice as fast: the stored 6 falls short
    (0.00069, 0.0003),       # rmsnorm stored at 56, then faster
    (0.0227, 0.015),         # the recurrence drops under the target
    (0.001, 1e-6),           # to max_inner
])
def test_a_short_round_grows_by_today_s_rule_and_stores_the_count(
        monkeypatch, first, later):
    stand = Stand(monkeypatch, first)
    timed = timing.make_timed(stand)
    stand.invoke(timed)
    stored = stand.rounds()[-1][1]
    stand.per_call = later
    _, counted = stand.invoke(timed)
    assert counted == (0, 1)
    rounds = [calls for _, calls in stand.rounds()]
    assert rounds[0] == stored and stand.rounds()[0][0]["stored"]
    assert not any(meta["stored"] for meta, _ in stand.rounds()[1:])
    expect, inner = [stored], stored
    while inner * stand.per_call < MIN_S and inner < MAX_INNER:
        dt = inner * stand.per_call
        inner = min(MAX_INNER, max(inner * 2, int(inner * MIN_S / dt)))
        expect.append(inner)
    assert rounds == expect
    settled = timing.settled_inner(later)
    _, counted = stand.invoke(timed)
    assert counted == (1, 0)
    assert [calls for _, calls in stand.rounds()] == [settled]


@pytest.mark.parametrize("per_calls", [
    [0.00069, 0.01, 0.00069],          # slower: the count stays
    [0.0052, 0.0227, 0.0052, 0.0026],  # slower, back, then faster
    [1e-6, 0.0052, 1e-7],              # at max_inner, slower, faster
    [0.0227, 1e-5, 1e-7, 0.0227],
])
def test_the_stored_count_follows_the_last_measured_speed_within_max_inner(
        monkeypatch, per_calls):
    """Each invocation starts at the count the loop settles on at the
    previous invocation's measured per-call time, so a program that got
    slower settles back on fewer calls, each round within `max_inner`."""
    stand = Stand(monkeypatch, per_calls[0])
    timed = timing.make_timed(stand)
    start = None
    for per_call in per_calls:
        stand.per_call = per_call
        stand.invoke(timed)
        rounds = [calls for _, calls in stand.rounds()]
        assert rounds[0] == (1 if start is None else start)
        assert all(c <= MAX_INNER for c in rounds)
        start = timing.settled_inner(per_call)


@pytest.mark.parametrize("per_calls", [
    [0.0052] * 3, [0.00069] * 3, [0.00128] * 3, [0.0227] * 3, [1e-6] * 3,
    [0.0199] * 3, [0.00069, 0.0007, 0.0009], [0.0026, 0.0052, 0.0227],
    [1e-5, 0.00128, 0.0052],
])
def test_a_later_measured_round_has_no_fewer_calls_than_a_fresh_one(
        monkeypatch, per_calls):
    """At a constant speed, or as the program gets slower: every
    invocation measures at least the calls a fresh calibration would."""
    fresh = [fresh_count(monkeypatch, p) for p in per_calls]
    stand = Stand(monkeypatch, per_calls[0])
    timed = timing.make_timed(stand)
    for per_call, n_fresh in zip(per_calls, fresh):
        stand.per_call = per_call
        stand.invoke(timed)
        measured = stand.rounds()[-1][1]
        assert measured >= n_fresh
        assert measured * stand.per_call >= MIN_S or measured == MAX_INNER


@pytest.mark.parametrize("case,counted", [
    ("fresh time_fn", (0, 1)),
    ("first invocation", (0, 1)),
    ("later, measured at the stored count", (1, 0)),
    ("later, stored count short", (0, 1)),
])
def test_the_counter_counts_each_invocation_once(monkeypatch, case, counted):
    stand = Stand(monkeypatch, 0.0052)
    timed = timing.make_timed(stand)
    if case == "fresh time_fn":
        _, got = stand.invoke(lambda: timing.time_fn(stand).seconds_per_call)
    else:
        if case != "first invocation":
            stand.invoke(timed)
        if case == "later, stored count short":
            stand.per_call = 0.001
        _, got = stand.invoke(timed)
    assert got == counted


def test_invocations_on_several_threads_share_one_stored_count():
    """The real clock, more threads than cores and a short switch
    interval: each thread's calls take their own time, so invocations
    measure at different counts.  Every invocation counts once, and the
    stored count is the settled count of one of them."""
    measured = []
    lock = threading.Lock()
    local = threading.local()

    def program():
        time.sleep(local.per_call)

    def worker(i):
        local.per_call = 0.0005 * (1 + i % 4)
        for _ in range(3):
            timed()

    timed = timing.make_timed(program)
    orig = timing.time_fn

    def spy(fn, **kw):
        t = orig(fn, **kw)
        with lock:
            measured.append(t)
        return t

    h0, c0 = timed_invocations()
    interval = sys.getswitchinterval()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(timing, "time_fn", spy)
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(16)]
        sys.setswitchinterval(1e-6)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        h1, c1 = timed_invocations()
        assert (h1 - h0) + (c1 - c0) == len(measured) == 48
        starts = []
        mp.setattr(timing, "time_fn",
                   lambda fn, **kw: starts.append(kw["stored_inner"])
                   or orig(fn, **kw))
        local.per_call = 0.0005
        timed()
    assert starts[0] in {timing.settled_inner(t.seconds_per_call)
                         for t in measured}
