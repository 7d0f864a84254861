"""Exit-code contract: deterministic precedence when failures co-occur.

The pipeline CLI and the benchmark harness can hit four failure
conditions in one run — infeasible plan (2), failed benchmarks (5),
strict-fast engine fallback (3), armed-SLO breach (4) — and
historically whichever check happened to run first won.  The contract
is now explicit: all conditions are evaluated, the winner comes from
`EXIT_PRECEDENCE` (2 beats 5 beats 3 beats 4), and both entry points
return from one resolver.
Each pairwise collision is pinned here, at the resolver and end-to-end
through `repro.cb.cli`.
"""
import json

import pytest

from repro.cb.cli import (EXIT_BREACH, EXIT_FAILED, EXIT_FALLBACK,
                          EXIT_INFEASIBLE, EXIT_PRECEDENCE, resolve_exit_code)
from repro.cb.cli import main as cli_main


# ------------------------------------------------------------ resolver

def test_precedence_table_is_the_documented_contract():
    assert EXIT_PRECEDENCE == (EXIT_INFEASIBLE, EXIT_FAILED, EXIT_FALLBACK,
                               EXIT_BREACH)
    assert EXIT_PRECEDENCE == (2, 5, 3, 4)


@pytest.mark.parametrize("pair, winner", [
    ((EXIT_INFEASIBLE, EXIT_FALLBACK), EXIT_INFEASIBLE),
    ((EXIT_INFEASIBLE, EXIT_BREACH), EXIT_INFEASIBLE),
    ((EXIT_FALLBACK, EXIT_BREACH), EXIT_FALLBACK),
])
def test_pairwise_collisions_resolve_by_precedence(pair, winner):
    """Each pairwise collision has one winner, independent of the order
    the conditions were detected in."""
    a, b = pair
    assert resolve_exit_code(a, b) == winner
    assert resolve_exit_code(b, a) == winner
    assert resolve_exit_code(0, a, 0, b) == winner


@pytest.mark.parametrize("other, winner", [
    (EXIT_INFEASIBLE, EXIT_INFEASIBLE),
    (EXIT_FALLBACK, EXIT_FAILED),
    (EXIT_BREACH, EXIT_FAILED),
])
def test_failed_benchmarks_rank_below_infeasible_only(other, winner):
    assert resolve_exit_code(EXIT_FAILED, other) == winner
    assert resolve_exit_code(other, EXIT_FAILED) == winner


def test_three_way_collision_and_identities():
    assert resolve_exit_code(EXIT_BREACH, EXIT_FALLBACK,
                             EXIT_INFEASIBLE) == EXIT_INFEASIBLE
    assert resolve_exit_code() == 0
    assert resolve_exit_code(0, 0) == 0
    assert resolve_exit_code(0, EXIT_BREACH) == EXIT_BREACH


def test_unknown_codes_are_never_swallowed():
    # a future condition added to one caller must fail loudly, not
    # vanish into 0 — but known codes still outrank it
    assert resolve_exit_code(0, 7) == 7
    assert resolve_exit_code(7, EXIT_BREACH) == EXIT_BREACH


# --------------------------------------------------------- end-to-end
#
# Real co-occurrence needs one (provider, mode) cell to fail one way
# while another cell (or the run as a whole) fails differently; the
# injections below force exactly that through public seams (the
# planner's plan() and the engine fallback log), then assert the
# process-level winner.

def _force_fallback(monkeypatch, reason="injected: test fallback"):
    import repro.faas.engine_vec as ev
    monkeypatch.setattr(ev, "get_fallback_log", lambda: [reason])


def _force_breach(monkeypatch):
    from repro.obs import Observability
    real = Observability.health

    def breached(self):
        h = real(self)
        h["verdict"] = "breach"
        return h

    monkeypatch.setattr(Observability, "health", breached)


def _infeasible_on(monkeypatch, provider):
    from repro.service.planner import (DeadlineCostPlanner,
                                       InfeasiblePlanError)
    real = DeadlineCostPlanner.plan

    def plan(self, workloads, **kw):
        if tuple(kw.get("providers") or ()) == (provider,):
            raise InfeasiblePlanError(kw.get("deadline_s"),
                                      kw.get("budget_usd"), 0)
        return real(self, workloads, **kw)

    monkeypatch.setattr(DeadlineCostPlanner, "plan", plan)


_FAST_SERVICE = ["--commits", "3", "--n-calls", "6", "--mode", "selective",
                 "--seed", "3", "--jobs", "2", "--engine", "fast"]


def test_cli_infeasible_beats_fallback(monkeypatch, capsys):
    """2+3: one provider's cells are infeasible, the other's degrade
    under strict fast — infeasible wins, and the healthy provider's
    summary is still printed (no early return eats it)."""
    _force_fallback(monkeypatch)
    _infeasible_on(monkeypatch, "azure")
    rc = cli_main(_FAST_SERVICE + ["--providers", "lambda,azure",
                                   "--deadline", "1800"])
    assert rc == EXIT_INFEASIBLE
    cap = capsys.readouterr()
    assert "infeasible" in cap.err
    assert "scalar loop" in cap.err
    summary = json.loads(cap.out.strip().splitlines()[0])
    assert summary["provider"] == "lambda"


def test_cli_infeasible_beats_breach(monkeypatch, capsys):
    """2+4: nothing admitted plus a breach verdict from the armed
    monitor — infeasible wins."""
    _force_breach(monkeypatch)
    rc = cli_main(_FAST_SERVICE + ["--providers", "lambda",
                                   "--deadline", "0.5", "--slo"])
    assert rc == EXIT_INFEASIBLE
    cap = capsys.readouterr()
    assert "infeasible" in cap.err
    assert "slo verdict: breach" in cap.err


def test_cli_fallback_beats_breach(monkeypatch, capsys):
    """3+4: a strict-fast degradation and an SLO breach in the same run
    — fallback wins (the breach was measured on the wrong core), and
    the summary line still comes out."""
    _force_fallback(monkeypatch)
    _force_breach(monkeypatch)
    rc = cli_main(_FAST_SERVICE + ["--providers", "lambda", "--slo"])
    assert rc == EXIT_FALLBACK
    cap = capsys.readouterr()
    assert "scalar loop" in cap.err
    assert "slo verdict: breach" in cap.err
    assert json.loads(cap.out.strip().splitlines()[0])["service"] is True


def test_cli_breach_alone_still_exits_4(monkeypatch, capsys):
    _force_breach(monkeypatch)
    rc = cli_main(_FAST_SERVICE + ["--providers", "lambda", "--slo"])
    assert rc == EXIT_BREACH
    assert "slo verdict: breach" in capsys.readouterr().err
