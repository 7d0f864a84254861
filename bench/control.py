"""Readings of the program, of its control and of planted statistics
faults, on several seeds in one process, for setting a cell's limits.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed, one short window of the cell at its own size and load, on
inputs made from that seed.  The line printed per seed holds each number
compared for the program (``program``) and for the control (``control``:
the plain reference computed in float8, put in the program's place and
judged by the same checks), and the statistics numbers of the window's
change results with a fault planted after the fact (``faults``): each
interval's upper end moved up by 1 pp, and the analysis redone over every
other pair.  It exits 1 unless the program is correct on every seed and
the control is not, with each duet's gap over its limit.  The
benchmark's own runs never run it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, NoChip, require_chips  # noqa: E402


def fault_readings(rec, min_results: int, seed: int) -> dict:
    """The statistics numbers with each fault planted in the window's
    change results."""
    from bench import check
    from repro.core.results import analyze
    moved = [(r.report.pairs,
              {b: dataclasses.replace(c, ci_high=c.ci_high + 1.0)
               for b, c in r.changes.items()}) for r in rec.suite_runs]
    halved = [(r.report.pairs,
               analyze(list(r.report.pairs)[::2], seed=seed,
                       min_results=min_results)) for r in rec.suite_runs]
    out = {}
    for name, runs in (("ci_end_moved_1pp", moved),
                       ("half_the_pairs", halved)):
        faults, surprise = check.stats_numbers(runs,
                                               min_results=min_results)
        out[name] = {"stats_faults": faults, "ci_end_surprise": surprise}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from bench import hooks
    from bench.entries import Entries
    from bench.window import run_window

    entries = Entries(ROOT)
    cell = entries.cell(args.workload)
    cfg = entries.config(cell["config"])
    try:
        require_chips(cell["chips"])
    except NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    sound = True
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = hooks.Recorder()
        result = run_window(args.workload, seed, args.seconds, False,
                            t_start=T_START, control=True, recorder=rec)
        control = result["checks"]
        program = result["program_checks"]
        gaps = [k for k in control if k.endswith("_gap")]
        sound &= (result["correct"] is False
                  and all(_over(control[k]) for k in gaps)
                  and not any(_over(c) for c in program.values()))
        print(json.dumps({
            "seed": seed, "control_correct": result["correct"],
            "program": program, "control": control,
            "faults": fault_readings(
                rec, cfg["deployment"]["pipeline"]["min_results"], seed)}),
            flush=True)
    return 0 if sound else 1


def _over(c: dict) -> bool:
    return c["value"] == "inf" or c["value"] > c["limit"]


if __name__ == "__main__":
    sys.exit(main())
