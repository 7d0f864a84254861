"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine whose first JAX device is a TPU
with at least the chips the cell asks for; anything else exits non-zero
with no result.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device`` and, when
traced, ``breakdown``; ``checks``, last, holds each number compared beside
its limit, and the same lines end standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class NoChip(RuntimeError):
    pass


def require_chips(chips: int) -> None:
    """The first device must be a TPU, and there must be `chips` of them;
    there is no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found: the first JAX device is "
                     f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.entries import Entries
    from bench.window import run_window

    cell = Entries(ROOT).cell(args.workload)
    t_devices = time.perf_counter()
    try:
        require_chips(cell["chips"])
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    marks = {"start_s": t_devices - T_START,
             "devices_s": time.perf_counter() - t_devices}
    result = run_window(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=T_START, setup_marks=marks)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
