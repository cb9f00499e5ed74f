"""Read the numbers that decide ``correct``, for the program and for the
control, on many seeds in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seconds 10 \\
        --seeds 1 2 3 ...

For each seed it sets the cell up, runs one window at the cell's own load
and sizes, and compares every answer with the plain reference, as a run of
``run.py`` does; then it puts the control (the reference at three-pass
bfloat16, ``chipbench.reference``) in the program's place on the same
inputs and compares again. One JSON line per seed, then one with the
largest program reading and the smallest control reading of each number:
the two readings a limit is set between. It needs a TPU, as ``run.py``
does; the benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

from chipbench import core  # noqa: E402


def readings(cell: str, seeds: list[int], seconds: float):
    """(seed, attempted, failed, program numbers, control numbers) per
    seed."""
    workload, config = core.cell_files(cell)
    traffic = core.load_module("traffic", workload["traffic"]["kind"])
    entry = core.load_module("entries", workload["traffic"]["entry"])
    for seed in seeds:
        run = traffic.prepare(entry, config, workload, seed, seconds)
        rec = core.Record()
        core.settle()
        run.window(rec)
        program = run.compare(rec)
        yield seed, rec.attempted, rec.failed, program, run.control_numbers()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    try:
        core.open_chips(1)
    except core.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    worst: dict[str, float] = {}
    least: dict[str, float] = {}
    for seed, attempted, failed, prog, ctrl in readings(
            args.workload, args.seeds, args.seconds):
        print(json.dumps({"seed": seed, "attempted": attempted,
                          "failed": failed, "program": prog,
                          "control": ctrl}), flush=True)
        for k, v in prog.items():
            worst[k] = max(worst.get(k, v), v)
        for k, v in ctrl.items():
            least[k] = min(least.get(k, v), v)
    print(json.dumps({"workload": args.workload, "program_max": worst,
                      "control_min": least,
                      "seconds_total": time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
