"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout on a machine with a TPU. The cell is an entry
of ``workloads`` in ``BENCHMARK.json``; its files are
``benchmarks/chip/workloads/<cell>.json`` and the configuration that file
names. The run sets up (device check, compilation caches in the checkout,
warm-up of the cell's own shapes), measures for ``--seconds``, compares
every answer of the window with the plain reference, and prints one JSON
line last: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics from a
profiled window), ``device`` and, traced, ``breakdown``; ``checks`` comes
last, each compared number beside its limit. Those numbers also end the
standard error, after one ``window`` line of readings that the result line
does not carry (``core.diagnose``).

It exits non-zero, and prints no result, when JAX's first device is not a
TPU or there are fewer devices than the cell's ``chips``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

from chipbench import core  # noqa: E402


def _finite(v):
    return v if not isinstance(v, float) or math.isfinite(v) else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = core.benchmark_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    workload, config = core.cell_files(args.workload)

    try:
        core.open_chips(int(cells[args.workload]["chips"]))
    except core.NoChip as e:
        print(e, file=sys.stderr)
        return 3

    out = core.run_cell(
        workload, config, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace),
        metrics=core.cell_metrics(spec, args.workload, bool(args.trace)),
        t_start=T_START)
    for name, c in out["checks"].items():
        c["value"] = _finite(c["value"])
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
