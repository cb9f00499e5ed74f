"""Offer an open-loop cell's traffic at several rates, in one process, to
find the highest rate it sustains (its knee).

    python3 benchmarks/chip/sweep.py --workload <cell> --seed 5 \\
        --seconds 10 --rates 25 50 100 200

For each rate, one JSON line: requests, failures, latency p50/p95 (from
when each was due), the generator's lateness p95, the mean batch size, the
median latency of the first and of the last fifth of the requests, and how
long after the last was due the window drained. A rate is sustained when
nothing failed, the p95 is under ``--limit-ms`` and the last fifth waits
no longer than twice the first (no growing backlog). The cell's fixed
``rate_per_s`` is then set by hand at about four fifths of the highest
sustained rate; the benchmark never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

from chipbench import core  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--limit-ms", type=float, required=True)
    args = ap.parse_args(argv)

    try:
        core.open_chips(1)
    except core.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    workload, config = core.cell_files(args.workload)
    entry = core.load_module("entries", workload["traffic"]["entry"])
    traffic = core.load_module("traffic", workload["traffic"]["kind"])
    for rate in args.rates:
        run = traffic.Run(entry, config, workload, args.seed, args.seconds,
                          rate=rate)
        rec = core.Record()
        core.settle()
        run.window(rec)
        lat = np.asarray(rec.latencies_ms)
        fifth = max(1, lat.size // 5)
        first, last = np.median(lat[:fifth]), np.median(lat[-fifth:])
        p95 = core.percentile(lat, 95)
        line = {
            "rate_per_s": rate, "requests": rec.attempted,
            "failed": rec.failed, "p50_ms": core.percentile(lat, 50),
            "p95_ms": p95,
            "gen_late_p95_ms": core.percentile(rec.gen_late_ms, 95),
            "mean_batch": float(np.mean(rec.batch_sizes)),
            "first_fifth_ms": float(first), "last_fifth_ms": float(last),
            "drain_s": rec.window_s - float(run.due[-1]),
            "sustained": bool(rec.failed == 0 and p95 < args.limit_ms
                              and last <= 2 * first)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
