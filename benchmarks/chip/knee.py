"""Find the knee of a serving cell: the highest rate it sustains.

    python3 benchmarks/chip/knee.py --workload gcn-cora.serve \
        --rates 1000,2000,4000 --seconds 8 --seed 5

One process sets the cell up once, then offers each rate in turn (lowest
first, up to the first one not sustained), open loop, for ``--seconds``,
and prints per rate: the median and 95th
percentile latency, how late the generator ran, and the median latency of
the first and of the last third of the window (a backlog that grows shows
as the last third reading far above the first).  The knee is the highest
rate whose 95th percentile stays within 50 ms (``LIMIT_MS``, the default
of ``serve.engine.ServeSLO.deadline_s``) with no growing backlog.  The cell's
traffic file then fixes its rate at about four fifths of the knee; this
tool is run once, when a serving cell is defined or the knee has moved.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

LIMIT_MS = 50.0


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    c = harness.open_cell(args.workload)
    harness.require_devices(c.entry["chips"])
    from repro.launch.runtime import float32_matmuls
    from run import Timers
    harness.start_compile_cache()
    traffic = c.traffic
    knee = None
    with float32_matmuls():
        d = c.driver.Driver(c.config, traffic, args.seed, Timers())
        for rate in (float(r) for r in args.rates.split(",")):
            d.traffic = dict(traffic, rate=rate)
            w = d.window(args.seconds)
            lat = d.run["done"] - d.run["t"]
            third = len(lat) // 3
            row = {"rate": rate, "requests": w["attempted"],
                   **w["metrics"],
                   "gen_late_p95_ms": w["gen_late_p95_ms"],
                   "batch_ms": w["batch_ms"], "hit_rate": w["hit_rate"],
                   "first_third_p50_ms": float(np.median(lat[:third])) * 1e3,
                   "last_third_p50_ms": float(np.median(lat[-third:])) * 1e3}
            row["sustained"] = bool(
                row["serve_p95_ms"] <= LIMIT_MS
                and row["last_third_p50_ms"]
                <= 2 * row["first_third_p50_ms"] + 1.0)
            print(json.dumps(row), flush=True)
            if not row["sustained"]:
                break                   # higher rates only back up more
            knee = rate
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "limit_ms": LIMIT_MS}), flush=True)


if __name__ == "__main__":
    main()
