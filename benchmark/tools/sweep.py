#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell, once: one server, one
window per rate.

    python3 benchmark/tools/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates 400,800,1200

A rate is SUSTAINED when every request is answered 200, the generator
sent 99 % of them within 2 ms of when they were due, and the p99 of the
window's last third is no more than twice that of its first third (no
backlog growing through the window). The knee is the highest sustained
rate; a steady cell runs at about four fifths of it. One JSON line per
rate, then the knee.
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.kinds import serve  # noqa: E402
from benchmark.run import open_run  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--control", default="")
    args = parser.parse_args()
    ctx = open_run(args)
    workload, found = ctx.workload, ctx.device
    served = serve.Served(ctx)
    knee = None
    try:
        for n, rate in enumerate(float(r) for r in args.rates.split(",")):
            traffic = dict(workload["traffic_params"], mode="open", rate=rate)
            win = served.window(traffic, args.seconds, args.seed + n)
            lat = np.asarray(win["latency_ms"])
            third = args.seconds / 3.0
            first = lat[win["due"] < third]
            last = lat[win["due"] >= 2 * third]
            late = np.asarray(win["lateness_ms"])
            row = {
                "rate": rate, "attempted": win["attempted"],
                "answered_200": int(win["ok"].sum()),
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p99_ms": float(np.percentile(lat, 99)),
                "p99_first_third_ms": float(np.percentile(first, 99)),
                "p99_last_third_ms": float(np.percentile(last, 99)),
                "late_p99_ms": float(np.percentile(late, 99)),
                "avg_batch": win["avg_batch"],
                "queue_wait_mean_ms": win["queue_wait_mean_ms"],
                "server_mean_ms": win["server_mean_ms"],
            }
            row["sustained"] = bool(
                row["answered_200"] == row["attempted"]
                and row["late_p99_ms"] <= 2.0
                and row["p99_last_third_ms"] <= 2.0 * row["p99_first_third_ms"]
            )
            if row["sustained"]:
                knee = rate if knee is None else max(knee, rate)
            print(json.dumps(row), flush=True)
    finally:
        served.close()
    from benchmark.lib import device

    found["memory_peak_bytes"] = device.memory_peak_bytes(workload["chips"])
    print(json.dumps({"knee": knee, "device": found}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
