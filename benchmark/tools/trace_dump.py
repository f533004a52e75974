#!/usr/bin/env python3
"""Look at a trace by hand: every plane and line of an ``.xplane.pb``,
with the events that took most time.

    python3 benchmark/tools/trace_dump.py <dir-or-file> [--json out.json --head 400]

``--json`` writes the trace as ``lib/trace.load`` sees it, cut to the
first ``--head`` events of each device line: a recorded trace small
enough to keep with the tests.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.lib import trace as tr  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("path")
    parser.add_argument("--json")
    parser.add_argument("--head", type=int, default=400)
    args = parser.parse_args()
    path = args.path if args.path.endswith(".pb") else tr.newest_xplane(args.path)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            totals, count = {}, 0
            for ev in line.events:
                count += 1
                t = totals.setdefault(ev.name, [0, 0.0])
                t[0] += 1
                t[1] += ev.duration_ns * 1e-9
            print(f"  line {line.name!r}: {count} events")
            for name, (n, s) in sorted(totals.items(), key=lambda kv: -kv[1][1])[:12]:
                print(f"    {s:10.6f} s  x{n:<6} {name[:110]}")
    if args.json:
        trace = tr.load(path)
        for lines in trace["devices"].values():
            for name in lines:
                lines[name] = lines[name][: args.head]
        with open(args.json, "w") as f:
            json.dump(trace, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
