#!/usr/bin/env python3
"""Where a traced training job's time went, by the program's own names:
device seconds by ``als.`` scope (side, bucket, phase), the share of the
ALS programs' busy time that carries a scope, idle seconds by ``pio.``
host span, and how much of the root span its children cover.

    python3 benchmark/tools/scope_table.py <dir-or-file> [--json out.json --head 4]

``--json`` writes the trace as ``lib/xplane.load`` sees it, cut to a test
fixture: of every execution of an ALS program the first ``--head``
operations of each stack of scopes (so every side, bucket and phase is
there), names cut to 160 characters, with every program event and every
kept host span.
"""

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.lib import scopes, xplane  # noqa: E402
from benchmark.lib import trace as tr  # noqa: E402

ALS_PROGRAM = r"^jit__als_"


def seconds(intervals) -> float:
    return sum(e - s for s, e in tr.union(intervals))


def table(trace) -> None:
    ops = scopes.scoped_ops(trace)
    programs = tr.program_events(trace, ALS_PROGRAM)
    inside = [(s, e, sc) for s, e, sc in ops
              if any(p[1] <= s and e <= p[1] + p[2] for p in programs)]
    busy = seconds((s, e) for s, e, _ in inside)
    scoped = seconds((s, e) for s, e, sc in inside if sc)
    print(f"ALS programs: {len(programs)} executions, busy {busy:.3f} s, "
          f"under an als. scope {scoped:.3f} s ({100 * scoped / busy:.2f} %)")
    names = sorted({name for _, _, sc in ops for name in sc})
    print("device seconds by scope (union of op intervals):")
    for name in names:
        total = seconds((s, e) for s, e, sc in ops if name in sc)
        print(f"  {total:10.4f} s  {100 * total / busy:6.2f} %  {name}")
    print("device seconds by name stack:")
    stacks = {}
    for s, e, sc in ops:
        # a while loop carries no stack: its body's operations are below
        stacks.setdefault("/".join(sc) or "(no stack: while loops, copies)", []).append((s, e))
    for stack, spans in sorted(stacks.items(), key=lambda kv: -seconds(kv[1]))[:40]:
        print(f"  {seconds(spans):10.4f} s  {stack}")
    print("idle seconds by the program's host span:")
    idle = scopes.idle_by_span(trace)
    for name, total in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"  {total:10.4f} s  {name}")
    spans = [ev for ev in trace["host"] if ev[0].startswith(scopes.SPAN_PREFIX)]
    roots = [ev for ev in spans if ev[0].split(" ")[0] == scopes.ROOT_SPAN]
    for _, start, length in roots:
        kids = [(s, s + d) for n, s, d in spans
                if n.split(" ")[0] != scopes.ROOT_SPAN and start <= s and s + d <= start + length]
        print(f"root span {length:.3f} s, its children cover {seconds(kids):.3f} s "
              f"({100 * seconds(kids) / length:.2f} %)")
        for n, s, d in spans:
            if start <= s and s + d <= start + length:
                print(f"  {d:10.4f} s  {n}")


def cut(trace, head: int):
    """The fixture: see the module's docstring."""
    plane = scopes.first_device(trace)
    lines = trace["devices"][plane]
    programs = [ev for ev in lines[tr.MODULE_LINE] if re.search(ALS_PROGRAM, ev[0])]
    seen, keep = {}, []
    for i, ((name, start, length), stack) in enumerate(zip(lines[tr.OP_LINE], trace["stacks"][plane])):
        run = next((n for n, p in enumerate(programs) if p[1] <= start < p[1] + p[2]), None)
        key = (run, *xplane.scopes_of(stack, "als."))
        seen[key] = seen.get(key, 0) + 1
        if seen[key] <= head:
            keep.append(i)
    return {
        "devices": {plane: {
            tr.MODULE_LINE: lines[tr.MODULE_LINE],
            tr.OP_LINE: [[lines[tr.OP_LINE][i][0][:160], *lines[tr.OP_LINE][i][1:]] for i in keep],
        }},
        "stacks": {plane: [trace["stacks"][plane][i] for i in keep]},
        "host": trace["host"],
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("path")
    parser.add_argument("--json")
    parser.add_argument("--head", type=int, default=4)
    args = parser.parse_args()
    path = args.path if args.path.endswith(".pb") else tr.newest_xplane(args.path)
    began = time.monotonic()
    trace = xplane.load(path)
    print(f"{path}: {os.path.getsize(path)} bytes, read in {time.monotonic() - began:.2f} s")
    if scopes.first_device(trace) is None:
        print("no operation ran on a device in this trace")
        return 1
    table(trace)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(cut(trace, args.head), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
