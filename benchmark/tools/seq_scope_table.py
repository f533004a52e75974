#!/usr/bin/env python3
"""Device seconds of the newest traced job under every ``seq.`` scope of
the sequence backbone, widest first, with its share of the device's busy
time: the table behind ``PERF.md`` §5 for ``train-qwen3next-packed8k``.

    python3 benchmark/run.py --workload train-qwen3next-packed8k --seed 7 --seconds 40 --trace 1
    python3 benchmark/tools/seq_scope_table.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    from benchmark.lib import scopes, seq_scopes
    from benchmark.lib import trace as tr

    trace = scopes.job_trace({})
    if not trace:
        sys.exit("no trace under .bench_work/trace: run a cell with --trace 1 first")
    ops = seq_scopes.scoped_ops(trace)
    busy = sum(e - s for s, e in tr.union([(s, e) for s, e, _ in ops]))
    names = sorted({n for _, _, found in ops for n in found})
    # (a ``while`` carries no name stack and lasts as long as its body's
    # operations, so "under no scope" cannot be read off as a difference)
    print(f"busy {busy:.3f} s in the window")
    rows = [(seq_scopes.scope_seconds(trace, n), n) for n in names]
    for seconds, name in sorted(rows, reverse=True):
        print(f"{name:24s} {seconds:8.3f} s {100 * seconds / busy:6.1f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main())
