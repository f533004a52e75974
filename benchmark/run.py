#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's file (``benchmark/workloads/<cell>.json``) names its
configuration and its kind; ``BENCHMARK.json`` says which metrics the
cell reports; each metric's file names the reader that takes it from what
the run observed. The last line of standard output is the result object.
``--control <name>`` (never passed by the driver) switches on the cell's
lower-precision control, which has to come out as not correct.
"""

import time

T0 = time.monotonic()  # set-up is counted from here

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import device, manifest  # noqa: E402
from benchmark.lib.spans import Spans  # noqa: E402


class Context:
    """What one run hands to its kind and its readers."""

    def __init__(self, args, workload, config, found):
        self.t0 = T0
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.control = bool(args.trace), args.control
        self.workload, self.config, self.device = workload, config, found
        self.rehearsal = bool(workload.get("rehearsal"))
        self.spans = Spans()
        self.trace_dir = os.path.join(manifest.WORK, "trace")

    @staticmethod
    def say(message: str) -> None:
        print(f"[bench] {message}", flush=True)


def open_run(args) -> Context:
    """What every entry does first: the cell's files, the look for the
    chip, the compile cache, an empty work directory."""
    workload = manifest.workload(args.workload)
    found = device.require(workload["chips"], bool(workload.get("rehearsal")))
    device.compile_cache()
    shutil.rmtree(manifest.WORK, ignore_errors=True)
    os.makedirs(manifest.WORK)
    return Context(args, workload, manifest.config(workload["config"]), found)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", default="")
    args = parser.parse_args(argv)

    ctx = open_run(args)
    workload, found, rehearsal = ctx.workload, ctx.device, ctx.rehearsal
    peaks = None if rehearsal else device.peaks(found["kind"])
    ctx.say(f"{args.workload} seed {args.seed} on {found}"
            + (f", control {args.control}" if args.control else ""))
    obs = manifest.kind(workload["kind"]).run(ctx)
    obs["peaks"] = peaks
    found["memory_peak_bytes"] = device.memory_peak_bytes(workload["chips"])

    result = {}
    if ctx.trace:
        from benchmark.lib import trace as tr

        obs["trace"] = tr.load(tr.newest_xplane(ctx.trace_dir))
        if rehearsal and not obs["trace"]["devices"]:
            ctx.say("no device plane in a trace taken off the chip")
        else:
            found["busy_s"], found["window_s"] = tr.busy_seconds(obs["trace"])
            result["breakdown"] = {
                "device_ops": tr.device_ops(obs["trace"]),
                "idle_gaps": tr.idle_gaps(obs["trace"]),
            }

    metrics = {}
    # a listed cell reports what BENCHMARK.json has it report; a rehearsal
    # names the listed cell it stands for, or, where no cell of its kind
    # is listed yet, the metrics whose readers it rehearses
    listed = args.workload in [w["name"] for w in manifest.benchmark()["workloads"]]
    if listed or "stands_for" in workload:
        names = manifest.metrics_of(
            args.workload if listed else workload["stands_for"], ctx.trace)
    else:
        names = workload["metrics"]
    for name in names:
        spec = manifest.metric(name)
        value = manifest.reader(spec["reader"])(obs, spec.get("params", {}))
        if value is not None:
            metrics[name] = {"value": float(value), "unit": spec["unit"]}

    for v in obs["verdict"]:
        ctx.say(f"compared {v['name']}: {v['value']!r} (limit {v['limit']!r}) "
                f"{'ok' if v['ok'] else 'NOT OK'}")
    correct = all(v["ok"] for v in obs["verdict"])
    if rehearsal:
        # a number from a run off the chip is never printed under the
        # name of a device metric: say which metrics found a reading
        result["rehearsed"] = sorted(metrics)
        metrics = {}
    result = {
        "correct": bool(correct), "attempted": obs["attempted"],
        "failed": obs["failed"], "metrics": metrics, "device": found, **result,
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
