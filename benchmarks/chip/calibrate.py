"""Readings that set a cell's limits and rate; not part of a benchmark run.

    python3 benchmarks/chip/calibrate.py limits --workload <cell> \
        --seeds 1,2,... --seconds <s> [--out <file>]
    python3 benchmarks/chip/calibrate.py knee --workload <cell> \
        --seeds 1,2 --rates 1.5,2,2.5,... --seconds <s> [--out <file>]

``limits`` runs the cell once per seed in one process (the same entry,
load and window as a benchmark run, at the cell's own size) and reads
``greedy_gap`` and ``sampled_gap`` and, on the same prompts and served
tokens, the float8 control's ``control_greedy_gap`` and
``control_sampled_gap``: the lower and upper readings a limit is set
between; ``control_correct`` is the control put through the same decision.
``knee`` runs an open-loop cell at each offered rate, for each seed, and
prints its end-to-end metrics, to find the highest rate the system
sustains. ``--stall-dump S`` prints the Python stack of any engine step
that takes longer than S seconds.
``trace`` makes one traced run and keeps a raw sample of its trace beside
``--out`` (the recorded trace the reduction's tests read).
Each reading is one JSON line on standard output (and in ``--out``).
"""
from __future__ import annotations

import time

import argparse
import copy
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import check, harness  # noqa: E402
from benchmarks.chip.run import context  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("limits", "knee", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--stall-dump", type=float, default=0.0)
    args = ap.parse_args(argv)
    bench = harness.benchmark()
    w = harness.cell(bench, args.workload)
    try:
        devices = harness.chips(w["chips"])
    except (harness.NoChip, KeyError) as e:
        harness.say(f"calibrate: {e}")
        return 2
    harness.use_compile_cache()
    out = open(args.out, "a") if args.out else None
    seeds = [int(s) for s in args.seeds.split(",")]
    points = ([(s, float(r)) for s in seeds for r in args.rates.split(",")]
              if args.what == "knee" else [(s, None) for s in seeds])
    for seed, rate in points:
        t0 = time.perf_counter()
        ctx = context(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds,
            trace=int(args.what == "trace")),
            devices, bench, t0, control=args.what == "limits")
        ctx.stall_dump_s = args.stall_dump
        if args.what == "trace":
            ctx.keep_trace = Path(args.out).with_suffix(".trace.json.gz")
        if rate is not None:
            tr = ctx.traffic = copy.deepcopy(ctx.traffic)
            tr["initial_requests"] = int(round(
                tr["initial_requests"] * rate / tr["rate_rps"]))
            tr["rate_rps"] = rate
        result, checks = harness.entry(ctx.traffic["entry"]).run(ctx)
        line = {"cell": args.workload, "seed": seed, "rate_rps": rate,
                "seconds": args.seconds,
                "metrics": {k: v["value"]
                            for k, v in result["metrics"].items()},
                "breakdown": result.get("breakdown"),
                "attempted": result["attempted"], "failed": result["failed"],
                "correct": result["correct"],
                "checks": {k: v["value"] for k, v in checks.items()},
                "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
        if args.what == "limits":
            line["control_correct"] = check.decide(
                line["checks"], ctx.limits, "control_")
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
        del result
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
