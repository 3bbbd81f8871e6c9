"""Run one cell of ``BENCHMARK.json`` once, on the machine it starts on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits nonzero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def context(args, devices, bench: dict, t_start: float, control=False):
    w = harness.cell(bench, args.workload)
    return harness.Ctx(
        cell=w["name"], devices=devices,
        cfg=harness.config_file(bench, w["config"]),
        traffic=harness.traffic_file(w["traffic"]),
        limits=harness.limits_file(w["name"]), seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_start=t_start,
        end_to_end=harness.cell_metrics(bench, w["name"], "end_to_end"),
        per_layer=harness.cell_metrics(bench, w["name"], "per_layer"),
        control=control)


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.benchmark()
    w = harness.cell(bench, args.workload)
    try:
        devices = harness.chips(w["chips"])
    except (harness.NoChip, KeyError) as e:
        harness.say(f"run: {e}")
        return 2
    harness.use_compile_cache()
    ctx = context(args, devices, bench, T_START)
    entry = harness.entry(ctx.traffic["entry"])
    result, checks = entry.run(ctx)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
