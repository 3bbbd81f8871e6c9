"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only fig10,fig13

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks.common import emit
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    ("fig1", "benchmarks.fig1_sampling_ratio", "Fig 1a: sampling ratio vs TP"),
    ("pipeline", "benchmarks.pipeline_sim", "Fig 1b/§3: pipeline bubbles"),
    ("fig_pipeline", "benchmarks.fig_pipeline",
     "Executable pipeline engine: measured baseline-vs-SIMPLE bubbles"),
    ("fig3", "benchmarks.fig3_throughput", "Fig 3: end-to-end throughput"),
    ("latency", "benchmarks.fig_latency",
     "Open-loop P95 latency: device vs host sampler modes"),
    ("fig5", "benchmarks.fig_latency_ecdf", "Fig 4/5/7: TPOT P95"),
    ("fig6", "benchmarks.fig6_load_latency", "Fig 6: load-latency"),
    ("overlap", "benchmarks.fig_overlap",
     "Overlapped engine + chunked prefill"),
    ("paged", "benchmarks.fig_paged",
     "Paged KV: admitted batch + throughput vs contiguous"),
    ("fig10", "benchmarks.fig10_ablation", "Fig 10: ablation ladder"),
    ("fig11", "benchmarks.fig11_sizing", "Fig 11/12: sizing model"),
    ("fig13", "benchmarks.fig13_tvd", "Fig 13: TVD exactness"),
    ("kernel", "benchmarks.kernel_bench", "Pallas kernels: HBM traffic"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated prefixes, e.g. fig10,fig13")
    args = ap.parse_args()
    enable_compile_cache()
    selected = args.only.split(",") if args.only else None

    print("name,us_per_call,derived")
    failures = []
    for key, module, desc in MODULES:
        if selected and key not in selected:
            continue
        print(f"# --- {desc} ({module}) ---", flush=True)
        t0 = time.perf_counter()
        try:
            import importlib
            mod = importlib.import_module(module)
            mod.run(emit)
        except Exception as e:
            failures.append((module, e))
            print(f"# ERROR in {module}: {e!r}", flush=True)
            traceback.print_exc()
        print(f"# ({module} took {time.perf_counter() - t0:.1f}s)", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
