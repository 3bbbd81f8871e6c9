"""What every run does around its entry: find the cell's files by name,
look for the chips, keep the compile cache in the checkout, read the
per-layer metrics and print the result line.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``).
The mix names its entry (``entries/<entry>.py``: how load reaches the
system under test) and the cell's limits live in ``limits/<cell>.json``.
A per-layer metric ``<name>`` is read by ``metrics/<name>.py``'s ``read``.
"""
from __future__ import annotations

import importlib
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
CACHE_DIR = CHECKOUT / ".jax_cache"
TRACE_DIR = CHECKOUT / ".bench_trace"


@dataclass
class Ctx:
    """Everything one run of a cell needs; built by ``run.py``."""

    cell: str
    devices: list
    cfg: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    end_to_end: list = field(default_factory=list)  # BENCHMARK.json entries
    per_layer: list = field(default_factory=list)   # for this cell
    control: bool = False     # also read the float8 control (calibration)
    keep_trace: Optional[Path] = None   # save a raw trace sample there
    stall_dump_s: float = 0.0   # print the stack of a step stuck this long


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips a cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(CHECKOUT / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_file(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(CHECKOUT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def limits_file(cell_name: str) -> dict:
    return load_json(HERE / "limits" / f"{cell_name}.json")


def entry(name: str):
    return importlib.import_module(f"benchmarks.chip.entries.{name}")


def metric_reader(name: str):
    return importlib.import_module(f"benchmarks.chip.metrics.{name}").read


def cell_metrics(bench: dict, cell_name: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def chips(wanted: int):
    """The accelerator's devices, or ``NoChip``: the run must not fall back
    to the CPU, and a device kind without published peaks is an error."""
    import jax
    from benchmarks.chip.peaks import peaks_for
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (default device: {devs[0].platform})")
    if len(devs) < wanted:
        raise NoChip(f"the cell needs {wanted} chips, JAX found {len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs[:wanted]


def use_compile_cache() -> None:
    """JAX's persistent cache in the checkout, at a fixed path, holding even
    the small programs (the default skips any that compile in under 1 s)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_facts(devs, peak_bytes: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


def peak_memory(devs) -> int:
    """``peak_bytes_in_use`` of the fullest chip (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return max(peaks)


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def emit(result: dict, checks: Dict[str, dict]) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, and the result as the last line of standard output
    (the checks last in it)."""
    for name, c in checks.items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)
