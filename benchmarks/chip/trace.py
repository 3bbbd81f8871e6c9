"""Profiler traces: capture a window, reduce it to what the metrics read.

The reduction keeps, for the traced window (the span of the benchmark's own
``bench.window`` annotation):

* ``modules``: every XLA program execution on the device (name, start, dur);
* ``busy``: the union of the device's op intervals, as sorted disjoint
  ``[start, end]`` pairs;
* ``op_self_ns``: each device op's time net of the ops nested in it, keyed
  ``<program>/<op>``;
* ``host``: the benchmark's own host spans (``client.*``, ``engine.step``).

Times are nanoseconds on the trace's clock, which the device and host lines
share. The reduced form is plain JSON, so a trace recorded on the chip can be
kept small and reduced again in a test.
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
import shutil
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

WINDOW = "bench.window"
HOST_SPANS = ("bench.window", "engine.step", "client.submit", "client.idle")


def start(log_dir: Path) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # Python frames would slow the host
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def _op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def _module_name(name: str) -> str:
    """``jit__decode_impl(1757...)`` -> ``jit__decode_impl``."""
    return re.sub(r"\(\d+\)$", "", name)


def load(log_dir: Path, keep: Optional[Path] = None) -> dict:
    """Read the ``.xplane.pb`` under ``log_dir`` and reduce it; the
    directory is removed afterwards. ``keep``: also write the raw events of
    the window's first ``KEEP_S`` seconds there (gzipped JSON, op names
    shortened), a sample for the reduction's tests."""
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    try:
        pd = ProfileData.from_file(str(files[-1]))
        raw = {"devices": [], "host": []}
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                dev = {"name": plane.name, "modules": [], "ops": []}
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                          for e in line.events]
                    elif line.name == "XLA Ops":
                        dev["ops"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
                raw["devices"].append(dev)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    raw["host"] += [[e.name, e.start_ns, e.duration_ns]
                                    for e in line.events
                                    if e.name in HOST_SPANS]
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    if keep is not None:
        save_sample(raw, keep)
    return reduce_raw(raw)


KEEP_S = 0.25


def save_sample(raw: dict, path: Path) -> None:
    w0 = min(s for n, s, d in raw["host"] if n == WINDOW)
    w1 = w0 + KEEP_S * 1e9
    cut = lambda evs, f=lambda n: n: [[f(n), s, min(d, w1 - s)]
                                      for n, s, d in evs if w0 <= s < w1]
    sample = {"host": [[WINDOW, w0, w1 - w0]] + [
        h for h in cut(raw["host"]) if h[0] != WINDOW],
        "devices": [{"name": d["name"], "modules": cut(d["modules"]),
                     "ops": cut(d["ops"], _op_name)}
                    for d in raw["devices"]]}
    with gzip.open(path, "wt") as f:
        json.dump(sample, f)


def _union(intervals) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(ops, module_of) -> Dict[str, float]:
    """Exclusive time per op: nested ops (a loop's body inside the loop op)
    are subtracted from the op that contains them."""
    acc: Dict[str, float] = defaultdict(float)
    stack: List[list] = []          # [end, key, child_ns, dur]
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        e = s + d
        while stack and stack[-1][0] <= s:
            end, key, child, dur = stack.pop()
            acc[key] += dur - child
        if stack:
            stack[-1][2] += d
        stack.append([e, f"{module_of(s)}/{_op_name(name)}", 0.0, d])
    while stack:
        end, key, child, dur = stack.pop()
        acc[key] += dur - child
    return dict(acc)


def reduce_raw(raw: dict) -> dict:
    """Reduce raw events (``load``'s intermediate form) to the window."""
    wins = [(s, s + d) for n, s, d in raw["host"] if n == WINDOW]
    if not wins:
        raise RuntimeError("the trace holds no bench.window span")
    w0, w1 = wins[0]
    devices = []
    for dev in raw["devices"]:
        mods = sorted(([_module_name(n), s, d] for n, s, d in dev["modules"]
                       if s < w1 and s + d > w0), key=lambda m: m[1])
        starts = [m[1] for m in mods]

        def module_of(t, mods=mods, starts=starts):
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t <= mods[i][1] + mods[i][2]:
                return mods[i][0]
            return "?"

        ops = [o for o in dev["ops"] if w0 <= o[1] < w1]
        busy = _union((max(s, w0), min(s + d, w1)) for _, s, d in ops)
        devices.append({"name": dev["name"], "modules": mods, "busy": busy,
                        "op_self_ns": _self_times(ops, module_of)})
    host = sorted(([n, s, d] for n, s, d in raw["host"]
                   if n != WINDOW and s < w1 and s + d > w0),
                  key=lambda h: h[1])
    return {"window": [w0, w1], "devices": devices, "host": host}


# -- readings ------------------------------------------------------------------
def window_s(red: dict) -> float:
    w0, w1 = red["window"]
    return (w1 - w0) * 1e-9


def busy_s(red: dict) -> float:
    """Seconds in which an op ran, averaged over the traced chips."""
    per = [sum(e - s for s, e in d["busy"]) for d in red["devices"]]
    if not per:
        raise RuntimeError("the trace holds no TPU device")
    return sum(per) / len(per) * 1e-9


def module_calls(red: dict, prefix: str) -> List[float]:
    """Durations (s) of every execution of programs named ``prefix*``."""
    return [d * 1e-9 for dev in red["devices"] for n, s, d in dev["modules"]
            if n.startswith(prefix)]


def top_ops(red: dict, n: int = 10) -> List[list]:
    acc: Dict[str, float] = defaultdict(float)
    for dev in red["devices"]:
        for k, v in dev["op_self_ns"].items():
            acc[k] += v * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(red: dict, n: int = 10) -> List[list]:
    """Idle device seconds in the window, summed by what the host was doing
    meanwhile: each idle interval is split over the benchmark's host spans
    it overlaps (``none`` where no span was open)."""
    w0, w1 = red["window"]
    host = red["host"]
    starts = [h[1] for h in host]
    acc: Dict[str, float] = defaultdict(float)
    for dev in red["devices"]:
        edges = [w0] + [t for iv in dev["busy"] for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            covered = 0.0
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while i < len(host) and host[i][1] < b:
                name, s, d = host[i]
                ov = min(b, s + d) - max(a, s)
                if ov > 0:
                    acc[name] += ov * 1e-9
                    covered += ov
                i += 1
            if b - a - covered > 0:
                acc["none"] += (b - a - covered) * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(red: Optional[dict]) -> dict:
    return {"device_ops": top_ops(red), "idle_gaps": idle_gaps(red)}
