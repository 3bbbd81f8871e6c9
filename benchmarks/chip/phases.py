"""The program's own spans and scopes in a cell's profiler trace: where the
device's idle time goes, step phase by step phase, and the decision plane's
share of the decode program. A tool, like ``calibrate.py``: not part of a
benchmark run, whose reduction (``trace.py``) keeps only the benchmark's
own host spans.

    python3 benchmarks/chip/phases.py --workload <cell> --seeds 1,2,... \
        --seconds <s> [--out <file>] [--keep <sample.json.gz>]

Each seed makes one traced run of the cell, exactly as ``run.py --trace 1``
does, in one process. Two things are kept that the run's own reduction
drops: the engine's phases, host events named ``obs.<kind>`` (DESIGN.md
§17), read from the trace before ``trace.load`` removes it; and the scope
path (``op_name``) of each op of the decode program, read from its
compiled HLO when the engine closes, since a v5e trace's op events carry
only the instruction's name. Readings, one JSON line per seed:

* ``idle_in_admit_share`` (%): device idle while the innermost open phase
  is ``obs.prefill`` or one of its ``obs.admit_*`` children, over the
  traced window;
* ``idle_in_step_host_share`` (%): device idle under any other phase;
* ``idle_outside_share`` (%): device idle under no phase; the three add up
  to ``device_idle_share``;
* ``admit_ms``: mean wall time of an ``obs.prefill`` phase inside the window;
* ``decision_share_of_decode`` (%): self time of the decode program's ops
  whose scope path holds ``decision``, over the self time of all its ops.

The innermost open phase at an instant is the one that started last. With
``--keep``, a raw sample of the trace around the window's first admission
is written there, with its phases and scopes (the tests' recorded trace).
"""
from __future__ import annotations

import argparse
import bisect
import gc
import gzip
import json
import re
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness, trace  # noqa: E402

PREFIX = "obs."
ADMIT = ("obs.prefill", "obs.admit_decide", "obs.admit_insert",
         "obs.admit_fetch")
DECODE = "jit__decode_impl"
SAMPLE_S = (0.1, 0.4)    # a sample keeps this much before/after an admission

_INSTR = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = .*?\bop_name="([^"]*)"')


# -- reading -------------------------------------------------------------------
def read_spans(xplane: Path) -> List[list]:
    """``[name, start_ns, dur_ns]`` of every host event named ``obs.*``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane))
    return [[e.name, e.start_ns, e.duration_ns]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` for every instruction of a compiled
    HLO module that carries one. The instruction names are the names of
    the ops in a profiler trace; a fusion carries its own ``op_name``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def with_phases(red: dict, spans: List[list],
                scopes: Optional[Dict[str, Dict[str, str]]] = None) -> dict:
    """``red`` (``trace.reduce_raw``'s form) plus ``spans``, the phases
    that overlap its window (clipped to it), and ``scopes``, each program's
    instruction -> scope path. The existing keys are left as they are."""
    w0, w1 = red["window"]
    out = dict(red)
    out["spans"] = sorted(
        ([n, max(s, w0), min(s + d, w1) - max(s, w0)] for n, s, d in spans
         if s < w1 and s + d > w0), key=lambda x: (x[1], -x[2]))
    out["scopes"] = scopes or {}
    return out


# -- readings ------------------------------------------------------------------
def _idle(dev: dict, w0: float, w1: float):
    """The device's idle intervals in the window, and a function giving
    the idle time in ``[w0, t]``."""
    edges = [w0] + [t for iv in dev["busy"] for t in iv] + [w1]
    ivs = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    starts = [a for a, _ in ivs]
    cum = [0.0]
    for a, b in ivs:
        cum.append(cum[-1] + b - a)

    def upto(t):
        k = bisect.bisect_right(starts, t) - 1
        if k < 0:
            return 0.0
        a, b = ivs[k]
        return cum[k] + min(t, b) - a
    return upto


def idle_by_phase(red: dict) -> Dict[str, float]:
    """Idle device seconds in the window, each instant's given to the
    innermost phase open then (the one that started last; ``none`` where
    no phase is open), averaged over the traced chips."""
    w0, w1 = red["window"]
    spans = red.get("spans", [])
    # edges: ends sort before starts at the same instant
    edges = sorted([(s, 1, i) for i, (_, s, _) in enumerate(spans)]
                   + [(s + d, 0, i) for i, (_, s, d) in enumerate(spans)])
    acc: Dict[str, float] = defaultdict(float)
    n = len(red["devices"])
    for dev in red["devices"]:
        upto = _idle(dev, w0, w1)
        open_: Dict[int, list] = {}
        t_prev = w0
        for t, start, i in edges + [(w1, 0, -1)]:
            t = min(max(t, w0), w1)
            if t > t_prev:
                idle = upto(t) - upto(t_prev)
                if idle > 0:
                    owner = max(open_.values(), key=lambda sp: sp[1])[0] \
                        if open_ else "none"
                    acc[owner] += idle * 1e-9 / n
                t_prev = t
            if i < 0:
                continue
            if start:
                open_[i] = spans[i]
            else:
                open_.pop(i, None)
    return dict(acc)


def idle_shares(red: dict) -> Optional[Dict[str, float]]:
    """``admit``, ``step_host`` and ``outside``: % of the window in which
    the device idled under an admission phase, another phase, or none.
    None where the trace holds no phase (a program without them)."""
    if not red.get("spans") or not red["devices"]:
        return None
    by = idle_by_phase(red)
    w = trace.window_s(red)
    admit = sum(v for k, v in by.items() if k in ADMIT)
    outside = by.get("none", 0.0)
    host = sum(by.values()) - admit - outside
    return {"admit": 100.0 * admit / w, "step_host": 100.0 * host / w,
            "outside": 100.0 * outside / w}


def admit_ms(red: dict) -> Optional[float]:
    """Mean wall time of an ``obs.prefill`` phase wholly inside the window
    (``with_phases`` clips those that cross an edge)."""
    w0, w1 = red["window"]
    ds = [d for n, s, d in red.get("spans", [])
          if n == "obs.prefill" and s > w0 and s + d < w1]
    return 1e-6 * sum(ds) / len(ds) if ds else None


def scope_shares(red: dict, program: str = DECODE) -> Optional[dict]:
    """Shares (%) of ``program``'s op self time by the first scope of each
    op's path that is ``forward`` or ``decision`` (``unscoped`` for
    neither, as the copies layout assignment adds), and ``covered``: the
    share of ops found in the program's HLO at all. None without scopes."""
    scopes = red.get("scopes", {}).get(program)
    if not scopes:
        return None
    acc: Dict[str, float] = defaultdict(float)
    found = 0.0
    for dev in red["devices"]:
        for key, ns in dev["op_self_ns"].items():
            mod, _, op = key.partition("/")
            if mod != program:
                continue
            path = scopes.get(op)
            found += ns if path is not None else 0.0
            parts = (path or "").split("/")
            scope = next((p for p in parts if p in ("forward", "decision")),
                         "unscoped")
            acc[scope] += ns
    total = sum(acc.values())
    if not total or not acc.get("decision"):
        return None
    out = {k: 100.0 * v / total for k, v in acc.items()}
    out["covered"] = 100.0 * found / total
    return out


def decision_share_of_decode(red: dict) -> Optional[float]:
    shares = scope_shares(red)
    return shares["decision"] if shares else None


def readings(red: dict) -> dict:
    """The tool's readings of one traced window (None where not found)."""
    shares = idle_shares(red) or {}
    steps = sum(1 for n, _, _ in red["host"] if n == "engine.step")
    return {
        "idle_in_admit_share": shares.get("admit"),
        "idle_in_step_host_share": shares.get("step_host"),
        "idle_outside_share": shares.get("outside"),
        "admit_ms": admit_ms(red),
        "decision_share_of_decode": decision_share_of_decode(red),
        "scope_shares": scope_shares(red),
        "idle_by_phase_s": idle_by_phase(red) if red.get("spans") else None,
        "phases": len(red.get("spans", [])),
        "engine_steps": steps,
    }


# -- the tool ------------------------------------------------------------------
def decode_hlo(eng) -> str:
    """The compiled HLO of the engine's decode program at its current
    operands (the persistent cache gives back the executable that ran)."""
    import jax.numpy as jnp
    import numpy as np
    B = eng.ecfg.max_batch
    return eng._decode_jit.lower(
        eng.params, eng.cache, eng.pstate, eng.last_tokens,
        eng._sp.as_params(), eng._sp.bias_array(),
        jnp.asarray(eng._nonce.copy()), jnp.asarray(eng._pos.copy()),
        jnp.asarray(0, jnp.int32), jnp.asarray(np.ones((B,), bool))
    ).compile().as_text()


def install(state: dict) -> None:
    """Keep what a traced run's own reduction drops: the phases (before
    ``trace.load`` removes the trace) and the decode program's scopes
    (when the engine closes, before it is freed)."""
    import repro.engine
    load = trace.load

    def keeping_load(log_dir, keep=None):
        files = sorted(Path(log_dir).rglob("*.xplane.pb"))
        state["spans"] = read_spans(files[-1]) if files else []
        state["red"] = load(log_dir, keep=keep)
        return state["red"]

    class Engine(repro.engine.Engine):
        def close(self):
            if not getattr(self, "_closed", True):
                t = time.perf_counter()
                try:
                    state["scopes"] = {DECODE: hlo_scopes(decode_hlo(self))}
                    harness.say(
                        f"[phases] decode HLO: {len(state['scopes'][DECODE])}"
                        f" scoped instructions in "
                        f"{time.perf_counter() - t:.3f} s")
                except Exception as e:     # the run's own result stands
                    harness.say(f"[phases] no decode HLO: {e!r}")
            super().close()

    trace.load = keeping_load
    repro.engine.Engine = Engine


def cut_sample(raw: dict, spans: List[list], scopes: dict) -> dict:
    """A raw sample (``trace.save_sample``'s form) cut to ``SAMPLE_S``
    around the window's first admission, with its phases and the scopes
    of the ops it holds."""
    w0, w1 = next((s, s + d) for n, s, d in raw["host"]
                  if n == trace.WINDOW)
    first = min((s for n, s, d in spans if n == "obs.prefill" and s >= w0),
                default=w0 + SAMPLE_S[0] * 1e9)
    c0 = max(w0, first - SAMPLE_S[0] * 1e9)
    c1 = min(w1, first + SAMPLE_S[1] * 1e9)
    inside = lambda evs: [e for e in evs if c0 <= e[1] < c1]
    ops = {dev["name"]: inside(dev["ops"]) for dev in raw["devices"]}
    held = {o[0] for v in ops.values() for o in v}
    return {
        "host": [[trace.WINDOW, c0, c1 - c0]] + [
            h for h in inside(raw["host"]) if h[0] != trace.WINDOW],
        "devices": [{"name": d["name"], "modules": [
            m for m in d["modules"] if m[1] < c1 and m[1] + m[2] > c0],
            "ops": ops[d["name"]]} for d in raw["devices"]],
        "spans": [s for s in spans if s[1] < c1 and s[1] + s[2] > c0],
        "scopes": {p: {o: v for o, v in m.items() if o in held}
                   for p, m in scopes.items()},
    }


def main(argv=None) -> int:
    from benchmarks.chip.run import context
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--keep", default="")
    args = ap.parse_args(argv)
    bench = harness.benchmark()
    w = harness.cell(bench, args.workload)
    try:
        devices = harness.chips(w["chips"])
    except (harness.NoChip, KeyError) as e:
        harness.say(f"phases: {e}")
        return 2
    harness.use_compile_cache()
    state: dict = {}
    install(state)
    out = open(args.out, "a") if args.out else None
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = context(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds,
            trace=1), devices, bench, t0)
        tmp = None
        if args.keep and k == 0:
            tmp = Path(tempfile.mkdtemp()) / "raw.json.gz"
            ctx.keep_trace = tmp
            trace.KEEP_S = args.seconds     # the whole traced window
        result, checks = harness.entry(ctx.traffic["entry"]).run(ctx)
        red = with_phases(state.pop("red"), state.pop("spans"),
                          state.pop("scopes", {}))
        line = {"cell": args.workload, "seed": seed,
                "correct": result["correct"],
                "metrics": {k: v["value"]
                            for k, v in result["metrics"].items()},
                "readings": readings(red)}
        r, idle = line["readings"], line["metrics"].get("device_idle_share")
        if r["idle_in_admit_share"] is not None and idle is not None:
            harness.say(
                f"[phases] seed {seed}: device idle {idle:.4f}% = admit "
                f"{r['idle_in_admit_share']:.4f}% + step host "
                f"{r['idle_in_step_host_share']:.4f}% + outside any phase "
                f"{r['idle_outside_share']:.4f}% (remainder "
                f"{idle - r['idle_in_admit_share'] - r['idle_in_step_host_share']:.4f}%)")
        if tmp is not None:
            with gzip.open(tmp, "rt") as f:
                raw = json.load(f)
            with gzip.open(args.keep, "wt") as f:
                json.dump(cut_sample(raw, red["spans"], red["scopes"]), f)
            tmp.unlink()
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
        del result, red
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
