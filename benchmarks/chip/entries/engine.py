"""Entry ``engine``: load reaches ``Engine.submit`` / ``Engine.step`` in
process, as ``Engine.generate`` drives them, on one chip.

Set-up, all counted in ``setup_s``: the weights (one jitted call from the
seed), the engine with the mix's ``engine`` settings, one admission group
of every size up to the mix's ``warm_max_group`` at every prompt bucket the
mix reaches (so every prefill program, the per-group decision ops and the
decode program are built), and ``warmup_s`` of the mix itself, so the
window opens on a loop in steady state.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

from benchmarks.chip import check, harness, loadgen, loop, trace, window
from benchmarks.chip.weights import dims, make_weights

TRACE_SECONDS = 4.0   # traced part of a --trace 1 window (a trace of a
#                       32-layer decode holds ~230k device ops a second)


@dataclass
class Readings:
    """What the per-layer readers (``metrics/<name>.py``) may read."""

    cfg: dict
    peaks: object
    batch: int
    red: Optional[dict]          # reduced trace of the traced window
    steps: list                  # loop.StepLog inside the traced window
    records: list                # the engine's StepRecords inside it
    compiles: int                # backend compiles inside it


class GcPauses:
    """Python's garbage-collector pauses while the window is open."""

    def __init__(self):
        self.pauses = []          # (generation, seconds)
        self._t0 = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def start(self):
        gc.callbacks.append(self._cb)

    def stop(self):
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)

    def __str__(self):
        full = [d for g, d in self.pauses if g == 2]
        total = sum(d for _, d in self.pauses)
        return (f"gc: {len(self.pauses)} collections ({len(full)} full), "
                f"{total * 1e3:.3f} ms in all, longest "
                f"{max([d for _, d in self.pauses] or [0]) * 1e3:.3f} ms")


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.config import ModelConfig
    g = dims(cfg)
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"unsupported activation {cfg['hidden_act']!r}")
    return ModelConfig(
        name=f"bench-{cfg['model_type']}", family="dense", num_layers=g["L"],
        d_model=g["d"], num_heads=g["H"], num_kv_heads=g["kv"], d_ff=g["f"],
        vocab_size=g["V"], head_dim=g["hd"], qk_norm=g["qk_norm"],
        rope_theta=float(cfg["rope_theta"]),
        rmsnorm_eps=float(cfg["rms_norm_eps"]), tie_embeddings=g["tied"],
        act="silu", dtype=cfg["serve_dtype"])


def check_layout(params, mcfg) -> None:
    """The weights must have exactly the tree, shapes and types the
    program's own initializer would build."""
    from repro.models.model import Model
    want = jax.eval_shape(Model(mcfg).init, jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(got) or \
            jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(got):
        raise RuntimeError("the benchmark's weights do not match the "
                           "program's parameter layout")


def sampling(spec):
    from repro.config import SamplingConfig
    return SamplingConfig(
        temperature=spec.temperature, top_k=spec.top_k, top_p=spec.top_p,
        min_p=spec.min_p, repetition_penalty=spec.repetition,
        presence_penalty=spec.presence, frequency_penalty=spec.frequency,
        seed=spec.seed, greedy=spec.greedy, logit_bias=dict(spec.bias))


def make_request(spec, due: float):
    from repro.engine import Request
    return Request(request_id=spec.rid, prompt=list(spec.prompt),
                   max_new_tokens=spec.max_new, sampling=sampling(spec),
                   arrival_time=due)


def warm_shapes(eng, traffic: dict, vocab: int, seed: int) -> int:
    """Admit one group of every size up to ``warm_max_group`` at every
    prompt bucket the mix reaches, and where the mix has logit biases one
    more group of each size whose first request carries one (the bias adds
    an operand to the admission's decision step). Each request is served
    one token, so no decode runs, but for the first group of one request
    and the biased one, served two: they build the decode program without
    and with the bias operand. Returns the groups run."""
    ecfg = eng.ecfg
    buckets = loadgen.reachable_prompt_buckets(traffic, ecfg.prompt_bucket,
                                               ecfg.max_seq_len)
    has_bias = traffic["contract"].get("logit_bias", {}).get("share", 0) > 0
    proto = next(loadgen.make_run(traffic, vocab, 0.0, seed))
    rng = np.random.default_rng([seed, 2])
    rid = 4_000_000_000      # request ids are uint32 in the engine
    groups = 0
    for P in range(1, traffic["warm_max_group"] + 1):
        shapes = [(Sp, False) for Sp in buckets]
        if has_bias:
            shapes.append((buckets[0], True))
        for j, (Sp, bias) in enumerate(shapes):
            reqs = []
            for i in range(P):
                spec = loadgen.Spec(
                    rid=rid, prompt=loadgen.zipf_tokens(
                        vocab, traffic["prompt_token_zipf"], Sp, rng).tolist(),
                    max_new=2 if P == 1 and (j == 0 or bias) else 1,
                    greedy=False, temperature=proto.temperature,
                    top_k=proto.top_k, top_p=proto.top_p, min_p=proto.min_p,
                    repetition=proto.repetition, presence=proto.presence,
                    frequency=proto.frequency, seed=rid,
                    bias=((1, 1.0),) if bias and i == 0 else ())
                reqs.append(make_request(spec, 0.0))
                rid += 1
            eng.submit(reqs)
            eng.run()
            groups += 1
    eng.scheduler.finished.clear()
    return groups


def group_sizes(steps) -> str:
    """``size:count`` of the admission groups in ``steps``."""
    sizes = np.bincount([len(s.admitted_prompts) for s in steps
                         if s.admitted_prompts] or [0])
    return " ".join(f"{p}:{n}" for p, n in enumerate(sizes) if n and p)


def run(ctx: harness.Ctx):
    """One run of the cell. Returns ``(result, checks)``."""
    from repro.engine import Engine, EngineConfig
    from benchmarks.chip.peaks import peaks_for

    cfg, traffic = ctx.cfg, ctx.traffic
    devs = ctx.devices
    counter = loop.CompileCounter()
    peaks = peaks_for(devs[0].device_kind) if devs[0].platform == "tpu" \
        else None
    mcfg = model_config(cfg)
    V = cfg["vocab_size"]
    with jax.default_device(devs[0]):
        params = jax.block_until_ready(make_weights(cfg, ctx.seed))
        check_layout(params, mcfg)
        eng = Engine(mcfg, params, EngineConfig(**traffic["engine"]))
        groups = warm_shapes(eng, traffic, V, ctx.seed)
        harness.say(f"[setup] weights+engine+{groups} admission groups: "
                    f"{time.perf_counter() - ctx.t_start:.3f} s, "
                    f"{counter.compiles} compiles ({counter.seconds:.3f} s), "
                    f"{counter.cache_hits} cache hits")
        specs = loadgen.make_run(traffic, V, ctx.seconds, ctx.seed)
        t_open = time.perf_counter() + traffic["warmup_s"]
        t_close = t_open + ctx.seconds
        t_trace = t_open + min(TRACE_SECONDS, ctx.seconds)
        at = {}
        tdir = harness.TRACE_DIR / f"{ctx.cell}-{ctx.seed}"
        pauses = GcPauses()

        def opened():
            pauses.start()
            at["open"] = (counter.compiles, eng.scheduler.step)
            if ctx.trace:
                trace.start(tdir)
                at["ann"] = jax.profiler.TraceAnnotation(trace.WINDOW)
                at["ann"].__enter__()

        def trace_end():
            at["trace_end"] = (counter.compiles, eng.scheduler.step)
            if ctx.trace:
                at["ann"].__exit__(None, None, None)
                trace.stop()

        d = loop.drive(eng, specs, make_request, traffic, t_open, t_close,
                       marks=[(t_open, opened), (t_trace, trace_end)],
                       stall_dump_s=ctx.stall_dump_s)
        at["close"] = (counter.compiles, eng.scheduler.step)
        pauses.stop()
        eng.flush()
        peak = harness.peak_memory(devs)
        e2e = window.end_to_end(d.sent, t_open, t_close)
        setup_s = t_open - ctx.t_start
        late = np.asarray(d.lateness or [0.0]) * 1e3
        inside = [s for s in d.steps if t_open <= s.t_end <= t_close]
        t_steps = np.diff([s.t_end for s in inside])
        harness.say(
            f"[window] {ctx.seconds} s: {e2e['n_due']} requests due, "
            f"{e2e['tokens']} tokens, {e2e['n_gaps']} gaps (output_tok_s "
            f"{e2e['output_tok_s']}, ttft_p95_ms {e2e['ttft_p95_ms']}, "
            f"itl_p95_ms {e2e['itl_p95_ms']}), "
            f"{at['close'][0] - at['open'][0]} compiles inside; generator "
            f"late p50 {np.percentile(late, 50):.3f} ms, p99 "
            f"{np.percentile(late, 99):.3f} ms, max {late.max():.3f} ms; "
            f"longest step {1e3 * t_steps.max(initial=0.0):.3f} ms; "
            f"admission group sizes (size:count) {group_sizes(inside)}, "
            f"in the warm-up traffic {group_sizes(d.steps[:-len(inside)])}; "
            f"peak_bytes_in_use {peak}; {pauses}")
        due_in = [s for s in d.sent if t_open <= s.due <= t_close]
        failed = sum(1 for s in due_in
                     if s.request.finish_reason not in (None, "length"))
        rng = np.random.default_rng([ctx.seed, 3])
        samples = [{"prompt": list(s.request.prompt),
                    "output": list(s.request.output), "spec": s.spec}
                   for greedy in (True, False)
                   for s in check.pick(d.sent, rng, traffic["check_tokens"],
                                       greedy)]
        result = {"correct": False, "attempted": e2e["n_due"],
                  "failed": failed}
        device = harness.device_facts(devs, peak)
        if ctx.trace:
            red = trace.load(tdir, keep=ctx.keep_trace)
            rec = [r for r in eng.stats_log
                   if at["open"][1] <= r.step < at["trace_end"][1]]
            rd = Readings(cfg=cfg, peaks=peaks,
                          batch=eng.ecfg.max_batch, red=red,
                          steps=[s for s in d.steps
                                 if t_open <= s.t_end <= t_trace],
                          records=rec,
                          compiles=at["trace_end"][0] - at["open"][0])
            metrics = {}
            for m in ctx.per_layer:
                v = harness.metric_reader(m["name"])(rd)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device["busy_s"] = trace.busy_s(red)
            device["window_s"] = trace.window_s(red)
            result["breakdown"] = trace.breakdown(red)
        else:
            values = dict(e2e, setup_s=setup_s)
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in ctx.end_to_end}
        eng.close()
        del eng, d
        gc.collect()
        t_chk = time.perf_counter()
        res = check.run(params, cfg, samples,
                        pad_to=traffic["engine"]["max_seq_len"],
                        control=ctx.control)
    harness.say(f"[check] {len(samples)} requests, {res['tokens']} tokens "
                f"({res['near_ties']} with the reference's top two within "
                f"0.5) vs the float32 reference in "
                f"{time.perf_counter() - t_chk:.3f} s")
    checks = {name: {"value": res[name],
                     "limit": ctx.limits.get(name, {}).get("limit")}
              for name in check.NUMBERS}
    if ctx.control:
        for name in check.NUMBERS:
            checks["control_" + name] = dict(
                checks[name], value=res["control_" + name])
        harness.say("[control] correct: "
                    f"{check.decide(res, ctx.limits, 'control_')}")
    result["correct"] = bool(check.decide(res, ctx.limits) and failed == 0
                             and e2e["n_due"] > 0)
    result["metrics"] = metrics
    result["device"] = device
    return result, checks
