"""Chip smoke: drive the serving path once on a TPU at full width.

    python chip_smoke.py                # one chip: six phases
    python chip_smoke.py --four-chips   # four chips: replica fleets only

The model is smollm-360m at its published widths (32 layers, d_model 960,
15/5 heads, d_ff 2560, vocab 49152, bf16) with random weights from a fixed
seed. Requests go in through the entry points a user calls:
``Engine.generate()``, and ``GatewayServer`` over a ``ReplicaFleet`` built
by ``repro.launch.serve``. One-chip phases:

* ``device``  — the default ``shvs`` backend, overlapped loop, contiguous
  cache; greedy and seeded requests under a full sampling contract. Every
  request ends by length, a logit-biased-away token never appears, and a
  second run gives the same streams.
* ``host``    — ``sampler_mode="host"``: the pool's state is on a CPU
  device and its greedy streams equal the device phase's.
* ``prefill`` — the requests' prompts through three prefill programs
  (one monolithic group, each prompt alone, chunks of ``prompt_chunk``):
  their last-position logits agree to float32 rounding with the model in
  float32, and the bfloat16 gaps are printed beside bfloat16's own
  rounding. This is why the next phase compares like with like.
* ``paged``   — paged cache with chunked prefill: greedy streams equal
  the contiguous cache's with the same chunked prefill.
* ``fused``   — the single-pass Pallas kernel, compiled (``tpu_custom_call``
  in the decode program): greedy streams equal the ``shvs`` ones, and on
  one step of inputs the kernel's decisions equal its oracle's
  (``ref.fused_sample_ref``) under XLA on the same device.
* ``gateway`` — one replica behind ``GatewayServer`` on an ephemeral
  localhost port; seeded HTTP/SSE streams, sent one at a time, equal
  in-process generation of one request per call.

``--four-chips`` runs a 4-replica fleet and a 2 prefill + 2 decode
``--disaggregate`` fleet over localhost HTTP, each against one in-process
engine, and checks that the replicas hold four distinct chips and each
served. Requests go in waves of one per admitting replica. Each phase
prints its facts (device, compile count and seconds, tokens, tok/s —
informational, no claim) on lines of its own; the last line is one JSON
object. Exits nonzero, printing no JSON, when the
default device is not a TPU or any phase fails.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import sys
import time
from dataclasses import dataclass, field, replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import SamplingConfig  # noqa: E402
from repro.engine import Engine, Request  # noqa: E402

ARCH = "smollm-360m"
SEED = 0            # model weights and requests
BANNED = 7          # logit-biased to -1e4 on every request: never sampled
FAVOURED = 11       # a positive bias on the seeded requests


@dataclass(frozen=True)
class Sizes:
    """Request shapes. Few distinct shapes keep compiles bounded: every
    request of a run is admitted in one group, so one prefill program."""

    requests: int = 16
    max_new: int = 32
    max_batch: int = 16
    max_seq: int = 1024
    prompt_lens: tuple = (30, 120, 400)   # buckets 32, 128, 416
    prompt_chunk: int = 128
    gateway_requests: int = 4
    fleet_requests: int = 8
    reduced: bool = False

    @classmethod
    def cpu(cls) -> "Sizes":
        """The same phases at a size the CPU runs in seconds."""
        return cls(requests=6, max_new=8, max_batch=4, max_seq=128,
                   prompt_lens=(6, 20, 40), prompt_chunk=16,
                   gateway_requests=3, fleet_requests=8, reduced=True)


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (a cache hit skips the backend compile)."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.compiles, self.seconds, self.cache_hits)


@dataclass
class Smoke:
    """State shared by the phases: sizes, weights, reference streams."""

    sizes: Sizes
    on_tpu: bool = True
    counter: CompileCounter = field(default_factory=CompileCounter)
    cfg: object = None
    ecfg: object = None
    params: object = None
    device_streams: dict = field(default_factory=dict)
    facts: list = field(default_factory=list)

    def say(self, phase: str, **facts) -> None:
        line = f"[{phase}] " + " ".join(f"{k}={v}" for k, v in facts.items())
        self.facts.append(line)
        print(line, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def device_facts() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def make_requests(ctx: Smoke):
    """Even ids greedy, odd ids seeded with the full sampling contract
    (temperature, top-k, top-p, min-p, three penalties, logit bias)."""
    s = ctx.sizes
    rng = np.random.default_rng(SEED)
    reqs = []
    for i in range(s.requests):
        plen = s.prompt_lens[i % len(s.prompt_lens)] - int(rng.integers(0, 4))
        prompt = rng.integers(1, ctx.cfg.vocab_size, plen).tolist()
        if i % 2 == 0:
            sc = SamplingConfig(greedy=True, logit_bias={BANNED: -1e4})
        else:
            sc = SamplingConfig(
                temperature=0.8, top_k=40, top_p=0.95, min_p=0.02,
                repetition_penalty=1.1, presence_penalty=0.2,
                frequency_penalty=0.1, seed=1000 * SEED + i,
                logit_bias={BANNED: -1e4, FAVOURED: 1.5})
        reqs.append(Request(request_id=i, prompt=prompt,
                            max_new_tokens=s.max_new, sampling=sc))
    return reqs


def generate(eng, reqs):
    """Stream ``reqs`` through ``Engine.generate()``; returns
    (streams, finish reasons, wall seconds)."""
    streams = {r.request_id: [] for r in reqs}
    reasons = {}
    t0 = time.perf_counter()
    for ev in eng.generate(reqs):
        if ev.token is not None:
            streams[ev.request_id].append(ev.token)
        if ev.finish_reason is not None:
            reasons[ev.request_id] = ev.finish_reason
    return streams, reasons, time.perf_counter() - t0


def greedy_ids(ctx: Smoke):
    return [i for i in range(ctx.sizes.requests) if i % 2 == 0]


def seeded_ids(ctx: Smoke):
    return [i for i in range(ctx.sizes.requests) if i % 2 == 1]


def run_phase(ctx: Smoke, phase: str, eng):
    """Two runs of the same requests on ``eng``: the first compiles, the
    second is warm and must repeat it token for token. Checks the finish
    contract and prints the phase's facts; returns the streams."""
    c0 = ctx.counter.snapshot()
    s1, reasons, cold = generate(eng, make_requests(ctx))
    c1 = ctx.counter.snapshot()
    s2, _, warm = generate(eng, make_requests(ctx))
    c2 = ctx.counter.snapshot()
    for rid, toks in s1.items():
        check(reasons.get(rid) == "length" and
              len(toks) == ctx.sizes.max_new,
              f"{phase}: request {rid} ended {reasons.get(rid)!r} after "
              f"{len(toks)} tokens, expected 'length' after "
              f"{ctx.sizes.max_new}")
        check(BANNED not in toks,
              f"{phase}: request {rid} sampled the banned token {BANNED}")
    check(s1 == s2, f"{phase}: a second run with the same seeds gave "
                    "different streams")
    tokens = sum(len(t) for t in s2.values())
    ctx.say(phase, device=jax.devices()[0].device_kind,
            compiles=c1[0] - c0[0], compile_s=f"{c1[1] - c0[1]:.3f}",
            cache_hits=c1[2] - c0[2], warm_compiles=c2[0] - c1[0],
            cold_s=f"{cold:.3f}", tokens=tokens, warm_s=f"{warm:.3f}",
            tok_s=f"{tokens / warm:.1f}")
    return s1


def agreement(ctx: Smoke, streams: dict, ref: dict) -> dict:
    """How many greedy and seeded streams (and seeded tokens) equal
    ``ref``'s."""
    g, sids = greedy_ids(ctx), seeded_ids(ctx)
    same = lambda ids: sum(int(streams[r] == ref[r]) for r in ids)
    toks = sum(int(a == b) for r in sids for a, b in zip(streams[r], ref[r]))
    return {"greedy_equal": f"{same(g)}/{len(g)}",
            "seeded_streams_equal": f"{same(sids)}/{len(sids)}",
            "seeded_tokens_equal":
                f"{toks}/{len(sids) * ctx.sizes.max_new}"}


def compare(ctx: Smoke, phase: str, streams: dict, ref: dict) -> None:
    """Greedy streams must equal ``ref``'s; seeded agreement is printed."""
    for rid in greedy_ids(ctx):
        check(streams[rid] == ref[rid],
              f"{phase}: greedy request {rid} differs from its reference:"
              f"\n  {streams[rid]}\n  {ref[rid]}")
    ctx.say(phase, **agreement(ctx, streams, ref))


def engine_like(ctx: Smoke, **changes) -> Engine:
    return Engine(ctx.cfg, ctx.params, replace(ctx.ecfg, **changes))


def close(eng) -> None:
    eng.close()
    del eng
    gc.collect()


# -- one-chip phases ----------------------------------------------------------
def phase_device(ctx: Smoke) -> None:
    """Builds the engine through ``serve.build_engine``; its weights and
    config seed every later phase."""
    from repro.launch.serve import build_engine
    s = ctx.sizes
    eng = build_engine(ARCH, s.reduced, "shvs", s.max_batch, s.max_seq,
                       seed=SEED, overlap=True)
    ctx.cfg, ctx.ecfg, ctx.params = eng.cfg, eng.ecfg, eng.params
    ctx.device_streams = run_phase(ctx, "device", eng)
    close(eng)


def phase_host(ctx: Smoke) -> None:
    eng = engine_like(ctx, sampler_mode="host")
    streams = run_phase(ctx, "host", eng)
    state_dev = {d.platform for d in eng.pstate.output_counts.devices()}
    pool_dev = eng.client.pool.device.platform
    close(eng)
    ctx.say("host", pool_device=pool_dev,
            state_device=",".join(sorted(state_dev)))
    check(pool_dev == "cpu" and state_dev == {"cpu"},
          f"host: the pool sampled on {pool_dev} with state on {state_dev},"
          " not on the CPU")
    compare(ctx, "host", streams, ctx.device_streams)


def prefill_logits(ctx: Smoke, cfg, params, prompts):
    """Last-position logits (float32 numpy) of ``prompts`` from three
    prefill programs: every prompt in one monolithic group, as the engine
    admits a group; each prompt alone; and every prompt in chunks of
    ``prompt_chunk``, as chunked prefill feeds them."""
    import jax.numpy as jnp
    from repro.models.model import Model
    model = Model(cfg)
    S, C = ctx.sizes.max_seq, ctx.sizes.prompt_chunk
    bucket = ctx.ecfg.prompt_bucket
    lens = np.array([len(p) for p in prompts])

    @jax.jit
    def mono(params, toks, lens):
        cache = model.init_cache(toks.shape[0], S)
        return model.prefill(params, {"tokens": toks}, cache,
                             true_lens=lens)[0]

    def monolithic(rows):
        Sp = -(-max(len(r) for r in rows) // bucket) * bucket
        toks = np.zeros((len(rows), Sp), np.int32)
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
        n = np.array([len(r) for r in rows], np.int32)
        return np.asarray(mono(params, toks, n).astype(jnp.float32))

    chunk = jax.jit(model.prefill_chunk)
    cache = model.init_cache(len(prompts), S)
    chunked = np.zeros((len(prompts), cfg.vocab_size), np.float32)
    for start in range(0, int(lens.max()), C):
        counts = np.clip(lens - start, 0, C).astype(np.int32)
        toks = np.zeros((len(prompts), C), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :counts[i]] = p[start:start + counts[i]]
        logits, cache = chunk(params, toks, cache, counts, counts > 0)
        ends = (counts > 0) & (start + C >= lens)
        chunked[ends] = np.asarray(logits.astype(jnp.float32))[ends]
    return (monolithic(prompts),
            np.concatenate([monolithic([p]) for p in prompts]), chunked)


def phase_prefill(ctx: Smoke) -> None:
    """Where does a prompt's stream depend on its prefill program's shape?
    The requests' prompts go through :func:`prefill_logits` twice: as
    served (bfloat16), and with the model in float32 at ``HIGHEST``
    matmul precision. A defect in the chunk program or in batched
    admission (a wrong mask, position or row) moves logits by their own
    scale in either dtype; rounding moves them by bfloat16's rounding
    (``bf16_vs_f32``) and vanishes in float32. Checked: the float32 gaps
    are below 1e-3 of the logits' scale."""
    import jax.numpy as jnp
    prompts = [r.prompt for r in make_requests(ctx)]
    c0 = ctx.counter.snapshot()
    f32 = lambda x: x.astype(jnp.float32) \
        if jnp.issubdtype(x.dtype, jnp.floating) else x
    served = prefill_logits(ctx, ctx.cfg, ctx.params, prompts)
    with jax.default_matmul_precision("highest"):
        full = prefill_logits(ctx, replace(ctx.cfg, dtype="float32"),
                              jax.tree_util.tree_map(f32, ctx.params),
                              prompts)
    c1 = ctx.counter.snapshot()
    gap = lambda a, b: float(np.max(np.abs(a - b)))
    same_top = lambda a, b: f"{int(np.sum(a.argmax(-1) == b.argmax(-1)))}" \
                            f"/{len(prompts)}"
    for dtype, (batched, solo, chunked) in ((ctx.cfg.dtype, served),
                                             ("float32", full)):
        ctx.say("prefill", dtype=dtype,
                scale=float(np.max(np.abs(batched))),
                chunked_gap=gap(chunked, batched),
                solo_gap=gap(solo, batched),
                chunked_top1_equal=same_top(chunked, batched),
                solo_top1_equal=same_top(solo, batched))
    ctx.say("prefill", bf16_vs_f32=gap(served[0], full[0]),
            compiles=c1[0] - c0[0], compile_s=f"{c1[1] - c0[1]:.3f}")
    batched, solo, chunked = full
    bound = 1e-3 * float(np.max(np.abs(batched)))
    for name, other in (("chunked", chunked), ("solo", solo)):
        check(gap(other, batched) <= bound,
              f"prefill: in float32 the {name} program's logits differ from "
              f"the batched program's by {gap(other, batched)} > {bound}: "
              "more than rounding")


def phase_paged(ctx: Smoke) -> None:
    """Paged KV against the contiguous cache, both with chunked prefill:
    the layout must be invisible in greedy streams. How far chunked
    prefill itself moves streams from the device phase's monolithic
    prefill is printed, not checked: on a TPU a chunk's attention
    reductions have other shapes, and a bf16 argmax can flip."""
    chunk = ctx.sizes.prompt_chunk
    eng = engine_like(ctx, prompt_chunk=chunk)
    chunked = run_phase(ctx, "chunked", eng)
    close(eng)
    ctx.say("chunked", vs="monolithic",
            **agreement(ctx, chunked, ctx.device_streams))
    eng = engine_like(ctx, cache="paged", prompt_chunk=chunk)
    streams = run_phase(ctx, "paged", eng)
    close(eng)
    compare(ctx, "paged", streams, chunked)


def decode_hlo(eng) -> str:
    """The engine's fused decode program as lowered for its device."""
    import jax.numpy as jnp
    B = eng.ecfg.max_batch
    return eng._decode_jit.lower(
        eng.params, eng.cache, eng.pstate, eng.last_tokens,
        eng._sp.as_params(), eng._sp.bias_array(),
        jnp.zeros((B,), jnp.uint32), jnp.zeros((B,), jnp.int32),
        jnp.asarray(0, jnp.int32), jnp.ones((B,), bool)).as_text()


def fused_vs_oracle(ctx: Smoke, eng) -> None:
    """One decode step of every request contract (seeded, greedy, logit
    bias) at four output positions, decided by the kernel the engine
    runs and by its oracle ``ref.fused_sample_ref`` under XLA, on the
    same device and inputs: random logits, the prompts' histograms plus
    random output counts, the rows' own uniforms. Seeded rows exercise
    what greedy ones skip — penalties, the uniforms' casts, the prefix
    sum and the draw. Checked: every token, ``exact`` and ``kept`` equal."""
    import functools
    import jax.numpy as jnp
    from repro.engine.engine import SlotParams
    from repro.kernels import ops, ref
    reqs, n = make_requests(ctx), 4
    rows = [r for r in reqs for _ in range(n)]
    B, V = len(rows), ctx.cfg.vocab_size
    sp = SlotParams(B, V)
    for i, r in enumerate(rows):
        sp.set_row(i, r.sampling)
    p = sp.as_params()
    rng = np.random.default_rng(SEED + 2)
    z = jnp.asarray(rng.normal(0, 4, (B, V)).astype(np.float32)) \
        + sp.bias_array()
    cp = np.zeros((B, V), np.int32)
    for i, r in enumerate(rows):
        np.add.at(cp[i], r.prompt, 1)
    co = rng.integers(0, 3, (B, V)).astype(np.int32)
    pos = np.tile(np.arange(n, dtype=np.int32), len(reqs))
    rids = np.array([r.request_id for r in rows], np.uint32)
    u = eng.decision.uniforms_tagged(rids, pos, p.seed, p.use_seed)[:, 1]
    hot, k_cap = eng.decision.hot_set.mask, eng.decision.k_cap
    kernel = jax.jit(functools.partial(ops.fused_sample, k_cap=k_cap))
    got = kernel(z, cp, co, p.strip_rng(), u, hot)
    want = ref.fused_sample_ref(
        z, cp, co, p.repetition_penalty, p.presence_penalty,
        p.frequency_penalty, p.temperature, p.top_k, p.top_p, p.min_p, u,
        hot, k_cap=k_cap, block_v=2048)
    got, want = ([np.asarray(x) for x in out] for out in (got, want))
    seeded = np.array([r.sampling.seed is not None for r in rows])
    eq = [g == w for g, w in zip(got, want)]
    ctx.say("fused", vs="oracle", tokens_equal=f"{eq[0].sum()}/{B}",
            seeded_tokens_equal=f"{eq[0][seeded].sum()}/{seeded.sum()}",
            exact_equal=f"{eq[1].sum()}/{B}", kept_equal=f"{eq[3].sum()}/{B}",
            alpha_max_gap=float(np.max(np.abs(got[2] - want[2]))))
    for name, e in zip(("tokens", "exact", "kept"), (eq[0], eq[1], eq[3])):
        check(e.all(), f"fused: the kernel's {name} differ from the "
                       f"oracle's in rows {np.flatnonzero(~e).tolist()}")


def phase_fused(ctx: Smoke) -> None:
    eng = engine_like(ctx, algorithm="fused")
    streams = run_phase(ctx, "fused", eng)
    kernel = "tpu_custom_call" in decode_hlo(eng)
    ctx.say("fused", tpu_custom_call=kernel)
    try:
        if ctx.on_tpu:
            check(kernel, "fused: no tpu_custom_call in the decode program "
                          "— the kernel did not compile into it")
        compare(ctx, "fused", streams, ctx.device_streams)
        fused_vs_oracle(ctx, eng)
    finally:
        close(eng)


def gateway_payloads(ctx: Smoke, n: int):
    """Seeded completions as the gateway receives them: raw token-id
    prompts of one length, so they share a prefill bucket."""
    rng = np.random.default_rng(SEED + 1)
    plen = ctx.sizes.prompt_lens[0]
    return [{"prompt": rng.integers(1, ctx.cfg.vocab_size, plen).tolist(),
             "max_tokens": ctx.sizes.max_new, "temperature": 0.8,
             "top_k": 40, "top_p": 0.95, "min_p": 0.02,
             "repetition_penalty": 1.1, "presence_penalty": 0.2,
             "frequency_penalty": 0.1, "seed": 5000 + i,
             "session_id": f"smoke-{i}"} for i in range(n)]


def reference_streams(eng, payloads):
    """In-process ``Engine.generate()`` on the requests the gateway builds
    from ``payloads`` (``GatewayServer._build_request``): one call per
    request, as the waves of :func:`wire_streams` admit them."""
    reqs = [Request(
        request_id=900 + i, prompt=list(p["prompt"]),
        max_new_tokens=p["max_tokens"],
        sampling=SamplingConfig(
            temperature=p["temperature"], top_k=p["top_k"],
            top_p=p["top_p"], min_p=p["min_p"],
            repetition_penalty=p["repetition_penalty"],
            presence_penalty=p["presence_penalty"],
            frequency_penalty=p["frequency_penalty"], seed=p["seed"]))
        for i, p in enumerate(payloads)]
    streams = {}
    for r in reqs:
        streams.update(generate(eng, [r])[0])
    return [streams[900 + i] for i in range(len(payloads))]


async def wire_streams(fleet, payloads, wave: int):
    """Serve ``fleet`` on an ephemeral localhost port and send ``payloads``
    over HTTP/SSE in concurrent waves of ``wave`` (the number of replicas
    that admit prompts): least-loaded routing gives each replica one
    request per wave, so every prefill runs alone, as in the reference
    (``[prefill]`` shows why: on a TPU the size of an admission group
    moves bfloat16 logits by rounding). Returns the streams."""
    from repro.gateway.client import stream_completion
    from repro.gateway.http import GatewayServer
    gw = GatewayServer(fleet)
    await gw.serve(port=0)
    try:
        results = []
        for k in range(0, len(payloads), wave):
            results += await asyncio.gather(*[
                stream_completion(gw.host, gw.port, p, timeout=600.0)
                for p in payloads[k:k + wave]])
    finally:
        await gw.shutdown()
    for p, res in zip(payloads, results):
        check(res.status == 200 and res.error is None,
              f"HTTP {res.status} for session {p['session_id']}: "
              f"{res.error}")
    return [res.tokens for res in results]


def phase_gateway(ctx: Smoke) -> None:
    """The reference is a fresh engine, like the fleet's: neither has
    served a ``logit_bias`` request, so both run the decode program
    without the bias add."""
    from repro.gateway.fleet import ReplicaFleet
    payloads = gateway_payloads(ctx, ctx.sizes.gateway_requests)
    ref_engine = engine_like(ctx)
    ref = reference_streams(ref_engine, payloads)
    close(ref_engine)
    c0 = ctx.counter.snapshot()
    t0 = time.perf_counter()
    fleet = ReplicaFleet([engine_like(ctx)], capacity=16)
    wire = asyncio.run(wire_streams(fleet, payloads, wave=1))
    dt = time.perf_counter() - t0
    c1 = ctx.counter.snapshot()
    n = len(wire)
    for i, (w, r) in enumerate(zip(wire, ref)):
        check(w == r, f"gateway: wire stream {i} differs from in-process "
                      f"generation:\n  {w}\n  {r}")
    tokens = sum(len(w) for w in wire)
    ctx.say("gateway", device=jax.devices()[0].device_kind,
            compiles=c1[0] - c0[0], compile_s=f"{c1[1] - c0[1]:.3f}",
            streams_equal=f"{n}/{n}", tokens=tokens,
            wall_s=f"{dt:.3f}", tok_s=f"{tokens / dt:.1f}")


def one_chip(ctx: Smoke) -> None:
    phase_device(ctx)
    phase_host(ctx)
    phase_prefill(ctx)
    phase_paged(ctx)
    phase_fused(ctx)
    phase_gateway(ctx)


# -- four chips ---------------------------------------------------------------
def fleet_args(ctx: Smoke, *flags):
    from repro.launch.serve import build_parser
    s = ctx.sizes
    argv = ["--arch", ARCH, "--batch", str(s.max_batch),
            "--max-seq", str(s.max_seq), "--replicas", "4"]
    if s.reduced:
        argv.append("--reduced")
    return build_parser().parse_args(argv + list(flags))


def phase_fleet(ctx: Smoke, name: str, args, payloads, ref) -> None:
    from repro.launch.serve import build_fleet
    c0 = ctx.counter.snapshot()
    t0 = time.perf_counter()
    fleet = build_fleet(args)
    devices = [r.engine.device for r in fleet.replicas]
    admitting = fleet.prefill_replicas if args.disaggregate \
        else fleet.replicas
    wire = asyncio.run(wire_streams(fleet, payloads, wave=len(admitting)))
    dt = time.perf_counter() - t0
    c1 = ctx.counter.snapshot()
    work = [r.served + r.handed_off for r in fleet.replicas]
    ctx.say(name, devices=",".join(str(d.id) for d in devices),
            roles=",".join(r.role for r in fleet.replicas),
            served=",".join(str(r.served) for r in fleet.replicas),
            handed_off=",".join(str(r.handed_off) for r in fleet.replicas),
            compiles=c1[0] - c0[0], compile_s=f"{c1[1] - c0[1]:.3f}",
            wall_s=f"{dt:.3f}")
    check(len(set(devices)) == 4,
          f"{name}: replicas share devices: {[d.id for d in devices]}")
    check(all(w >= 1 for w in work),
          f"{name}: a replica served no request: {work}")
    if args.disaggregate:
        check(sum(r.handed_off for r in fleet.prefill_replicas) > 0,
              f"{name}: no request migrated prefill -> decode")
    for i, (w, r) in enumerate(zip(wire, ref)):
        check(w == r, f"{name}: wire stream {i} differs from the "
                      f"in-process engine:\n  {w}\n  {r}")
    ctx.say(name, streams_equal=f"{len(wire)}/{len(wire)}")


def four_chips(ctx: Smoke) -> None:
    from repro.launch.serve import build_engine
    check(len(jax.devices()) >= 4,
          f"--four-chips needs four devices, found {len(jax.devices())}")
    s = ctx.sizes
    ref_eng = build_engine(ARCH, s.reduced, "shvs", s.max_batch, s.max_seq,
                           seed=SEED)
    ctx.cfg = ref_eng.cfg
    payloads = gateway_payloads(ctx, s.fleet_requests)
    ref = reference_streams(ref_eng, payloads)
    close(ref_eng)
    phase_fleet(ctx, "fleet", fleet_args(ctx), payloads, ref)
    gc.collect()
    phase_fleet(ctx, "disaggregated",
                fleet_args(ctx, "--disaggregate", "--cache", "paged"),
                payloads, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip replica-fleet phase")
    args = ap.parse_args(argv)
    dev = device_facts()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: the default device is {dev['platform']}, not a "
              "TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    ctx = Smoke(Sizes())
    print(f"[setup] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} arch={ARCH}", flush=True)
    if args.four_chips:
        four_chips(ctx)
    else:
        one_chip(ctx)
    print(json.dumps({"ok": True, "device": device_facts()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
