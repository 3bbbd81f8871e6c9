"""Serving driver: run the engine end-to-end on a real (CPU) device.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
        --requests 16 --max-new 24

The driver is a plain client of the decision-plane service API (DESIGN.md
§11): it streams tokens through ``Engine.generate()`` — events fire as
tokens *commit*, one step behind dispatch under the overlapped loop — and
reports each request's ``finish_reason`` at the end.

Engine execution mode (DESIGN.md §2/§8/§9/§12):

    --overlap / --no-overlap    double-buffered vs synchronous iteration loop
    --prompt-chunk N            chunked prefill width (0 = monolithic)
    --long-prompts              synthesize a long-prompt-heavy workload
    --cache paged               block-pool KV cache (vLLM-style paging)
    --block-size N              tokens per KV block (paged)
    --num-blocks N              pool size; 0 = memory-equal to contiguous
    --stages P                  pipeline-parallel stages; P>1 runs the
                                microbatched PipelineEngine (DESIGN.md §12)
    --microbatches M            microbatches in flight (0 = P); batch % M = 0
    --samplers M                host sampler pool workers (pipeline)
    --sampler-mode MODE         host (CPU sampler pool; the pipeline's
                                default) or device (on the accelerator; sync
                                on the last stage under --stages, Eq. 4);
                                adaptive = §15 controller switches placement
                                and pool size online from the stat streams

Per-request sampling contract (DESIGN.md §11):

    --algorithm NAME            any registered sampler backend (e.g.
                                ``fused`` = the single-pass kernel, §14)
    --seed N                    per-request sampling seeds (request i gets
                                N+i; streams are pure functions of the seed)
    --greedy                    argmax decoding for every request
    --stop 5,9 [--stop 7]       token-level stop sequences (repeatable)

Gateway mode (DESIGN.md §16) serves over HTTP/SSE instead of running a
synthetic batch — every engine flag above still shapes the replicas:

    PYTHONPATH=src python -m repro.launch.serve --gateway --replicas 2 \
        --arch smollm-360m --reduced
    curl -N localhost:8100/v1/completions -d \
        '{"prompt": "the quick brown fox", "max_tokens": 16, "seed": 7,
          "stream": true}'

    --gateway                   serve an OpenAI-style completions endpoint
                                over a replica fleet (Ctrl-C drains)
    --replicas N                engine replicas (identical params: every
                                replica is built from the same model seed)
    --disaggregate              split the fleet into prefill-role and
                                decode-role replicas (DESIGN.md §18):
                                prompts prefill on one instance and
                                migrate their paged-KV state to a decode
                                instance at the first committed token
    --prefill-replicas N        prefill-role replicas (--disaggregate)
    --decode-replicas N         decode-role replicas (--disaggregate)
    --http-host / --http-port   bind address (default 127.0.0.1:8100)
    --capacity N                per-replica open-request bound; beyond it
                                admissions answer 429 + Retry-After
    --codec NAME                registered text codec (default 'byte')
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.config import ARCH_IDS, SamplingConfig, SHVSConfig, get_arch
from repro.core.sampler_backend import registered_backends
from repro.engine import Engine, PipelineConfig, PipelineEngine, Request
from repro.engine.engine import EngineConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.obs import StepTracer, Telemetry, write_chrome_trace


def build_engine(arch: str, reduced: bool, algorithm: str, batch: int,
                 max_seq: int, seed: int = 0, overlap: bool = True,
                 prompt_chunk: int = 0, cache: str = "contiguous",
                 block_size: int = 16, num_blocks: int = 0,
                 stages: int = 1, microbatches: int = 0, samplers: int = 2,
                 sampler_mode: str = None, telemetry: Telemetry = None,
                 device=None):
    """``device``: where the engine lives (its parameters are made and
    committed there, and the engine follows them); default device if
    None."""
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    with jax.default_device(device):
        params = jax.device_put(model.init(jax.random.PRNGKey(seed)), device)
    common = dict(max_batch=batch, max_seq_len=max_seq,
                  algorithm=algorithm,
                  shvs=SHVSConfig(hot_size=min(1024, cfg.vocab_size // 4)),
                  k_cap=min(256, cfg.vocab_size), seed=seed,
                  cache=cache, block_size=block_size,
                  num_blocks=num_blocks, samplers=samplers)
    if stages > 1 or microbatches:
        if prompt_chunk:
            raise ValueError(
                "--prompt-chunk is not supported with --stages/"
                "--microbatches: the pipeline engine prefills prompts "
                "monolithically (DESIGN.md §12)")
        ecfg = PipelineConfig(stages=stages, microbatches=microbatches,
                              sampler_mode=sampler_mode or "host",
                              **common)
        return PipelineEngine(cfg, params, ecfg, telemetry=telemetry)
    # single-stage default stays "device" (the §2 fused overlap loop);
    # "host" disaggregates the decode-step sampling to the CPU pool (§13)
    ecfg = EngineConfig(overlap=overlap, prompt_chunk=prompt_chunk,
                        sampler_mode=sampler_mode or "device", **common)
    return Engine(cfg, params, ecfg, telemetry=telemetry)


def _trace_telemetry(trace_out: str) -> Telemetry:
    """A telemetry bundle with the flight recorder ON — only built when
    --trace-out asks for a trace, so default runs pay nothing."""
    return Telemetry(tracer=StepTracer(capacity=65536, enabled=True)) \
        if trace_out else None


def synth_requests(n: int, vocab: int, max_new: int, rng_seed: int = 0,
                   long_prompts: bool = False, seed=None, greedy: bool = False,
                   stop_sequences=()):
    rng = np.random.default_rng(rng_seed)
    reqs = []
    for i in range(n):
        if long_prompts and i % 4 == 0:
            plen = int(rng.integers(96, 192))
        else:
            plen = int(rng.integers(4, 24))
        reqs.append(Request(
            request_id=i,
            prompt=rng.integers(1, vocab, plen).tolist(),
            max_new_tokens=max_new,
            sampling=SamplingConfig(temperature=0.8, top_k=40, top_p=0.95,
                                    repetition_penalty=1.1,
                                    seed=None if seed is None else seed + i,
                                    greedy=greedy,
                                    stop_sequences=tuple(stop_sequences)),
        ))
    return reqs


def build_fleet(args):
    """N identically-parameterized replicas (same model seed → the same
    weights, so seeded streams match across replicas) wrapped in a
    :class:`~repro.gateway.fleet.ReplicaFleet`. Replica *i* lives on
    ``jax.devices()[i % n]``: on a four-chip host, four replicas hold
    four chips.

    With ``--disaggregate`` the fleet is P prefill-role + D decode-role
    replicas (DESIGN.md §18): ``GatewayServer`` builds its router via
    ``Router.for_fleet``, which installs the decode-placement hook on
    every prefill replica, so each admitted prompt prefills on one
    instance and carries its KV state to a decode instance at the first
    committed token."""
    from repro.gateway import ReplicaFleet
    roles = None
    if args.disaggregate:
        if args.stages > 1 or args.microbatches:
            raise ValueError(
                "--disaggregate needs single-stage engines: the pipeline "
                "engine shards its KV cache per stage and has no "
                "migration seam (DESIGN.md §18)")
        n_prefill = args.prefill_replicas or max(1, args.replicas // 2)
        n_decode = args.decode_replicas or max(1, args.replicas - n_prefill)
        roles = ["prefill"] * n_prefill + ["decode"] * n_decode
    n = len(roles) if roles else args.replicas
    devices = jax.devices()
    engines = [
        build_engine(args.arch, args.reduced, args.algorithm, args.batch,
                     args.max_seq, overlap=args.overlap,
                     prompt_chunk=args.prompt_chunk, cache=args.cache,
                     block_size=args.block_size, num_blocks=args.num_blocks,
                     stages=args.stages, microbatches=args.microbatches,
                     samplers=args.samplers, sampler_mode=args.sampler_mode,
                     telemetry=_trace_telemetry(args.trace_out),
                     device=devices[i % len(devices)])
        for i in range(n)]
    return ReplicaFleet(engines, capacity=args.capacity, roles=roles)


def run_gateway(args) -> None:
    """Boot the §16 gateway and serve until SIGINT/SIGTERM, then drain:
    stop admissions, let in-flight streams finish, close every replica."""
    import asyncio
    import signal

    from repro.gateway import GatewayServer

    async def _serve() -> None:
        gw = GatewayServer(build_fleet(args), codec=args.codec,
                           trace=bool(args.trace_out))
        await gw.serve(args.http_host, args.http_port)
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        if gw.fleet.disaggregated:
            shape = (f"{len(gw.fleet.prefill_replicas)} prefill + "
                     f"{len(gw.fleet.decode_replicas)} decode replicas")
        else:
            shape = f"{len(gw.fleet.replicas)} replica(s)"
        print(f"gateway listening on http://{gw.host}:{gw.port} "
              f"({shape}, capacity {args.capacity}, "
              f"codec '{args.codec}') — Ctrl-C drains and exits")
        await stop.wait()
        print("draining gateway ...")
        await gw.shutdown()
        print("gateway closed")
        if args.trace_out:
            # after shutdown: every replica drained, every span recorded
            sources = [("gateway", gw.tracer)] + [
                (f"replica:{rep.name}", rep.engine.tracer)
                for rep in gw.fleet.replicas
                if getattr(rep.engine, "tracer", None) is not None]
            n = write_chrome_trace(args.trace_out, sources)
            print(f"wrote {n} trace events to {args.trace_out} "
                  f"(chrome://tracing / ui.perfetto.dev)")

    asyncio.run(_serve())


def run_disaggregated_batch(args) -> None:
    """Non-gateway ``--disaggregate``: drive the synthetic batch through
    an in-process :class:`~repro.engine.handoff.HandoffScheduler` — one
    prefill engine, one decode engine, every request migrating its KV
    state at the first committed token (DESIGN.md §18). Streams stay
    bit-identical to a single-engine run; this path exists to eyeball
    migration cost without the HTTP stack."""
    from repro.engine import HandoffScheduler

    def _one():
        return build_engine(
            args.arch, args.reduced, args.algorithm, args.batch,
            args.max_seq, overlap=args.overlap,
            prompt_chunk=args.prompt_chunk, cache=args.cache,
            block_size=args.block_size, num_blocks=args.num_blocks,
            samplers=args.samplers, sampler_mode=args.sampler_mode,
            telemetry=_trace_telemetry(args.trace_out))

    stop_sequences = tuple(
        tuple(int(t) for t in s.split(",") if t.strip()) for s in args.stop)
    prefill_eng, decode_eng = _one(), _one()
    hs = HandoffScheduler(prefill_eng, decode_eng)
    reqs = synth_requests(args.requests, prefill_eng.cfg.vocab_size,
                          args.max_new, long_prompts=args.long_prompts,
                          seed=args.seed, greedy=args.greedy,
                          stop_sequences=stop_sequences)
    t0 = time.perf_counter()
    for r in reqs:
        r.arrival_time = t0
    n_events = sum(1 for _ in hs.generate(reqs))
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in reqs)
    hs.close()
    print(f"\nserved {len(reqs)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s) [disaggregated prefill/decode, "
          f"{hs.migrated}/{len(reqs)} requests migrated, "
          f"{n_events} events]")
    for r in sorted(reqs, key=lambda r: r.request_id):
        print(f"  req {r.request_id:3d}: {len(r.output):3d} tokens, "
              f"handoffs={r.handoff_count}, "
              f"finish_reason={r.finish_reason}")


def build_parser() -> argparse.ArgumentParser:
    """This module's command line (also parsed by ``chip_smoke.py`` so
    its fleets are built from the flags a user would pass)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="smollm-360m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke-size config (CPU-friendly)")
    ap.add_argument("--algorithm", default="shvs",
                    choices=registered_backends(),
                    help="sampler backend (decision-plane service registry)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--overlap", dest="overlap", action="store_true",
                    default=True, help="overlapped iteration loop (default)")
    ap.add_argument("--no-overlap", dest="overlap", action="store_false",
                    help="synchronous loop: drain every iteration")
    ap.add_argument("--prompt-chunk", type=int, default=0,
                    help="chunked-prefill width; 0 = monolithic prefill")
    ap.add_argument("--long-prompts", action="store_true",
                    help="mix in long prompts (exercises chunked prefill)")
    ap.add_argument("--cache", choices=("contiguous", "paged"),
                    default="contiguous",
                    help="KV layout: per-slot slabs or a paged block pool")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged cache)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="paged pool size; 0 = memory-equal to contiguous")
    ap.add_argument("--stages", type=int, default=1,
                    help="pipeline-parallel stages; >1 runs the "
                         "microbatched PipelineEngine (DESIGN.md §12)")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="microbatches in flight (0 = stages); "
                         "batch must divide into them")
    ap.add_argument("--samplers", type=int, default=2,
                    help="host sampler pool workers (host sampler mode)")
    ap.add_argument("--sampler-mode",
                    choices=("device", "host", "adaptive"),
                    default=None,
                    help="decision-plane placement (DESIGN.md §13/§15): "
                         "'device' samples on the accelerator, 'host' "
                         "disaggregates to the CPU sampler pool, committed "
                         "one step (pipeline: one re-entry) behind; "
                         "'adaptive' lets the DecisionPlaneController "
                         "switch placement and resize the pool online. "
                         "Default: device for the single-stage engine, "
                         "host for --stages>1")
    ap.add_argument("--seed", type=int, default=None,
                    help="per-request sampling seeds (request i uses seed+i); "
                         "token streams become pure functions of the seed")
    ap.add_argument("--greedy", action="store_true",
                    help="argmax decoding for every request")
    ap.add_argument("--stop", action="append", default=[],
                    metavar="IDS",
                    help="token-level stop sequence as comma-separated ids; "
                         "repeatable (finish_reason becomes 'stop')")
    ap.add_argument("--gateway", action="store_true",
                    help="serve HTTP/SSE completions over a replica fleet "
                         "(DESIGN.md §16) instead of a synthetic batch")
    ap.add_argument("--replicas", type=int, default=1,
                    help="gateway engine replicas (identical parameters)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="prefill/decode disaggregation (DESIGN.md §18): "
                         "split the fleet into prefill-role and "
                         "decode-role replicas; each request prefills on "
                         "one instance and migrates its KV state to a "
                         "decode instance at the first committed token")
    ap.add_argument("--prefill-replicas", type=int, default=0,
                    help="prefill-role replicas under --disaggregate "
                         "(0 = replicas // 2)")
    ap.add_argument("--decode-replicas", type=int, default=0,
                    help="decode-role replicas under --disaggregate "
                         "(0 = replicas - prefill)")
    ap.add_argument("--http-host", default="127.0.0.1")
    ap.add_argument("--http-port", type=int, default=8100)
    ap.add_argument("--capacity", type=int, default=16,
                    help="per-replica open-request bound (429 beyond it)")
    ap.add_argument("--codec", default="byte",
                    help="registered text codec for the gateway")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable the §17 flight recorder and write a "
                         "Chrome trace-event JSON (chrome://tracing / "
                         "ui.perfetto.dev) to PATH on exit; covers the "
                         "engines' step spans, the pool workers' "
                         "fetch/sample spans, and (gateway mode) the "
                         "wire-level request spans")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()

    if args.gateway:
        run_gateway(args)
        return
    if args.disaggregate:
        if args.stages > 1 or args.microbatches:
            raise ValueError(
                "--disaggregate needs single-stage engines: the pipeline "
                "engine shards its KV cache per stage and has no "
                "migration seam (DESIGN.md §18)")
        run_disaggregated_batch(args)
        return

    stop_sequences = tuple(
        tuple(int(t) for t in s.split(",") if t.strip()) for s in args.stop)
    eng = build_engine(args.arch, args.reduced, args.algorithm, args.batch,
                       args.max_seq, overlap=args.overlap,
                       prompt_chunk=args.prompt_chunk, cache=args.cache,
                       block_size=args.block_size, num_blocks=args.num_blocks,
                       stages=args.stages, microbatches=args.microbatches,
                       samplers=args.samplers,
                       sampler_mode=args.sampler_mode,
                       telemetry=_trace_telemetry(args.trace_out))
    reqs = synth_requests(args.requests, eng.cfg.vocab_size, args.max_new,
                          long_prompts=args.long_prompts, seed=args.seed,
                          greedy=args.greedy, stop_sequences=stop_sequences)
    t0 = time.perf_counter()
    for r in reqs:
        r.arrival_time = t0
    # stream through the service surface: events fire at commit
    n_events = 0
    first_event_at = None
    for ev in eng.generate(reqs):
        if first_event_at is None and ev.token is not None:
            first_event_at = time.perf_counter()
        n_events += 1
    dt = time.perf_counter() - t0
    done = reqs
    toks = sum(len(r.output) for r in done)
    pipelined = args.stages > 1 or args.microbatches
    if pipelined:
        mode = (f"pipeline p={eng.p} M={eng.M} "
                f"samplers={args.samplers} ({eng.client.mode} sampling)")
    else:
        mode = "overlapped" if args.overlap else "sequential"
        mode += f", {eng.client.mode} sampling"
    chunk = f", prompt_chunk={args.prompt_chunk}" if args.prompt_chunk else ""
    kv = ""
    if args.cache == "paged":
        kv = (f", paged bs={eng.pcfg.block_size} "
              f"pool={eng.pcfg.num_blocks} "
              f"preemptions={eng.scheduler.preemptions}")
    print(f"\nserved {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s) [{args.algorithm}, {mode}{chunk}{kv}]")
    if pipelined:
        rep = eng.pipeline_report()
        util = " ".join(f"s{s}={u:.1%}"
                        for s, u in enumerate(rep["stage_util"]))
        print(f"pipeline: bubble_frac={rep['bubble_frac']:.1%} over "
              f"{rep['cycles']} steady-state cycles, "
              f"cycle={rep['mean_cycle_ms']:.2f}ms, "
              f"commit_stall={rep['stall_ms_mean']:.2f}ms, "
              f"sampler={rep['sampler_ms_mean']:.2f}ms "
              f"(+{rep['transfer_ms_mean']:.2f}ms transfer)")
        print(f"per-stage utilization: {util}")
    elif eng.client.is_host:
        stalls = [s["stall_ms"] for s in eng.stats_log if "stall_ms" in s]
        samp = [s["sampler_ms"] for s in eng.stats_log if "sampler_ms" in s]
        xfer = [s["transfer_ms"] for s in eng.stats_log
                if "transfer_ms" in s]
        # a run whose work all landed via prefill/chunk paths commits no
        # decode steps — report n/a instead of np.mean([]) warnings
        fmt = lambda xs: f"{np.mean(xs):.2f}ms" if xs else "n/a"
        print(f"host sampler pool: commit_stall={fmt(stalls)} "
              f"sampler={fmt(samp)} (+{fmt(xfer)} transfer) per step")
    eng.close()
    if first_event_at is not None:
        print(f"first streamed event after {(first_event_at - t0) * 1e3:.1f}ms "
              f"({n_events} events)")
    print("per-request finish reasons:")
    for r in sorted(done, key=lambda r: r.request_id):
        seed_s = "-" if r.sampling.seed is None else str(r.sampling.seed)
        print(f"  req {r.request_id:3d}: {len(r.output):3d} tokens, "
              f"seed={seed_s:>4s}, finish_reason={r.finish_reason}")
    tpot = []
    ttft = []
    for r in done:
        if len(r.token_times) > 1:
            tpot.extend(np.diff(r.token_times))
        if r.first_token_time is not None:
            ttft.append(r.first_token_time - r.arrival_time)
    if tpot:
        print(f"TPOT p50={np.percentile(tpot, 50) * 1e3:.1f}ms "
              f"p95={np.percentile(tpot, 95) * 1e3:.1f}ms")
    if ttft:
        print(f"TTFT p50={np.percentile(ttft, 50) * 1e3:.1f}ms "
              f"p95={np.percentile(ttft, 95) * 1e3:.1f}ms")
    if eng.stats_log:
        # NaN accept rates mean "no active rows sampled that step" (§13);
        # keep them out of the headline mean
        accs = [s.accept_rate for s in eng.stats_log
                if np.isfinite(s.accept_rate)]
        acc = f"{np.mean(accs):.2%}" if accs else "n/a"
        print(f"decision plane: mean fast-path acceptance {acc} "
              f"({len(eng.stats_log)} iterations)")
    if args.trace_out:
        n = write_chrome_trace(args.trace_out,
                               [("engine", eng.tracer)])
        print(f"wrote {n} trace events to {args.trace_out} "
              f"(chrome://tracing / ui.perfetto.dev)")


if __name__ == "__main__":
    main()
