"""The serving engine: continuous batching + the SIMPLE decision plane.

Architecture (paper §4.2, DESIGN.md §2): the *data plane* (model forward)
and the *decision plane* (DecisionPlane.step) are two separately jitted
programs. The engine's iteration is:

  ⓪ scheduler.schedule()            — retire / admit / emit scheduling output
  ① prefill newly admitted requests — masked insert (or one prompt chunk)
  ②③ decode forward                 — logits leave sharded (B@batch, V@model)
  ④⑤ decision plane                 — S1 re-shard + S2/S3 sampling
  ⑥ scheduler.commit()              — tokens back into request state

**Overlapped mode (default).** Steps ②–⑤ are dispatched asynchronously and
only *device* values flow between iterations: iteration N's sampled tokens
feed iteration N+1's forward as a JAX future, never crossing to the host.
The host fetch + ⑥ commit for iteration N happen one step late — while the
device is already running iteration N+1 — so scheduling, stats, and token
materialization hide behind the forward (the paper's "overlappable"
property realized via async dispatch rather than a CPU sidecar). The cost
is a one-step commit lag: a request whose stop condition is in flight gets
one speculative decode whose token is rolled back at commit, and its slot
frees one iteration later (DESIGN.md §2). With ``overlap=False`` every
iteration drains immediately (the classic synchronous loop).

Determinism: uniforms are keyed on (request-id, output position) —
``DecisionPlane.uniforms_tagged`` — so the token stream of every request is
bit-identical between overlapped and sequential mode, and invariant to slot
placement and admission timing, for every registered backend.

**Paged KV mode** (``cache="paged"``, DESIGN.md §9). The per-slot slab
cache is replaced by a vLLM-style block pool: the scheduler admits by free
blocks (``ceil((prompt+max_new)/block_size)``), allocation is lazy as
sequences grow, and pool exhaustion preempts the most recently admitted
request (blocks freed, re-queued at the front, recompute-on-resume).
Decode and chunked prefill run the same jitted programs over gathered
block views, so token streams stay bit-identical to the contiguous cache
in every overlap/prefill mode (tests/test_paged_engine.py).

**Service API v1** (DESIGN.md §11). The decision plane is a service behind
the ``SamplerBackend`` registry — the engine speaks only the protocol
(``EngineConfig.algorithm`` names a registered backend; unknown names raise
a ``ValueError`` listing the registry). The per-request contract
(``SamplingConfig``: seed / greedy / logit_bias / stop_sequences) lives in
per-slot :class:`SlotParams` rows threaded into every jitted program, and
clients stream results through :meth:`Engine.generate`, which yields
``(request_id, token, finish_reason)`` events at **commit** time.

**Host sampler mode** (``sampler_mode="host"``, DESIGN.md §13). The engine
reaches the decision plane through a unified
:class:`~repro.engine.decision_client.DecisionPlaneClient`: device mode
keeps the decision fused into the decode program (everything above); host
mode dispatches a forward-only program and hands the logits *future* to
the client's CPU sampler pool — the workers block on the in-flight device
compute, sample sequence-parallel shards through the identical
``DecisionPlane.step``, and the engine resolves the ticket at the top of
the next step (before admissions overwrite any slot's rows), committing
one step behind exactly like the overlapped device loop. Streams are
bit-identical to device mode in every engine mode
(``tests/test_decision_client.py``).

The engine is deliberately token-only (dense/moe/ssm/hybrid archs); the
multimodal frontends are exercised by the dry-run and smoke tests.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, SamplingConfig, SHVSConfig
from repro.core.decision_plane import DecisionPlane
from repro.core.host_sampler import PoolResult, SampleTicket
from repro.core.sampling import SamplingParams
from repro.core import penalties as pen
from repro.engine.decision_client import (DecisionPlaneClient,
                                          canonical_sampler_mode)
from repro.engine.migration import KVPayload, stamp_export
from repro.engine.paged_cache import (BlockAllocator, PagedCacheConfig,
                                      gather_slot_kv, init_paged_cache,
                                      scatter_slot_kv)
from repro.engine.request import Request, RequestState
from repro.engine.scheduler import ChunkTask, Scheduler
from repro.models.attention import flat_block_indices, scatter_block_kv
from repro.models.model import Model
from repro.obs import EngineMetrics, StepRecord, Telemetry


@dataclass
class EngineConfig:
    max_batch: int = 8               # batch slots (B)
    max_seq_len: int = 512           # cache capacity per slot
    algorithm: str = "shvs"          # decision-plane algorithm
    shvs: SHVSConfig = SHVSConfig()
    sampling_parallelism: str = "sequence_parallel"
    k_cap: int = 256
    seed: int = 0
    prompt_bucket: int = 32          # prompts padded to multiples of this
    overlap: bool = True             # double-buffered iteration loop (§2)
    prompt_chunk: int = 0            # >0: chunked prefill width (§8)
    priority_admission: bool = True  # single-chunk prompts admitted first
    max_admission_wait: int = 64     # aging bound for priority admission
    cache: str = "contiguous"        # KV layout: "contiguous" | "paged" (§9)
    block_size: int = 16             # paged: tokens per KV block
    num_blocks: int = 0              # paged pool size; 0 = memory-equal to
    #                                  the contiguous cache (B * S / bs)
    sampler_mode: str = "device"     # decision plane placement (§13/§15):
    #                                  "device" (fused into the decode
    #                                  program) | "host" (CPU sampler pool,
    #                                  committed one step behind) |
    #                                  "adaptive" (a DecisionPlaneController
    #                                  switches placement and resizes the
    #                                  pool online from the engine's own
    #                                  stat streams)
    samplers: int = 2                # host-mode sampler pool workers
    stats_window: int = 4096         # stats_log / cycle_log ring size: a
    #                                  long-lived gateway replica keeps the
    #                                  most recent window, never grows (§17)


def _bucket(n: int, mult: int) -> int:
    return max(mult, ((n + mult - 1) // mult) * mult)


def _donates() -> bool:
    """Whether the step programs donate ``cache``/``pstate``: everywhere
    but the CPU, whose runtime executes a donating program synchronously
    on the calling thread, which defeats the async dispatch the
    overlapped loop is built on. Tests steer it to rehearse donation."""
    return jax.default_backend() != "cpu"


def locked_api(fn):
    """Serialize a public engine method on the instance's ``_api_lock``.

    Both engines were written for a single consumer; the gateway's replica
    fleet (and any client running several ``generate_stream`` iterators
    from different threads) submits and steps concurrently. The lock is
    reentrant so locked methods may nest (``step`` → ``flush`` on paged
    preemption, ``close`` → ``flush``), and it only serializes the
    host-side orchestration — the device work those calls dispatch stays
    async underneath. Arrays the call creates land on the engine's
    ``device`` (the default device for engines without one)."""
    import functools

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._api_lock, \
                jax.default_device(getattr(self, "device", None)):
            return fn(self, *args, **kwargs)
    return wrapper


@dataclass(frozen=True)
class GenerationEvent:
    """One streamed output item from :meth:`Engine.generate`.

    ``token`` is ``None`` only on a terminal event that carries a
    ``finish_reason`` without a new token (e.g. a request truncated at KV
    capacity after its last committed token had already streamed).
    ``finish_reason`` is set on each request's final event and ``None``
    before that (``eos | length | stop | truncated``,
    ``Request.finish_reason``).
    """

    request_id: int
    token: Optional[int]
    finish_reason: Optional[str] = None


class StreamCursor:
    """Incremental view of one request's committed tokens as
    :class:`GenerationEvent` items.

    The cursor owns the emitted/closed bookkeeping that used to live as
    closure state inside :func:`generate_stream`; factoring it out lets
    every consumer of the engine protocol — ``generate_stream`` here, the
    gateway's replica workers (``repro.gateway.fleet``) — share one
    definition of "which committed tokens have been delivered", so the
    wire stream cannot drift from the in-process stream by construction.
    """

    def __init__(self, request: Request):
        self.request = request
        self.emitted = 0
        self.closed = False

    def drain(self) -> Iterator[GenerationEvent]:
        """Yield every committed-but-undelivered token (the final one
        carrying ``finish_reason``); a request that finished without a
        fresh token (e.g. truncated at KV capacity) yields a terminal
        ``token=None`` marker event."""
        r = self.request
        if self.closed:
            return
        while self.emitted < len(r.output):
            tok = r.output[self.emitted]
            self.emitted += 1
            fin = r.finish_reason if self.emitted == len(r.output) else None
            if fin is not None:
                self.closed = True
            yield GenerationEvent(r.request_id, tok, fin)
        if not self.closed and r.finish_reason is not None:
            self.closed = True
            yield GenerationEvent(r.request_id, None, r.finish_reason)


def generate_stream(eng, requests: List[Request], max_steps: int = 10_000):
    """Shared client surface behind :meth:`Engine.generate` and
    :meth:`PipelineEngine.generate` (DESIGN.md §11/§12): submit
    ``requests``, drive ``eng.step()`` and yield :class:`GenerationEvent`
    items as tokens **commit** on the host. ``eng`` needs only the narrow
    engine protocol — ``submit`` / ``step`` / ``flush`` / ``in_flight`` /
    ``scheduler.has_work``.

    Concurrency: the engine's public methods are serialized on an internal
    lock, so several ``generate_stream`` iterators may drive ONE engine
    from different threads — each drains only its own requests, and the
    (request, position) RNG keying keeps every stream bit-identical to a
    serial run regardless of how admissions interleave
    (``tests/test_engine_concurrency.py``)."""
    requests = list(requests)
    if not requests:
        return
    eng.submit(requests)
    cursors = [StreamCursor(r) for r in requests]

    def drain():
        for c in cursors:
            yield from c.drain()

    steps = 0
    try:
        while not all(c.closed for c in cursors) and steps < max_steps and \
                (eng.scheduler.has_work or eng.in_flight):
            eng.step()
            steps += 1
            yield from drain()
    except GeneratorExit:
        # the caller abandoned the iterator mid-stream: commit everything
        # in flight so no sampler-pool ticket (host mode) or device future
        # is left dangling — pool threads go idle and a later
        # ``eng.close()`` cannot block on abandoned work (DESIGN.md §13)
        eng.flush()
        raise
    eng.flush()
    yield from drain()
    if not all(c.closed for c in cursors):
        # never end the stream silently mid-request: a client must be
        # able to distinguish completion from the step cap
        open_ids = [c.request.request_id for c in cursors if not c.closed]
        raise RuntimeError(
            f"generate() hit max_steps={max_steps} with requests still "
            f"unfinished: {open_ids}")


def admission_shape(eng, new_requests: List[Request]):
    """Each admitted row's context (prompt, or prompt+output for a resumed
    row) and the group's padded length ``Sp`` (bucketed, capped at the
    cache)."""
    ctxs = [r.context_tokens() if r.output else r.prompt
            for r in new_requests]
    Sp = _bucket(max(len(c) for c in ctxs), eng.ecfg.prompt_bucket)
    return ctxs, min(Sp, eng.ecfg.max_seq_len)


def admission_decision(decision: DecisionPlane):
    """The admission's first-token ``decision.step`` as one jitted program:
    its operands are shaped by the group size P alone (and by whether the
    dense bias operand is present), so it compiles once per (P, bias), not
    per prompt length. Build it anew wherever the decision plane's hot set
    changes: the hot set is traced into the program, and the fresh closure
    keeps jax's trace cache from handing back the old one."""
    def admit_decide(logits, pstate, sparams, step_idx, nonces, positions,
                     bias):
        tokens, pstate, _ = decision.step(
            logits, pstate, sparams, step_idx,
            rng_tags=(nonces, positions), logit_bias=bias)
        return tokens, pstate
    return jax.jit(admit_decide)


def prefill_new_rows(eng, new_requests: List[Request], step_idx: int):
    """Shared admission math behind :meth:`Engine._admit` and
    :meth:`PipelineEngine._admit_group` — one implementation so the
    engines' bit-identity contract (§12) cannot drift: bucket and pad the
    requests' contexts, run the monolithic prefill program (jit-cached per
    ``(P, Sp)``), rebuild resumed rows' prompt/output histogram split
    (presence/frequency penalties read C_o — Eq. 5), and sample each row's
    first token at its resume position with the compiled first-token
    decision (one program per group size P, :func:`admission_decision`).
    ``eng`` needs ``cfg`` / ``ecfg`` / ``params`` / ``tracer`` /
    ``_prefill_cache`` / ``_prefill_impl`` / ``_admit_decide_jit``.

    Returns ``(first, rows_cache, rows_pstate, lens, bases, rids)`` —
    ``first`` is the (P,) device token array; the caller owns the install
    into its batch/stage state."""
    P = len(new_requests)
    ctxs, Sp = admission_shape(eng, new_requests)
    toks = np.zeros((P, Sp), np.int32)
    lens = np.zeros((P,), np.int32)
    bases = np.zeros((P,), np.int32)   # next output position per row
    for i, (r, c) in enumerate(zip(new_requests, ctxs)):
        c = c[-Sp:]
        toks[i, :len(c)] = c
        lens[i] = len(c)
        bases[i] = len(r.output)
    key = (P, Sp)
    if key not in eng._prefill_cache:
        eng._prefill_cache[key] = jax.jit(eng._prefill_impl)
    logits, rows_cache, rows_pstate = eng._prefill_cache[key](
        eng.params, jnp.asarray(toks), jnp.asarray(lens))
    rids = np.array([r.request_id for r in new_requests], np.uint32)
    # resumed rows: the prefill batched prompt+output into one sequence,
    # but the penalty state must keep the prompt/output split — rebuild
    V = eng.cfg.vocab_size
    for i, r in enumerate(new_requests):
        if not r.output:
            continue
        pp = jnp.asarray(np.asarray(r.prompt, np.int32)[None, :])
        oo = jnp.asarray(np.asarray(r.output, np.int32)[None, :])
        rows_pstate = pen.PenaltyState(
            prompt_counts=rows_pstate.prompt_counts.at[i].set(
                pen.histogram(pp, V)[0]),
            output_counts=rows_pstate.output_counts.at[i].set(
                pen.histogram(oo, V)[0]))
    # first sampled token (output position `bases`, 0 for fresh rows)
    sp_rows = SlotParams(P, V)
    for i, r in enumerate(new_requests):
        sp_rows.set_row(i, r.sampling)
    with eng.tracer.phase("admit_decide", rows=P):
        first, rows_pstate = eng._admit_decide_jit(
            logits, rows_pstate, sp_rows.as_params(),
            jnp.asarray(step_idx, jnp.int32), jnp.asarray(rids),
            jnp.asarray(bases), sp_rows.bias_array())
    return first, rows_cache, rows_pstate, lens, bases, rids


@dataclass
class _Pending:
    """One dispatched-but-uncommitted iteration result (DESIGN.md §2/§13).

    ``kind="decode"`` carries device futures (tokens + stats) from the
    fused decode program; ``kind="host"`` carries a sampler-pool
    :class:`SampleTicket` instead — resolved (tokens/penalty state
    installed into engine state) before the next dispatch needs them,
    committed to request state at the drain point one step behind;
    ``kind="first"`` carries chunk finishers' first tokens.
    """

    kind: str                                   # "decode" | "host" | "first"
    tokens: Optional[jnp.ndarray] = None        # (B,) device future
    step: int = -1
    stats: Optional[object] = None              # DecisionStats (decode only)
    active: Optional[np.ndarray] = None         # (B,) bool snapshot
    slot_request: Optional[List[Optional[Request]]] = None
    finishers: List[Tuple[int, Request]] = field(default_factory=list)
    ticket: Optional[SampleTicket] = None       # host mode: pending shards
    res: Optional[PoolResult] = None            # host mode: resolved result
    stall: float = 0.0                          # host mode: block on ticket
    t_dispatch: float = 0.0                     # perf_counter at dispatch (§17)


class Engine:
    """Serving engine. Optional online hot-size autotuning (paper §9 future
    work (i)): pass ``hot_counts`` (a token-frequency vector, e.g. from the
    offline trace) and ``autotune=True`` — the engine feeds the measured
    hot mass into :class:`repro.core.autotune.HotSizeController` and
    rebuilds the hot set (re-jitting the decode program) when H* moves."""

    def __init__(self, model_cfg: ModelConfig, params, engine_cfg: EngineConfig,
                 hot_set=None, hot_counts=None, autotune: bool = False,
                 telemetry: Optional[Telemetry] = None):
        # first, before anything can raise: the public-API lock (the engine
        # was written for one consumer; the gateway's fleet bridge and
        # concurrent generate_stream iterators serialize on it) and the
        # closed flag (close() must be safe on a half-constructed engine —
        # fleet shutdown paths double-close and close after failed startup)
        self._api_lock = threading.RLock()
        self._closed = False
        self.cfg = model_cfg
        self.ecfg = engine_cfg
        self.model = Model(model_cfg)
        self.params = params
        # one engine, one device: wherever its parameters were placed
        # (``launch.serve.build_fleet`` puts replica i on device i); its
        # cache, decision state and imported payloads go there too
        self.device = next(iter(
            jax.tree_util.tree_leaves(params)[0].devices()))
        # chunked prefill is gated to full-causal dense decoders (§8)
        self._chunk_ok = (engine_cfg.prompt_chunk > 0
                          and model_cfg.family in ("dense", "moe")
                          and not model_cfg.is_encdec
                          and not model_cfg.sliding_window)
        chunk = engine_cfg.prompt_chunk if self._chunk_ok else 0
        # fail fast: a chunk's slab write needs lens + C <= max_seq_len even
        # for the last partial chunk (worst case lens = window - 1 with
        # window = max_seq_len - C), i.e. C <= max_seq_len // 2
        assert chunk <= engine_cfg.max_seq_len // 2, (
            f"prompt_chunk={chunk} must be <= max_seq_len//2 "
            f"({engine_cfg.max_seq_len // 2})")
        # paged KV mode (§9): block-pool cache + block-based admission;
        # gated to the same full-causal dense archs as chunked prefill
        # (the gathered block view reuses the cached-attention masks)
        self._paged = engine_cfg.cache == "paged"
        assert engine_cfg.cache in ("contiguous", "paged"), engine_cfg.cache
        B, S = engine_cfg.max_batch, engine_cfg.max_seq_len
        kv_gate = None
        if self._paged:
            assert (model_cfg.family in ("dense", "moe")
                    and not model_cfg.is_encdec
                    and not model_cfg.sliding_window), \
                "cache='paged': full-causal dense/moe decoders only"
            bs = engine_cfg.block_size
            assert S % bs == 0, (
                f"max_seq_len={S} must be a multiple of block_size={bs} so "
                "the gathered block view is shaped exactly like the "
                "contiguous cache (bit-identity, DESIGN.md §9)")
            mb = S // bs
            self.pcfg = PagedCacheConfig(
                block_size=bs,
                num_blocks=engine_cfg.num_blocks or B * mb,
                max_blocks_per_seq=mb)
            self.alloc = BlockAllocator(self.pcfg, B)
            # host mirror of each slot's dispatch-time cache length (device
            # `len` is a future under the overlapped loop)
            self._slot_len = np.zeros((B,), np.int64)
            kv_gate = self._kv_gate
        self.scheduler = Scheduler(
            engine_cfg.max_batch, prompt_chunk=chunk,
            priority_admission=engine_cfg.priority_admission,
            max_admission_wait=engine_cfg.max_admission_wait,
            max_prompt=max(chunk, engine_cfg.max_seq_len - chunk),
            kv_gate=kv_gate, on_free=self._on_slot_free)
        self.decision = DecisionPlane(
            model_cfg.vocab_size, algorithm=engine_cfg.algorithm,
            shvs=engine_cfg.shvs, hot_set=hot_set,
            sampling_parallelism=engine_cfg.sampling_parallelism,
            k_cap=min(engine_cfg.k_cap, model_cfg.vocab_size),
            seed=engine_cfg.seed)
        # the decision-plane client (§13): device mode keeps the decision
        # fused into the decode program (§2); host mode splits the forward
        # off and ships logits to the client's CPU sampler pool, committing
        # one step behind exactly like the overlapped device loop
        # "adaptive" (§15) starts on device — the winning placement at
        # light load, where there is no sampling work to overlap — and
        # lets the controller disaggregate online under queue pressure
        self._adaptive = engine_cfg.sampler_mode == "adaptive"
        # telemetry plane (§17): a flight-recorder tracer (off by default)
        # plus the metrics registry; the tracer rides into the client so
        # pool workers record their fetch/sample spans on the same clock
        self.obs = telemetry if telemetry is not None else Telemetry()
        self.tracer = self.obs.tracer
        self._metrics = EngineMetrics(self.obs.metrics)
        self.client = DecisionPlaneClient(
            self.decision,
            "device" if self._adaptive else engine_cfg.sampler_mode,
            engine_cfg.samplers, tracer=self.tracer,
            switchable=self._adaptive)
        self._host = self.client.is_host
        self._metrics.mode_host.set(1.0 if self._host else 0.0)
        self._metrics.pool_workers.set(float(engine_cfg.samplers))
        # committed from the start, as every step program's outputs are:
        # the first admission then compiles the programs later ones reuse
        with jax.default_device(self.device):
            self.cache = jax.device_put(
                init_paged_cache(model_cfg, B, self.pcfg) if self._paged
                else self.model.init_cache(B, S), self.device)
            self.last_tokens = jax.device_put(jnp.zeros((B,), jnp.int32),
                                              self.device)
        # host mode keeps the (B, V) penalty histograms on the pool's CPU
        # device, so they never cross the link on a decode step
        self.pstate = jax.device_put(self.decision.init_state(B),
                                     self._state_device())
        self._sp = SlotParams(B, model_cfg.vocab_size)
        # per-slot RNG tags: request nonce + next output position (host-side;
        # activity is decided by the scheduler, so no device sync is needed)
        self._nonce = np.zeros((B,), np.uint32)
        self._pos = np.zeros((B,), np.int32)
        self._pending: List[_Pending] = []
        self._jit_programs()
        self._prefill_cache: Dict[int, callable] = {}
        # bounded flight log of typed StepRecords (§17) — a long-lived
        # replica keeps the most recent window instead of growing forever
        self.stats_log: Deque[StepRecord] = deque(
            maxlen=engine_cfg.stats_window)
        # migration flow counters (§18) + the free-block gauge the router
        # debugs against (-1 signals "contiguous cache, no pool")
        self.migrations_in = 0
        self.migrations_out = 0
        self._metrics.free_blocks.set(
            float(self.alloc.num_free) if self._paged else -1.0)
        self._hot_counts = hot_counts
        self._controller = None
        hot = None
        if autotune and engine_cfg.algorithm in ("shvs", "fused"):
            from repro.core.autotune import HotSizeController
            assert hot_counts is not None, "autotune needs hot_counts"
            hot = HotSizeController(
                vocab_size=model_cfg.vocab_size,
                h_current=int(self.decision.hot_set.size))
        self._dpc = None
        if self._adaptive:
            # global decision-plane controller (§15): placement + pool
            # sizing from the per-step stat streams, H* as a sub-policy
            from repro.core.autotune import DecisionPlaneController
            self._dpc = DecisionPlaneController(
                mode=self.client.mode, samplers=engine_cfg.samplers,
                queue_high=float(engine_cfg.max_batch), hot=hot)
        else:
            self._controller = hot

    def _jit_programs(self) -> None:
        # last_tokens / nonces / pos are never donated — pending commits hold
        # references to token buffers across dispatches (§2). cache/pstate
        # are (see _donates): every reader holds the program's outputs, and
        # host-mode workers are joined before the state they read is
        # replaced (_resolve_host_pending)
        donate = _donates()
        self._decode_jit = jax.jit(self._decode_impl,
                                   donate_argnums=(1, 2) if donate else ())
        self._chunk_jit = jax.jit(self._chunk_impl,
                                  donate_argnums=(1, 2) if donate else ())
        # host sampler mode (§13): forward-only program — the decision
        # plane runs in the client's CPU pool on the fetched logits
        self._forward_jit = jax.jit(self._forward_impl,
                                    donate_argnums=(1,) if donate else ())
        # admission's first-token decision: rebuilt here, with the decode
        # program, so a hot-set swap reaches it too
        self._admit_decide_jit = admission_decision(self.decision)

    def _state_device(self):
        """Where ``pstate`` lives: the host pool's CPU device in host mode,
        the engine's device otherwise."""
        return self.client.pool.device if self._host else self.device

    # -- jitted bodies ---------------------------------------------------------
    def _decode_impl(self, params, cache, pstate, last_tokens, sparams, bias,
                     nonces, pos, step, active):
        lens0 = cache["len"]
        # the scopes name the program's two halves in its ops' metadata
        # (``op_name``), so a profiler trace splits the step between them
        with jax.named_scope("forward"):
            logits, cache = self.model.decode_step(params, last_tokens,
                                                   cache)
        # inactive rows (mid-prefill / retired-but-uncommitted slots) must
        # not advance their cache write offset
        cache = dict(cache)
        cache["len"] = jnp.where(active, lens0 + 1, lens0)
        with jax.named_scope("decision"):
            tokens, pstate, stats = self.decision.step(
                logits, pstate, sparams, step, active=active,
                rng_tags=(nonces, pos), logit_bias=bias)
        tokens = jnp.where(active, tokens, 0)
        return tokens, cache, pstate, stats

    def _forward_impl(self, params, cache, last_tokens, active):
        """Decode forward WITHOUT the decision epilogue (host sampler
        mode, §13): returns the step's logits; the client's pool fetches
        them and runs the identical ``DecisionPlane.step`` off-device."""
        lens0 = cache["len"]
        logits, cache = self.model.decode_step(params, last_tokens, cache)
        cache = dict(cache)
        cache["len"] = jnp.where(active, lens0 + 1, lens0)
        return logits, cache

    def _prefill_impl(self, params, tokens, true_lens):
        """Prefill a fresh batch (P rows); returns (first tokens' logits
        source cache rows, pstate rows)."""
        P, Sp = tokens.shape
        cache = self.model.init_cache(P, self.ecfg.max_seq_len)
        logits, cache = self.model.prefill(params, {"tokens": tokens}, cache,
                                           true_lens=true_lens)
        pstate = pen.init_state(P, self.cfg.vocab_size, tokens, true_lens)
        return logits, cache, pstate

    def _chunk_impl(self, params, cache, pstate, toks, counts, mask, finish,
                    sparams, bias, nonces, last_tokens, step):
        """One prompt chunk for every mid-prefill row; rows finishing their
        prompt sample their first token (position 0) in the same program."""
        with jax.named_scope("forward"):
            logits, cache = self.model.prefill_chunk(params, toks, cache,
                                                     counts, mask)
        with jax.named_scope("decision"):
            tokens, pstate, _ = self.decision.step(
                logits, pstate, sparams, step, active=finish,
                rng_tags=(nonces, jnp.zeros_like(nonces, jnp.int32)),
                logit_bias=bias)
        tokens = jnp.where(finish, tokens, 0)
        last_tokens = jnp.where(finish, tokens, last_tokens)
        return tokens, last_tokens, cache, pstate

    # -- paged KV bookkeeping (§9) ---------------------------------------------
    def _blocks_for(self, req: Request) -> int:
        """Worst-case block demand of a request — the admission unit.
        Invariant across preemption/resume: prompt+output+remaining always
        sums to prompt_len + max_new_tokens."""
        total = min(req.prompt_len + req.max_new_tokens,
                    self.ecfg.max_seq_len)
        return self.alloc.blocks_needed(total)

    def _kv_gate(self, req: Request, round_admits: List[Request]) -> bool:
        """Block-based admission: a request enters only when its worst-case
        ceil((prompt+max_new)/block_size) blocks are free, net of the
        worst-case demand of requests admitted earlier this round."""
        reserved = sum(self._blocks_for(r) for r in round_admits)
        return self._blocks_for(req) <= self.alloc.num_free - reserved

    def _on_slot_free(self, slot: int, req: Request) -> None:
        """A slot gave up its claim (retire or preemption): reset its
        sampling-contract row so nothing stale can be dispatched for the
        slot's next occupant, and release its KV blocks (paged mode)."""
        self._sp.reset_row(slot)
        if self._paged:
            self.alloc.release(slot)
            self._slot_len[slot] = 0

    def _push_block_table(self) -> None:
        """Upload the host allocator's block table to the device cache."""
        cache = dict(self.cache)
        cache["block_table"] = jnp.asarray(
            self.alloc.table(self.ecfg.max_batch))
        self.cache = cache

    def _pick_victim(self) -> Optional[Request]:
        """Preemption victim: the lowest-priority slotted request = the most
        recently admitted (ties broken by slot for determinism)."""
        cands = [r for r in self.scheduler.slots if r is not None and
                 r.state in (RequestState.RUNNING, RequestState.PREFILLING)]
        if len(cands) <= 1:
            return None
        return max(cands, key=lambda r: (r.admit_step, r.slot))

    def _ensure_blocks(self, slot: int, target_len: int,
                       plan: Optional["SchedulingOutput"] = None) -> bool:
        """Grow ``slot``'s allocation to cover ``target_len`` tokens,
        preempting under pool pressure. Returns False iff the slot's own
        request was the preemption victim (it frees itself and skips this
        iteration). Replaces the old hard ``RuntimeError`` on exhaustion."""
        if self.alloc.blocks_needed(target_len) > \
                self.pcfg.max_blocks_per_seq:
            # per-sequence capacity, not pool pressure: preemption can't help
            raise RuntimeError(
                f"sequence of {target_len} tokens exceeds cache capacity "
                f"({self.pcfg.max_blocks_per_seq} blocks per sequence)")
        owner = self.scheduler.slots[slot]
        while True:
            try:
                self.alloc.ensure(slot, target_len)
                return True
            except RuntimeError:
                pass
            # commit in-flight iterations and retire what finished — their
            # released blocks may already cover the demand
            self.flush()
            if self.scheduler.slots[slot] is not owner:
                # the flush retired this very row: don't claim blocks for
                # an empty slot — the caller recomputes activity
                return False
            try:
                self.alloc.ensure(slot, target_len)
                return True
            except RuntimeError:
                pass
            victim = self._pick_victim()
            if victim is None:
                raise RuntimeError(
                    "paged KV pool cannot hold a single sequence "
                    f"(need {self.alloc.blocks_needed(target_len)} blocks, "
                    f"pool={self.pcfg.num_blocks})")
            vslot = victim.slot
            self.scheduler.preempt(victim)
            if plan is not None:
                plan.active_slots[vslot] = False
                plan.slot_request[vslot] = None
            if vslot == slot:
                return False

    def _decode_activity(self) -> np.ndarray:
        return np.array(
            [s is not None and s.state is RequestState.RUNNING
             and not s.should_stop() for s in self.scheduler.slots])

    def _prepare_paged_decode(self, plan) -> np.ndarray:
        """Ensure every decoding row has a block for its next token; on
        exhaustion, preempt lowest-priority requests (recompute-on-resume).
        Returns the refreshed activity mask (a fixed point: ensuring one
        row may evict another already-checked one, so loop until stable).

        A row whose next token would exceed the per-sequence cache capacity
        is stopped (``Request.truncated``) instead of crashing the engine:
        requests with prompt+max_new > max_seq_len are admitted (the gate
        clamps their block demand) and simply finish at capacity."""
        while True:
            active = self._decode_activity()
            aborted = False
            for b in np.flatnonzero(active):
                s = self.scheduler.slots[b]
                if s is None or s.state is not RequestState.RUNNING:
                    aborted = True      # evicted mid-sweep
                    break
                if int(self._slot_len[b]) + 1 > self.ecfg.max_seq_len:
                    s.truncated = True  # capacity stop, not pool pressure
                    aborted = True
                    break
                if not self._ensure_blocks(
                        int(b), int(self._slot_len[b]) + 1, plan):
                    aborted = True      # a row was evicted mid-sweep
                    break
            if not aborted and np.array_equal(self._decode_activity(),
                                              active):
                return active

    # -- public API --------------------------------------------------------------
    @locked_api
    def submit(self, requests: List[Request]) -> None:
        if self._closed:
            raise RuntimeError("Engine is closed")
        if self._paged:
            # validate the whole batch before enqueueing any of it: the
            # admission gate would skip an oversized request on every round
            # (silent starvation) — the pool can never cover its worst
            # case, even completely drained
            for r in requests:
                if self._blocks_for(r) > self.pcfg.num_blocks:
                    raise ValueError(
                        f"request {r.request_id} needs {self._blocks_for(r)} "
                        f"KV blocks (prompt {r.prompt_len} + max_new "
                        f"{r.max_new_tokens}) > pool of "
                        f"{self.pcfg.num_blocks}")
        for r in requests:
            self.scheduler.submit(r)

    @property
    def in_flight(self) -> int:
        """Dispatched-but-uncommitted iterations (0 or 1 in overlap mode)."""
        return len(self._pending)

    @locked_api
    def step(self) -> dict:
        """One engine iteration. Returns observability stats (in overlapped
        mode: the stats of the iteration committed this call, i.e. lagged by
        one step)."""
        # NOTE: no opportunistic "commit early if the device result already
        # landed" here — is_ready()-style checks make the schedule trace
        # depend on wall-clock timing, which shifts admission *grouping*
        # (different (P, Sp) prefill programs → bitwise logit drift) and
        # breaks run-to-run determinism. The drain point is fixed instead.
        # Each synchronous stretch is a tracer phase (DESIGN.md §17), so a
        # profiler trace attributes the device's idle time to it.
        tr = self.tracer
        with tr.phase("schedule"):
            plan = self.scheduler.schedule()
        if self._host:
            # install the in-flight ticket's tokens + penalty state BEFORE
            # admission/chunks overwrite their slots' rows: the CPU workers
            # sampled step t while the host side ran ahead; step t+1's
            # forward consumes their tokens. (The request-state commit
            # still lands at the drain point, one step behind — the plan
            # above was computed without step t's tokens, exactly like the
            # device-mode overlap loop.)
            self._resolve_host_pending()
        if plan.new_requests:
            self._admit(plan.new_requests)
        if plan.new_chunked:
            with tr.phase("prefill", name=f"chunked x{len(plan.new_chunked)}",
                          rows=len(plan.new_chunked)):
                self._admit_chunked(plan.new_chunked)
        if plan.chunks:
            with tr.phase("chunk", rows=len(plan.chunks)):
                self._run_chunks(plan.chunks)
        with tr.phase("dispatch", step=plan.step):
            dispatched = self._dispatch(plan)
        # drain: sequential mode syncs everything now; overlapped mode keeps
        # exactly one decode in flight so the device never waits on the host
        keep = 1 if (self.ecfg.overlap and dispatched) else 0
        rec: Optional[StepRecord] = None
        while len(self._pending) > keep:
            rec = self._drain_one() or rec
        return rec if rec is not None else {}

    def _dispatch(self, plan) -> bool:
        """Dispatch the step's decode (device mode) or forward-only
        program plus its sampler-pool ticket (host mode) for every active
        row. Returns whether anything was dispatched."""
        # refresh decode activity: a prompt's first token may already satisfy
        # the stop condition; chunk finishers join the decode batch
        plan.active_slots = np.array(
            [s is not None and s.state is RequestState.RUNNING
             and not s.should_stop() for s in self.scheduler.slots])
        if self._paged and plan.active_slots.any():
            # grow each decoding row's allocation by one token (preempting
            # under pressure) and publish the refreshed block table
            plan.active_slots = self._prepare_paged_decode(plan)
            self._push_block_table()
        dispatched = bool(plan.active_slots.any())
        if dispatched:
            active = jnp.asarray(plan.active_slots)
            if self._host:
                # §13: dispatch the forward-only program (async) and hand
                # the logits FUTURE to the sampler pool — the workers, not
                # this thread, block on the in-flight device compute; the
                # engine keeps running the next step's host-side work
                t_disp = time.perf_counter()
                logits, self.cache = self._forward_jit(
                    self.params, self.cache, self.last_tokens, active)
                # the pool's copy of the contract rows stays on its CPU
                cpu = self.client.pool.device
                ticket = self.client.submit(
                    logits, self.pstate, self._sp.as_params(cpu),
                    self._sp.bias_array(cpu),
                    self._nonce.copy(), self._pos.copy(), plan.step,
                    plan.active_slots.copy())
                self._pending.append(_Pending(
                    kind="host", ticket=ticket, step=plan.step,
                    active=plan.active_slots.copy(),
                    slot_request=list(plan.slot_request),
                    t_dispatch=t_disp))
            else:
                # .copy(): jnp.asarray can alias host numpy buffers
                # zero-copy on CPU, and the async in-flight program must
                # not observe the engine mutating _nonce/_pos after dispatch
                t_disp = time.perf_counter()
                tokens, self.cache, self.pstate, stats = self._decode_jit(
                    self.params, self.cache, self.pstate, self.last_tokens,
                    self._sp.as_params(), self._sp.bias_array(),
                    jnp.asarray(self._nonce.copy()),
                    jnp.asarray(self._pos.copy()),
                    jnp.asarray(plan.step, jnp.int32), active)
                self.last_tokens = tokens
                self._pending.append(_Pending(
                    kind="decode", tokens=tokens, step=plan.step, stats=stats,
                    active=plan.active_slots.copy(),
                    slot_request=list(plan.slot_request),
                    t_dispatch=t_disp))
            self._pos += plan.active_slots
            if self._paged:
                self._slot_len += plan.active_slots
        return dispatched

    @locked_api
    def flush(self) -> None:
        """Commit every in-flight iteration and retire what finished."""
        while self._pending:
            self._drain_one()
        self.scheduler.retire_finished()

    def run(self, max_steps: int = 10_000) -> List[Request]:
        steps = 0
        while (self.scheduler.has_work or self._pending) and \
                steps < max_steps:
            self.step()
            steps += 1
        self.flush()
        return self.scheduler.finished

    def generate(self, requests: List[Request], max_steps: int = 10_000):
        """Submit ``requests`` and stream :class:`GenerationEvent` items as
        their tokens are generated — the client surface of the service API
        (DESIGN.md §11).

        Overlap-aware: an event fires when its token **commits** on the
        host (one step after dispatch under the overlapped loop, §2), never
        at dispatch — so speculative decodes that get rolled back are never
        observable. The stream is incremental (the first event arrives
        while later requests are still decoding) and, collected per
        request, bit-identical to the ``submit()`` + ``run()`` path: both
        are views of the same committed token streams. Each request's final
        event carries its ``finish_reason``. Raises ``RuntimeError`` if
        ``max_steps`` is exhausted with requests still open — the stream
        never just stops mid-request.
        """
        yield from generate_stream(self, requests, max_steps)

    def close(self) -> None:
        """Shut down the decision-plane client's sampler pool (host-mode
        worker threads), mirroring :meth:`PipelineEngine.close`. In-flight
        iterations are committed first so no ticket is stranded.

        Idempotent, and safe on a partially constructed engine (a failed
        ``__init__`` leaves attributes missing): fleet shutdown paths
        double-close replicas, and the second close must be a no-op — it
        must never flush into an already-shut sampler pool."""
        if getattr(self, "_closed", False):
            return
        lock = getattr(self, "_api_lock", None)
        if lock is None:           # __init__ died before the first stmt
            self._closed = True
            return
        with lock, jax.default_device(getattr(self, "device", None)):
            if self._closed:
                return
            self._closed = True
            if getattr(self, "scheduler", None) is not None and \
                    getattr(self, "_pending", None) is not None:
                self.flush()
            client = getattr(self, "client", None)
            if client is not None:
                client.close()

    # -- KV migration (prefill/decode disaggregation, DESIGN.md §18) -----------
    @locked_api
    def export_request(self, request_id: int) -> KVPayload:
        """Quiesce one RUNNING request at the commit boundary and detach
        it as a portable :class:`KVPayload` (DESIGN.md §18).

        The quiesce point is ``flush()``: every dispatched token is
        committed, so the invariants the payload is built on hold exactly —
        the cache holds ``T`` entries covering the prefilled window plus
        all-but-the-last committed token, ``last_tokens[slot]`` is
        ``output[-1]`` (sampled but not yet forwarded), the penalty
        histograms already count it, and the RNG position is
        ``len(output)``. Importing on any engine with the same parameters
        resumes the stream bit-identically (tests/test_disagg.py).

        Raises ``KeyError`` for an unknown/unslotted id and ``ValueError``
        for a request that cannot migrate (mid-chunked-prefill, no
        committed output yet, or already finished — the flush may finish
        it, in which case it retires here and there is nothing to move).
        """
        self.flush()
        req = None
        for s in self.scheduler.slots:
            if s is not None and s.request_id == request_id:
                req = s
                break
        if req is None:
            raise KeyError(
                f"request {request_id} is not slotted on this engine")
        if req.state is not RequestState.RUNNING or not req.output:
            raise ValueError(
                f"request {request_id} cannot migrate: state={req.state}, "
                f"{len(req.output)} committed tokens (needs a RUNNING "
                "request past its first token)")
        if req.should_stop():
            raise ValueError(f"request {request_id} already finished")
        t0 = time.perf_counter()
        slot = req.slot
        assert int(self._pos[slot]) == len(req.output), \
            "quiesce invariant violated: RNG position != committed output"
        if self._paged:
            T = int(self._slot_len[slot])
            k, v = gather_slot_kv(self.cache, self.alloc.owned[slot], T,
                                  self.pcfg)
            self.alloc.export_slot(slot)
            self._slot_len[slot] = 0
        else:
            if set(self.cache.keys()) != {"k", "v", "len", "pos"}:
                raise RuntimeError(
                    "KV migration supports plain attention caches only "
                    f"(leaves: {sorted(self.cache.keys())})")
            T = int(np.asarray(self.cache["len"])[slot])
            k = np.asarray(self.cache["k"][:, slot, :T])
            v = np.asarray(self.cache["v"][:, slot, :T])
        payload = KVPayload(
            request_id=req.request_id, prompt=list(req.prompt),
            output=list(req.output), max_new_tokens=req.max_new_tokens,
            sampling=req.sampling, eos_token=req.eos_token,
            prompt_offset=req.prompt_offset,
            arrival_time=req.arrival_time, kv_len=T, k=k, v=v,
            prompt_counts=np.asarray(self.pstate.prompt_counts[slot]),
            output_counts=np.asarray(self.pstate.output_counts[slot]),
            last_token=int(req.output[-1]), next_pos=len(req.output),
            source=f"engine@{id(self):x}", request=req)
        # detach: frees the slot (on_free releases any remaining block
        # claim and resets the SlotParams row) without re-queueing
        self.scheduler.remove(req)
        req.kv_payload = payload
        self.migrations_out += 1
        self._metrics.migrations_out.inc()
        if self._paged:
            self._metrics.free_blocks.set(float(self.alloc.num_free))
        stamp_export(payload)
        if self.tracer.enabled:
            self.tracer.add("kv_migrate", t0, payload.exported_at,
                            name=f"export#{req.request_id}",
                            request_id=int(req.request_id), kv_len=T,
                            bytes=payload.nbytes, direction="out")
        return payload

    @locked_api
    def import_request(self, payload: KVPayload) -> Request:
        """Admit a migrated request carrying its KV (DESIGN.md §18): the
        payload rides through the normal admission path (queueing, slot
        assignment, block gating) and ``_admit`` installs it directly —
        no re-prefill. Returns the request object that will stream here."""
        self._validate_payload(payload)
        req = payload.request if payload.request is not None \
            else payload.to_request()
        req.kv_payload = payload
        req.slot = -1
        req.state = RequestState.WAITING
        req.prompt_pos = 0
        self.submit([req])
        self._metrics.pending_imports.set(float(sum(
            1 for r in self.scheduler.waiting if r.kv_payload is not None)))
        return req

    def _validate_payload(self, p: KVPayload) -> None:
        L = self.cfg.num_layers
        kv, hd = self.cfg.num_kv_heads, self.cfg.resolved_head_dim
        want = (L, p.kv_len, kv, hd)
        if tuple(p.k.shape) != want or tuple(p.v.shape) != want:
            raise ValueError(
                f"payload KV shape {tuple(p.k.shape)} does not match this "
                f"engine's model ({want})")
        if p.prompt_counts.shape != (self.cfg.vocab_size,):
            raise ValueError(
                f"payload vocab {p.prompt_counts.shape[0]} != "
                f"{self.cfg.vocab_size}")
        if p.kv_len + 1 > self.ecfg.max_seq_len:
            raise ValueError(
                f"payload of {p.kv_len} KV entries cannot decode within "
                f"max_seq_len={self.ecfg.max_seq_len}")
        if p.next_pos != len(p.output) or not p.output:
            raise ValueError("corrupt payload: RNG position != output")

    def _install_imports(self, carried: List[Request]) -> None:
        """Install migrated requests' state into their assigned slots —
        the import half of the migration seam (DESIGN.md §18). Replaces
        the prefill of ``_admit``: KV entries are scattered bitwise into
        freshly allocated blocks (or the slot's slab rows), the penalty
        histograms and sampling contract land in the slot's rows, and the
        RNG position resumes at ``len(output)`` — the decode program
        cannot tell the request ever moved."""
        for r in carried:
            p: KVPayload = r.kv_payload
            # consumed on install: a later preemption of this request
            # falls back to recompute-on-resume over prompt+output
            r.kv_payload = None
            t0 = time.perf_counter()
            if self.tracer.enabled and p.exported_at:
                self.tracer.add("handoff_wait", p.exported_at, t0,
                                name=f"handoff#{r.request_id}",
                                request_id=int(r.request_id),
                                kv_len=int(p.kv_len))
            slot, T = r.slot, int(p.kv_len)
            if self._paged:
                self.alloc.release(slot)       # stale claims (defensive)
                self.alloc.ensure(slot, T)
                self._slot_len[slot] = T
                self._push_block_table()
                self.cache = scatter_slot_kv(
                    self.cache, self.alloc.owned[slot], p.k, p.v, self.pcfg)
                cache = dict(self.cache)
            else:
                cache = dict(self.cache)
                cache["k"] = cache["k"].at[:, slot, :T].set(
                    jnp.asarray(p.k, cache["k"].dtype))
                cache["v"] = cache["v"].at[:, slot, :T].set(
                    jnp.asarray(p.v, cache["v"].dtype))
            cache["len"] = cache["len"].at[slot].set(T)
            self.cache = cache
            self.pstate = pen.PenaltyState(
                prompt_counts=self.pstate.prompt_counts.at[slot].set(
                    jnp.asarray(p.prompt_counts)),
                output_counts=self.pstate.output_counts.at[slot].set(
                    jnp.asarray(p.output_counts)))
            self.last_tokens = self.last_tokens.at[slot].set(
                jnp.int32(p.last_token))
            self._sp.set_row(slot, r.sampling)
            self._nonce[slot] = np.uint32(r.request_id)
            self._pos[slot] = int(p.next_pos)
            r.handoff_count += 1
            self.migrations_in += 1
            self._metrics.migrations_in.inc()
            if self.tracer.enabled:
                self.tracer.add("kv_migrate", t0, time.perf_counter(),
                                name=f"import#{r.request_id}",
                                request_id=int(r.request_id), kv_len=T,
                                bytes=p.nbytes, direction="in")
        if self._paged:
            self._metrics.free_blocks.set(float(self.alloc.num_free))
        self._metrics.pending_imports.set(float(sum(
            1 for r in self.scheduler.waiting if r.kv_payload is not None)))

    @locked_api
    def migration_stats(self) -> dict:
        """Per-engine disaggregation counters for ``GET /v1/stats`` —
        free-block headroom and migration flow (DESIGN.md §18)."""
        return {
            "free_blocks": self.alloc.num_free if self._paged else None,
            "migrations_in": self.migrations_in,
            "migrations_out": self.migrations_out,
            "pending_imports": sum(
                1 for r in self.scheduler.waiting
                if r.kv_payload is not None),
        }

    # -- commit ----------------------------------------------------------------
    def _resolve_host_pending(self) -> None:
        """Host mode (§13): collect the in-flight ticket's sampled tokens
        and updated penalty rows into engine state so the next dispatch can
        consume them. Idempotent; the blocking time is the measured
        sampler-pool stall (zero when the workers beat the host's slack).
        The scheduler-side commit still happens at the drain point."""
        for ent in self._pending:
            if ent.kind == "host" and ent.res is None:
                self._resolve_ticket(ent)

    def _resolve_ticket(self, ent: _Pending) -> None:
        """Block on one host ticket (the measured pool stall) and install
        its tokens and penalty state into engine state."""
        with self.tracer.phase("pool_stall", name=f"stall@step{ent.step}",
                               step=ent.step) as ph:
            ent.res = ent.ticket.result()
        ent.stall = ph.t1 - ph.t0
        self.last_tokens = jax.device_put(ent.res.tokens, self.device)
        self.pstate = ent.res.state

    def _drain_one(self) -> Optional[StepRecord]:
        """Fetch the oldest pending result to the host and commit it. This
        is the only place engine iterations block on the device (device
        mode) or the sampler pool (host mode, if not already resolved)."""
        ent = self._pending.pop(0)
        with self.tracer.phase("drain", step=ent.step):
            if ent.kind == "host":
                if ent.res is None:   # sequential mode drains immediately
                    self._resolve_ticket(ent)
                toks_np = ent.res.tokens
            else:
                toks_np = np.asarray(ent.tokens)      # host sync point
        now = time.perf_counter()
        if ent.kind == "decode" and self.tracer.enabled:
            # dispatch -> host materialization of the fused decode program
            self.tracer.add("forward", ent.t_dispatch, now,
                            name=f"decode@step{ent.step}", step=ent.step)
        with self.tracer.phase("commit", name=f"commit@step{ent.step}",
                               step=ent.step):
            return self._commit(ent, toks_np, now)

    def _commit(self, ent: _Pending, toks_np: np.ndarray,
                now: float) -> Optional[StepRecord]:
        """Commit one drained result to request state, then fold its
        record into the controller(s), the metrics and ``stats_log``."""
        if ent.kind == "first":
            for slot, req in ent.finishers:
                req.record_token(int(toks_np[slot]), now)
            return None
        self.scheduler.commit(toks_np, ent.slot_request, ent.active, now=now)
        # queue state is stamped on EVERY record (§17): the controller,
        # /metrics, and the benchmarks consume one validated stream
        common = dict(step=ent.step, batch=int(ent.active.sum()),
                      queue_depth=float(len(self.scheduler.waiting)),
                      queue_delay_ms=self._queue_delay_ms())
        if ent.kind == "host":
            rec = StepRecord(accept_rate=ent.res.accept_rate,
                             alpha_mean=ent.res.alpha_mean,
                             fallback_rate=ent.res.fallback_rate,
                             stall_ms=ent.stall * 1e3,
                             sampler_ms=ent.res.sampler_time * 1e3,
                             transfer_ms=ent.res.transfer_time * 1e3,
                             **common)
        else:
            rec = StepRecord(accept_rate=float(ent.stats.accept_rate),
                             alpha_mean=float(ent.stats.alpha_mean),
                             fallback_rate=float(ent.stats.fallback_rate),
                             **common)
        if self._controller is not None:
            new_h = self._controller.observe(rec.alpha_mean)
            if new_h:
                self._apply_hot_size(new_h)
                rec.hot_size = new_h
        if self._dpc is not None:
            act = self._dpc.observe_record(rec)
            if act:
                if act.hot_size is not None:
                    self._apply_hot_size(act.hot_size)
                    rec.hot_size = act.hot_size
                if act.samplers is not None:
                    # resolving first keeps the drained ticket's result
                    # installed before the executor recycle
                    self._resolve_host_pending()
                    self.client.resize_pool(act.samplers)
                    rec.samplers = act.samplers
                    self._metrics.pool_workers.set(float(act.samplers))
                if act.sampler_mode is not None:
                    self.set_sampler_mode(act.sampler_mode)
                    rec.sampler_mode = act.sampler_mode
                self._metrics.decisions.inc()
                if self.tracer.enabled:
                    self.tracer.instant(
                        "decision", name=f"decision@step{ent.step}",
                        step=ent.step, hot_size=act.hot_size,
                        samplers=act.samplers,
                        sampler_mode=act.sampler_mode)
        self._metrics.observe_step(rec)
        if self._paged:
            self._metrics.free_blocks.set(float(self.alloc.num_free))
        self.stats_log.append(rec)
        return rec

    def set_sampler_mode(self, mode: str) -> bool:
        """Re-route the decision plane online (§15): resolve the in-flight
        host ticket FIRST — after a host->device switch ``self._host`` goes
        False and the top-of-step resolution would never fire for a
        stranded ticket — then re-route the client. The per-entry
        ``_Pending.kind`` makes mixed-placement in-flight work commit
        correctly on either side, so the switch cannot move any request's
        stream. Returns True iff the mode changed."""
        mode = canonical_sampler_mode(mode)
        if mode == self.client.mode:
            return False
        self._resolve_host_pending()
        self.client.set_mode(mode)
        self._host = self.client.is_host
        # the histograms follow the placement: a jitted step cannot mix
        # arrays committed to two devices
        self.pstate = jax.device_put(self.pstate, self._state_device())
        self._metrics.mode_host.set(1.0 if self._host else 0.0)
        return True

    def _apply_hot_size(self, new_h: int) -> None:
        """Swap the SHVS hot set to ``new_h`` ids and re-jit. An in-flight
        ticket's workers read the pool's program at call time: join them
        BEFORE the swap so their microbatch samples against the hot set it
        was dispatched under (matching device mode, where the in-flight
        execution keeps the old traced program) — never a wall-clock
        race."""
        self._resolve_host_pending()
        from repro.core.hot_vocab import build_hot_set
        self.decision.hot_set = build_hot_set(
            self._hot_counts, new_h, self.cfg.vocab_size)
        # hot-set shape changed: re-jit the decision programs on both
        # sides of the client seam
        self._jit_programs()
        self.client.refresh()

    def _queue_delay_ms(self) -> float:
        """Oldest waiting request's queueing delay. 0 with an empty queue;
        NaN when arrivals carry no wall-clock stamps (offline traces leave
        ``arrival_time`` at 0.0), which the controller ignores."""
        if not self.scheduler.waiting:
            return 0.0
        now = time.perf_counter()
        ds = [now - r.arrival_time
              for r in self.scheduler.waiting if r.arrival_time]
        return max(ds) * 1e3 if ds else float("nan")

    # -- admission ------------------------------------------------------------
    def _admit(self, new_requests: List[Request]) -> None:
        """Prefill new requests (padded batch) and insert rows into state.

        A *resumed* request (re-queued by preemption with committed output,
        §9) re-prefills prompt+output and samples its next token at output
        position len(output) — the (request, position) RNG keying makes the
        continuation bit-identical to the unpreempted stream.

        A *migrated* request (carrying a :class:`KVPayload`, §18) skips
        the prefill entirely: its KV, penalty state, and RNG position are
        installed bitwise into the assigned slot."""
        carried = [r for r in new_requests if r.kv_payload is not None]
        fresh = [r for r in new_requests if r.kv_payload is None]
        shape = {}
        if fresh:
            ctxs, Sp = admission_shape(self, fresh)
            shape = dict(padded=Sp, tokens=sum(len(c) for c in ctxs))
        with self.tracer.phase("prefill", name=f"prefill x{len(fresh)}",
                               rows=len(fresh), **shape):
            if carried:
                self._install_imports(carried)
            if fresh:
                self._prefill_rows(fresh)

    def _prefill_rows(self, new_requests: List[Request]) -> None:
        """The prefill half of :meth:`_admit`: run the group's prefill and
        first-token decision (one compiled program per group size), insert
        the rows into the batch state, and commit each row's first token
        once it is on the host."""
        tr = self.tracer
        if tr.enabled:
            # arrival -> admission wait per request (0-stamped offline
            # traces carry no arrival clock; skip those)
            t_pf = time.perf_counter()
            for r in new_requests:
                if r.arrival_time:
                    tr.add("queue_wait", r.arrival_time, t_pf,
                           name=f"wait#{r.request_id}",
                           request_id=int(r.request_id))
        first, rows_cache, rows_pstate, lens, bases, rids = \
            prefill_new_rows(self, new_requests, self.scheduler.step)
        # insert rows into batch state (device-side, chains off any
        # still-running decode through the donated cache/pstate futures)
        with tr.phase("admit_insert", rows=len(new_requests)):
            rows_pstate = jax.device_put(rows_pstate, self._state_device())
            slots = jnp.asarray([r.slot for r in new_requests], jnp.int32)
            if self._paged:
                self._paged_insert(new_requests, rows_cache, lens)
            else:
                self.cache = _insert_rows(self.cache, rows_cache, slots)
            self.pstate = pen.PenaltyState(
                prompt_counts=self.pstate.prompt_counts.at[slots].set(
                    rows_pstate.prompt_counts),
                output_counts=self.pstate.output_counts.at[slots].set(
                    rows_pstate.output_counts),
            )
            self.last_tokens = self.last_tokens.at[slots].set(first)
        with tr.phase("admit_fetch", rows=len(new_requests)):
            first_np = np.asarray(first)   # blocks on the prefill program
        # stamped once the first tokens are on the host, as a client sees
        # them (Request.first_token_time, token_times[0])
        now = time.perf_counter()
        for i, r in enumerate(new_requests):
            self._sp.set_row(r.slot, r.sampling)
            self._nonce[r.slot] = rids[i]
            self._pos[r.slot] = int(bases[i]) + 1
            r.record_token(int(first_np[i]), now)

    def _paged_insert(self, new_requests: List[Request], rows_cache,
                      lens: np.ndarray) -> None:
        """Scatter freshly prefilled contiguous rows into the block pool:
        allocate each slot's blocks, publish the table, then one jitted
        scatter moves the rows' valid K/V entries to their physical blocks."""
        for i, r in enumerate(new_requests):
            self.alloc.release(r.slot)         # stale claims (defensive)
            self.alloc.ensure(r.slot, int(lens[i]))
            self._slot_len[r.slot] = int(lens[i])
        self._push_block_table()
        P = len(new_requests)
        key = ("paged_insert", P)
        if key not in self._prefill_cache:
            self._prefill_cache[key] = jax.jit(self._paged_insert_impl)
        slot_ids = np.asarray([r.slot for r in new_requests], np.int32)
        row_bt = self.alloc.table(self.ecfg.max_batch)[slot_ids]
        self.cache = self._prefill_cache[key](
            self.cache, rows_cache["k"], rows_cache["v"],
            jnp.asarray(row_bt), jnp.asarray(slot_ids), jnp.asarray(lens))

    def _paged_insert_impl(self, cache, rows_k, rows_v, row_bt, slot_ids,
                           true_lens):
        """rows_k/v: (L, P, Sc, kv, hd) contiguous prefill rows; write the
        first true_lens[p] entries of row p into its slot's blocks."""
        Sc = rows_k.shape[2]
        valid = jnp.arange(Sc)[None, :] < true_lens[:, None]
        flat = flat_block_indices(row_bt, jnp.zeros_like(true_lens), valid,
                                  self.pcfg.block_size, self.pcfg.num_blocks)
        cache = dict(cache)
        cache["k_pool"] = scatter_block_kv(cache["k_pool"], rows_k, flat)
        cache["v_pool"] = scatter_block_kv(cache["v_pool"], rows_v, flat)
        cache["len"] = cache["len"].at[slot_ids].set(true_lens)
        return cache

    def _admit_chunked(self, new_chunked: List[Request]) -> None:
        """Claim slots for chunked-prefill requests: reset the rows' cache
        offsets and seed their penalty state with the full-prompt histogram
        (available up front — Eq. 5 is position-independent)."""
        if self.tracer.enabled:
            now = time.perf_counter()
            for r in new_chunked:
                if r.arrival_time:
                    self.tracer.add("queue_wait", r.arrival_time, now,
                                    name=f"wait#{r.request_id}",
                                    request_id=int(r.request_id))
        P = len(new_chunked)
        V = self.cfg.vocab_size
        windows = [r.prompt[r.prompt_offset:] for r in new_chunked]
        maxlen = max(len(w) for w in windows)
        toks = np.zeros((P, maxlen), np.int32)
        lens = np.zeros((P,), np.int32)
        for i, w in enumerate(windows):
            toks[i, :len(w)] = w
            lens[i] = len(w)
        rows_pstate = pen.init_state(P, V, jnp.asarray(toks),
                                     jnp.asarray(lens))
        slots = jnp.asarray([r.slot for r in new_chunked], jnp.int32)
        self.pstate = pen.PenaltyState(
            prompt_counts=self.pstate.prompt_counts.at[slots].set(
                rows_pstate.prompt_counts),
            output_counts=self.pstate.output_counts.at[slots].set(
                rows_pstate.output_counts),
        )
        cache = dict(self.cache)
        cache["len"] = cache["len"].at[slots].set(0)
        self.cache = cache
        for r in new_chunked:
            self._sp.set_row(r.slot, r.sampling)
            self._nonce[r.slot] = np.uint32(r.request_id)
            self._pos[r.slot] = 0
            if self._paged:
                self.alloc.release(r.slot)     # stale claims (defensive)
                self._slot_len[r.slot] = 0

    def _run_chunks(self, chunks: List[ChunkTask]) -> None:
        """Run one prompt chunk per mid-prefill slot (single (B, C) program);
        rows that complete their prompt sample their first token and join
        the decode batch this iteration."""
        if self._paged:
            # grow each chunk row's allocation to cover its slab before
            # dispatch; a task whose request was evicted during another
            # task's recovery (or its own) is dropped — re-admission
            # restarts its prefill from scratch
            kept: List[ChunkTask] = []
            for task in chunks:
                if self.scheduler.slots[task.slot] is not task.request:
                    continue
                need = int(self._slot_len[task.slot]) + task.end - task.start
                if self._ensure_blocks(task.slot, need):
                    kept.append(task)
            chunks = [t for t in kept
                      if self.scheduler.slots[t.slot] is t.request]
            if not chunks:
                return
            self._push_block_table()
        B = self.ecfg.max_batch
        C = self.scheduler.prompt_chunk
        toks = np.zeros((B, C), np.int32)
        counts = np.zeros((B,), np.int32)
        mask = np.zeros((B,), bool)
        finish = np.zeros((B,), bool)
        finishers: List[Tuple[int, Request]] = []
        for task in chunks:
            seg = task.request.prompt[task.start:task.end]
            toks[task.slot, :len(seg)] = seg
            counts[task.slot] = len(seg)
            mask[task.slot] = True
            if task.final:
                finish[task.slot] = True
                finishers.append((task.slot, task.request))
        # the chunk program samples finishers' first tokens on the device;
        # in host mode the histograms visit it and go back to the CPU
        first, self.last_tokens, self.cache, pstate = self._chunk_jit(
            self.params, self.cache,
            jax.device_put(self.pstate, self.device), jnp.asarray(toks),
            jnp.asarray(counts), jnp.asarray(mask), jnp.asarray(finish),
            self._sp.as_params(), self._sp.bias_array(),
            jnp.asarray(self._nonce.copy()),
            self.last_tokens, jnp.asarray(self.scheduler.step, jnp.int32))
        self.pstate = jax.device_put(pstate, self._state_device())
        if self._paged:
            for task in chunks:
                self._slot_len[task.slot] += task.end - task.start
        for slot, _ in finishers:
            self._pos[slot] = 1
        if finishers:
            # first tokens are committed through the pending queue so the
            # device chain is never broken mid-iteration
            self._pending.append(_Pending(kind="first", tokens=first,
                                          finishers=finishers))


def _insert_rows(batch_cache, rows_cache, slots):
    """Scatter per-row cache entries into the engine's batch cache at
    ``slots``. Every cache leaf except len/pos is (L|G, B, ...) with the
    batch on axis 1; ``len`` is (B,); ``pos`` is scalar."""
    out = {}
    for k in batch_cache:
        if k == "pos":
            out[k] = batch_cache[k]
        elif k == "len":
            out[k] = batch_cache[k].at[slots].set(rows_cache[k])
        else:
            out[k] = batch_cache[k].at[:, slots].set(rows_cache[k])
    return out


class SlotParams:
    """Per-slot sampling contract rows as numpy arrays -> SamplingParams.

    One row per batch slot, carrying the full per-request contract
    (DESIGN.md §11): the 7 core controls (``greedy`` is realized as
    temperature 0 — every backend's τ=0 path), the per-request RNG seed
    tags, and the sparse logit-bias rows. The device-side structs are
    cached and only rebuilt after a row changes; every lifecycle edge that
    can reassign a slot must go through :meth:`set_row` (admission/resume)
    or :meth:`reset_row` (retire/preempt via the engine's slot-free hook),
    both of which invalidate the cache — so a stale cached row can never be
    dispatched for a slot's next occupant
    (``tests/test_service_api.py::test_slot_reuse_never_dispatches_stale_params``).
    """

    def __init__(self, batch: int, vocab_size: int):
        self.batch = batch
        self.vocab_size = vocab_size
        self.temperature = np.ones(batch, np.float32)
        self.top_k = np.zeros(batch, np.int32)
        self.top_p = np.ones(batch, np.float32)
        self.min_p = np.zeros(batch, np.float32)
        self.repetition = np.ones(batch, np.float32)
        self.presence = np.zeros(batch, np.float32)
        self.frequency = np.zeros(batch, np.float32)
        self.seed = np.zeros(batch, np.uint32)
        self.use_seed = np.zeros(batch, bool)
        # dense (B, V) bias rows, allocated on first use and updated
        # row-wise — never rebuilt from scratch on the scheduling hot path.
        # Sticky: once any request used logit_bias, keep passing the dense
        # operand so the jitted program signature stops flip-flopping
        # (zero rows are exact no-ops on the logits).
        self._bias_dense: Optional[np.ndarray] = None
        # device structs, one per device they were asked for (None = the
        # default device): host mode keeps its copy on the pool's CPU
        self._cached: Dict[object, SamplingParams] = {}
        self._bias_cached: Dict[object, jnp.ndarray] = {}

    def set_row(self, i: int, cfg: SamplingConfig) -> None:
        self.temperature[i] = cfg.effective_temperature
        self.top_k[i] = cfg.top_k
        self.top_p[i] = cfg.top_p
        self.min_p[i] = cfg.min_p
        self.repetition[i] = cfg.repetition_penalty
        self.presence[i] = cfg.presence_penalty
        self.frequency[i] = cfg.frequency_penalty
        self.seed[i] = np.uint32(cfg.seed_u32)
        self.use_seed[i] = cfg.seeded
        if cfg.logit_bias and self._bias_dense is None:
            self._bias_dense = np.zeros((self.batch, self.vocab_size),
                                        np.float32)
        if self._bias_dense is not None:
            self._bias_dense[i] = 0.0
            for t, b in cfg.logit_bias:
                if 0 <= t < self.vocab_size:
                    self._bias_dense[i, t] += b
            self._bias_cached = {}
        self._cached = {}

    def reset_row(self, i: int) -> None:
        """Return row ``i`` to the default contract when its slot frees
        (retire/preempt) so nothing stale survives into the next occupant."""
        self.set_row(i, SamplingConfig())

    def as_params(self, device=None) -> SamplingParams:
        """The rows as device arrays on ``device`` (default device if
        None), rebuilt only after a row changed."""
        if device not in self._cached:
            # .copy(): the device structs may alias host numpy buffers
            # zero-copy; set_row mutations must never reach a program that
            # is already in flight (or silently change the cached struct)
            put = lambda x: jax.device_put(x.copy(), device)
            self._cached[device] = SamplingParams(
                temperature=put(self.temperature),
                top_k=put(self.top_k),
                top_p=put(self.top_p),
                min_p=put(self.min_p),
                repetition_penalty=put(self.repetition),
                presence_penalty=put(self.presence),
                frequency_penalty=put(self.frequency),
                seed=put(self.seed),
                use_seed=put(self.use_seed),
            )
        return self._cached[device]

    def bias_array(self, device=None) -> Optional[jnp.ndarray]:
        """Dense (B, V) logit-bias operand on ``device``, or None while no
        request has ever used logit_bias (the jitted programs then skip
        the add)."""
        if self._bias_dense is None:
            return None
        if device not in self._bias_cached:
            # .copy() for the same aliasing reason as as_params()
            self._bias_cached[device] = jax.device_put(
                self._bias_dense.copy(), device)
        return self._bias_cached[device]
