"""Pipeline-parallel serving: microbatched multi-stage decode with a
disaggregated host-side sampler pool (DESIGN.md §12).

The paper's Eq. 4 argument — sampling executed on the last pipeline stage
caps the pipeline frequency, idling every other stage ``t_sampling`` per
cycle — was previously reproduced only by the analytic simulator in
``benchmarks/pipeline_sim.py``. This module makes it *executable*:

* **stage split** — the transformer layer stack is sliced into ``p``
  contiguous stages (``models.transformer.stage_bounds`` /
  ``slice_stage_params``), each with its own layer-sliced KV cache
  (contiguous slabs or paged pools); the input embedding rides on stage 1
  and the tied LM head on stage ``p`` (``Model.decode_stage``);
* **microbatches + cycle clock** — the ``B`` batch slots are partitioned
  into ``M ≥ p`` microbatch groups of ``B/M`` rows. An explicit cycle
  clock (:class:`MicrobatchPlanner`) round-robins them: at cycle ``c``
  stage ``s`` serves microbatch ``(c − s) mod M``, activations handed
  stage-to-stage between jitted stage programs;
* **disaggregated sampling** — last-stage logits go to a
  :class:`~repro.core.host_sampler.HostSamplerPool` of ``m`` CPU workers
  (sequence-parallel shards through the ``SamplerBackend`` registry) and
  the sampled tokens are **committed only when the microbatch re-enters
  stage 1**, ``(M − p)`` cycles later — the paper's slack. The pipeline
  stalls only if the pool cannot make that slack, and the stall is
  measured (``cycle_log``). ``sampler_mode="device"`` instead samples
  synchronously right after the last stage's forward, putting
  ``t_sampling`` back on the cycle critical path for the bubble
  comparison (``benchmarks/fig_pipeline.py``).

**Identity discipline** (tests/test_pipeline_engine.py): for any ``p`` and
``M``, committed token streams are bit-identical to the single-stage
:class:`~repro.engine.engine.Engine` under the same seeds/contracts,
across {overlap, seq} × {contiguous, paged}. The argument: (i) the
per-stage ``lax.scan`` slices compose exactly like the full-depth scan
(pinned per-program by the stage-split tests), (ii) every per-row decision
computation is row-local, so sharding rows across sampler workers or
microbatches cannot change them, and (iii) uniforms are keyed on
(request, position), so tokens are invariant to the cycle schedule
entirely.

Scope gates: dense/moe full-causal decoders, monolithic prefill
(``prompt_chunk=0`` — a prompt prefills through all stages in one
program; per-stage chunked prefill is future work), and in paged mode a
*reserving* admission gate (a request enters only when its worst-case
block demand fits net of every running request's outstanding worst case),
which makes mid-flight preemption unnecessary — in-flight microbatches
never lose blocks.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core import penalties as pen
from repro.core.decision_plane import DecisionPlane
from repro.core.host_sampler import PoolResult, SampleTicket
from repro.engine.decision_client import DecisionPlaneClient
from repro.engine.engine import (EngineConfig, SlotParams, _insert_rows,
                                 admission_decision, admission_shape,
                                 generate_stream, locked_api,
                                 prefill_new_rows)
from repro.engine.paged_cache import (BlockAllocator, PagedCacheConfig,
                                      init_paged_cache)
from repro.engine.request import Request, RequestState
from repro.engine.scheduler import Scheduler
from repro.models.attention import flat_block_indices, scatter_block_kv
from repro.models.model import Model
from repro.models.transformer import (slice_stage_cache, slice_stage_params,
                                      stage_bounds)
from repro.obs import (CycleRecord, EngineMetrics, StepRecord, Telemetry)


@dataclass
class PipelineConfig(EngineConfig):
    """Engine config plus the pipeline dimensions (DESIGN.md §12)."""

    stages: int = 2                   # p — pipeline stages
    microbatches: int = 0             # M in flight; 0 -> p (minimum legal)
    samplers: int = 2                 # m — host sampler pool workers
    sampler_mode: str = "host"        # "device" samples synchronously on the
    #                                   last stage (Eq. 4); "adaptive" -> §15
    #                                   controller switches placement /
    #                                   resizes the pool online


@dataclass
class _Dispatch:
    """One microbatch's in-flight token: dispatched at stage 1, sampled at
    stage p, committed at the next stage-1 re-entry."""

    microbatch: int
    dispatch_cycle: int
    active: np.ndarray                       # (R,) bool snapshot
    slot_request: List[Optional[Request]]    # (R,) snapshot at dispatch
    nonces: np.ndarray                       # (R,) uint32 RNG tag snapshot
    positions: np.ndarray                    # (R,) int32 RNG tag snapshot
    exit_cycle: Optional[int] = None         # last-stage forward cycle
    commit_due: Optional[int] = None         # next stage-1 re-entry cycle


class MicrobatchPlanner:
    """Cycle clock + in-flight ledger for the microbatched pipeline.

    The planner owns WHICH microbatch each stage serves each cycle and
    WHEN a sampled token may commit; the engine owns the tensors. Keeping
    it free of device state makes the scheduling invariants directly
    checkable (hypothesis suite in ``tests/test_property.py``):

    * slot-group disjointness — a dispatch may only cover its own group's
      slots, and no slot is ever covered by two in-flight dispatches;
    * single in-flight token per microbatch — a microbatch cannot be
      re-dispatched before its previous token committed;
    * commit timing — a token commits exactly at its microbatch's first
      stage-1 re-entry after the last-stage exit (never earlier), i.e.
      ``commit_due = exit_cycle + ((i − exit_cycle) mod M or M)``.
    """

    def __init__(self, stages: int, microbatches: int, rows_per_group: int):
        assert stages >= 1 and rows_per_group >= 1
        assert microbatches >= stages, \
            f"need M >= p microbatches in flight (got M={microbatches}, " \
            f"p={stages})"
        self.p = stages
        self.M = microbatches
        self.R = rows_per_group
        self.cycle = 0
        self.inflight: Dict[int, _Dispatch] = {}

    # -- schedule geometry ---------------------------------------------------
    def group_slots(self, microbatch: int) -> range:
        """Global slot ids owned by ``microbatch`` (fixed partition)."""
        return range(microbatch * self.R, (microbatch + 1) * self.R)

    def stage_for(self, cycle: int, stage: int) -> int:
        """The microbatch stage ``stage`` serves at ``cycle``."""
        return (cycle - stage) % self.M

    def reentry(self, cycle: int) -> int:
        """The microbatch re-entering stage 1 at ``cycle``."""
        return cycle % self.M

    # -- ledger -------------------------------------------------------------
    def dispatch(self, microbatch: int, active: np.ndarray,
                 slot_request: List[Optional[Request]],
                 nonces: np.ndarray, positions: np.ndarray) -> _Dispatch:
        i = microbatch
        assert i == self.reentry(self.cycle), \
            f"microbatch {i} dispatched off-schedule at cycle {self.cycle}"
        assert i not in self.inflight, \
            f"microbatch {i} re-dispatched with a token still in flight"
        mine = set(self.group_slots(i))
        for other in self.inflight.values():
            other_slots = {r.slot for a, r in zip(other.active,
                                                  other.slot_request)
                           if a and r is not None}
            assert not (mine & other_slots), \
                "slot aliased by two in-flight microbatches"
        for a, r in zip(active, slot_request):
            if a:
                assert r is not None and r.slot in mine, \
                    "dispatch covers a slot outside its microbatch group"
        rec = _Dispatch(microbatch=i, dispatch_cycle=self.cycle,
                        active=np.asarray(active, bool).copy(),
                        slot_request=list(slot_request),
                        nonces=np.asarray(nonces).copy(),
                        positions=np.asarray(positions).copy())
        self.inflight[i] = rec
        return rec

    def mark_exit(self, microbatch: int) -> _Dispatch:
        """Last-stage forward done, sampling dispatched: fix the commit
        cycle = the microbatch's next stage-1 re-entry."""
        rec = self.inflight[microbatch]
        assert rec.exit_cycle is None, "microbatch exited twice"
        assert self.stage_for(self.cycle, self.p - 1) == microbatch, \
            "last stage ran off-schedule"
        rec.exit_cycle = self.cycle
        due = (microbatch - self.cycle) % self.M
        rec.commit_due = self.cycle + (due or self.M)
        return rec

    def commit(self, microbatch: int) -> _Dispatch:
        rec = self.inflight.pop(microbatch)
        assert rec.exit_cycle is not None, \
            "token committed before the last-stage forward"
        assert self.cycle >= rec.commit_due, \
            "token committed before its microbatch's re-entry cycle"
        assert self.cycle == rec.commit_due, \
            "commit missed the re-entry cycle it was due at"
        return rec

    def tick(self) -> None:
        self.cycle += 1


@dataclass
class _Microbatch:
    """Per-microbatch device-side state between cycles."""

    x: Optional[jnp.ndarray] = None          # activation awaiting stage_next
    stage_next: int = 0
    ticket: Optional[SampleTicket] = None    # pending host-sampled tokens
    ready: Optional[PoolResult] = None       # baseline: sampled synchronously
    block_table: Optional[jnp.ndarray] = None    # paged: (R, MB) snapshot


class PipelineEngine:
    """Microbatched ``p``-stage pipeline engine with disaggregated
    sampling (DESIGN.md §12). Drop-in for :class:`Engine` on the service
    surface: ``submit`` / ``step`` / ``run`` / ``flush`` / ``generate``.
    """

    def __init__(self, model_cfg: ModelConfig, params,
                 engine_cfg: PipelineConfig, hot_set=None,
                 telemetry: Optional[Telemetry] = None):
        # first, before anything can raise: see Engine.__init__ — the
        # public-API lock for concurrent consumers (the gateway fleet)
        # and the closed flag for idempotent/half-constructed close()
        self._api_lock = threading.RLock()
        self._closed = False
        self.cfg = model_cfg
        self.ecfg = engine_cfg
        p = engine_cfg.stages
        M = engine_cfg.microbatches or p
        B = engine_cfg.max_batch
        assert model_cfg.family in ("dense", "moe") \
            and not model_cfg.is_encdec and not model_cfg.sliding_window, \
            "PipelineEngine: full-causal dense/moe decoders only"
        assert engine_cfg.prompt_chunk == 0, \
            "PipelineEngine: chunked prefill not supported (prompts " \
            "prefill through all stages in one program)"
        assert B % M == 0, f"max_batch={B} must divide into M={M} microbatches"
        self.p, self.M, self.R = p, M, B // M
        self.num_slots = B
        self.model = Model(model_cfg)
        self.params = params
        self.bounds = stage_bounds(model_cfg.num_layers, p)
        # stage-sliced parameters; the tied embedding table is replicated on
        # the first stage (input embed) and the last (LM head)
        self.stage_params: List[dict] = []
        for s, (lo, hi) in enumerate(self.bounds):
            sp = {"stack": slice_stage_params(params["stack"], lo, hi,
                                              last=(s == p - 1))}
            if s == 0 or s == p - 1:
                sp["emb"] = params["emb"]
            self.stage_params.append(sp)
        self.decision = DecisionPlane(
            model_cfg.vocab_size, algorithm=engine_cfg.algorithm,
            shvs=engine_cfg.shvs, hot_set=hot_set,
            sampling_parallelism=engine_cfg.sampling_parallelism,
            k_cap=min(engine_cfg.k_cap, model_cfg.vocab_size),
            seed=engine_cfg.seed)
        # the unified decision-plane client (§13): "host" ships last-stage
        # logits to the CPU sampler pool; "device" samples synchronously on
        # the last stage's critical path (the paper's baseline, Eq. 4)
        # "adaptive" (§15) starts on host — the pipeline's structural win
        # (Eq. 4: synchronous sampling caps the cycle) — and lets the
        # controller fall back to device / resize the pool online
        self._adaptive = engine_cfg.sampler_mode == "adaptive"
        # telemetry plane (§17): shared tracer/metrics wiring with Engine —
        # the tracer rides into the pool workers via the client
        self.obs = telemetry if telemetry is not None else Telemetry()
        self.tracer = self.obs.tracer
        self._metrics = EngineMetrics(self.obs.metrics)
        self.client = DecisionPlaneClient(
            self.decision,
            "host" if self._adaptive else engine_cfg.sampler_mode,
            engine_cfg.samplers, tracer=self.tracer)
        self.pool = self.client.pool
        self._metrics.mode_host.set(1.0 if self.client.is_host else 0.0)
        self._metrics.pool_workers.set(float(engine_cfg.samplers))
        self.planner = MicrobatchPlanner(p, M, self.R)
        S = engine_cfg.max_seq_len
        self._paged = engine_cfg.cache == "paged"
        assert engine_cfg.cache in ("contiguous", "paged"), engine_cfg.cache
        kv_gate = None
        if self._paged:
            bs = engine_cfg.block_size
            assert S % bs == 0, (
                f"max_seq_len={S} must be a multiple of block_size={bs}")
            mb = S // bs
            self.pcfg = PagedCacheConfig(
                block_size=bs,
                num_blocks=engine_cfg.num_blocks or B * mb,
                max_blocks_per_seq=mb)
            self.alloc = BlockAllocator(self.pcfg, B)
            self._slot_len = np.zeros((B,), np.int64)
            kv_gate = self._kv_gate
            # per-stage layer-sliced pools, shared across microbatches (the
            # block pool is a global resource; block ids are stage-invariant)
            full = init_paged_cache(model_cfg, self.R, self.pcfg)
            self.pools = [{"k_pool": full["k_pool"][lo:hi],
                           "v_pool": full["v_pool"][lo:hi]}
                          for lo, hi in self.bounds]
            self.caches = [[{"len": jnp.zeros((self.R,), jnp.int32),
                             "pos": jnp.zeros((), jnp.int32)}
                            for _ in range(M)] for _ in range(p)]
        else:
            full = self.model.init_cache(self.R, S)
            # jnp arrays are immutable and every update is functional, so
            # microbatches may share the initial zero slices
            self.caches = [[slice_stage_cache(full, lo, hi)
                            for _ in range(M)] for lo, hi in self.bounds]
        self.scheduler = Scheduler(
            B, prompt_chunk=0,
            priority_admission=engine_cfg.priority_admission,
            max_admission_wait=engine_cfg.max_admission_wait,
            max_prompt=engine_cfg.max_seq_len,
            kv_gate=kv_gate, on_free=self._on_slot_free)
        V = model_cfg.vocab_size
        self._mb = [_Microbatch() for _ in range(M)]
        self.pstate: List[pen.PenaltyState] = [
            self.decision.init_state(self.R) for _ in range(M)]
        self.last_tokens = [np.zeros((self.R,), np.int32) for _ in range(M)]
        self._sp = [SlotParams(self.R, V) for _ in range(M)]
        self._nonce = [np.zeros((self.R,), np.uint32) for _ in range(M)]
        self._pos = [np.zeros((self.R,), np.int32) for _ in range(M)]
        self._stage_jits = [jax.jit(self._make_stage_impl(s))
                            for s in range(p)]
        self._prefill_cache: Dict[Tuple, callable] = {}
        self._admit_decide_jit = admission_decision(self.decision)
        self._draining = False
        # bounded typed flight logs (§17): StepRecord per commit,
        # CycleRecord per cycle — a long-lived replica keeps a window
        self.stats_log: Deque[StepRecord] = deque(
            maxlen=engine_cfg.stats_window)
        self.cycle_log: Deque[CycleRecord] = deque(
            maxlen=engine_cfg.stats_window)
        self._cycle_rec: Optional[CycleRecord] = None
        self._dpc = None
        if self._adaptive:
            from repro.core.autotune import DecisionPlaneController
            self._dpc = DecisionPlaneController(
                mode=self.client.mode, samplers=engine_cfg.samplers,
                queue_high=float(B))

    # -- jitted stage body ---------------------------------------------------
    def _make_stage_impl(self, s: int):
        first, last = s == 0, s == self.p - 1

        def impl(stage_params, inputs, cache, active):
            lens0 = cache["len"]
            out, cache = self.model.decode_stage(
                stage_params, inputs, cache, first=first, last=last)
            # inactive rows must not advance their cache write offset
            cache = dict(cache)
            cache["len"] = jnp.where(active, lens0 + 1, lens0)
            return out, cache

        return impl

    # -- paged bookkeeping (reserving admission; DESIGN.md §12) --------------
    def _blocks_for(self, req: Request) -> int:
        total = min(req.prompt_len + req.max_new_tokens,
                    self.ecfg.max_seq_len)
        return self.alloc.blocks_needed(total)

    def _kv_gate(self, req: Request, round_admits: List[Request]) -> bool:
        """Reserving admission: a request enters only when its worst-case
        block demand fits net of every running request's *outstanding*
        worst case (demand minus blocks already owned). Under this gate
        lazy growth can never exhaust the pool, so in-flight microbatches
        never need preemption."""
        reserved = sum(self._blocks_for(r) for r in round_admits)
        for r in self.scheduler.slots:
            # requests admitted earlier THIS round are already slotted (the
            # scheduler installs before gating the next candidate) but own
            # no blocks yet — they are counted once via round_admits above
            if r is None or any(r is a for a in round_admits):
                continue
            reserved += self._blocks_for(r) - len(self.alloc.owned[r.slot])
        return self._blocks_for(req) <= self.alloc.num_free - reserved

    def _on_slot_free(self, slot: int, req: Request) -> None:
        i, local = divmod(slot, self.R)
        self._sp[i].reset_row(local)
        if self._paged:
            self.alloc.release(slot)
            self._slot_len[slot] = 0

    # -- public API ----------------------------------------------------------
    @locked_api
    def submit(self, requests: List[Request]) -> None:
        if self._closed:
            raise RuntimeError("PipelineEngine is closed")
        for r in requests:
            if r.kv_payload is not None:
                # KV migration (DESIGN.md §18) targets the single-stage
                # engine: the pipeline's per-stage cache shards have no
                # import seam yet — refuse loudly instead of silently
                # re-prefilling a payload-carrying request
                raise ValueError(
                    f"request {r.request_id} carries a KVPayload; "
                    "PipelineEngine does not support KV import — "
                    "route migrations to a single-stage Engine")
        if self._paged:
            for r in requests:
                if self._blocks_for(r) > self.pcfg.num_blocks:
                    raise ValueError(
                        f"request {r.request_id} needs {self._blocks_for(r)} "
                        f"KV blocks > pool of {self.pcfg.num_blocks}")
        for r in requests:
            self.scheduler.submit(r)

    @property
    def in_flight(self) -> int:
        """Microbatches with an uncommitted token (activation mid-pipeline
        or sampled tokens awaiting their re-entry commit)."""
        return sum(1 for mb in self._mb
                   if mb.x is not None or mb.ticket is not None
                   or mb.ready is not None)

    @locked_api
    def step(self) -> dict:
        """Advance the pipeline by ONE cycle: every stage serves its
        scheduled microbatch, the re-entering microbatch commits its
        pending token and dispatches the next. Returns the commit's
        observability stats (empty dict when no commit landed)."""
        c = self.planner.cycle
        self._cycle_rec = CycleRecord(cycle=c, busy=[None] * self.p)
        rec = {}
        for s in range(self.p - 1, -1, -1):
            i = self.planner.stage_for(c, s)
            mb = self._mb[i]
            if s == 0:
                rec = self._reenter(i) or rec
            elif mb.x is not None and mb.stage_next == s:
                self._run_stage(i, s)
        self.cycle_log.append(self._cycle_rec)
        self._cycle_rec = None
        self.planner.tick()
        return rec

    @locked_api
    def flush(self) -> None:
        """Drain every in-flight microbatch (no new admissions) and retire
        what finished."""
        self._draining = True
        try:
            guard = 2 * (self.M + self.p) + 4
            while self.in_flight and guard:
                self.step()
                guard -= 1
            assert not self.in_flight, "flush failed to drain the pipeline"
        finally:
            self._draining = False
        self.scheduler.retire_finished()

    def run(self, max_steps: int = 50_000) -> List[Request]:
        steps = 0
        while (self.scheduler.has_work or self.in_flight) and \
                steps < max_steps:
            self.step()
            steps += 1
        self.flush()
        return self.scheduler.finished

    def generate(self, requests: List[Request], max_steps: int = 50_000):
        """Stream :class:`GenerationEvent` items at commit time — the same
        client surface as :meth:`Engine.generate` (DESIGN.md §11)."""
        yield from generate_stream(self, requests, max_steps)

    def close(self) -> None:
        """Commit every in-flight microbatch, then shut down the
        decision-plane client's sampler pool — the same contract as
        :meth:`Engine.close`, so sampled-but-uncommitted tokens are never
        silently dropped. Idempotent and safe after a failed startup
        (missing attributes are skipped), matching :meth:`Engine.close`:
        fleet shutdown paths double-close replicas."""
        if getattr(self, "_closed", False):
            return
        lock = getattr(self, "_api_lock", None)
        if lock is None:
            self._closed = True
            return
        with lock:
            if self._closed:
                return
            self._closed = True
            if getattr(self, "scheduler", None) is not None and \
                    getattr(self, "_mb", None) is not None:
                self.flush()
            client = getattr(self, "client", None)
            if client is not None:
                client.close()

    # -- cycle internals ----------------------------------------------------
    def _reenter(self, i: int) -> Optional[dict]:
        """Microbatch ``i``'s stage-1 re-entry: commit its pending token,
        run scheduling for its slot group, and dispatch the next token."""
        mb = self._mb[i]
        rec = None
        if mb.ticket is not None or mb.ready is not None:
            rec = self._commit(i)
        if self._draining:
            return rec
        plan = self.scheduler.schedule(group=self.planner.group_slots(i))
        if plan.new_requests:
            self._admit_group(i, plan.new_requests)
        active = self._group_activity(i)
        if self._paged and active.any():
            active = self._prepare_paged_group(i, active)
        if not active.any():
            return rec
        group = self.planner.group_slots(i)
        slot_request = [self.scheduler.slots[g] for g in group]
        self.planner.dispatch(i, active, slot_request,
                              self._nonce[i], self._pos[i])
        self._pos[i] += active
        if self._paged:
            self._slot_len[list(group)] += active
        self._run_stage(i, 0, active)
        return rec

    def _group_activity(self, i: int) -> np.ndarray:
        out = np.zeros((self.R,), bool)
        for local, slot in enumerate(self.planner.group_slots(i)):
            s = self.scheduler.slots[slot]
            out[local] = (s is not None
                          and s.state is RequestState.RUNNING
                          and not s.should_stop())
        return out

    def _prepare_paged_group(self, i: int, active: np.ndarray) -> np.ndarray:
        """Grow each decoding row's allocation by one token (infallible
        under the reserving gate) and snapshot the group's block table for
        the whole traversal. Rows at per-sequence capacity stop with
        ``finish_reason="truncated"`` instead of crashing."""
        active = active.copy()
        group = list(self.planner.group_slots(i))
        for local, slot in enumerate(group):
            if not active[local]:
                continue
            if int(self._slot_len[slot]) + 1 > self.ecfg.max_seq_len:
                self.scheduler.slots[slot].truncated = True
                active[local] = False
                continue
            self.alloc.ensure(slot, int(self._slot_len[slot]) + 1)
        self._mb[i].block_table = jnp.asarray(
            self.alloc.table(self.num_slots)[group])
        return active

    def _stage_cache(self, s: int, i: int) -> dict:
        cache = dict(self.caches[s][i])
        if self._paged:
            cache["k_pool"] = self.pools[s]["k_pool"]
            cache["v_pool"] = self.pools[s]["v_pool"]
            cache["block_table"] = self._mb[i].block_table
        return cache

    def _store_stage_cache(self, s: int, i: int, cache: dict) -> None:
        if self._paged:
            self.pools[s]["k_pool"] = cache.pop("k_pool")
            self.pools[s]["v_pool"] = cache.pop("v_pool")
            cache.pop("block_table", None)
        self.caches[s][i] = cache

    def _run_stage(self, i: int, s: int,
                   active: Optional[np.ndarray] = None) -> None:
        mb = self._mb[i]
        rec = self.planner.inflight[i]
        if active is None:
            active = rec.active
        inputs = jnp.asarray(self.last_tokens[i]) if s == 0 else mb.x
        # one timeline row per stage: overlap between stage rows and the
        # pool workers' host_sample rows is the paper's Eq. 4 win
        with self.tracer.phase("stage", name=f"s{s}/mb{i}",
                               track=f"stage{s}", microbatch=i, stage=s,
                               cycle=self.planner.cycle) as ph:
            out, cache = self._stage_jits[s](
                self.stage_params[s], inputs, self._stage_cache(s, i),
                jnp.asarray(active))
            out.block_until_ready()      # honest per-stage busy time
        self._store_stage_cache(s, i, dict(cache))
        if self._cycle_rec is not None:
            self._cycle_rec.busy[s] = ph.t1 - ph.t0
        if s == self.p - 1:
            mb.x = None
            mb.stage_next = 0
            self.planner.mark_exit(i)
            self._dispatch_sampling(i, out, rec)
        else:
            mb.x = out
            mb.stage_next = s + 1

    def _dispatch_sampling(self, i: int, logits, rec: _Dispatch) -> None:
        """Hand the exit logits to the decision plane: asynchronously to
        the host sampler pool (disaggregated), or synchronously on the
        last stage's critical path (baseline, Eq. 4)."""
        mb = self._mb[i]
        sp = self._sp[i]
        args = (logits, self.pstate[i], sp.as_params(), sp.bias_array(),
                rec.nonces, rec.positions, rec.exit_cycle,
                rec.active)
        if not self.client.is_host:
            # Eq. 4 baseline: the draw sits ON the last stage's row, right
            # where it blocks the cycle
            with self.tracer.phase("host_sample", name=f"sync-sample/mb{i}",
                                   track=f"stage{self.p - 1}",
                                   microbatch=i) as ph:
                mb.ready = self.client.sample_sync(*args)
            dt = ph.t1 - ph.t0
            if self._cycle_rec is not None:
                self._cycle_rec.sample = dt
                if self._cycle_rec.busy[self.p - 1] is not None:
                    self._cycle_rec.busy[self.p - 1] += dt
        else:
            mb.ticket = self.client.submit(*args)

    def _commit(self, i: int) -> StepRecord:
        """Commit microbatch ``i``'s sampled token at its re-entry cycle;
        the block on the ticket is the measured sampler-pool stall."""
        mb = self._mb[i]
        rec = self.planner.commit(i)
        if mb.ready is not None:
            res, mb.ready = mb.ready, None
            stall = 0.0
        else:
            with self.tracer.phase("pool_stall", name=f"stall/mb{i}",
                                   microbatch=i,
                                   cycle=self.planner.cycle) as ph:
                res = mb.ticket.result()
            stall = ph.t1 - ph.t0
            mb.ticket = None
        if self._cycle_rec is not None:
            self._cycle_rec.stall = stall
            self._cycle_rec.sampler = res.sampler_time
            self._cycle_rec.transfer = res.transfer_time
        with self.tracer.phase("commit", name=f"commit/mb{i}", microbatch=i,
                               cycle=self.planner.cycle):
            return self._commit_result(i, rec, res, stall)

    def _commit_result(self, i: int, rec: _Dispatch, res,
                       stall: float) -> StepRecord:
        """Commit microbatch ``i``'s drawn tokens to request state and its
        record to the stats stream, the controller and the metrics."""
        now = time.perf_counter()
        self.scheduler.commit(res.tokens, rec.slot_request, rec.active,
                              now=now)
        self.pstate[i] = res.state
        self.last_tokens[i] = np.where(rec.active, res.tokens, 0).astype(
            np.int32)
        out = StepRecord(
            step=rec.dispatch_cycle, batch=int(rec.active.sum()),
            accept_rate=res.accept_rate, alpha_mean=res.alpha_mean,
            fallback_rate=res.fallback_rate, stall_ms=stall * 1e3,
            sampler_ms=res.sampler_time * 1e3,
            transfer_ms=res.transfer_time * 1e3,
            queue_depth=float(len(self.scheduler.waiting)),
            queue_delay_ms=self._queue_delay_ms(),
            bubble_frac=self._last_bubble())
        self.stats_log.append(out)
        if self._dpc is not None:
            act = self._dpc.observe_record(out)
            if act:
                # the client drains outstanding tickets before re-routing /
                # recycling the executor; per-microbatch tickets already
                # resolved keep their results, so every in-flight
                # microbatch still commits under its dispatch placement
                if act.samplers is not None:
                    self.client.resize_pool(act.samplers)
                    out.samplers = act.samplers
                    self._metrics.pool_workers.set(float(act.samplers))
                if act.sampler_mode is not None:
                    self.client.set_mode(act.sampler_mode)
                    out.sampler_mode = act.sampler_mode
                    self._metrics.mode_host.set(
                        1.0 if self.client.is_host else 0.0)
                self._metrics.decisions.inc()
                if self.tracer.enabled:
                    self.tracer.instant(
                        "decision", name=f"decision/mb{i}",
                        cycle=self.planner.cycle, hot_size=act.hot_size,
                        samplers=act.samplers,
                        sampler_mode=act.sampler_mode)
        self._metrics.observe_step(out)
        return out

    def _queue_delay_ms(self) -> float:
        """Oldest waiting request's queueing delay (the §15 controller's
        primary saturation signal); NaN when arrivals carry no wall-clock
        stamps."""
        if not self.scheduler.waiting:
            return 0.0
        now = time.perf_counter()
        ds = [now - r.arrival_time
              for r in self.scheduler.waiting if r.arrival_time]
        return max(ds) * 1e3 if ds else float("nan")

    def _last_bubble(self) -> float:
        """Bubble fraction of the most recent FULL cycle (every stage
        timed), Eq. 4's ``Σ_s (C − busy_s) / (p·C)``; NaN during fill.
        Walks the deque newest-first (no slicing — cycle_log is a bounded
        ring) and gives up after 2·M cycles, matching the old window."""
        for n, r in enumerate(reversed(self.cycle_log)):
            if n >= 2 * self.M:
                break
            if r.full:
                busy = np.asarray(r.busy, float)
                busy[0] += r.stall
                C = float(busy.max())
                if C > 0:
                    return float((C - busy).sum() / (self.p * C))
        return float("nan")

    # -- admission -----------------------------------------------------------
    def _prefill_impl(self, params, tokens, true_lens):
        """Monolithic prefill over the FULL stack (a prompt traverses all
        stages in one program — composition-identical to per-stage
        prefill); rows are stage-split on insert."""
        P, Sp = tokens.shape
        cache = self.model.init_cache(P, self.ecfg.max_seq_len)
        logits, cache = self.model.prefill(params, {"tokens": tokens}, cache,
                                           true_lens=true_lens)
        pstate = pen.init_state(P, self.cfg.vocab_size, tokens, true_lens)
        return logits, cache, pstate

    def _admit_group(self, i: int, new_requests: List[Request]) -> None:
        """Prefill newly admitted requests for microbatch ``i`` and install
        the rows into its per-stage caches — the admission math is shared
        with :meth:`Engine._admit` (``engine.prefill_new_rows``), so the
        engines' bit-identity cannot drift; only the install targets one
        slot group here."""
        ctxs, Sp = admission_shape(self, new_requests)
        with self.tracer.phase("prefill",
                               name=f"prefill x{len(new_requests)}/mb{i}",
                               rows=len(new_requests), padded=Sp,
                               tokens=sum(len(c) for c in ctxs),
                               microbatch=i):
            self._prefill_group(i, new_requests)

    def _prefill_group(self, i: int, new_requests: List[Request]) -> None:
        tr = self.tracer
        if tr.enabled:
            t_pf = time.perf_counter()
            for r in new_requests:
                if r.arrival_time:
                    tr.add("queue_wait", r.arrival_time, t_pf,
                           name=f"wait#{r.request_id}",
                           request_id=int(r.request_id), microbatch=i)
        first, rows_cache, rows_pstate, lens, bases, rids = \
            prefill_new_rows(self, new_requests, self.planner.cycle)
        with tr.phase("admit_insert", rows=len(new_requests)):
            self._insert_group(i, new_requests, rows_cache, rows_pstate,
                               lens)
        with tr.phase("admit_fetch", rows=len(new_requests)):
            first_np = np.asarray(first)
        # stamped once the first tokens are on the host (Engine._admit)
        now = time.perf_counter()
        base_slot = i * self.R
        for k, r in enumerate(new_requests):
            local = r.slot - base_slot
            self._sp[i].set_row(local, r.sampling)
            self._nonce[i][local] = rids[k]
            self._pos[i][local] = int(bases[k]) + 1
            self.last_tokens[i][local] = int(first_np[k])
            r.record_token(int(first_np[k]), now)

    def _insert_group(self, i: int, new_requests: List[Request], rows_cache,
                      rows_pstate, lens: np.ndarray) -> None:
        """Install freshly prefilled rows into microbatch ``i``'s per-stage
        caches and penalty state."""
        base_slot = i * self.R
        locals_ = np.asarray([r.slot - base_slot for r in new_requests],
                             np.int32)
        slots_j = jnp.asarray(locals_)
        if self._paged:
            self._paged_insert_group(i, new_requests, rows_cache, lens,
                                     locals_)
        else:
            for s, (lo, hi) in enumerate(self.bounds):
                rows_s = {"k": rows_cache["k"][lo:hi],
                          "v": rows_cache["v"][lo:hi],
                          "len": rows_cache["len"], "pos": rows_cache["pos"]}
                self.caches[s][i] = _insert_rows(self.caches[s][i], rows_s,
                                                 slots_j)
        # the group's state may sit on the host pool's CPU device
        rows_pstate = jax.device_put(
            rows_pstate, next(iter(self.pstate[i].prompt_counts.devices())))
        self.pstate[i] = pen.PenaltyState(
            prompt_counts=self.pstate[i].prompt_counts.at[slots_j].set(
                rows_pstate.prompt_counts),
            output_counts=self.pstate[i].output_counts.at[slots_j].set(
                rows_pstate.output_counts))

    def _paged_insert_group(self, i: int, new_requests: List[Request],
                            rows_cache, lens: np.ndarray,
                            locals_: np.ndarray) -> None:
        """Scatter freshly prefilled rows into every stage's pool slice
        (block ids are stage-invariant, so one destination map serves all
        stages)."""
        for k, r in enumerate(new_requests):
            self.alloc.release(r.slot)         # stale claims (defensive)
            self.alloc.ensure(r.slot, int(lens[k]))
            self._slot_len[r.slot] = int(lens[k])
        row_bt = jnp.asarray(
            self.alloc.table(self.num_slots)[[r.slot for r in new_requests]])
        Sc = rows_cache["k"].shape[2]
        true_lens = jnp.asarray(lens)
        valid = jnp.arange(Sc)[None, :] < true_lens[:, None]
        flat = flat_block_indices(row_bt, jnp.zeros_like(true_lens), valid,
                                  self.pcfg.block_size, self.pcfg.num_blocks)
        key = ("paged_insert", len(new_requests))
        if key not in self._prefill_cache:
            self._prefill_cache[key] = jax.jit(
                lambda pool, rows, f: scatter_block_kv(pool, rows, f))
        scatter = self._prefill_cache[key]
        for s, (lo, hi) in enumerate(self.bounds):
            self.pools[s]["k_pool"] = scatter(
                self.pools[s]["k_pool"], rows_cache["k"][lo:hi], flat)
            self.pools[s]["v_pool"] = scatter(
                self.pools[s]["v_pool"], rows_cache["v"][lo:hi], flat)
            self.caches[s][i] = dict(self.caches[s][i])
            self.caches[s][i]["len"] = \
                self.caches[s][i]["len"].at[jnp.asarray(locals_)].set(
                    true_lens)

    # -- observability -------------------------------------------------------
    def pipeline_report(self) -> dict:
        """Aggregate the cycle log into the paper's Eq. 4 quantities,
        measured: steady-state cycle time ``C = max_s busy_s`` (baseline:
        the last stage's busy includes the synchronous sampling; the
        stage-1 slot includes any sampler-pool stall), per-stage
        utilization ``busy_s / C``, and the bubble fraction
        ``Σ_s (C − busy_s) / (p·C)``. Only *full* cycles — every stage
        served a microbatch — count (the fill/drain ramp is excluded, as
        in Eq. 4's steady-state regime)."""
        full = [r for r in self.cycle_log if r.full]
        if not full:
            return {"cycles": 0, "bubble_frac": 0.0,
                    "stage_util": [0.0] * self.p, "mean_cycle_ms": 0.0,
                    "stall_ms_mean": 0.0, "sample_ms_mean": 0.0,
                    "sampler_ms_mean": 0.0, "transfer_ms_mean": 0.0}
        busy = np.zeros((len(full), self.p))
        for k, r in enumerate(full):
            busy[k] = r.busy
            busy[k][0] += r.stall
        C = busy.max(axis=1)
        bubble = (C[:, None] - busy).sum() / (self.p * C.sum())
        samplers = [r.sampler for r in full if r.sampler is not None]
        transfers = [r.transfer for r in full if r.transfer is not None]
        return {
            "cycles": len(full),
            "bubble_frac": float(bubble),
            "stage_util": [float(u) for u in busy.sum(0) / C.sum()],
            "mean_cycle_ms": float(C.mean() * 1e3),
            "stall_ms_mean": float(np.mean([r.stall for r in full]) * 1e3),
            "sample_ms_mean": float(np.mean([r.sample for r in full]) * 1e3),
            # pool-side decomposition (§13): sampler_ms is pure CPU
            # sampling on the workers' critical path; transfer_ms is the
            # device_get wait (in-flight compute + D2H) — previously
            # conflated, which overstated the pool's cost in the bubble
            # accounting
            "sampler_ms_mean": float(np.mean(samplers) * 1e3) if samplers
            else 0.0,
            "transfer_ms_mean": float(np.mean(transfers) * 1e3) if transfers
            else 0.0,
        }
