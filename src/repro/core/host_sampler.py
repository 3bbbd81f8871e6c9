"""Host-side sampler worker pool — the disaggregated decision plane behind
``DecisionPlaneClient`` for BOTH serving engines (DESIGN.md §12/§13).

The paper's structural claim (§1, Eq. 4) is that sampling neither expands
with TP nor balances across PP stages: executed on the last stage's
accelerator it caps the pipeline frequency, idling every other stage for
``t_sampling`` each cycle. SIMPLE moves the draw to a *pool of host
samplers*: last-stage logits are ``device_get``'d and ``m`` CPU workers run
**sequence-parallel shards** (mechanism S1 applied across workers — each
worker owns a contiguous slice of the microbatch's rows, the vocabulary
replicated per shard) through the existing
:class:`~repro.core.decision_plane.DecisionPlane`, so every registered
:class:`~repro.core.sampler_backend.SamplerBackend` works unchanged.

Determinism: each row's uniforms come from the plane's counter-based
(request, position) keys and every other per-row computation — penalties,
filtering, the backend draw, the Eq. 5 histogram update — is row-local, so
the sampled stream is bit-identical for 1 worker or 64, and to the
single-stage engine's fused on-device decision (pinned by
``tests/test_pipeline_engine.py``).

The pool is deliberately synchronous-free on the submit path: ``submit``
returns a :class:`SampleTicket` immediately and the caller blocks only in
:meth:`SampleTicket.result` — which the pipeline engine calls when the
microbatch re-enters stage 1, ``(M − p)`` cycles later, and the
single-stage engine calls one overlapped step later (§13). The measured
block time is exactly the paper's "sampler pool too slow for the slack"
stall; the worker-side ``device_get`` wait and the CPU sampling itself are
accounted separately (``transfer_time`` vs ``sampler_time``).
"""
from __future__ import annotations

from concurrent.futures import Future, ThreadPoolExecutor
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import penalties as pen
from repro.core.decision_plane import DecisionPlane
from repro.obs.tracer import NULL_TRACER, StepTracer


class PoolResult(NamedTuple):
    """One microbatch's assembled sampling outcome.

    ``sampler_time`` and ``transfer_time`` are accounted separately: a
    worker's clock on the *sampling* critical path starts only after its
    ``device_get`` returns, so blocking on an in-flight forward (device
    compute + D2H transfer) can never masquerade as CPU sampling cost —
    conflating the two would poison the bubble accounting that decides
    whether the pool makes the pipeline's ``(M − p)``-cycle slack.
    """

    tokens: np.ndarray           # (R,) int32; inactive rows are 0
    state: pen.PenaltyState      # updated (R, V) histogram rows
    accept_rate: float
    alpha_mean: float
    fallback_rate: float
    sampler_time: float          # max worker CPU-sampling wall time (s) —
    #                              the pool's critical path, fetch excluded
    transfer_time: float         # max worker device_get wall time (s):
    #                              blocking on in-flight compute + D2H copy
    active_rows: int             # rows that actually sampled this call


def _shard_bounds(rows: int, workers: int) -> List[tuple]:
    """Contiguous row ranges: ``min(workers, rows)`` near-equal shards —
    the same balanced partition as the pipeline's layer split."""
    from repro.models.transformer import stage_bounds
    return stage_bounds(rows, max(1, min(workers, rows)))


class _ShardResult(NamedTuple):
    """One worker's slice of a microbatch."""

    tokens: np.ndarray
    state: pen.PenaltyState
    stats: tuple                 # (accept_rate, alpha_mean, fallback_rate)
    active_rows: int
    transfer_time: float
    sampler_time: float


class SampleTicket:
    """Pending sampled tokens for one microbatch (one future per shard).

    ``result()`` blocks until every shard worker finishes and assembles the
    full-microbatch :class:`PoolResult`; ``done`` is a non-blocking probe.
    """

    def __init__(self, futures: List[Future]):
        self._futures = futures

    @property
    def done(self) -> bool:
        return all(f.done() for f in self._futures)

    def wait(self) -> None:
        """Join every shard worker without assembling the result — the
        drain step of the client's mode-switch / resize discipline (§15):
        after this, no worker thread can still be reading the pool's
        traced program or the plane's operands."""
        for f in self._futures:
            f.result()

    def result(self) -> PoolResult:
        parts: List[_ShardResult] = [f.result() for f in self._futures]
        tokens = np.concatenate([p.tokens for p in parts])
        state = pen.PenaltyState(
            prompt_counts=jnp.concatenate(
                [p.state.prompt_counts for p in parts]),
            output_counts=jnp.concatenate(
                [p.state.output_counts for p in parts]))
        return PoolResult(tokens=tokens, state=state,
                          **_pool_stats(parts),
                          sampler_time=max(p.sampler_time for p in parts),
                          transfer_time=max(p.transfer_time for p in parts),
                          active_rows=sum(p.active_rows for p in parts))


def _pool_stats(parts: List["_ShardResult"]) -> dict:
    """Pool shard stats weighted by ACTIVE rows, not shard width.

    A mostly-drained microbatch has shards whose rows are nearly all
    inactive; width-weighting those shards' means skews the pooled
    ``alpha_mean`` that feeds the SHVS autotuner. Shards with zero active
    rows carry zero weight (their backend means are meaningless — possibly
    NaN — and must not propagate); with no active rows anywhere the stats
    are NaN, which :class:`repro.core.autotune.HotSizeController` ignores.
    """
    total = float(sum(p.active_rows for p in parts))
    if total == 0.0:
        return {"accept_rate": float("nan"), "alpha_mean": float("nan"),
                "fallback_rate": float("nan")}
    wmean = lambda idx: float(sum(
        p.active_rows * float(p.stats[idx])
        for p in parts if p.active_rows) / total)
    return {"accept_rate": wmean(0), "alpha_mean": wmean(1),
            "fallback_rate": wmean(2)}


class HostSamplerPool:
    """``m`` CPU sampler workers behind the decision-plane service.

    ``submit`` shards a microbatch's rows across the workers
    (sequence-parallel, S1) and returns a ticket; ``sample_sync`` runs the
    identical math full-width on the calling thread — the pipeline
    engine's ``device`` mode (sampling synchronously on the last stage,
    Eq. 4) and the two paths are bit-identical by construction.

    Placement: ``submit`` commits the fetched logits, the penalty state,
    the params and every other operand to the host CPU device
    (``self.device``), so the jitted step runs on the CPU whatever the
    default device is; the returned state stays there, and the owning
    engine keeps it there between steps. ``sample_sync`` moves every
    operand to the logits' own device instead.
    """

    def __init__(self, plane: DecisionPlane, num_workers: int = 2,
                 tracer: Optional[StepTracer] = None):
        self.plane = plane
        self.device = jax.devices("cpu")[0]
        self.num_workers = max(1, num_workers)
        # the owning engine's flight recorder (§17): workers record their
        # d2h_transfer / host_sample spans on their own thread tracks —
        # the Eq. 4 overlap with the engine's next forward, made visible
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._ex: Optional[ThreadPoolExecutor] = None
        self._closed = False
        self.refresh()

    def refresh(self) -> None:
        """(Re-)jit the worker-side decision program. Call after the
        plane's configuration changed under the pool — e.g. the SHVS
        autotuner swapping ``hot_set`` — since the traced program captured
        the backend as of trace time."""
        def _step(logits, state, params, bias, nonces, pos, step, active):
            tokens, state, stats = self.plane.step(
                logits, state, params, step, active=active,
                rng_tags=(nonces, pos), logit_bias=bias)
            tokens = jnp.where(active, tokens, 0)
            return tokens, state, stats

        self._step_jit = jax.jit(_step)

    # -- worker body ---------------------------------------------------------
    def _fetch(self, logits, lo: int, hi: int):
        """The disaggregation boundary: the shard's logits cross to the
        host CPU device explicitly. Blocks on any in-flight device compute
        producing them — a separate seam so that wait is timed (and
        testable) apart from the CPU sampling that follows."""
        return jax.device_put(logits[lo:hi], self.device).block_until_ready()

    def _run_shard(self, lo: int, hi: int, logits, state, params, bias,
                   nonces, pos, step, active,
                   on_host: bool = True) -> _ShardResult:
        tr = self.tracer
        # phases on this worker's thread: the profiler trace shows them
        # beside the device, and the ring (when enabled) records the very
        # stamps of the returned decomposition, so the trace and the
        # stats stream can never disagree about where the time went
        with tr.phase("d2h_transfer", name=f"fetch[{lo}:{hi}]",
                      step=int(step)) as fetch:
            shard = self._fetch(logits, lo, hi) if on_host \
                else logits[lo:hi]
        # the sampling clock starts AFTER the fetch
        with tr.phase("host_sample", name=f"sample[{lo}:{hi}]",
                      step=int(step)) as sample:
            sl = lambda a: None if a is None else a[lo:hi]
            # host: commit every operand to the CPU device (a no-op for
            # state the engine already keeps there); sync: to the logits'
            # device
            devs = shard.devices()
            dev = self.device if on_host else \
                (next(iter(devs)) if len(devs) == 1 else None)
            put = (lambda x: jax.device_put(x, dev)) if dev is not None \
                else (lambda x: jax.tree_util.tree_map(jnp.asarray, x))
            tokens, new_state, stats = self._step_jit(
                shard,
                put(jax.tree_util.tree_map(sl, state)),
                put(jax.tree_util.tree_map(sl, params)),
                None if bias is None else put(sl(bias)),
                put(nonces[lo:hi]), put(pos[lo:hi]),
                put(np.asarray(step, np.int32)), put(active[lo:hi]))
            toks = np.asarray(tokens)        # worker-side host sync
            stats_host = (float(stats.accept_rate), float(stats.alpha_mean),
                          float(stats.fallback_rate))
        return _ShardResult(tokens=toks, state=new_state, stats=stats_host,
                            active_rows=int(np.count_nonzero(active[lo:hi])),
                            transfer_time=fetch.t1 - fetch.t0,
                            sampler_time=sample.t1 - sample.t0)

    # -- client surface ------------------------------------------------------
    def submit(self, logits, state: pen.PenaltyState, params, bias,
               nonces: np.ndarray, pos: np.ndarray, step: int,
               active: np.ndarray) -> SampleTicket:
        """Dispatch one microbatch's rows to the worker shards.

        ``logits``: (R, V) device array (may still be an in-flight future —
        workers block on it, not the caller). ``nonces``/``pos``/``active``
        are host snapshots taken at the microbatch's stage-1 dispatch.
        """
        if self._closed:
            # the executor is created lazily, so without this guard a
            # submit after close() would silently restart worker threads
            # the owner believes are gone (fleet double-shutdown paths)
            raise RuntimeError("HostSamplerPool is closed")
        if self._ex is None:
            self._ex = ThreadPoolExecutor(
                max_workers=self.num_workers,
                thread_name_prefix="host-sampler")
        bounds = _shard_bounds(logits.shape[0], self.num_workers)
        futures = [self._ex.submit(self._run_shard, lo, hi, logits, state,
                                   params, bias, nonces, pos, step, active)
                   for lo, hi in bounds]
        return SampleTicket(futures)

    def sample_sync(self, logits, state, params, bias, nonces, pos, step,
                    active) -> PoolResult:
        """Full-width draw on the calling thread (the pipeline's device
        mode): the same decision program, blocking the caller's cycle on
        the result."""
        R = logits.shape[0]
        part = self._run_shard(0, R, logits, state, params, bias, nonces,
                               pos, step, active, on_host=False)
        return PoolResult(tokens=part.tokens, state=part.state,
                          **_pool_stats([part]),
                          sampler_time=part.sampler_time,
                          transfer_time=part.transfer_time,
                          active_rows=part.active_rows)

    def resize(self, num_workers: int) -> None:
        """Change the worker count online (the §15 controller's pool-sizing
        knob). Joins any in-flight shard work — ``shutdown(wait=True)``
        drains the executor's queue, and completed futures keep their
        results, so outstanding tickets still resolve — then recycles the
        executor lazily at the new width on the next submit. Bit-identity
        is untouched: sharding is row-local (S1), so the worker count can
        never move a request's stream (``test_worker_count_invariance``)."""
        n = max(1, int(num_workers))
        if n == self.num_workers:
            return
        if self._ex is not None:
            self._ex.shutdown(wait=True)
            self._ex = None
        self.num_workers = n

    def close(self) -> None:
        """Idempotent: joins in-flight shards on the first call; later
        calls (double-close from fleet shutdown paths) are no-ops."""
        self._closed = True
        if self._ex is not None:
            self._ex.shutdown(wait=True)
            self._ex = None
