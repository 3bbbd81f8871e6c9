"""Unified decision-plane client — ONE sampling seam for every engine
(DESIGN.md §13).

Both serving engines speak to the decision plane through this client, in
one of two modes:

* ``device`` — the decision executes on the accelerator, synchronous with
  the engine's own program chain. The single-stage :class:`Engine` fuses
  ``DecisionPlane.step`` into its jitted decode program (the §2 overlapped
  loop); the :class:`PipelineEngine` runs it full-width on the calling
  thread right after the last stage's forward (the paper's Eq. 4
  baseline).
* ``host`` — the paper's disaggregation: logits are ``device_get``'d and a
  :class:`~repro.core.host_sampler.HostSamplerPool` of CPU workers runs
  sequence-parallel row shards through the identical
  :class:`~repro.core.decision_plane.DecisionPlane`. ``submit`` never
  blocks; the engine collects the :class:`SampleTicket` one step (or one
  pipeline re-entry) later, so CPU sampling for step *t* overlaps the
  host-side work — and any still-in-flight device compute — of step
  *t+1*.

The two modes are bit-identical by construction: every per-row decision
computation (penalties, filters, the backend draw, the Eq. 5 histogram
update) is row-local and uniforms are keyed on (request, position), so
neither the worker sharding nor the commit timing can move any request's
stream (``tests/test_decision_client.py``, ``tests/test_pipeline_engine.py``).
"""
from __future__ import annotations

from typing import List, Optional

import jax
import numpy as np

from repro.core.decision_plane import DecisionPlane
from repro.core.host_sampler import HostSamplerPool, PoolResult, SampleTicket
from repro.obs.tracer import StepTracer

#: sampler backends whose draw is a Pallas kernel: on the CPU such a
#: kernel only runs in the Pallas interpreter
PALLAS_BACKENDS = ("fused",)

#: the client's placements (``"adaptive"`` is the engines' own: it starts
#: in one of these and a controller switches between them)
SAMPLER_MODES = ("device", "host")


def canonical_sampler_mode(mode: str) -> str:
    """Check a ``sampler_mode``: ``device`` | ``host`` pass through;
    anything else raises a ``ValueError`` listing the accepted modes."""
    if mode not in SAMPLER_MODES:
        raise ValueError(
            f"unknown sampler_mode {mode!r}; expected one of "
            f"{list(SAMPLER_MODES)}")
    return mode


class DecisionPlaneClient:
    """The engines' handle on the (possibly remote) decision plane.

    Thin by design: the sharding, RNG, and assembly live in
    :class:`HostSamplerPool`; the client owns mode selection, the worker
    pool's lifecycle, and the re-jit hook the autotuner needs. The pool's
    executor threads are started lazily on the first host-mode ``submit``,
    so a device-mode client costs nothing.

    On a machine with an accelerator, a plane whose draw is a Pallas kernel
    is refused (``ValueError``) wherever host placement can happen: at
    construction in host mode or when ``switchable`` (the adaptive
    controller may move the decision to the host later), and at
    :meth:`set_mode`. On the host CPU such a kernel could only run in the
    Pallas interpreter, far slower than on either side of the link.
    """

    def __init__(self, plane: DecisionPlane, mode: str = "device",
                 workers: int = 2, tracer: Optional[StepTracer] = None,
                 switchable: bool = False):
        self.mode = canonical_sampler_mode(mode)
        self.plane = plane
        # the engine's flight recorder rides through to the pool workers
        # (§17) so their fetch/sample spans land in the same trace
        self.pool = HostSamplerPool(plane, workers, tracer=tracer)
        if self.is_host or switchable:
            self._refuse_pallas_on_host()
        self._tickets: List[SampleTicket] = []   # outstanding host work

    @property
    def is_host(self) -> bool:
        return self.mode == "host"

    def _refuse_pallas_on_host(self) -> None:
        algorithm = self.plane.algorithm
        if algorithm in PALLAS_BACKENDS and jax.default_backend() != "cpu":
            raise ValueError(
                f"sampler backend {algorithm!r} is a Pallas kernel and "
                "cannot run on the host sampler pool's CPU of a "
                f"{jax.default_backend()} machine; use a jnp backend "
                "(e.g. 'shvs') or keep the decision on the device")

    # -- the async surface ---------------------------------------------------
    def submit(self, logits, state, params, bias, nonces: np.ndarray,
               pos: np.ndarray, step: int,
               active: np.ndarray) -> SampleTicket:
        """Dispatch one batch's sampling to the host pool (host mode).
        Never blocks: ``logits`` may still be an in-flight device future —
        the pool's workers block on it, not the caller."""
        assert self.is_host, "submit() is the host-mode path"
        ticket = self.pool.submit(logits, state, params, bias, nonces, pos,
                                  step, active)
        # track outstanding tickets so a mode switch / pool resize can
        # drain them (bounded: prune landed work — at most the engines'
        # in-flight depth, 1 step or M microbatches, survives a prune)
        self._tickets = [t for t in self._tickets if not t.done]
        self._tickets.append(ticket)
        return ticket

    def drain(self) -> None:
        """Join every outstanding ticket's shard workers. Callers that hold
        the tickets still own installing their results; this only
        guarantees no worker thread is mid-shard."""
        for t in self._tickets:
            t.wait()
        self._tickets = []

    def set_mode(self, mode: str) -> bool:
        """Re-route the sampling seam online (DESIGN.md §15): switch
        between the fused on-device decision and the host pool. Drains the
        in-flight ticket(s) BEFORE re-routing — the same join-before-re-jit
        discipline as hot-set swaps (§13) — so a dispatched step always
        completes under the placement it was dispatched with, and
        bit-identity survives mid-run switches. Returns True iff the mode
        changed. The engines' own commit bookkeeping is per-dispatch
        (``_Pending.kind`` / per-microbatch tickets), so mixed-placement
        in-flight work commits correctly on either side of the switch."""
        mode = canonical_sampler_mode(mode)
        if mode == self.mode:
            return False
        if mode == "host":
            self._refuse_pallas_on_host()
        self.drain()
        self.mode = mode
        return True

    def resize_pool(self, workers: int) -> None:
        """Resize the host sampler pool online (the §15 controller's
        second knob); drains outstanding tickets first so no in-flight
        shard is cancelled by the executor recycle."""
        self.drain()
        self.pool.resize(workers)

    def sample_sync(self, logits, state, params, bias, nonces, pos, step,
                    active) -> PoolResult:
        """Full-width draw on the calling thread — the device-mode path for
        an engine that does not fuse the decision into its forward program
        (the pipeline's last-stage Eq. 4 baseline)."""
        return self.pool.sample_sync(logits, state, params, bias, nonces,
                                     pos, step, active)

    # -- lifecycle -----------------------------------------------------------
    def refresh(self) -> None:
        """Re-jit the pool's decision program after the plane's
        configuration changed under it (the SHVS autotuner swapping
        ``hot_set`` re-shapes the backend's operands)."""
        self.pool.refresh()

    def close(self) -> None:
        """Shut down the worker pool; blocks until in-flight shards land."""
        self.pool.close()


__all__ = ["DecisionPlaneClient", "SAMPLER_MODES", "canonical_sampler_mode",
           "PoolResult", "SampleTicket"]
