"""Host sampler pool unit suite (DESIGN.md §13): stall accounting and
pooled-stat weighting — the measurement bugs that would otherwise poison
the latency/bubble numbers.

* ``sampler_time`` must exclude the ``device_get`` wait: a worker's clock
  on the sampling critical path starts only after its fetch completes, and
  the wait is reported separately as ``transfer_time``.
* Pooled stats (``accept_rate`` / ``alpha_mean`` / ``fallback_rate``) must
  be weighted by ACTIVE rows per shard, not shard width — a mostly-drained
  microbatch's empty shards would otherwise skew the ``alpha_mean`` that
  feeds the SHVS autotuner.
"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SamplingConfig
from repro.core import penalties as pen
from repro.core.decision_plane import DecisionPlane
from repro.core.host_sampler import (HostSamplerPool, _pool_stats,
                                     _ShardResult)
from repro.core.sampling import SamplingParams


def _pool(V=64, workers=2, algorithm="reference"):
    return HostSamplerPool(DecisionPlane(V, algorithm=algorithm, k_cap=32,
                                         seed=0), workers)


def _inputs(B=8, V=64, active=None, seed=0):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(0, 2, (B, V)).astype(np.float32))
    state = pen.PenaltyState(
        prompt_counts=jnp.zeros((B, V), jnp.int32),
        output_counts=jnp.zeros((B, V), jnp.int32))
    params = SamplingParams.broadcast(B, SamplingConfig(
        temperature=0.9, top_k=16))
    if active is None:
        active = np.ones((B,), bool)
    return (logits, state, params, None, np.arange(B, dtype=np.uint32),
            np.zeros((B,), np.int32), 0, np.asarray(active, bool))


class TestStallAccounting:
    def test_sampler_time_excludes_delayed_fetch(self):
        """The acceptance bar (ISSUE 5): submit logits whose fetch is
        deliberately delayed — blocking on the in-flight computation must
        land in ``transfer_time``, never in ``sampler_time``. (The CPU
        backend dispatches callbacks synchronously, so the delay is
        injected at the pool's fetch seam — the exact boundary the
        original bug mis-timed.)"""
        pool = _pool(workers=2)
        delay = 0.15
        orig = pool._fetch

        def slow_fetch(logits, lo, hi):
            time.sleep(delay)          # stand-in for in-flight device work
            return orig(logits, lo, hi)

        try:
            args = _inputs()
            pool.submit(*args).result()   # compile outside the timed draw
            pool._fetch = slow_fetch
            res = pool.submit(*args).result()
        finally:
            pool.close()
        assert res.transfer_time >= delay, res
        assert res.sampler_time < delay, (
            f"sampler_time={res.sampler_time:.3f}s still includes the "
            f"{delay}s fetch wait — the clock must start after device_get")

    def test_sync_and_async_report_both_components(self):
        pool = _pool(workers=3)
        try:
            args = _inputs()
            for res in (pool.sample_sync(*args), pool.submit(*args).result()):
                assert res.transfer_time >= 0.0
                assert res.sampler_time > 0.0
                assert res.active_rows == 8
        finally:
            pool.close()


class TestActiveRowWeighting:
    def _shard(self, stats, rows, width_unused=None):
        return _ShardResult(
            tokens=np.zeros((4,), np.int32),
            state=pen.PenaltyState(prompt_counts=jnp.zeros((4, 8), jnp.int32),
                                   output_counts=jnp.zeros((4, 8), jnp.int32)),
            stats=stats, active_rows=rows, transfer_time=0.0,
            sampler_time=1e-4)

    def test_weights_are_active_rows_not_width(self):
        # shard A: 4 active rows, accept 1.0; shard B: 1 active row (of the
        # same width), accept 0.0 -> pooled accept = 4/5, not 1/2
        parts = [self._shard((1.0, 1.0, 0.0), 4),
                 self._shard((0.0, 0.5, 1.0), 1)]
        stats = _pool_stats(parts)
        assert stats["accept_rate"] == pytest.approx(0.8)
        assert stats["alpha_mean"] == pytest.approx((4 * 1.0 + 0.5) / 5)
        assert stats["fallback_rate"] == pytest.approx(0.2)

    def test_zero_active_shard_carries_no_weight_even_when_nan(self):
        parts = [self._shard((0.25, 0.5, 0.75), 3),
                 self._shard((float("nan"),) * 3, 0)]
        stats = _pool_stats(parts)
        assert stats["accept_rate"] == pytest.approx(0.25)
        assert stats["alpha_mean"] == pytest.approx(0.5)
        assert stats["fallback_rate"] == pytest.approx(0.75)

    def test_all_inactive_is_nan_safe(self):
        stats = _pool_stats([self._shard((float("nan"),) * 3, 0)])
        assert all(np.isnan(v) for v in stats.values())
        # the autotuner's contract: non-finite observations are ignored
        from repro.core.autotune import HotSizeController
        ctl = HotSizeController(vocab_size=1024, h_current=256)
        assert ctl.observe(stats["alpha_mean"]) is None
        assert ctl._alpha_ewma is None

    def test_pool_end_to_end_matches_active_weighting(self):
        """2 workers, second shard fully drained: pooled stats must equal
        the first shard's alone (and carry no NaN)."""
        pool = _pool(workers=2)
        try:
            active = np.zeros((8,), bool)
            active[:4] = True          # shard 2 (rows 4..8) fully inactive
            res = pool.submit(*_inputs(active=active)).result()
            full = pool.sample_sync(*_inputs(active=active))
        finally:
            pool.close()
        assert res.active_rows == 4
        for v in (res.accept_rate, res.alpha_mean, res.fallback_rate):
            assert np.isfinite(v)
        # the same draw, sharded or full-width, commits identical tokens
        np.testing.assert_array_equal(res.tokens, full.tokens)


def test_refresh_rejits_worker_program():
    pool = _pool(workers=1)
    try:
        before = pool._step_jit
        pool.refresh()
        assert pool._step_jit is not before
    finally:
        pool.close()


class TestPlacement:
    def test_pool_step_runs_on_a_cpu_device(self):
        """``submit`` commits every operand to the host CPU device, so the
        jitted step — and the state it returns — live there whatever the
        default device is; ``sample_sync`` stays with the logits."""
        pool = _pool(workers=2)
        try:
            assert pool.device.platform == "cpu"
            res = pool.submit(*_inputs()).result()
            sync = pool.sample_sync(*_inputs())
        finally:
            pool.close()
        for leaf in (res.state.prompt_counts, res.state.output_counts):
            assert leaf.devices() == {pool.device}
            assert leaf.committed
        assert sync.state.output_counts.devices() == \
            _inputs()[0].devices()

    def test_pallas_backend_refused_on_an_accelerator_host(self,
                                                           monkeypatch):
        """On a machine with an accelerator, a Pallas kernel placed on the
        pool's CPU would only run interpreted: refused at construction
        wherever host placement can happen, and at a switch to it."""
        import jax
        from repro.engine.decision_client import DecisionPlaneClient
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        shvs = DecisionPlane(64, algorithm="shvs", k_cap=32, seed=0)
        DecisionPlaneClient(shvs, "host", switchable=True).close()  # jnp: fine
        plane = DecisionPlane(64, algorithm="fused", k_cap=32, seed=0)
        for mode, switchable in (("host", False), ("device", True)):
            with pytest.raises(ValueError, match="Pallas kernel"):
                DecisionPlaneClient(plane, mode, switchable=switchable)
        client = DecisionPlaneClient(plane, "device")   # device: fine
        with pytest.raises(ValueError, match="Pallas kernel"):
            client.set_mode("host")
        assert client.mode == "device"
        client.close()
