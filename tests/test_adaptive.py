"""Adaptive decision-plane controller (ISSUE 7, DESIGN.md §15): policy
unit tests (placement hysteresis + dwell, geometric pool sizing, NaN-laced
observation streams, bounded decision logs) and engine-level differential
identity for ``sampler_mode="adaptive"`` on both engines.

Streams can never be at stake — placement is an execution strategy whose
streams are bit-identical by construction (§13) — so every test here is
either about the *policy* (when the controller moves) or about the
switch *discipline* (that moving is invisible in the tokens).
"""
import jax
import numpy as np
import pytest

from repro.config import ModelConfig, SamplingConfig, SHVSConfig
from repro.core.autotune import (CONTROLLER_STREAMS, ControllerAction,
                                 DecisionPlaneController, HotSizeController)
from repro.engine import Engine, EngineConfig, Request

adaptive = pytest.mark.adaptive

NAN = float("nan")


def _drive(ctl, n, **streams):
    """Feed ``n`` identical observations; collect emitted actions."""
    acts = []
    for _ in range(n):
        a = ctl.observe(**streams)
        if a:
            acts.append(a)
    return acts


class TestControllerAction:
    def test_falsy_when_empty(self):
        assert not ControllerAction()
        assert ControllerAction(sampler_mode="host")
        assert ControllerAction(samplers=4)
        assert ControllerAction(hot_size=512)


class TestPlacementPolicy:
    def test_pressure_switches_device_to_host(self):
        ctl = DecisionPlaneController(mode="device", dwell=8,
                                      adjust_every=2)
        acts = _drive(ctl, 32, queue_depth=10.0)
        assert [a.sampler_mode for a in acts] == ["host"]
        assert ctl.mode == "host"

    def test_drained_queue_switches_host_to_device(self):
        ctl = DecisionPlaneController(mode="host", dwell=8,
                                      adjust_every=2)
        acts = _drive(ctl, 32, queue_depth=0.0, batch=3.0)
        assert [a.sampler_mode for a in acts] == ["device"]

    def test_hysteresis_band_holds_placement(self):
        """Queue depths inside (queue_low, queue_high) move nothing in
        either direction — the band is what prevents thrash."""
        for mode in ("device", "host"):
            ctl = DecisionPlaneController(mode=mode, queue_low=1.0,
                                          queue_high=6.0, dwell=2,
                                          adjust_every=2)
            assert _drive(ctl, 64, queue_depth=3.0) == []
            assert ctl.mode == mode

    def test_dwell_bounds_switch_rate(self):
        """A workload oscillating across both thresholds every step can
        switch at most once per ``dwell`` observations."""
        ctl = DecisionPlaneController(mode="device", dwell=16,
                                      adjust_every=1, ewma=1.0)
        switches = []
        for i in range(200):
            q = 0.0 if (i // 4) % 2 == 0 else 50.0
            a = ctl.observe(queue_depth=q)
            if a and a.sampler_mode:
                switches.append(i)
        assert switches, "oscillating load never switched"
        gaps = np.diff(switches)
        assert (gaps >= 16).all(), gaps

    def test_occupancy_gate_blocks_empty_batch_host_switch(self):
        """With ``occupancy_min`` set, queue pressure alone (a burst the
        batch has not absorbed yet) does not disaggregate — the switch
        pays off only when there is sampling work to overlap."""
        ctl = DecisionPlaneController(mode="device", occupancy_min=2.0,
                                      dwell=2, adjust_every=2)
        assert _drive(ctl, 32, queue_depth=10.0, batch=0.5) == []
        acts = _drive(ctl, 32, queue_depth=10.0, batch=4.0)
        assert acts and acts[0].sampler_mode == "host"


class TestPoolPolicy:
    def test_stall_doubles_workers_up_to_cap(self):
        ctl = DecisionPlaneController(mode="host", samplers=2,
                                      max_samplers=8, dwell=4,
                                      adjust_every=2, queue_low=-1.0)
        acts = _drive(ctl, 64, stall_ms=50.0, queue_depth=0.0)
        assert [a.samplers for a in acts] == [4, 8]

    def test_idle_pool_halves_workers(self):
        ctl = DecisionPlaneController(mode="host", samplers=8,
                                      min_samplers=1, dwell=4,
                                      adjust_every=2, queue_low=-1.0)
        acts = _drive(ctl, 128, stall_ms=0.0, queue_depth=0.0)
        assert [a.samplers for a in acts] == [4, 2, 1]

    def test_geometric_moves_keep_reachable_set_small(self):
        """Both directions are geometric, so every reachable worker count
        is a power of two of the initial value — the set a serving warmup
        pre-traces (fig_latency warms exactly this set)."""
        ctl = DecisionPlaneController(mode="host", samplers=2, dwell=1,
                                      adjust_every=1, queue_low=-1.0)
        seen = {2}
        rng = np.random.default_rng(0)
        for _ in range(400):
            a = ctl.observe(queue_depth=0.0,
                            stall_ms=float(rng.choice([0.0, 50.0])))
            if a and a.samplers is not None:
                seen.add(a.samplers)
        assert seen <= {1, 2, 4, 8}, seen

    def test_device_mode_never_resizes(self):
        ctl = DecisionPlaneController(mode="device", samplers=2, dwell=1,
                                      adjust_every=1, queue_high=1e9)
        assert all(a.samplers is None
                   for a in _drive(ctl, 64, queue_depth=5.0,
                                   stall_ms=50.0))


class TestNaNStreams:
    """ISSUE 7 regression: every observation stream may carry NaN
    (all-inactive shards pool to NaN stats; device-mode steps have no
    stall/sampler/transfer decomposition at all) and must be dropped per
    stream WITHOUT stalling the adjust clock."""

    def test_nan_laced_trace_still_converges(self):
        ctl = DecisionPlaneController(mode="device", dwell=8,
                                      adjust_every=2)
        rng = np.random.default_rng(1)
        acts = []
        for i in range(64):
            # every stream goes non-finite on a rotating schedule; the
            # finite queue observations alone must still force the switch
            acts += filter(None, [ctl.observe(
                queue_depth=NAN if i % 3 == 0 else 12.0,
                queue_delay_ms=NAN,
                batch=float(rng.choice([NAN, 4.0])),
                stall_ms=NAN, sampler_ms=NAN, transfer_ms=NAN,
                bubble_frac=NAN, alpha_mean=NAN)])
        assert [a.sampler_mode for a in acts] == ["host"]

    def test_all_nan_steps_tick_the_clock(self):
        """A burst of fully-NaN observations must advance ``_step`` so the
        next finite observation can act immediately at the adjust
        boundary, not ``adjust_every`` steps later."""
        ctl = DecisionPlaneController(mode="device", dwell=4,
                                      adjust_every=4)
        for _ in range(31):
            assert ctl.observe(queue_depth=NAN, stall_ms=NAN) is None
        assert ctl._step == 31
        assert ctl.signals["queue_depth"] is None
        a = ctl.observe(queue_depth=40.0)      # step 32: adjust boundary
        assert a and a.sampler_mode == "host"

    def test_nan_never_poisons_a_signal(self):
        ctl = DecisionPlaneController(adjust_every=1000)
        ctl.observe(queue_depth=4.0)
        ctl.observe(queue_depth=NAN)
        ctl.observe(queue_depth=2.0)
        assert np.isfinite(ctl.signals["queue_depth"])

    def test_unknown_stream_rejected(self):
        ctl = DecisionPlaneController()
        with pytest.raises(AssertionError, match="unknown controller"):
            ctl.observe(queue_dept=1.0)


class TestBoundedHistory:
    """ISSUE 7 regression: decision logs must not grow without bound in a
    long-running server, while keeping the examples' ``history[-1]``
    access pattern."""

    def test_hot_size_controller_history_capped(self):
        ctl = HotSizeController(vocab_size=32768, h_current=1024,
                                adjust_every=1, history_cap=16)
        for _ in range(200):
            ctl.observe(0.9)
        assert len(ctl.history) == 16
        assert ctl.history[-1]["h_current"] == ctl.h_current

    def test_decision_controller_history_capped(self):
        ctl = DecisionPlaneController(mode="host", dwell=0, adjust_every=1,
                                      history_cap=8, queue_low=5.0,
                                      queue_high=6.0, ewma=1.0)
        for i in range(100):
            ctl.observe(queue_depth=0.0 if i % 2 else 50.0)
        assert len(ctl.history) == 8
        assert ctl.history[-1]["mode"] == ctl.mode

    def test_hot_sub_policy_rides_along(self):
        hot = HotSizeController(vocab_size=32768, h_current=8192,
                                adjust_every=4)
        ctl = DecisionPlaneController(mode="device", hot=hot,
                                      adjust_every=1000)
        acts = _drive(ctl, 64, alpha_mean=0.999, queue_depth=3.0)
        assert acts, "H* sub-policy never moved under extreme alpha"
        assert all(a.hot_size is not None for a in acts)
        assert all(a.sampler_mode is None for a in acts)


# -- engine-level: adaptive placement is invisible in the streams ---------

@pytest.fixture(scope="module")
def model():
    from repro.models.model import Model
    cfg = ModelConfig(name="adaptive-tiny", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=512)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


_ENGINE_KW = dict(max_batch=3, max_seq_len=64, algorithm="shvs",
                  shvs=SHVSConfig(hot_size=64), k_cap=64, prompt_bucket=8)


def _reqs(cfg, n=8):
    rng = np.random.default_rng(3)
    return [Request(
        request_id=i,
        prompt=rng.integers(1, cfg.vocab_size,
                            int(rng.integers(3, 10))).tolist(),
        max_new_tokens=int(rng.integers(4, 9)),
        sampling=SamplingConfig(temperature=0.9, top_k=30, top_p=0.95,
                                repetition_penalty=1.1, seed=100 + i))
        for i in range(n)]


def _streams(cfg, params, mode, tweak=None):
    eng = Engine(cfg, params, EngineConfig(sampler_mode=mode, **_ENGINE_KW))
    if tweak is not None:
        tweak(eng)
    eng.submit(_reqs(cfg))
    done = eng.run(max_steps=4000)
    assert len(done) == 8
    out = {r.request_id: r.output for r in done}
    log = list(eng.stats_log)
    eng.close()
    return out, log


@adaptive
def test_single_stage_adaptive_bit_identical(model):
    """``sampler_mode="adaptive"`` with the controller forced to act —
    fast clocks, thresholds that flip placement both ways mid-run — must
    commit the static device-mode streams bit-for-bit."""
    cfg, params = model

    def force(eng):
        eng._dpc.adjust_every = 2
        eng._dpc.dwell = 2
        eng._dpc.queue_high = -1.0       # device -> host immediately...
        eng._dpc.queue_low = 99.0        # ...and straight back, so the
        # run oscillates and exercises switches in BOTH directions

    got, log = _streams(cfg, params, "adaptive", tweak=force)
    switched = [r["sampler_mode"] for r in log if "sampler_mode" in r]
    assert "host" in switched and "device" in switched, switched
    ref, _ = _streams(cfg, params, "device")
    assert got == ref


@adaptive
def test_pipeline_adaptive_bit_identical(model):
    """The pipeline engine's adaptive mode — switches and pool resizes
    mid-run — commits the device-placement (baseline) streams."""
    from repro.engine.pipeline import PipelineConfig, PipelineEngine
    cfg, params = model
    kw = dict(max_batch=4, stages=2, microbatches=2, samplers=2,
              max_seq_len=64, algorithm="shvs", shvs=SHVSConfig(hot_size=64),
              k_cap=64, prompt_bucket=8, prompt_chunk=0)

    def run(mode, tweak=None):
        eng = PipelineEngine(cfg, params,
                             PipelineConfig(sampler_mode=mode, **kw))
        if tweak is not None:
            tweak(eng)
        eng.submit(_reqs(cfg))
        done = eng.run(max_steps=20_000)
        out = {r.request_id: r.output for r in done}
        log = list(eng.stats_log)
        eng.close()
        assert len(out) == 8
        return out, log

    def force(eng):
        eng._dpc.adjust_every = 2
        eng._dpc.dwell = 2
        eng._dpc.queue_low = 99.0        # host -> device immediately...
        eng._dpc.queue_high = -1.0       # ...and straight back (oscillate)
        eng._dpc.stall_grow_ms = 0.0     # and grow the pool on any stall

    got, log = run("adaptive", tweak=force)
    assert any("sampler_mode" in r for r in log), "controller never acted"
    ref, _ = run("device")
    assert got == ref


@adaptive
def test_adaptive_engine_exposes_controller(model):
    """The wiring contract the benchmark and serving CLI rely on: an
    adaptive engine starts on device with a live controller; static modes
    have none."""
    cfg, params = model
    eng = Engine(cfg, params, EngineConfig(sampler_mode="adaptive",
                                           **_ENGINE_KW))
    assert eng._dpc is not None and eng._dpc.mode == "device"
    assert eng.client.mode == "device"
    assert eng.set_sampler_mode("host") is True
    assert eng.client.is_host and eng._host
    eng.close()
    eng2 = Engine(cfg, params, EngineConfig(**_ENGINE_KW))
    assert eng2._dpc is None
    eng2.close()


@adaptive
def test_adaptive_switches_with_donated_buffers(model, monkeypatch):
    """The accelerator's donation branch, rehearsed on the CPU: with
    ``cache``/``pstate`` donated, placement switches in both directions
    move the histograms between the pool's CPU device and the engine's
    and read no donated buffer — the streams stay the device-mode ones."""
    from repro.engine import engine as engine_mod
    cfg, params = model
    ref, _ = _streams(cfg, params, "device")

    def force(eng):
        eng._dpc.adjust_every = 2
        eng._dpc.dwell = 2
        eng._dpc.queue_high = -1.0
        eng._dpc.queue_low = 99.0

    monkeypatch.setattr(engine_mod, "_donates", lambda: True)
    got, log = _streams(cfg, params, "adaptive", tweak=force)
    switched = [r["sampler_mode"] for r in log if "sampler_mode" in r]
    assert "host" in switched and "device" in switched, switched
    assert got == ref
