"""Pipeline-parallel engine: differential identity + bubble measurement
(DESIGN.md §12).

The pipeline is an *execution strategy*, not a semantics change: for any
stage count ``p`` and microbatch count ``M``, the committed token streams
must be bit-identical to the single-stage ``Engine`` on the same seeded
requests — across {overlap, sequential} single-stage modes and
{contiguous, paged} KV layouts, with sampling disaggregated to the host
pool or run synchronously on the last stage. And the point of the
subsystem: the disaggregated mode's *measured* bubble fraction must sit
strictly below the baseline's at p >= 2 (the paper's Eq. 4, measured
rather than simulated)."""
import jax
import numpy as np
import pytest

from repro.config import ModelConfig, SamplingConfig, SHVSConfig
from repro.engine import (Engine, EngineConfig, PipelineConfig,
                          PipelineEngine, Request)
from repro.engine.pipeline import MicrobatchPlanner

pytestmark = pytest.mark.pipeline


@pytest.fixture(scope="module")
def model4():
    """A 4-layer tiny dense model (p=4 needs >= 4 layers to split)."""
    from repro.models.model import Model
    cfg = ModelConfig(name="pipe-tiny", family="dense", num_layers=4,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=512)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


_ENGINE_KW = dict(max_seq_len=64, algorithm="shvs",
                  shvs=SHVSConfig(hot_size=64), k_cap=64, prompt_bucket=8,
                  block_size=8)


def _reqs(cfg, n=9, seed=0, max_new=6, **skw):
    """Heterogeneous lengths + stop conditions: slot churn across
    microbatch groups, staggered retirement."""
    rng = np.random.default_rng(seed)
    return [Request(
        request_id=i,
        prompt=rng.integers(1, cfg.vocab_size,
                            int(rng.integers(3, 12))).tolist(),
        max_new_tokens=int(rng.integers(2, max_new + 1)),
        sampling=SamplingConfig(temperature=0.9, top_k=30, top_p=0.95,
                                repetition_penalty=1.1, **skw))
        for i in range(n)]


def _single(cfg, params, reqs, **kw):
    ekw = dict(_ENGINE_KW, max_batch=4)
    ekw.update(kw)
    eng = Engine(cfg, params, EngineConfig(**ekw))
    eng.submit(reqs)
    done = eng.run(max_steps=800)
    assert len(done) == len(reqs)
    return {r.request_id: r.output for r in done}


def _pipeline(cfg, params, reqs, *, stages, microbatches, rows=2, **kw):
    ekw = dict(_ENGINE_KW, max_batch=rows * microbatches, stages=stages,
               microbatches=microbatches, samplers=2)
    ekw.update(kw)
    eng = PipelineEngine(cfg, params, PipelineConfig(**ekw))
    eng.submit(reqs)
    done = eng.run(max_steps=20_000)
    eng.close()
    assert len(done) == len(reqs)
    return {r.request_id: r.output for r in done}, eng


@pytest.fixture(scope="module")
def reference(model4):
    """Single-stage streams, pinned equal across {overlap, seq} x
    {contiguous, paged} before any pipeline comparison."""
    cfg, params = model4
    ref = _single(cfg, params, _reqs(cfg), overlap=False)
    assert _single(cfg, params, _reqs(cfg), overlap=True) == ref
    assert _single(cfg, params, _reqs(cfg), cache="paged") == ref
    assert _single(cfg, params, _reqs(cfg), cache="paged",
                   overlap=False) == ref
    return ref


@pytest.mark.parametrize("stages", [1, 2, 4])
@pytest.mark.parametrize("mfactor", [1, 2])
def test_pipeline_bit_identical(model4, reference, stages, mfactor):
    """p in {1,2,4}, M in {p,2p}: disaggregated host-pool sampling,
    contiguous cache — streams identical to the single-stage engine."""
    cfg, params = model4
    got, _ = _pipeline(cfg, params, _reqs(cfg), stages=stages,
                       microbatches=stages * mfactor)
    assert got == reference


@pytest.mark.parametrize("stages,mfactor", [(1, 2), (2, 1), (2, 2), (4, 2)])
def test_pipeline_bit_identical_paged(model4, reference, stages, mfactor):
    cfg, params = model4
    got, eng = _pipeline(cfg, params, _reqs(cfg), stages=stages,
                         microbatches=stages * mfactor, cache="paged")
    assert got == reference
    # reserving admission: no preemption machinery needed, and no leaks
    assert eng.scheduler.preemptions == 0
    assert eng.alloc.num_free == eng.pcfg.num_blocks


@pytest.mark.parametrize("cache", ["contiguous", "paged"])
def test_baseline_sampler_mode_identical(model4, reference, cache):
    """Sampling synchronously on the last stage is the same math — only
    the schedule (and the bubble) differs."""
    cfg, params = model4
    got, _ = _pipeline(cfg, params, _reqs(cfg), stages=2, microbatches=4,
                       sampler_mode="device", cache=cache)
    assert got == reference


def test_sampler_pool_width_invariance(model4, reference):
    """1 worker or 8: sequence-parallel sharding across the pool must not
    change any row's stream (S1 row-locality)."""
    cfg, params = model4
    for m in (1, 8):
        got, _ = _pipeline(cfg, params, _reqs(cfg), stages=2,
                           microbatches=4, rows=4, samplers=m)
        assert got == reference


def test_per_request_contract_through_pipeline(model4):
    """Seeded / greedy / stop-sequence contracts (DESIGN.md §11) ride
    through the pipeline unchanged."""
    cfg, params = model4
    mk = lambda: _reqs(cfg, n=6, seed=3, greedy=False)
    for r in mk():
        assert r.sampling.seed is None
    seeded = lambda: [Request(r.request_id, list(r.prompt), r.max_new_tokens,
                              SamplingConfig(temperature=0.9, top_k=30,
                                             seed=100 + r.request_id))
                      for r in mk()]
    ref = _single(cfg, params, seeded())
    got, _ = _pipeline(cfg, params, seeded(), stages=2, microbatches=4)
    assert got == ref
    greedy = lambda: [Request(r.request_id, list(r.prompt), r.max_new_tokens,
                              SamplingConfig(greedy=True))
                      for r in mk()]
    assert _pipeline(cfg, params, greedy(), stages=2,
                     microbatches=4)[0] == _single(cfg, params, greedy())


def test_generate_stream_matches_run(model4, reference):
    """The streaming surface fires events at commit and, collected, equals
    the run() streams; every request closes with a finish_reason."""
    cfg, params = model4
    eng = PipelineEngine(cfg, params, PipelineConfig(
        max_batch=4, stages=2, microbatches=2, samplers=2, **_ENGINE_KW))
    reqs = _reqs(cfg)
    streams: dict = {}
    finishes: dict = {}
    for ev in eng.generate(reqs, max_steps=20_000):
        if ev.token is not None:
            streams.setdefault(ev.request_id, []).append(ev.token)
        if ev.finish_reason is not None:
            finishes[ev.request_id] = ev.finish_reason
    eng.close()
    assert streams == reference
    assert set(finishes) == {r.request_id for r in reqs}
    assert all(f in ("eos", "length", "stop", "truncated")
               for f in finishes.values())


def test_paged_reserving_admission_throttles(model4, reference):
    """A pool far smaller than total demand admits in waves; everything
    still finishes with identical streams, zero preemptions, no leaked
    blocks."""
    cfg, params = model4
    got, eng = _pipeline(cfg, params, _reqs(cfg), stages=2, microbatches=2,
                         cache="paged", num_blocks=24)
    assert got == reference
    assert eng.scheduler.preemptions == 0
    assert eng.alloc.num_free == eng.pcfg.num_blocks


def test_reserving_gate_admits_exact_fit_in_one_round(model4):
    """Two requests whose combined worst case exactly fills the pool must
    both be admitted in the SAME scheduling round — the gate must not
    double-count a round's earlier admits (once via round_admits, once via
    the already-installed slot)."""
    cfg, params = model4
    eng = PipelineEngine(cfg, params, PipelineConfig(
        max_batch=2, stages=1, microbatches=1, cache="paged",
        num_blocks=4, **_ENGINE_KW))
    # prompt 8 + max_new 8 = 16 tokens = exactly 2 blocks of 8 each
    reqs = [Request(i, list(range(1, 9)), 8) for i in range(2)]
    eng.submit(reqs)
    eng.step()
    assert eng.scheduler.num_active() == 2, \
        "reserving gate rejected an admission that exactly fits"
    done = eng.run(max_steps=5000)
    eng.close()
    assert len(done) == 2
    assert eng.alloc.num_free == eng.pcfg.num_blocks


def test_oversized_request_rejected_at_submit(model4):
    cfg, params = model4
    eng = PipelineEngine(cfg, params, PipelineConfig(
        max_batch=4, stages=2, microbatches=2, cache="paged",
        num_blocks=4, **_ENGINE_KW))
    with pytest.raises(ValueError, match="KV blocks"):
        eng.submit([Request(0, list(range(1, 40)), 30)])
    eng.close()


def test_planner_rejects_early_commit():
    planner = MicrobatchPlanner(2, 4, 1)
    req = Request(0, [1, 2], 4)
    req.slot = 0
    planner.dispatch(0, np.array([True]), [req],
                     np.zeros(1, np.uint32), np.zeros(1, np.int32))
    planner.tick()
    planner.tick()   # cycle 2: stage p-1 serves (2-1)%4 = 1... not yet
    with pytest.raises(KeyError):
        planner.commit(1)          # never dispatched
    planner.tick()   # cycle 3 -> planner.stage_for(1)==... exit window
    with pytest.raises(AssertionError):
        planner.commit(0)          # no last-stage exit yet


def test_planner_rejects_double_dispatch():
    planner = MicrobatchPlanner(1, 1, 1)
    req = Request(0, [1], 4)
    req.slot = 0
    planner.dispatch(0, np.array([True]), [req],
                     np.zeros(1, np.uint32), np.zeros(1, np.int32))
    with pytest.raises(AssertionError):
        planner.dispatch(0, np.array([True]), [req],
                         np.zeros(1, np.uint32), np.zeros(1, np.int32))


def test_close_commits_in_flight_microbatches(model4):
    """close() mid-run must flush (the Engine.close contract, §13): tokens
    the pool already sampled commit instead of being dropped with the
    threads."""
    cfg, params = model4
    eng = PipelineEngine(cfg, params, PipelineConfig(
        max_batch=4, stages=2, microbatches=2, samplers=2, **_ENGINE_KW))
    eng.submit(_reqs(cfg, n=4))
    for _ in range(4):        # leaves microbatches mid-pipeline
        eng.step()
    assert eng.in_flight > 0
    eng.close()
    assert eng.in_flight == 0, "close() dropped in-flight tokens"


def test_measured_bubble_disaggregated_below_baseline(model4):
    """The acceptance bar: on the executable pipeline, disaggregating the
    sampler strictly lowers the measured bubble fraction at p >= 2. A
    vocab-heavy decision plane (full-V reference backend) makes the
    sampling epilogue material, as in the paper's Fig. 1b."""
    from benchmarks.fig_pipeline import measure
    base = measure(stages=2, microbatches=4, mode="device")
    simple = measure(stages=2, microbatches=4, mode="host")
    assert base["cycles"] > 0 and simple["cycles"] > 0
    assert simple["bubble_frac"] < base["bubble_frac"], (
        f"disaggregated bubble {simple['bubble_frac']:.3f} not below "
        f"baseline {base['bubble_frac']:.3f}")
