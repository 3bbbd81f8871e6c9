"""Unified decision-plane client (DESIGN.md §13): differential identity of
``sampler_mode="host"`` on the single-stage engine.

Host sampling is an *execution strategy*, not a semantics change: the CPU
pool runs the identical ``DecisionPlane.step`` on fetched logits, uniforms
are keyed on (request, position), and every per-row computation is
row-local — so the committed token streams must be bit-identical to device
mode across {overlap, sequential} × {contiguous, paged}, any worker count,
every per-request contract, and through preemption/resume."""
import jax
import numpy as np
import pytest

from repro.config import ModelConfig, SamplingConfig, SHVSConfig
from repro.engine import (DecisionPlaneClient, Engine, EngineConfig, Request,
                          canonical_sampler_mode)

paged = pytest.mark.paged


@pytest.fixture(scope="module")
def model():
    from repro.models.model import Model
    cfg = ModelConfig(name="client-tiny", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=512)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


_ENGINE_KW = dict(max_batch=3, max_seq_len=64, algorithm="shvs",
                  shvs=SHVSConfig(hot_size=64), k_cap=64, prompt_bucket=8,
                  block_size=8)


def _reqs(cfg, n=9, seed=0, max_new=6, **skw):
    """Heterogeneous lengths + stop conditions: slot churn and staggered
    retirement — the cases where the host path's commit lag could
    plausibly diverge."""
    rng = np.random.default_rng(seed)
    return [Request(
        request_id=i,
        prompt=rng.integers(1, cfg.vocab_size,
                            int(rng.integers(3, 12))).tolist(),
        max_new_tokens=int(rng.integers(2, max_new + 1)),
        sampling=SamplingConfig(temperature=0.9, top_k=30, top_p=0.95,
                                repetition_penalty=1.1, **skw))
        for i in range(n)]


def _run(cfg, params, reqs=None, n=9, max_steps=2000, **kw):
    ekw = dict(_ENGINE_KW)
    ekw.update(kw)
    eng = Engine(cfg, params, EngineConfig(**ekw))
    reqs = reqs if reqs is not None else _reqs(cfg, n)
    eng.submit(reqs)
    done = eng.run(max_steps=max_steps)
    assert len(done) == len(reqs), f"{len(done)}/{len(reqs)} finished"
    assert eng.in_flight == 0
    eng.close()
    return {r.request_id: r.output for r in done}, eng


@pytest.fixture(scope="module")
def reference(model):
    """Device-mode sequential streams — the §2 oracle — pinned equal to
    device overlap before any host comparison."""
    cfg, params = model
    ref, _ = _run(cfg, params, overlap=False)
    assert _run(cfg, params, overlap=True)[0] == ref
    return ref


@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("cache", [
    "contiguous", pytest.param("paged", marks=paged)])
def test_host_bit_identical(model, reference, overlap, cache):
    """The tentpole bar: host sampling composes with {overlap, seq} ×
    {contiguous, paged} and every combination commits the device-mode
    streams bit-for-bit."""
    cfg, params = model
    got, _ = _run(cfg, params, sampler_mode="host", overlap=overlap,
                  cache=cache)
    assert got == reference


def test_worker_count_invariance(model, reference):
    """1 worker or 8: sequence-parallel sharding across the pool must not
    move any row's stream (S1 row-locality)."""
    cfg, params = model
    for m in (1, 8):
        got, _ = _run(cfg, params, sampler_mode="host", samplers=m)
        assert got == reference


def test_chunked_prefill_composes_with_host_mode(model, reference):
    """Chunked prefill (§8) samples chunk finishers' first tokens on
    device while decode sampling runs in the pool — streams unchanged."""
    cfg, params = model
    got, _ = _run(cfg, params, sampler_mode="host", prompt_chunk=8)
    assert got == reference


def test_per_request_contracts_through_host_mode(model):
    """Seeded and greedy contracts (DESIGN.md §11) ride through the pool
    unchanged."""
    cfg, params = model
    seeded = lambda: _reqs(cfg, n=6, seed=3)
    for r in seeded():
        assert r.sampling.seed is None
    mk = lambda skw: [Request(r.request_id, list(r.prompt), r.max_new_tokens,
                              SamplingConfig(temperature=0.9, top_k=30,
                                             **skw))
                      for r in seeded()]
    for skw in ({"seed": 100}, {"greedy": True}):
        ref, _ = _run(cfg, params, reqs=mk(skw))
        got, _ = _run(cfg, params, reqs=mk(skw), sampler_mode="host")
        assert got == ref, skw


@paged
def test_preemption_resume_under_host_mode(model):
    """Pool pressure mid-run: victims are evicted, re-prefilled, and must
    continue their streams bit-identically with host sampling in both
    loop modes."""
    cfg, params = model

    def mk():
        rng = np.random.default_rng(7)
        return [Request(
            request_id=i,
            prompt=rng.integers(1, cfg.vocab_size,
                                int(rng.integers(4, 9))).tolist(),
            max_new_tokens=40,
            sampling=SamplingConfig(temperature=0.9, top_k=30, top_p=0.95,
                                    repetition_penalty=1.1))
            for i in range(5)]

    ref, _ = _run(cfg, params, reqs=mk(), max_steps=4000)
    for overlap in (True, False):
        got, eng = _run(cfg, params, reqs=mk(), max_steps=4000,
                        sampler_mode="host", overlap=overlap,
                        cache="paged", num_blocks=8)
        assert eng.scheduler.preemptions > 0, \
            "pool was meant to exhaust mid-run"
        assert got == ref, f"preempted host streams diverged ({overlap=})"
        assert eng.alloc.num_free == eng.pcfg.num_blocks


def test_host_stats_report_pool_decomposition(model):
    """Host-mode step records carry the §13 decomposition — commit stall,
    CPU sampling, and transfer wait as separate fields — device-mode
    records don't."""
    cfg, params = model
    _, host = _run(cfg, params, sampler_mode="host")
    decodes = [s for s in host.stats_log if "stall_ms" in s]
    assert decodes, "host mode logged no pool-backed steps"
    for s in decodes:
        assert s["sampler_ms"] > 0.0
        assert s["transfer_ms"] >= 0.0
        assert s["stall_ms"] >= 0.0
    _, dev = _run(cfg, params)
    assert all("stall_ms" not in s for s in dev.stats_log)


def test_generate_stream_host_matches_run(model, reference):
    cfg, params = model
    eng = Engine(cfg, params, EngineConfig(sampler_mode="host",
                                           **_ENGINE_KW))
    streams, finishes = {}, {}
    for ev in eng.generate(_reqs(cfg), max_steps=2000):
        if ev.token is not None:
            streams.setdefault(ev.request_id, []).append(ev.token)
        if ev.finish_reason is not None:
            finishes[ev.request_id] = ev.finish_reason
    eng.close()
    assert streams == reference
    assert set(finishes) == set(reference)


def test_abandoned_generate_flushes_in_flight(model):
    """A caller that walks away mid-stream must not strand the engine's
    in-flight sampler ticket: closing the iterator drains it, and the
    engine (and its pool) shuts down cleanly afterwards."""
    cfg, params = model
    eng = Engine(cfg, params, EngineConfig(sampler_mode="host",
                                           **_ENGINE_KW))
    gen = eng.generate(_reqs(cfg), max_steps=2000)
    next(gen)                       # start streaming, then abandon
    gen.close()
    assert eng.in_flight == 0, "abandoned stream left a ticket in flight"
    eng.close()
    assert eng.client.pool._ex is None


def test_engine_close_shuts_down_pool(model):
    cfg, params = model
    _, eng = _run(cfg, params, sampler_mode="host", n=3)
    assert eng.client.pool._ex is None, "close() left pool threads running"
    # device mode never spins the pool up at all
    _, dev = _run(cfg, params, n=3)
    assert dev.client.pool._ex is None


def test_sampler_mode_names():
    assert canonical_sampler_mode("device") == "device"
    assert canonical_sampler_mode("host") == "host"
    # the pipeline's old spellings are gone, not aliased
    for name in ("gpu", "baseline", "disaggregated"):
        with pytest.raises(ValueError, match="sampler_mode"):
            canonical_sampler_mode(name)


def test_engine_rejects_unknown_sampler_mode(model):
    cfg, params = model
    with pytest.raises(ValueError, match="sampler_mode"):
        Engine(cfg, params,
               EngineConfig(sampler_mode="sidecar", **_ENGINE_KW))


# -- ISSUE 7: online mode switching (the §15 controller's primary knob) ----

def test_set_mode_drains_before_reroute(model):
    """``set_mode`` must join every outstanding ticket before re-routing
    (join-before-re-route, §15) and report whether anything changed."""
    cfg, params = model
    eng = Engine(cfg, params, EngineConfig(sampler_mode="host",
                                           **_ENGINE_KW))
    eng.submit(_reqs(cfg, n=2))
    eng.step()                       # dispatch: a ticket is now in flight
    assert eng.client._tickets, "host step left no outstanding ticket"
    assert eng.client.set_mode("host") is False      # no-op keeps tickets
    assert eng.client.set_mode("device") is True
    assert eng.client._tickets == [], "switch left tickets outstanding"
    assert eng.client.mode == "device"
    assert eng.client.set_mode("host") is True
    assert eng.client.is_host
    eng.run(max_steps=2000)
    eng.close()


def test_resize_pool_recycles_executor(model):
    """Online pool resize (§15): the executor is recycled at the new
    width, outstanding work still resolves, and the row-local sharding
    keeps streams untouched (test_worker_count_invariance pins that)."""
    cfg, params = model
    eng = Engine(cfg, params, EngineConfig(sampler_mode="host", samplers=2,
                                           **_ENGINE_KW))
    eng.submit(_reqs(cfg, n=3))
    eng.step()
    assert eng.client.pool._ex is not None
    eng.client.resize_pool(4)
    assert eng.client.pool.num_workers == 4
    assert eng.client.pool._ex is None, "resize must recycle the executor"
    eng.client.resize_pool(4)        # same width: nothing to recycle
    done = eng.run(max_steps=2000)
    assert len(done) == 3
    eng.close()


def _run_switching(cfg, params, reqs, every=3, **kw):
    """Drive the engine while toggling device <-> host every ``every``
    committed steps — the §15 controller's switch pattern, exercised
    deterministically."""
    ekw = dict(_ENGINE_KW)
    ekw.update(kw)
    eng = Engine(cfg, params, EngineConfig(**ekw))
    eng.submit(reqs)
    steps = 0
    while eng.scheduler.has_work or eng.in_flight:
        eng.step()
        steps += 1
        assert steps < 4000, "switching run did not finish"
        if steps % every == 0:
            eng.set_sampler_mode(
                "host" if eng.client.mode == "device" else "device")
    eng.flush()
    done = eng.scheduler.finished
    assert len(done) == len(reqs), f"{len(done)}/{len(reqs)} finished"
    out = {r.request_id: r.output for r in done}
    eng.close()
    return out


@pytest.mark.adaptive
@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("cache", [
    "contiguous", pytest.param("paged", marks=paged)])
def test_mid_generation_switch_bit_identical(model, reference, overlap,
                                             cache):
    """ISSUE 7 differential bar: ``set_mode()`` firing mid-generation —
    every 3 committed steps, both directions — must leave the committed
    streams bit-identical to static device mode across {overlap, seq} ×
    {contiguous, paged}."""
    cfg, params = model
    got = _run_switching(cfg, params, _reqs(cfg), overlap=overlap,
                         cache=cache)
    assert got == reference


@pytest.mark.adaptive
def test_mid_generation_switch_seeded_and_greedy(model):
    """Seeded and greedy per-request contracts (§11) survive mid-run
    placement switches unchanged."""
    cfg, params = model
    base = _reqs(cfg, n=6, seed=3)
    mk = lambda skw: [Request(r.request_id, list(r.prompt),
                              r.max_new_tokens,
                              SamplingConfig(temperature=0.9, top_k=30,
                                             **skw))
                      for r in base]
    for skw in ({"seed": 100}, {"greedy": True}):
        ref, _ = _run(cfg, params, reqs=mk(skw))
        got = _run_switching(cfg, params, mk(skw), every=2)
        assert got == ref, skw
