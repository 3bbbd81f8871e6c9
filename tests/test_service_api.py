"""Decision-plane service API v1 (DESIGN.md §11).

Four contract surfaces:

* the **backend registry** — selection by name, loud ``ValueError`` on
  unknown names, at construction and at step time;
* the **backend-differential identity suite** (``backends`` marker; CI
  runs it once per registered backend via ``REPRO_BACKEND``, the
  ``shvs-fallback`` variant under ``shvs``) — backends are bit-identical
  to the reference sampler on shared configs (greedy / single-token
  supports) across {overlapped, sequential} × {contiguous, paged}, with
  the decision on the device and, for ``shvs`` and ``fused``, in the host
  sampler pool; bit-identical to themselves across modes on seeded
  stochastic configs; and seeded streams are invariant to batch
  composition;
* the **per-request contract** — seed / greedy / logit_bias /
  stop_sequences / finish_reason;
* the **streaming surface** — ``Engine.generate()`` events fire at commit,
  incrementally, and collect to exactly the ``submit``+``run`` streams.
"""
import jax
import numpy as np
import pytest

from repro.config import SamplingConfig, SHVSConfig, get_arch
from repro.core.decision_plane import DecisionPlane
from repro.core.sampler_backend import (SamplerBackend, make_backend,
                                        registered_backends)
from repro.core.sampling import SamplingParams
from repro.engine import Engine, GenerationEvent, Request, SlotParams
from repro.engine.engine import EngineConfig
from repro.models.model import Model

from _variants import (SHVS_FALLBACK, backends_under_test, variant_config,
                       variants_under_test)

BUILTIN_BACKENDS = ("fused", "reference", "shvs")


@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("smollm-360m").reduced()
    params = Model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def stream_cache():
    """Memoized engine runs: (backend, overlap, cache, workload) -> streams."""
    return {}


def _engine(cfg, params, **kw):
    defaults = dict(max_batch=3, max_seq_len=64, algorithm="shvs",
                    shvs=SHVSConfig(hot_size=64), k_cap=64, prompt_bucket=8)
    defaults.update(kw)
    return Engine(cfg, params, EngineConfig(**defaults))


def _copy(reqs):
    return [Request(r.request_id, list(r.prompt), r.max_new_tokens,
                    r.sampling, eos_token=r.eos_token) for r in reqs]


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_builtins_registered(self):
        assert registered_backends() == BUILTIN_BACKENDS

    def test_make_backend_unknown_lists_registered(self):
        with pytest.raises(ValueError) as ei:
            make_backend("definitely_not_a_backend")
        msg = str(ei.value)
        assert "definitely_not_a_backend" in msg
        for b in BUILTIN_BACKENDS:
            assert b in msg, "error must list the registered backends"

    def test_decision_plane_rejects_unknown_algorithm_at_init(self):
        with pytest.raises(ValueError, match="registered backends"):
            DecisionPlane(64, algorithm="nope")

    def test_decision_plane_step_rejects_mutated_unknown_algorithm(self):
        """The dry-run mutates ``dp.algorithm`` post-init; a typo there must
        fail loudly at step time, not fall through to some default."""
        import jax.numpy as jnp
        dp = DecisionPlane(32, algorithm="reference", k_cap=16)
        state = dp.init_state(2)
        params = SamplingParams.broadcast(2, SamplingConfig())
        dp.algorithm = "bogus"
        with pytest.raises(ValueError, match="registered backends"):
            dp.step(jnp.zeros((2, 32)), state, params,
                    jnp.zeros((), jnp.int32))

    def test_engine_rejects_unknown_algorithm(self, small_model):
        cfg, params = small_model
        with pytest.raises(ValueError, match="registered backends"):
            _engine(cfg, params, algorithm="not_a_sampler")

    def test_backends_satisfy_protocol(self):
        for name in registered_backends():
            b = make_backend(name, vocab_size=64, k_cap=16, seed=0)
            assert isinstance(b, SamplerBackend)
            assert b.name == name


# ---------------------------------------------------------------------------
# Backend-differential identity (CI matrix: once per $REPRO_BACKEND)
# ---------------------------------------------------------------------------

MODES = [(True, "contiguous"), (False, "contiguous"),
         (True, "paged"), (False, "paged")]


def _shared_reqs(cfg):
    """Configs on which every exact backend's draw rule coincides with the
    reference: greedy rows (flag and τ=0) and single-token supports
    (top_k=1 — support is argmax regardless of the uniform)."""
    rng = np.random.default_rng(11)
    reqs = []
    for i in range(5):
        prompt = rng.integers(1, cfg.vocab_size,
                              int(rng.integers(3, 8))).tolist()
        if i % 3 == 0:
            s = SamplingConfig(greedy=True, temperature=0.9,
                               repetition_penalty=1.1)
        elif i % 3 == 1:
            s = SamplingConfig(temperature=0.8, top_k=1)
        else:
            s = SamplingConfig(temperature=0.0)
        reqs.append(Request(i, prompt, 4, s))
    return reqs


def _seeded_reqs(cfg):
    """Seeded stochastic filtered configs: every backend must reproduce its
    own stream bit-for-bit across engine modes."""
    rng = np.random.default_rng(23)
    return [Request(
        i, rng.integers(1, cfg.vocab_size, int(rng.integers(3, 8))).tolist(),
        4, SamplingConfig(temperature=0.9, top_k=20, top_p=0.95,
                          repetition_penalty=1.1, seed=1000 + i))
        for i in range(4)]


def _variant_engine(cfg, params, variant, **kw):
    algorithm, shvs = variant_config(variant, hot_size=64)
    return _engine(cfg, params, algorithm=algorithm, shvs=shvs, **kw)


def _streams(cfg, params, cache_dict, backend, overlap, kv, workload,
             reqs_fn, sampler_mode="device"):
    key = (backend, overlap, kv, workload, sampler_mode)
    if key not in cache_dict:
        eng = _variant_engine(cfg, params, backend, overlap=overlap,
                              cache=kv, sampler_mode=sampler_mode)
        reqs = reqs_fn(cfg)
        eng.submit(reqs)
        done = eng.run(max_steps=400)
        eng.close()
        assert len(done) == len(reqs)
        if backend == SHVS_FALLBACK:
            # both workloads sample filtered rows, which the one-token hot
            # set cannot hold: the S2 fallback must really have served
            assert max(r.fallback_rate for r in eng.stats_log) > 0.0, \
                "no row took the full-vocabulary fallback"
        cache_dict[key] = {r.request_id: list(r.output) for r in done}
    return cache_dict[key]


def _placements():
    """Every variant with the decision on the device, plus ``shvs`` and
    ``fused`` in the host sampler pool."""
    out = [pytest.param(v, "device", id=v) for v in variants_under_test()]
    out += [pytest.param(b, "host", id=f"{b}-host")
            for b in backends_under_test() if b in ("shvs", "fused")]
    return out


@pytest.mark.backends
class TestBackendDifferential:
    @pytest.mark.parametrize("overlap,kv", MODES)
    @pytest.mark.parametrize("backend,sampler_mode", _placements())
    def test_bit_identical_to_reference_on_shared_configs(
            self, small_model, stream_cache, backend, sampler_mode, overlap,
            kv):
        cfg, params = small_model
        ref = _streams(cfg, params, stream_cache, "reference", False,
                       "contiguous", "shared", _shared_reqs)
        got = _streams(cfg, params, stream_cache, backend, overlap, kv,
                       "shared", _shared_reqs, sampler_mode)
        assert got == ref, (
            f"{backend} [{sampler_mode}, "
            f"{'overlap' if overlap else 'seq'}, {kv}] diverged "
            f"from the reference sampler on shared configs")

    @pytest.mark.parametrize("backend", variants_under_test())
    def test_cross_mode_identity_on_seeded_stochastic_configs(
            self, small_model, stream_cache, backend):
        cfg, params = small_model
        a = _streams(cfg, params, stream_cache, backend, True, "contiguous",
                     "seeded", _seeded_reqs)
        b = _streams(cfg, params, stream_cache, backend, False, "paged",
                     "seeded", _seeded_reqs)
        assert a == b, f"{backend}: overlap+contiguous != sequential+paged"

    @pytest.mark.parametrize("backend", variants_under_test())
    def test_seeded_stream_invariant_to_batch_composition(
            self, small_model, backend):
        """Same per-request seed, different co-resident requests, different
        request id, different admission order -> identical stream."""
        cfg, params = small_model
        rng = np.random.default_rng(31)
        prompt = rng.integers(1, cfg.vocab_size, 6).tolist()
        scfg = SamplingConfig(temperature=0.9, top_k=16, top_p=0.95,
                              repetition_penalty=1.1, seed=123)

        def distractor(rid, plen_seed):
            r2 = np.random.default_rng(plen_seed)
            return Request(rid, r2.integers(
                1, cfg.vocab_size, int(r2.integers(3, 8))).tolist(), 4,
                SamplingConfig(temperature=1.1, top_k=8, seed=50 + rid))

        runs = []
        for rid, order in ((50, "last"), (7, "first")):
            target = Request(rid, list(prompt), 5, scfg)
            others = [distractor(100 + rid + j, 7 * rid + j)
                      for j in range(2 if order == "last" else 3)]
            batch = others + [target] if order == "last" \
                else [target] + others
            eng = _variant_engine(cfg, params, backend)
            eng.submit(batch)
            eng.run(max_steps=400)
            assert target.done
            runs.append(list(target.output))
        assert runs[0] == runs[1], (
            f"{backend}: seeded stream depends on batch composition")


# ---------------------------------------------------------------------------
# Per-request contract: seed / greedy / logit_bias / stop / finish_reason
# ---------------------------------------------------------------------------


class TestPerRequestContract:
    def test_same_seed_same_prompt_same_stream_in_one_batch(self, small_model):
        """Two co-resident requests sharing (seed, prompt, params) must emit
        identical tokens — the stream is a function of the seed, not the
        request id or slot."""
        cfg, params = small_model
        rng = np.random.default_rng(3)
        prompt = rng.integers(1, cfg.vocab_size, 5).tolist()
        scfg = SamplingConfig(temperature=0.9, top_k=20, seed=99)
        a, b = (Request(i, list(prompt), 5, scfg) for i in (0, 1))
        eng = _engine(cfg, params)
        eng.submit([a, b])
        eng.run(max_steps=100)
        assert a.output == b.output and len(a.output) == 5

    def test_seeded_stream_independent_of_engine_seed(self, small_model):
        cfg, params = small_model
        rng = np.random.default_rng(4)
        prompt = rng.integers(1, cfg.vocab_size, 5).tolist()
        outs = []
        for eng_seed in (0, 42):
            req = Request(0, list(prompt), 5,
                          SamplingConfig(temperature=0.9, top_k=20, seed=7))
            eng = _engine(cfg, params, seed=eng_seed)
            eng.submit([req])
            eng.run(max_steps=100)
            outs.append(list(req.output))
        assert outs[0] == outs[1]

    def test_unseeded_requests_keep_engine_keyed_streams(self, small_model):
        """seed=None preserves the PR1/PR2 contract: the stream is keyed on
        (engine seed, request id) and reproducible run-to-run."""
        cfg, params = small_model
        rng = np.random.default_rng(5)
        prompt = rng.integers(1, cfg.vocab_size, 5).tolist()
        scfg = SamplingConfig(temperature=0.9, top_k=20)   # seed=None
        outs = []
        for _ in range(2):
            req = Request(3, list(prompt), 5, scfg)
            eng = _engine(cfg, params)
            eng.submit([req])
            eng.run(max_steps=100)
            outs.append(list(req.output))
        assert outs[0] == outs[1]

    def test_greedy_flag_equals_temperature_zero(self, small_model):
        cfg, params = small_model
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, cfg.vocab_size, 5).tolist()
                   for _ in range(3)]
        outs = {}
        for name, scfg in (("flag", SamplingConfig(greedy=True,
                                                   temperature=0.9,
                                                   top_k=30)),
                           ("tau0", SamplingConfig(temperature=0.0))):
            reqs = [Request(i, list(p), 4, scfg)
                    for i, p in enumerate(prompts)]
            eng = _engine(cfg, params)
            eng.submit(reqs)
            eng.run(max_steps=100)
            outs[name] = {r.request_id: r.output for r in reqs}
        assert outs["flag"] == outs["tau0"]

    def test_logit_bias_forces_token(self, small_model):
        cfg, params = small_model
        rng = np.random.default_rng(7)
        forced = 7
        req = Request(0, rng.integers(1, cfg.vocab_size, 5).tolist(), 4,
                      SamplingConfig(temperature=0.9,
                                     logit_bias={forced: 80.0}))
        eng = _engine(cfg, params)
        eng.submit([req])
        eng.run(max_steps=100)
        assert req.output == [forced] * 4

    def test_logit_bias_normalized_hashable_any_spelling(self):
        """dict / sorted tuple / unsorted tuple of the same bias must
        compare and hash equal (configs are jit static args / dict keys)."""
        a = SamplingConfig(logit_bias={3: 1.0, 1: -2.0})
        b = SamplingConfig(logit_bias=((1, -2.0), (3, 1.0)))
        c = SamplingConfig(logit_bias=((3, 1.0), (1, -2.0)))
        assert a == b == c
        assert hash(a) == hash(b) == hash(c)

    def test_unbiased_coresident_stream_unchanged(self, small_model):
        """A biased request joining the batch must not perturb its
        neighbours' streams (bias rows are exact zeros elsewhere)."""
        cfg, params = small_model
        rng = np.random.default_rng(8)
        prompt = rng.integers(1, cfg.vocab_size, 5).tolist()
        plain_cfg = SamplingConfig(temperature=0.9, top_k=20)
        solo = Request(1, list(prompt), 5, plain_cfg)
        eng = _engine(cfg, params)
        eng.submit([solo])
        eng.run(max_steps=100)
        plain = Request(1, list(prompt), 5, plain_cfg)
        biased = Request(2, rng.integers(1, cfg.vocab_size, 5).tolist(), 5,
                         SamplingConfig(temperature=0.9,
                                        logit_bias={3: 50.0}))
        eng = _engine(cfg, params)
        eng.submit([plain, biased])
        eng.run(max_steps=100)
        assert plain.output == solo.output

    def test_stop_sequence_finishes_with_stop_reason(self, small_model):
        cfg, params = small_model
        rng = np.random.default_rng(9)
        prompt = rng.integers(1, cfg.vocab_size, 5).tolist()
        probe = Request(0, list(prompt), 6, SamplingConfig(temperature=0.0))
        eng = _engine(cfg, params)
        eng.submit([probe])
        eng.run(max_steps=100)
        head = tuple(probe.output[:2])
        req = Request(1, list(prompt), 6,
                      SamplingConfig(temperature=0.0,
                                     stop_sequences=(head,)))
        eng = _engine(cfg, params, overlap=True)
        eng.submit([req])
        eng.run(max_steps=100)
        assert req.output == list(head), \
            "generation must stop right after the stop sequence commits"
        assert req.finish_reason == "stop"

    def test_finish_reason_length_and_eos(self, small_model):
        cfg, params = small_model
        rng = np.random.default_rng(10)
        prompt = rng.integers(1, cfg.vocab_size, 5).tolist()
        by_len = Request(0, list(prompt), 3, SamplingConfig(temperature=0.0))
        eng = _engine(cfg, params)
        eng.submit([by_len])
        eng.run(max_steps=100)
        assert by_len.finish_reason == "length"
        first = by_len.output[0]
        by_eos = Request(1, list(prompt), 6, SamplingConfig(temperature=0.0),
                         eos_token=first)
        eng = _engine(cfg, params)
        eng.submit([by_eos])
        eng.run(max_steps=100)
        assert by_eos.finish_reason == "eos" and by_eos.output == [first]


# ---------------------------------------------------------------------------
# SlotParams lifecycle (satellite: stale-cache regression)
# ---------------------------------------------------------------------------


class TestSlotParams:
    def test_cache_invalidation_unit(self):
        sp = SlotParams(2, 16)
        p1 = sp.as_params()
        assert sp.as_params() is p1, "cache must be reused untouched"
        sp.set_row(0, SamplingConfig(temperature=0.3, top_k=5, seed=9,
                                     greedy=False))
        p2 = sp.as_params()
        assert p2 is not p1
        assert float(p2.temperature[0]) == pytest.approx(0.3)
        assert int(p2.top_k[0]) == 5
        assert bool(p2.use_seed[0]) and int(p2.seed[0]) == 9
        sp.reset_row(0)
        p3 = sp.as_params()
        assert float(p3.temperature[0]) == 1.0 and int(p3.top_k[0]) == 0
        assert not bool(p3.use_seed[0])
        # the previously built struct is immutable — in-flight programs
        # holding p2 never observe later row edits
        assert int(p2.top_k[0]) == 5

    def test_greedy_maps_to_temperature_zero(self):
        sp = SlotParams(1, 8)
        sp.set_row(0, SamplingConfig(greedy=True, temperature=1.3))
        assert float(sp.as_params().temperature[0]) == 0.0

    def test_bias_rows_dense_and_sticky(self):
        sp = SlotParams(2, 8)
        assert sp.bias_array() is None
        sp.set_row(1, SamplingConfig(logit_bias={3: 2.0}))
        dense = np.asarray(sp.bias_array())
        assert dense.shape == (2, 8)
        assert dense[1, 3] == 2.0 and dense.sum() == 2.0
        sp.reset_row(1)
        dense = np.asarray(sp.bias_array())   # sticky operand, zeroed row
        assert dense.sum() == 0.0

    @pytest.mark.parametrize("cache", ["contiguous", "paged"])
    def test_slot_reuse_never_dispatches_stale_params(self, small_model,
                                                      cache):
        """Regression (service API satellite): every dispatched decode must
        carry, for each active slot, exactly the sampling params of the
        request occupying that slot at dispatch time — through retirement,
        slot reuse, and (paged) preemption/resume."""
        cfg, params = small_model
        kw = dict(max_batch=2, algorithm="reference", cache=cache)
        if cache == "paged":
            kw.update(block_size=16, num_blocks=6)   # force preemption
        eng = _engine(cfg, params, **kw)
        rng = np.random.default_rng(12)
        temps = [0.3, 0.0, 1.2, 0.7, 0.9]
        kks = [5, 0, 7, 3, 11]
        reqs = [Request(
            i, rng.integers(1, cfg.vocab_size,
                            int(rng.integers(3, 8))).tolist(), 4,
            SamplingConfig(temperature=temps[i], top_k=kks[i], seed=i * 11))
            for i in range(5)]

        violations = []
        orig = eng._decode_jit

        def spy(p, cache_, pstate, last, sparams, bias, nonces, pos, step,
                active):
            occ = list(eng.scheduler.slots)
            t = np.asarray(sparams.temperature)
            k = np.asarray(sparams.top_k)
            s = np.asarray(sparams.seed)
            for b in np.flatnonzero(np.asarray(active)):
                r = occ[b]
                if r is None:
                    continue
                want_t = 0.0 if r.sampling.greedy else r.sampling.temperature
                if not (np.isclose(t[b], want_t) and k[b] == r.sampling.top_k
                        and s[b] == (r.sampling.seed or 0)):
                    violations.append((int(step), int(b), r.request_id))
            return orig(p, cache_, pstate, last, sparams, bias, nonces, pos,
                        step, active)

        eng._decode_jit = spy
        eng.submit(reqs)
        done = eng.run(max_steps=400)
        assert len(done) == 5
        assert not violations, \
            f"stale SlotParams dispatched after slot reuse: {violations}"
        if cache == "paged":
            assert eng.scheduler.preemptions >= 0   # path exercised


# ---------------------------------------------------------------------------
# Engine.generate() streaming surface
# ---------------------------------------------------------------------------


def _gen_reqs(cfg, n=7, max_new=5):
    rng = np.random.default_rng(13)
    return [Request(
        i, rng.integers(1, cfg.vocab_size, int(rng.integers(3, 9))).tolist(),
        int(rng.integers(2, max_new + 1)),
        SamplingConfig(temperature=0.9, top_k=30, top_p=0.95,
                       repetition_penalty=1.1, seed=500 + i))
        for i in range(n)]


class TestGenerate:
    def test_streams_incrementally(self, small_model):
        """First event must arrive while the batch is still working —
        streaming, not collect-then-replay."""
        cfg, params = small_model
        eng = _engine(cfg, params)
        reqs = _gen_reqs(cfg)
        gen = eng.generate(reqs)
        first = next(gen)
        assert isinstance(first, GenerationEvent)
        assert first.token is not None
        assert any(not r.done for r in reqs), \
            "first event should precede batch completion"
        list(gen)   # drain
        assert all(r.done or r.should_stop() for r in reqs)

    @pytest.mark.parametrize("overlap", [True, False])
    def test_collected_events_bit_identical_to_run(self, small_model,
                                                   overlap):
        cfg, params = small_model
        reqs = _gen_reqs(cfg)
        ref_eng = _engine(cfg, params, overlap=overlap)
        ref = _copy(reqs)
        ref_eng.submit(ref)
        ref_eng.run(max_steps=400)
        want = {r.request_id: list(r.output) for r in ref}

        eng = _engine(cfg, params, overlap=overlap)
        got: dict = {}
        fins: dict = {}
        for ev in eng.generate(_copy(reqs)):
            if ev.token is not None:
                got.setdefault(ev.request_id, []).append(ev.token)
            if ev.finish_reason is not None:
                assert ev.request_id not in fins, \
                    "finish_reason must be emitted exactly once per request"
                fins[ev.request_id] = ev.finish_reason
        assert got == want, "generate() streams != submit+run streams"
        assert set(fins) == set(want)
        assert all(v in ("eos", "length", "stop", "truncated")
                   for v in fins.values())

    def test_final_event_carries_finish_reason(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params)
        reqs = _gen_reqs(cfg, n=3)
        seen: dict = {}
        for ev in eng.generate(reqs):
            assert seen.get(ev.request_id) is None, \
                "no events may follow a finish_reason event"
            if ev.finish_reason is not None:
                seen[ev.request_id] = ev.finish_reason
        assert len(seen) == 3
        for r in reqs:
            assert seen[r.request_id] == r.finish_reason

    def test_empty_request_list(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params)
        assert list(eng.generate([])) == []

    def test_step_cap_raises_instead_of_silent_stop(self, small_model):
        """A streaming client must be able to distinguish completion from
        the step cap — the stream never just ends mid-request."""
        cfg, params = small_model
        eng = _engine(cfg, params)
        rng = np.random.default_rng(15)
        reqs = [Request(i, rng.integers(1, cfg.vocab_size, 5).tolist(), 8,
                        SamplingConfig(temperature=0.9, top_k=20))
                for i in range(2)]
        with pytest.raises(RuntimeError, match="max_steps"):
            list(eng.generate(reqs, max_steps=1))

    def test_generate_with_stop_sequences(self, small_model):
        cfg, params = small_model
        rng = np.random.default_rng(14)
        prompt = rng.integers(1, cfg.vocab_size, 5).tolist()
        probe = Request(0, list(prompt), 4, SamplingConfig(temperature=0.0))
        eng = _engine(cfg, params)
        eng.submit([probe])
        eng.run(max_steps=100)
        head = tuple(probe.output[:2])
        eng = _engine(cfg, params)
        req = Request(1, list(prompt), 8,
                      SamplingConfig(temperature=0.0, stop_sequences=(head,)))
        events = list(eng.generate([req]))
        toks = [e.token for e in events if e.token is not None]
        assert toks == list(head)
        assert events[-1].finish_reason == "stop"
