"""The decision plane's serving path and its host-pool placement.

* **Penalties** — Eq. 1 as it serves (``apply_penalties_rows`` then
  ``temperature_scale``) against the plain oracle ``ref.penalty_ref``,
  over shape/dtype sweeps.
* **SHVS masses** — ``core.shvs.shvs_masses``, the streaming pass SHVS
  serves with (Eq. 6–7), against a float64 numpy computation.
* **Host-pool placement** — the plane-level identity checks of
  ``tests/test_kernels.py`` run through a two-worker
  :class:`~repro.core.host_sampler.HostSamplerPool` for every variant
  (``tests/_variants.py``): the pool's draw must be bit-identical to the
  plane called directly, and keep each check's own contract.
* **A pool on a ``fused`` plane** — draws bit-identically to the device
  path, and ``refresh()`` carries a hot-set swap into the pool's program.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SamplingConfig
from repro.core import penalties as pen
from repro.core.decision_plane import DecisionPlane
from repro.core.host_sampler import HostSamplerPool
from repro.core.hot_vocab import build_hot_set
from repro.core.sampling import SamplingParams, temperature_scale
from repro.core.shvs import make_hot_set, shvs_masses
from repro.kernels import ref

from _variants import assert_fallback, variant_config, variants_under_test

SHAPES = [(1, 128), (4, 512), (8, 1024), (3, 700), (16, 2048), (5, 4096)]
DTYPES = [jnp.float32, jnp.bfloat16]


# ---------------------------------------------------------------------------
# Penalties + temperature on the serving path
# ---------------------------------------------------------------------------


def _penalty_inputs(B, V, dtype, seed=0):
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.normal(0, 4, (B, V)).astype(np.float32)).astype(dtype)
    cp = jnp.asarray(rng.integers(0, 3, (B, V)), jnp.int32)
    co = jnp.asarray(rng.integers(0, 3, (B, V)), jnp.int32)
    rep = jnp.asarray(rng.uniform(1.0, 2.0, B), jnp.float32)
    pres = jnp.asarray(rng.uniform(0, 1, B), jnp.float32)
    freq = jnp.asarray(rng.uniform(0, 0.5, B), jnp.float32)
    temp = jnp.asarray(rng.uniform(0.3, 1.5, B), jnp.float32)
    return z, cp, co, rep, pres, freq, temp


def _serving_penalties(z, cp, co, rep, pres, freq, temp):
    state = pen.PenaltyState(prompt_counts=cp, output_counts=co)
    return temperature_scale(pen.apply_penalties_rows(z, state, rep, pres,
                                                      freq), temp)


class TestServingPenalties:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, shape, dtype):
        """Both forms upcast to f32 first and apply the same ops in the
        same order, so they agree bit for bit at either input dtype."""
        B, V = shape
        args = _penalty_inputs(B, V, dtype)
        out = _serving_penalties(*args)
        assert out.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(ref.penalty_ref(*args)))

    def test_noop_penalties_only_scale(self):
        B, V = 4, 512
        z, cp, co, *_ = _penalty_inputs(B, V, jnp.float32)
        one = jnp.ones((B,), jnp.float32)
        zero = jnp.zeros((B,), jnp.float32)
        temp = jnp.full((B,), 2.0)
        out = _serving_penalties(z, cp, co, one, zero, zero, temp)
        np.testing.assert_allclose(np.asarray(out), np.asarray(z) / 2.0,
                                   rtol=1e-5)


# ---------------------------------------------------------------------------
# SHVS's streaming pass (Eq. 6-7)
# ---------------------------------------------------------------------------


def _masses_f64(z, hot):
    """(m, s_hot, s_tail, tail_max) in float64 numpy."""
    z = np.asarray(z, np.float64)
    m = z.max(-1)
    w = np.exp(z - m[:, None])
    return (m, (w * hot).sum(-1), (w * ~hot).sum(-1),
            np.where(hot[None, :], -1e30, z).max(-1))


def _assert_masses(z, hot):
    got = shvs_masses(jnp.asarray(z), make_hot_set(np.flatnonzero(hot),
                                                   z.shape[1]))
    for g, w, name in zip(got, _masses_f64(z, hot),
                          ("m", "s_hot", "s_tail", "tail_max")):
        g = np.asarray(g)
        assert np.isfinite(g).all(), name
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5,
                                   err_msg=name)


class TestSHVSMasses:
    @pytest.mark.parametrize("shape", SHAPES)
    def test_matches_float64(self, shape):
        B, V = shape
        rng = np.random.default_rng(1)
        z = rng.normal(0, 5, (B, V)).astype(np.float32)
        _assert_masses(z, rng.random(V) < 0.25)

    def test_extreme_logits_stable(self):
        z = np.asarray([[1e4, -1e4, 0.0, 5e3] * 128], np.float32)
        _assert_masses(z, np.asarray([True, False] * 256))


# ---------------------------------------------------------------------------
# Host-pool placement: the plane-level identity checks through the pool
# ---------------------------------------------------------------------------

V = 512
B = 6


def _plane(variant, hot_set=None):
    algorithm, shvs = variant_config(variant, hot_size=64)
    return DecisionPlane(V, algorithm=algorithm, shvs=shvs, hot_set=hot_set,
                         k_cap=64, seed=0)


def _state(seed):
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(0, V, (B, 8)), jnp.int32)
    return pen.init_state(B, V, prompts)


def _logits(seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0, 3, (B, V)).astype(np.float32))


def _tags(rows=B):
    return (np.arange(100, 100 + rows, dtype=np.uint32),
            np.full((rows,), 9, np.int32))


class _Pool:
    """A two-worker pool on ``plane`` beside the plane itself: ``draw``
    samples through the pool and checks the tokens and histograms against
    a direct ``plane.step`` on the same operands."""

    def __init__(self, plane):
        self.plane = plane
        self.pool = HostSamplerPool(plane, num_workers=2)

    def draw(self, logits, state, params, tags, bias=None):
        rows = logits.shape[0]
        active = np.ones((rows,), bool)
        res = self.pool.submit(logits, state, params, bias, *tags, 0,
                               active).result()
        want, want_state, _ = self.plane.step(
            logits, state, params, 0, active=jnp.asarray(active),
            rng_tags=tuple(jnp.asarray(t) for t in tags),
            logit_bias=None if bias is None else jnp.asarray(bias))
        np.testing.assert_array_equal(res.tokens, np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(res.state.output_counts),
            np.asarray(want_state.output_counts))
        return res

    def close(self):
        self.pool.close()


@pytest.fixture
def pools():
    made = []

    def make(plane):
        made.append(_Pool(plane))
        return made[-1]

    yield make
    for p in made:
        p.close()


@pytest.mark.backends
class TestHostPoolPlacement:
    @pytest.mark.parametrize("backend", variants_under_test())
    def test_greedy_multistep_identity_vs_reference(self, pools, backend):
        cfg = SamplingConfig(temperature=0.0, repetition_penalty=1.3,
                             presence_penalty=0.5, frequency_penalty=0.2)
        dut = pools(_plane(backend))
        oracle = _plane("reference")
        params = SamplingParams.broadcast(B, cfg)
        state_d = state_o = _state(0)
        for step in range(4):
            z = _logits(10 + step)
            res = dut.draw(z, state_d, params, _tags())
            t_o, state_o, _ = oracle.step(z, state_o, params, step)
            np.testing.assert_array_equal(res.tokens, np.asarray(t_o),
                                          err_msg=f"step {step}")
            state_d = res.state
            assert_fallback(backend, res.fallback_rate, served=False)

    @pytest.mark.parametrize("backend", variants_under_test())
    def test_top_k1_identity_vs_reference(self, pools, backend):
        cfg = SamplingConfig(temperature=0.8, top_k=1, repetition_penalty=1.2)
        params = SamplingParams.broadcast(B, cfg)
        logits, state = _logits(2), _state(2)
        res = pools(_plane(backend)).draw(logits, state, params, _tags())
        t_o, _, _ = _plane("reference").step(logits, state, params, 0)
        np.testing.assert_array_equal(res.tokens, np.asarray(t_o))
        assert_fallback(backend, res.fallback_rate, served=True)

    @pytest.mark.parametrize("backend", variants_under_test())
    def test_logit_bias_forces_token(self, pools, backend):
        forced = np.arange(7, 7 + B, dtype=np.int64) * 13 % V
        bias = np.zeros((B, V), np.float32)
        bias[np.arange(B), forced] = 1e9
        params = SamplingParams.broadcast(
            B, SamplingConfig(temperature=1.0, top_k=4))
        res = pools(_plane(backend)).draw(_logits(3), _state(3), params,
                                          _tags(), bias=bias)
        np.testing.assert_array_equal(res.tokens, forced)
        assert_fallback(backend, res.fallback_rate, served=True)

    @pytest.mark.parametrize("backend", variants_under_test())
    def test_allow_mask_restricts_support(self, pools, backend):
        """The pool takes no allow mask; a -1e30 bias on the disallowed
        columns must draw exactly what the plane's ``allow_mask`` draws."""
        rng = np.random.default_rng(4)
        allow = np.zeros((B, V), bool)
        for b in range(B):
            allow[b, rng.choice(V, 8, replace=False)] = True
        bias = np.where(allow, 0.0, -1e30).astype(np.float32)
        params = SamplingParams.broadcast(
            B, SamplingConfig(temperature=1.0))
        dut = pools(_plane(backend))
        res = dut.draw(_logits(4), _state(4), params, _tags(), bias=bias)
        assert allow[np.arange(B), res.tokens].all()
        want, _, _ = dut.plane.step(
            _logits(4), _state(4), params, 0,
            rng_tags=tuple(jnp.asarray(t) for t in _tags()),
            allow_mask=jnp.asarray(allow))
        np.testing.assert_array_equal(res.tokens, np.asarray(want))
        assert_fallback(backend, res.fallback_rate, served=False)

    @pytest.mark.parametrize("backend", variants_under_test())
    def test_batch_composition_invariance(self, pools, backend):
        cfg = SamplingConfig(temperature=0.9, top_k=8)
        logits, state = _logits(5), _state(5)
        nonces, pos = _tags()
        full = pools(_plane(backend)).draw(
            logits, state, SamplingParams.broadcast(B, cfg), (nonces, pos))
        assert_fallback(backend, full.fallback_rate, served=True)
        keep = np.asarray([1, 3, 4])
        sub_state = pen.PenaltyState(state.prompt_counts[keep],
                                     state.output_counts[keep])
        sub = pools(_plane(backend)).draw(
            logits[keep], sub_state,
            SamplingParams.broadcast(len(keep), cfg),
            (nonces[keep], pos[keep]))
        np.testing.assert_array_equal(sub.tokens, full.tokens[keep])


# ---------------------------------------------------------------------------
# A pool whose plane is ``fused``
# ---------------------------------------------------------------------------


def _pool_args(seed):
    rng = np.random.default_rng(seed)
    rows = 8
    logits = jnp.asarray(rng.normal(0, 2, (rows, V)).astype(np.float32))
    state = pen.PenaltyState(
        prompt_counts=jnp.asarray(rng.integers(0, 2, (rows, V)), jnp.int32),
        output_counts=jnp.zeros((rows, V), jnp.int32))
    params = SamplingParams.broadcast(rows, SamplingConfig(
        temperature=0.9, top_k=16, repetition_penalty=1.2))
    return (logits, state, params, None, np.arange(rows, dtype=np.uint32),
            np.zeros((rows,), np.int32), 0, np.ones((rows,), bool))


def _swapped_hot_set(h=128, seed=7):
    """A frequency-ranked hot set unlike the default prefix [0, H)."""
    return build_hot_set(np.random.default_rng(seed).random(V), h, V)


@pytest.mark.kernels
class TestPoolOnFusedPlane:
    def test_pool_matches_device_fused_bitwise(self):
        """Three host workers on a ``fused`` plane draw what the same plane
        draws on the device path: uniforms are (request, position)-keyed
        and the kernel is row-local, so the sharding moves no token."""
        plane = _plane("fused")
        logits, state, params, _, nonces, pos, _, active = _pool_args(1)
        pool = HostSamplerPool(plane, num_workers=3)
        try:
            got = pool.submit(*_pool_args(1)).result()
        finally:
            pool.close()
        want, want_state, _ = plane.step(
            logits, state, params, 0, active=jnp.asarray(active),
            rng_tags=(jnp.asarray(nonces), jnp.asarray(pos)))
        np.testing.assert_array_equal(got.tokens, np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got.state.output_counts),
                                      np.asarray(want_state.output_counts))

    def test_refresh_carries_hot_swap_into_pool_plane(self):
        """The stale-operand regression at the pool seam: after the engine
        swaps ``plane.hot_set`` and calls ``refresh()``, the pool must
        sample against the NEW hot set — bit-identical to a pool built on
        it — and its worker program must have re-jitted."""
        plane = _plane("fused")
        pool = HostSamplerPool(plane, num_workers=2)
        hot2 = _swapped_hot_set()
        fresh = HostSamplerPool(_plane("fused", hot_set=hot2), num_workers=2)
        args = _pool_args(2)
        try:
            before_jit = pool._step_jit
            stale = pool.submit(*args).result()
            plane.hot_set = hot2              # the autotuner's swap ...
            pool.refresh()                    # ... and the engine's hook
            assert pool._step_jit is not before_jit, \
                "refresh() must re-trace the worker program"
            got = pool.submit(*args).result()
            want = fresh.submit(*args).result()
        finally:
            pool.close()
            fresh.close()
        np.testing.assert_array_equal(got.tokens, want.tokens)
        assert got.alpha_mean == want.alpha_mean
        assert stale.alpha_mean != got.alpha_mean, \
            "swap must actually change the measured hot mass"
