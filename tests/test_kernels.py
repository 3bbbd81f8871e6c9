"""The fused Pallas kernel vs its pure-jnp oracle: shape/dtype sweeps,
interpret mode.

Registry era: beyond the kernel-vs-oracle class, the
``TestRegisteredBackendIdentity`` class drives every registered
:class:`~repro.core.sampler_backend.SamplerBackend` (and the
``shvs-fallback`` variant, ``tests/_variants.py``) through the
DecisionPlane shell and checks the service-level contracts (greedy
identity to reference, single-token supports, logit-bias forcing,
allow-mask restriction, batch-composition invariance). ``REPRO_BACKEND``
narrows the parametrization to one backend — the CI matrix knob shared
with ``tests/test_service_api.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SamplingConfig
from repro.core.decision_plane import DecisionPlane
from repro.core.sampling import SamplingParams
from repro.kernels import ops, ref

from _variants import assert_fallback, variant_config, variants_under_test

pytestmark = pytest.mark.kernels

SHAPES = [(1, 128), (4, 512), (8, 1024), (3, 700), (16, 2048), (5, 4096)]
DTYPES = [jnp.float32, jnp.bfloat16]


# ---------------------------------------------------------------------------
# Fused single-pass sampler (penalties → temp → truncation → Gumbel draw)
# ---------------------------------------------------------------------------


def _fused_inputs(B, V, seed=0, dtype=jnp.float32, hot_frac=0.25):
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.normal(0, 4, (B, V)).astype(np.float32)).astype(dtype)
    cp = jnp.asarray(rng.integers(0, 3, (B, V)), jnp.int32)
    co = jnp.asarray(rng.integers(0, 3, (B, V)), jnp.int32)
    params = SamplingParams(
        temperature=jnp.asarray(rng.uniform(0.3, 1.5, B), jnp.float32),
        top_k=jnp.asarray(rng.integers(0, 32, B), jnp.int32),
        top_p=jnp.asarray(rng.uniform(0.7, 1.0, B), jnp.float32),
        min_p=jnp.asarray(rng.uniform(0.0, 0.1, B), jnp.float32),
        repetition_penalty=jnp.asarray(rng.uniform(1.0, 2.0, B), jnp.float32),
        presence_penalty=jnp.asarray(rng.uniform(0, 1, B), jnp.float32),
        frequency_penalty=jnp.asarray(rng.uniform(0, 0.5, B), jnp.float32))
    u = jnp.asarray(rng.random(B), jnp.float32)
    hot = jnp.asarray(rng.random(V) < hot_frac)
    return z, cp, co, params, u, hot


def _assert_fused_matches_oracle(z, cp, co, params, u, hot, *, k_cap,
                                 block_b=8, block_v=512):
    """Kernel ≡ tile-faithful oracle, bitwise, on all four outputs."""
    got = ops.fused_sample(z, cp, co, params, u, hot, k_cap=k_cap,
                           block_b=block_b, block_v=block_v)
    want = ref.fused_sample_ref(
        z, cp, co, params.repetition_penalty, params.presence_penalty,
        params.frequency_penalty, params.temperature, params.top_k,
        params.top_p, params.min_p, u, hot, k_cap=k_cap, block_b=block_b,
        block_v=block_v)
    for g, w, name in zip(got, want, ("tokens", "exact", "alpha", "kept")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


class TestFusedKernel:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_bit_identical_to_oracle(self, shape, dtype):
        B, V = shape
        z, cp, co, params, u, hot = _fused_inputs(B, V, dtype=dtype)
        _assert_fused_matches_oracle(z, cp, co, params, u, hot, k_cap=64)

    @pytest.mark.parametrize("block_v", [128, 256, 1024])
    def test_block_shapes_each_match_oracle(self, block_v):
        """Kernel ≡ oracle at every tiling (the oracle walks the same
        tiles, so parity must hold per-block_v, accumulation order and
        all)."""
        z, cp, co, params, u, hot = _fused_inputs(4, 2048, seed=11)
        _assert_fused_matches_oracle(z, cp, co, params, u, hot, k_cap=64,
                                     block_v=block_v)

    def test_extreme_logits(self):
        """±inf injections and fully-masked rows never poison the pass."""
        B, V = 5, 512
        z, cp, co, params, u, hot = _fused_inputs(B, V, seed=7)
        z = np.asarray(z).copy()
        z[0, 17] = np.inf
        z[1, ::3] = -np.inf
        z[2, :] = -1e30          # constrained-decoding all-masked row
        z[3, :] = -np.inf        # degenerate: empty support
        z = jnp.asarray(z)
        _assert_fused_matches_oracle(z, cp, co, params, u, hot, k_cap=32)
        toks = np.asarray(ops.fused_sample(z, cp, co, params, u, hot,
                                           k_cap=32)[0])
        assert ((toks >= 0) & (toks < V)).all()

    @pytest.mark.parametrize("hot_frac", [0.0, 1.0])
    def test_empty_and_full_hot_set(self, hot_frac):
        z, cp, co, params, u, hot = _fused_inputs(4, 512, seed=3,
                                                  hot_frac=hot_frac)
        _assert_fused_matches_oracle(z, cp, co, params, u, hot, k_cap=64)
        alpha = np.asarray(ops.fused_sample(z, cp, co, params, u, hot,
                                            k_cap=64)[2])
        np.testing.assert_allclose(alpha, hot_frac, atol=1e-6)

    def test_tau_zero_is_penalized_argmax(self):
        """Greedy rows (τ=0) return the argmax of the *penalized* logits —
        the single pass keeps Eq. 1 in front of the greedy shortcut."""
        B, V = 6, 512
        z, cp, co, params, u, hot = _fused_inputs(B, V, seed=5)
        params = params._replace(
            temperature=jnp.zeros((B,), jnp.float32),
            top_k=jnp.zeros((B,), jnp.int32))
        _assert_fused_matches_oracle(z, cp, co, params, u, hot, k_cap=64)
        toks = np.asarray(ops.fused_sample(z, cp, co, params, u, hot,
                                           k_cap=64)[0])
        zp = ref.penalty_ref(z, cp, co, params.repetition_penalty,
                             params.presence_penalty,
                             params.frequency_penalty,
                             jnp.ones((B,), jnp.float32))
        np.testing.assert_array_equal(toks, np.asarray(jnp.argmax(zp, -1)))


# ---------------------------------------------------------------------------
# Registry-era identity: every registered backend through the plane shell
# ---------------------------------------------------------------------------


def _plane(variant, V=512, seed=0):
    algorithm, shvs = variant_config(variant, hot_size=64)
    return DecisionPlane(V, algorithm=algorithm, shvs=shvs, k_cap=64,
                         seed=seed)


def _plane_inputs(plane, B=6, seed=0):
    rng = np.random.default_rng(seed)
    V = plane.vocab_size
    prompts = jnp.asarray(rng.integers(0, V, (B, 8)), jnp.int32)
    state = plane.init_state(B, prompt_tokens=prompts)
    logits = jnp.asarray(rng.normal(0, 3, (B, V)).astype(np.float32))
    return logits, state


class TestRegisteredBackendIdentity:
    """Plane-level differential identity, parametrized over the registry
    and ``shvs-fallback`` (the kernel-tier mirror of
    ``tests/test_service_api.py``'s engine-level suite): on deterministic
    supports every backend must agree with the ``reference`` backend
    bit-for-bit, penalties and histogram feedback included."""

    @pytest.mark.parametrize("backend", variants_under_test())
    def test_greedy_multistep_identity_vs_reference(self, backend):
        cfg = SamplingConfig(temperature=0.0, repetition_penalty=1.3,
                             presence_penalty=0.5, frequency_penalty=0.2)
        dut, oracle = _plane(backend), _plane("reference")
        logits, state_d = _plane_inputs(dut)
        _, state_o = _plane_inputs(oracle)
        params = SamplingParams.broadcast(6, cfg).strip_rng()
        rng = np.random.default_rng(1)
        for step in range(4):
            z = jnp.asarray(rng.normal(0, 3, logits.shape)
                            .astype(np.float32))
            t_d, state_d, stats = dut.step(z, state_d, params, step)
            t_o, state_o, _ = oracle.step(z, state_o, params, step)
            np.testing.assert_array_equal(np.asarray(t_d), np.asarray(t_o),
                                          err_msg=f"step {step}")
            assert_fallback(backend, float(stats.fallback_rate),
                            served=False)

    @pytest.mark.parametrize("backend", variants_under_test())
    def test_top_k1_identity_vs_reference(self, backend):
        """top_k=1 at τ>0: a single-token support, so the draw is forced
        and every backend must match reference exactly."""
        cfg = SamplingConfig(temperature=0.8, top_k=1,
                             repetition_penalty=1.2)
        dut, oracle = _plane(backend), _plane("reference")
        logits, state_d = _plane_inputs(dut, seed=2)
        _, state_o = _plane_inputs(oracle, seed=2)
        params = SamplingParams.broadcast(6, cfg).strip_rng()
        t_d, _, stats = dut.step(logits, state_d, params, 0)
        t_o, _, _ = oracle.step(logits, state_o, params, 0)
        np.testing.assert_array_equal(np.asarray(t_d), np.asarray(t_o))
        assert_fallback(backend, float(stats.fallback_rate), served=True)

    @pytest.mark.parametrize("backend", variants_under_test())
    def test_logit_bias_forces_token(self, backend):
        plane = _plane(backend)
        B, V = 6, plane.vocab_size
        logits, state = _plane_inputs(plane, seed=3)
        forced = np.arange(7, 7 + B, dtype=np.int64) * 13 % V
        bias = np.zeros((B, V), np.float32)
        bias[np.arange(B), forced] = 1e9
        params = SamplingParams.broadcast(
            B, SamplingConfig(temperature=1.0, top_k=4)).strip_rng()
        toks, _, stats = plane.step(logits, state, params, 0,
                                    logit_bias=jnp.asarray(bias))
        np.testing.assert_array_equal(np.asarray(toks), forced)
        assert_fallback(backend, float(stats.fallback_rate), served=True)

    @pytest.mark.parametrize("backend", variants_under_test())
    def test_allow_mask_restricts_support(self, backend):
        plane = _plane(backend)
        B, V = 6, plane.vocab_size
        logits, state = _plane_inputs(plane, seed=4)
        rng = np.random.default_rng(4)
        allow = np.zeros((B, V), bool)
        for b in range(B):
            allow[b, rng.choice(V, 8, replace=False)] = True
        params = SamplingParams.broadcast(
            B, SamplingConfig(temperature=1.0)).strip_rng()
        toks, _, stats = plane.step(logits, state, params, 0,
                                    allow_mask=jnp.asarray(allow))
        assert allow[np.arange(B), np.asarray(toks)].all()
        assert_fallback(backend, float(stats.fallback_rate), served=False)

    @pytest.mark.parametrize("backend", variants_under_test())
    def test_batch_composition_invariance(self, backend):
        """With (request, position)-keyed uniforms, a row's token cannot
        depend on which other rows share the batch."""
        cfg = SamplingConfig(temperature=0.9, top_k=8)
        B = 6
        plane = _plane(backend)
        logits, state = _plane_inputs(plane, seed=5)
        params = SamplingParams.broadcast(B, cfg)
        nonces = np.arange(100, 100 + B, dtype=np.uint32)
        pos = np.full((B,), 9, np.int32)
        full, _, stats = plane.step(
            logits, state, params, 0,
            rng_tags=(jnp.asarray(nonces), jnp.asarray(pos)))
        full = np.asarray(full)
        assert_fallback(backend, float(stats.fallback_rate), served=True)

        keep = np.asarray([1, 3, 4])
        sub_plane = _plane(backend)
        rng = np.random.default_rng(5)
        prompts = jnp.asarray(rng.integers(0, plane.vocab_size, (B, 8)),
                              jnp.int32)[keep]
        sub_state = sub_plane.init_state(len(keep), prompt_tokens=prompts)
        sub_params = SamplingParams.broadcast(len(keep), cfg)
        sub = np.asarray(sub_plane.step(
            logits[keep], sub_state, sub_params, 0,
            rng_tags=(jnp.asarray(nonces[keep]), jnp.asarray(pos[keep])))[0])
        np.testing.assert_array_equal(sub, full[keep])


class TestMosaicLowerableHelpers:
    """The shared helpers were rewritten in forms Mosaic lowers; each must
    give the bits its direct jnp form gives, so streams do not move."""

    def test_u32_to_f32_matches_direct_cast(self):
        edge = np.array([0, 1, 2**16 - 1, 2**16, 2**24 - 1, 2**24,
                         2**24 + 1, 2**24 + 3, 2**31 - 1, 2**31,
                         2**32 - 129, 2**32 - 128, 2**32 - 127, 2**32 - 1],
                        np.uint64).astype(np.uint32)
        rnd = np.random.default_rng(0).integers(
            0, 2**32, 200_000, dtype=np.uint64).astype(np.uint32)
        x = jnp.asarray(np.concatenate([edge, rnd]))
        got = np.asarray(ref._u32_to_f32(x)).view(np.uint32)
        want = np.asarray(x.astype(jnp.float32)).view(np.uint32)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("seed", [0, 12345, ref.FUSED_DRAW_SALT])
    def test_hash_uniform_gives_the_same_bits_as_before(self, seed):
        def direct(seed, b, v):        # the form before the int32 halves
            x = (b.astype(jnp.uint32) * jnp.uint32(2654435761) ^
                 v.astype(jnp.uint32) * jnp.uint32(40503) ^
                 jnp.uint32(seed))
            x = x ^ (x >> jnp.uint32(16))
            x = x * jnp.uint32(2246822519)
            x = x ^ (x >> jnp.uint32(13))
            x = x * jnp.uint32(3266489917)
            x = x ^ (x >> jnp.uint32(16))
            return (x.astype(jnp.float32) + 0.5) * (1.0 / 4294967296.0)

        b = jax.lax.broadcasted_iota(jnp.int32, (64, 4096), 0)
        v = jax.lax.broadcasted_iota(jnp.int32, (64, 4096), 1) * 37
        np.testing.assert_array_equal(
            np.asarray(ref._hash_uniform(seed, b, v)).view(np.uint32),
            np.asarray(direct(seed, b, v)).view(np.uint32))

    def test_u32_from_uniform_matches_direct_cast(self):
        u = np.random.default_rng(1).random(100_000).astype(np.float32)
        u = jnp.asarray(np.concatenate(
            [u, np.float32([0.0, 0.5, 1.0 - 2**-24])]))
        np.testing.assert_array_equal(
            np.asarray(ref._u32_from_uniform(u)),
            np.asarray((u * 16777216.0).astype(jnp.uint32)))
