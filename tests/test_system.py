"""End-to-end behaviour tests for the SIMPLE reproduction: the full
serve-with-decision-plane path preserves output quality (TVD, Fig. 13) and
delivers the structural properties the paper claims."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SamplingConfig, SHVSConfig, get_arch
from repro.core.decision_plane import DecisionPlane
from repro.core.hot_vocab import build_hot_set, counts_from_trace, synthetic_trace
from repro.core.sampling import SamplingParams, masked_probs_reference
from repro.core import penalties as pen
from repro.models.model import Model


@pytest.fixture(scope="module")
def model_and_logits():
    cfg = get_arch("tinyllama-1.1b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B = 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 12), 0,
                              cfg.vocab_size)
    cache = model.init_cache(B, 32)
    logits, _ = model.prefill(params, {"tokens": toks}, cache)
    return cfg, np.asarray(logits), toks


def test_end_to_end_tvd_below_noise(model_and_logits):
    """Fig. 13: TVD between the SHVS decision plane and the baseline target
    distribution is statistically indistinguishable from zero on real model
    logits."""
    cfg, logits, toks = model_and_logits
    B = logits.shape[0]
    trace = synthetic_trace(cfg.vocab_size, 20000, s=1.2)
    hot = build_hot_set(counts_from_trace(trace, cfg.vocab_size), 64,
                        cfg.vocab_size)
    dp = DecisionPlane(cfg.vocab_size, algorithm="shvs",
                       shvs=SHVSConfig(hot_size=64), hot_set=hot, k_cap=128)
    params = SamplingParams.broadcast(B, SamplingConfig(temperature=0.8,
                                                        top_k=40))
    state = dp.init_state(B, toks)
    z = pen.apply_penalties_rows(jnp.asarray(logits), state,
                                 params.repetition_penalty,
                                 params.presence_penalty,
                                 params.frequency_penalty)
    target = np.asarray(masked_probs_reference(z, params))
    N = 3000
    keys = jax.random.split(jax.random.PRNGKey(2), N)

    def draw(k):
        from repro.core.shvs import shvs_sample
        u = jax.random.uniform(k, (B, 3))
        return shvs_sample(z, params, dp.hot_set, u[:, 0], u[:, 1], u[:, 2],
                           k_cap=128).tokens

    toks_s = np.asarray(jax.vmap(draw)(keys))
    tvds = []
    for b in range(B):
        emp = np.bincount(toks_s[:, b], minlength=cfg.vocab_size) / N
        tvds.append(0.5 * np.abs(emp - target[b]).sum())
    noise_floor = np.sqrt(40 / (2 * np.pi * N)) * 2.5
    assert np.mean(tvds) < max(0.01, noise_floor), np.mean(tvds)


def test_decision_plane_is_separate_program(model_and_logits):
    """Structural disaggregation: the decision plane runs as its own jitted
    program consuming logits — no model state crosses the boundary."""
    cfg, logits, toks = model_and_logits
    B = logits.shape[0]
    dp = DecisionPlane(cfg.vocab_size, algorithm="shvs",
                       shvs=SHVSConfig(hot_size=64), k_cap=64)
    params = SamplingParams.broadcast(B, SamplingConfig(temperature=0.9))
    state = dp.init_state(B)
    stepped = jax.jit(dp.step)
    tokens, state2, stats = stepped(jnp.asarray(logits), state, params,
                                    jnp.asarray(0))
    assert tokens.shape == (B,)
    assert int(state2.output_counts.sum()) == B   # exactly one token per row


def test_histograms_track_served_tokens(model_and_logits):
    cfg, logits, toks = model_and_logits
    B = logits.shape[0]
    # a one-token hot set: SHVS's full-vocabulary fallback serves the rows
    dp = DecisionPlane(cfg.vocab_size, algorithm="shvs",
                       shvs=SHVSConfig(hot_size=1), k_cap=64)
    params = SamplingParams.broadcast(B, SamplingConfig(temperature=0.7,
                                                        top_k=20))
    state = dp.init_state(B)
    z = jnp.asarray(logits)
    seen = []
    for step in range(4):
        tokens, state, _ = dp.step(z, state, params, step)
        seen.append(np.asarray(tokens))
    total = np.zeros((B, cfg.vocab_size), np.int32)
    for t in seen:
        total[np.arange(B), t] += 1
    np.testing.assert_array_equal(np.asarray(state.output_counts), total)
