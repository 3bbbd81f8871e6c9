"""Admission's first-token decision runs as one compiled program per group
size (``engine.admission_decision``): the tokens it admits are the ones
the op-by-op ``DecisionPlane.step`` picks on the same prefill logits, it
compiles once per (P, bias operand) and not per prompt length, and a hot
set swap rebuilds it with the decode program."""
import jax
import numpy as np
import pytest

from repro.config import SamplingConfig, SHVSConfig, get_arch
from repro.engine import (Engine, EngineConfig, PipelineConfig,
                          PipelineEngine, Request)
from repro.models.model import Model

_KW = dict(max_seq_len=64, algorithm="shvs", shvs=SHVSConfig(hot_size=64),
           k_cap=64, prompt_bucket=8)

# each case is one admission group of two rows
CONTRACTS = {
    "greedy": (SamplingConfig(temperature=0.0),
               SamplingConfig(temperature=0.0)),
    "sampled": (SamplingConfig(temperature=0.9, top_k=40, top_p=0.9),
                SamplingConfig(temperature=1.0, top_p=0.95, min_p=0.05)),
    "penalised": (SamplingConfig(temperature=0.8, repetition_penalty=1.3,
                                 presence_penalty=0.5,
                                 frequency_penalty=0.3),
                  SamplingConfig(temperature=0.0, repetition_penalty=1.5)),
    "biased": (SamplingConfig(temperature=0.9, top_p=0.95,
                              logit_bias=((7, 4.0), (11, -2.0))),
               SamplingConfig(temperature=0.9, top_k=50)),
}


@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("smollm-360m").reduced()
    params = Model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _group(cfg, contracts, length=5, rid0=0, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(request_id=rid0 + i,
                    prompt=rng.integers(1, cfg.vocab_size, length).tolist(),
                    max_new_tokens=1, sampling=s)
            for i, s in enumerate(contracts)]


def _record(eng):
    """Wrap the engine's admission program; returns the list of
    ``(operands, (tokens, pstate))`` of every admission it decides."""
    calls = []
    real = eng._admit_decide_jit

    def spy(*args):
        out = real(*args)
        calls.append((args, out))
        return out

    eng._admit_decide_jit = spy
    return calls


def _eager(decision, args):
    """The same decision op by op, outside any jit."""
    logits, pstate, sparams, step_idx, nonces, positions, bias = args
    tokens, _, _ = decision.step(logits, pstate, sparams, step_idx,
                                 rng_tags=(nonces, positions),
                                 logit_bias=bias)
    return np.asarray(tokens)


def _engine(kind, cfg, params):
    if kind == "pipeline":
        return PipelineEngine(cfg, params, PipelineConfig(
            max_batch=4, stages=2, microbatches=2, **_KW))
    return Engine(cfg, params, EngineConfig(max_batch=4, **_KW))


@pytest.mark.parametrize("kind", ["engine", "pipeline"])
@pytest.mark.parametrize("contract", list(CONTRACTS))
def test_admitted_first_tokens_match_eager_decision(small_model, kind,
                                                    contract):
    cfg, params = small_model
    eng = _engine(kind, cfg, params)
    calls = _record(eng)
    reqs = _group(cfg, CONTRACTS[contract])
    eng.submit(reqs)
    eng.run(max_steps=50)
    assert len(calls) == 1
    args, (tokens, _) = calls[0]
    assert args[0].shape == (2, cfg.vocab_size)
    assert (args[6] is not None) == (contract == "biased")
    want = _eager(eng.decision, args)
    np.testing.assert_array_equal(np.asarray(tokens), want)
    assert [r.output[0] for r in reqs] == want.tolist()
    eng.close()


def test_one_decision_program_per_group_size_and_bias(small_model):
    """A second group of the same size at another padded length builds a
    prefill program but no decision program; a bias operand builds one
    more."""
    cfg, params = small_model
    eng = _engine("engine", cfg, params)
    sampled = SamplingConfig(temperature=0.9, top_p=0.9)
    biased = SamplingConfig(temperature=0.9, logit_bias=((3, 2.0),))
    for length, contracts, programs in [(5, (sampled, sampled), 1),
                                        (13, (sampled, sampled), 1),
                                        (21, (sampled, sampled), 1),
                                        (5, (biased, sampled), 2)]:
        eng.submit(_group(cfg, contracts, length=length, rid0=10 * length))
        eng.run(max_steps=50)
        assert eng._admit_decide_jit._cache_size() == programs
    assert len(eng._prefill_cache) == 3        # one per (2, Sp)
    eng.close()


def test_hot_set_swap_rebuilds_admission_decision(small_model):
    """After ``_apply_hot_size`` the next admission decides against the new
    hot set: the program is rebuilt beside the decode program, and the old
    one would have drawn other tokens from the same operands."""
    cfg, params = small_model
    counts = np.arange(cfg.vocab_size, 0, -1, dtype=np.float64)
    eng = Engine(cfg, params, EngineConfig(max_batch=4, **_KW),
                 hot_counts=counts)
    contracts = [SamplingConfig(temperature=1.0)] * 4
    eng.submit(_group(cfg, contracts, rid0=0))
    eng.run(max_steps=50)                  # builds the old program
    old, old_decode = eng._admit_decide_jit, eng._decode_jit
    eng._apply_hot_size(8)
    assert eng._admit_decide_jit is not old
    assert eng._decode_jit is not old_decode
    calls = _record(eng)
    reqs = _group(cfg, contracts, rid0=100, seed=1)
    eng.submit(reqs)
    eng.run(max_steps=50)
    assert len(calls) == 1
    args, (tokens, _) = calls[0]
    want = _eager(eng.decision, args)
    np.testing.assert_array_equal(np.asarray(tokens), want)
    assert [r.output[0] for r in reqs] == want.tolist()
    with jax.default_device(eng.device):   # the engine's trace context
        stale, _ = old(*args)
    assert not np.array_equal(np.asarray(stale), want)
    assert eng.decision.hot_set.size == 8
    eng.close()
