"""The served path against the chip benchmark's plain float32 reference.

Each case is one run of the benchmark's own entry (``entries/engine.run``)
on the CPU at toy widths, as ``benchmarks/chip/tests/test_faults.py``
drives it: seeded greedy and sampled requests of a closed-loop mix go
through ``Engine.submit``/``step`` (the scheduler, the contiguous cache,
the overlapped loop and the ``shvs`` decision fused into the decode
program) on the benchmark's ``ModelConfig`` and weights (with the head's
logit profile), and the served tokens through ``check.run``/
``check.decide``: each token's gap is the least change to the reference's
float32 logits that puts it inside the request's contract. Served in
bfloat16 the gaps stay small; a model that leaves out part of the
mathematics (qk-norm, the untied head), or a decision whose
full-vocabulary fallback serves a wrong token, reads far wider. A mutation
changes only the engine that serves, never the reference.

A shape-only case holds the benchmark's Qwen3-8B last stage to its
published widths, parameter count and cache size without allocating it.
"""
import time
from dataclasses import replace

import jax
import numpy as np
import pytest

import repro.core.shvs as shvs
import repro.engine
from benchmarks.chip import check, harness
from benchmarks.chip.entries import engine as entry
from benchmarks.chip.weights import make_weights
from repro.models.model import Model

DATA = harness.HERE / "tests" / "data"
SEED = 2**31 + 15
# Served in bfloat16, both gaps read at most ~0.02 at these widths
# (rounding can swap near ties of logits of std up to 16); the float8
# control reads ~1.9 on greedy tokens (benchmarks/chip/tests/test_faults.py);
# serving tiny-qwen3 with qk-norm off reads ~5, with its head tied ~29, and
# a fallback whose tokens are moved one id up ~47. 0.5 lies well between.
TOLERANCE = {"greedy_gap": {"limit": 0.5}, "sampled_gap": {"limit": 0.5}}


def tiny(name):
    return harness.load_json(DATA / f"{name}.json")


def wide_vocab():
    """tiny-qwen3 with a vocabulary of 8192, whose default hot set is its
    first 2048 ids, and a head flat enough (exponent 0.35) that the
    containment guard fails on about a quarter of the top-k 50 rows: those
    rows are served by the full-vocabulary fallback."""
    cfg = tiny("tiny-qwen3")
    return dict(cfg, vocab_size=8192, weights={
        "head_zipf": {"top_logit_std": 16.0, "exponent": 0.35}})


def serve(monkeypatch, cfg, mutate=None):
    """One run of the entry on ``cfg``; returns its result, its checks,
    what ``check.run`` read and the engine that served. ``mutate(mcfg,
    params)`` gives the model the engine serves instead."""
    made, read = [], []

    class Served(repro.engine.Engine):
        def __init__(self, mcfg, params, ecfg, **kw):
            if mutate:
                mcfg, params = mutate(mcfg, params)
            super().__init__(mcfg, params, ecfg, **kw)
            made.append(self)

    def run_check(*a, **k):
        read.append(real_run(*a, **k))
        return read[-1]

    real_run = check.run
    monkeypatch.setattr(repro.engine, "Engine", Served)
    monkeypatch.setattr(check, "run", run_check)
    ctx = harness.Ctx(
        cell="toy", devices=jax.devices()[:1], cfg=cfg,
        traffic=tiny("tiny-closed"), limits=TOLERANCE, seed=SEED,
        seconds=1.5, trace=False, t_start=time.perf_counter(),
        end_to_end=harness.benchmark()["end_to_end"])
    result, checks = entry.run(ctx)
    assert result["attempted"] > 0 and result["failed"] == 0
    eng, = made
    res, = read
    # the longest greedy and sampled requests at the least: a few dozen
    assert res["tokens"] >= 20, res
    return result, checks, res, eng


@pytest.mark.parametrize("name", ["tiny-qwen3", "tiny-llama"])
def test_served_tokens_within_the_reference_contract(monkeypatch, name):
    result, checks, res, _ = serve(monkeypatch, tiny(name))
    assert result["correct"] and check.decide(res, TOLERANCE), res


def qk_norm_off(mcfg, params):
    return replace(mcfg, qk_norm=False), params


def head_tied(mcfg, params):
    emb = {"tok": params["emb"]["tok"]}
    return replace(mcfg, tie_embeddings=True), dict(params, emb=emb)


@pytest.mark.parametrize("mutate", [qk_norm_off, head_tied],
                         ids=lambda f: f.__name__)
def test_serving_without_qwen3_mathematics_fails(monkeypatch, mutate):
    result, checks, res, _ = serve(monkeypatch, tiny("tiny-qwen3"), mutate)
    assert not result["correct"], res
    assert res["greedy_gap"] > 2 * TOLERANCE["greedy_gap"]["limit"]


def fallback_shifted(monkeypatch, V):
    """The full-vocabulary fallback's tokens moved one id up; the hot fast
    path, a call over the hot block's columns alone, is left as it is."""
    real = shvs.truncation_first_sample

    def shifted(z, *a, **k):
        out = real(z, *a, **k)
        if z.shape[-1] != V:
            return out
        return out._replace(tokens=(out.tokens + 1) % V)

    monkeypatch.setattr(shvs, "truncation_first_sample", shifted)


@pytest.mark.parametrize("fault", [False, True],
                         ids=["sound", "fallback_shifted"])
def test_fallback_rows_are_checked(monkeypatch, fault):
    """Rows whose filter support leaves the hot set are served by the
    full-vocabulary fallback; their tokens go through the same check, so a
    fallback that serves a wrong token fails the run."""
    cfg = wide_vocab()
    if fault:
        fallback_shifted(monkeypatch, cfg["vocab_size"])
    result, checks, res, eng = serve(monkeypatch, cfg)
    B = eng.ecfg.max_batch
    rates = [r.accept_rate for r in eng.stats_log if r.batch == B]
    assert rates and min(rates) < 1.0 and max(rates) > 0.0, rates
    assert result["correct"] is not fault, res
    if fault:
        assert res["sampled_gap"] > 2 * TOLERANCE["sampled_gap"]["limit"]


def test_qwen3_last_stage_at_published_widths():
    """Shapes only (``jax.eval_shape``): the benchmark's Qwen3-8B last
    pipeline stage, its weights and its 64 x 1024 cache."""
    bench = harness.benchmark()
    cfg = harness.config_file(bench, "qwen3-8b-pp4-last")
    mcfg = entry.model_config(cfg)
    assert (mcfg.d_model, mcfg.num_heads, mcfg.num_kv_heads,
            mcfg.resolved_head_dim, mcfg.d_ff, mcfg.vocab_size,
            mcfg.num_layers) == (4096, 32, 8, 128, 12288, 151936, 9)
    assert mcfg.qk_norm and not mcfg.tie_embeddings
    params = jax.eval_shape(lambda: make_weights(cfg, SEED))
    entry.check_layout(params, mcfg)
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert round(n / 1e9, 2) == 2.98
    assert params["emb"]["head"].shape == (4096, 151936)
    ecfg = harness.traffic_file(
        harness.cell(bench, "qwen3-8b-pp4-last.batch")["traffic"])["engine"]
    assert (ecfg["max_batch"], ecfg["max_seq_len"]) == (64, 1024)
    cache = jax.eval_shape(lambda: Model(mcfg).init_cache(
        ecfg["max_batch"], ecfg["max_seq_len"]))
    kv = cache["k"].size * cache["k"].dtype.itemsize \
        + cache["v"].size * cache["v"].dtype.itemsize
    assert kv == 2 * 9 * 64 * 1024 * 8 * 128 * 2
    assert round(kv / 1e9, 2) == 2.42
