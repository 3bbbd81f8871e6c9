"""A whole run with the chip check skipped, on the CPU at toy size: sound,
it is correct; with a token altered where the decision plane produces it,
or with top-p ignored on the sampled rows alone, ``correct`` comes out
false."""
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness
from benchmarks.chip.entries import engine

DATA = harness.HERE / "tests" / "data"
# toy widths: bfloat16 reads ~0.02 on greedy and ~0 on sampled tokens,
# float8 ~1.9 on greedy ones
LIMITS = {"greedy_gap": {"limit": 0.5}, "sampled_gap": {"limit": 0.5}}


def run_once(traffic, seed):
    ctx = harness.Ctx(
        cell="toy", devices=jax.devices()[:1],
        cfg=harness.load_json(DATA / "tiny-qwen3.json"),
        traffic=harness.load_json(DATA / f"{traffic}.json"),
        limits=LIMITS, seed=seed, seconds=1.5,
        trace=False, t_start=time.perf_counter(),
        end_to_end=harness.benchmark()["end_to_end"])
    return engine.run(ctx)


def altered_token(step):
    def altered(self, logits, *a, **k):
        tokens, state, stats = step(self, logits, *a, **k)
        return (tokens + 1) % logits.shape[-1], state, stats
    return altered


def top_p_ignored(step):
    def ignored(self, logits, state, params, *a, **k):
        params = params._replace(top_p=jnp.ones_like(params.top_p))
        return step(self, logits, state, params, *a, **k)
    return ignored


FAULTS = {"sound": (None, None), "token": (altered_token, "greedy_gap"),
          "top_p": (top_p_ignored, "sampled_gap")}


@pytest.mark.parametrize("traffic", ["tiny-open", "tiny-closed"])
@pytest.mark.parametrize("fault", list(FAULTS))
def test_altered_token_fails_the_run(monkeypatch, traffic, fault):
    plant, number = FAULTS[fault]
    if plant:
        from repro.core.decision_plane import DecisionPlane
        monkeypatch.setattr(DecisionPlane, "step", plant(DecisionPlane.step))
    result, checks = run_once(traffic, 2**31 + 99)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert all(c["value"] is not None for c in checks.values())
    assert result["correct"] is (not plant)
    if plant:
        assert checks[number]["value"] > LIMITS[number]["limit"]
    assert set(result["metrics"]) == {
        m["name"] for m in harness.benchmark()["end_to_end"]}
