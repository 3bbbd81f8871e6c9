"""The weight generator: the program's layout, one call, the stated skew."""
import jax
import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.entries import engine
from benchmarks.chip.weights import head_row_std, make_weights

DATA = harness.HERE / "tests" / "data"


@pytest.mark.parametrize("name", ["tiny-llama", "tiny-qwen3"])
def test_layout_matches_the_program(name):
    cfg = harness.load_json(DATA / f"{name}.json")
    params = make_weights(cfg, 2**31 + 5)
    engine.check_layout(params, engine.model_config(cfg))
    leaves = jax.tree_util.tree_leaves(params)
    assert {str(a.dtype) for a in leaves} == {"bfloat16"}


def test_same_seed_same_weights_large_seeds_differ():
    cfg = harness.load_json(DATA / "tiny-llama.json")
    a = make_weights(cfg, 2**33 + 1)["emb"]["tok"]
    b = make_weights(cfg, 2**33 + 1)["emb"]["tok"]
    c = make_weights(cfg, 1)["emb"]["tok"]
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


@pytest.mark.parametrize("name,key", [("tiny-llama", "tok"),
                                      ("tiny-qwen3", "head")])
def test_head_rows_follow_the_stated_zipf_profile(name, key):
    """Row v's RMS is top_logit_std * (v + 1) ** -exponent / sqrt(d): fit
    log RMS against log(v + 1) over the whole vocabulary."""
    cfg = dict(harness.load_json(DATA / f"{name}.json"))
    cfg["vocab_size"] = 4096          # enough rows for a tight fit
    w = np.asarray(make_weights(cfg, 11)["emb"][key], np.float64)
    rows = w if key == "tok" else w.T
    rms = np.sqrt(np.mean(rows ** 2, axis=1))
    v = np.arange(len(rms)) + 1.0
    slope, icpt = np.polyfit(np.log(v), np.log(rms), 1)
    z = cfg["weights"]["head_zipf"]
    assert slope == pytest.approx(-z["exponent"], abs=0.01)
    assert np.exp(icpt) == pytest.approx(
        z["top_logit_std"] / np.sqrt(cfg["hidden_size"]), rel=0.05)
    assert np.allclose(head_row_std(cfg)[[0, 99]],
                       z["top_logit_std"] * np.array([1, 100.0]) ** -0.3
                       / np.sqrt(cfg["hidden_size"]))
