"""The plain reference against the program's prefill and cached decode,
for a Llama (tied) and a Qwen3 (qk_norm, untied) stack at toy widths."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import check, harness, loadgen
from benchmarks.chip.entries import engine
from benchmarks.chip.references import dense_decoder
from benchmarks.chip.weights import make_weights

DATA = harness.HERE / "tests" / "data"
F32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)


def program_logits(cfg, params, prompt, out):
    """Logits of the program's own prefill, then of its cached decode on
    each served token, in float32 at HIGHEST precision."""
    from repro.models.model import Model
    mcfg = replace(engine.model_config(cfg), dtype="float32")
    model = Model(mcfg)
    S = 64
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(1, S)
        toks = np.zeros((1, 32), np.int32)
        toks[0, :len(prompt)] = prompt
        z, cache = jax.jit(model.prefill)(
            F32(params), {"tokens": jnp.asarray(toks)}, cache,
            true_lens=jnp.asarray([len(prompt)]))
        rows = [np.asarray(z[0])]
        step = jax.jit(model.decode_step)
        for t in out[:-1]:
            z, cache = step(F32(params), jnp.asarray([t], jnp.int32), cache)
            rows.append(np.asarray(z[0]))
    return np.stack(rows)


@pytest.mark.parametrize("name", ["tiny-llama", "tiny-qwen3"])
def test_reference_matches_prefill_and_cached_decode(name):
    cfg = harness.load_json(DATA / f"{name}.json")
    params = make_weights(cfg, 21)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg["vocab_size"], 19).tolist()
    out = rng.integers(1, cfg["vocab_size"], 9).tolist()
    got = program_logits(cfg, params, prompt, out)
    seq = prompt + out[:-1]
    ref = dense_decoder.logits(F32(params), cfg, seq,
                               np.arange(len(prompt) - 1, len(seq)))
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= 1e-4 * scale


def test_contract_logits_follow_the_stated_penalties():
    spec = loadgen.Spec(rid=0, prompt=[1], max_new=2, greedy=True,
                        repetition=2.0, presence=0.5, frequency=0.25,
                        bias=((3, 1.0),))
    z = np.array([[4.0, -2.0, 1.0, 0.0], [4.0, -2.0, 1.0, 0.0]])
    got = check.contract_logits(z, [1], [0, 0], spec, 4)
    # row 0: token 1 is in the prompt (-2 * 2); token 3 biased (+1)
    assert got[0].tolist() == [4.0, -4.0, 1.0, 1.0]
    # row 1: token 0 seen once in the output: 4 / 2 - 0.5 - 0.25
    assert got[1].tolist() == [1.25, -4.0, 1.0, 1.0]
    assert check.gaps(got, [0, 2]).tolist() == [0.0, 0.25]


def sampled(**kw):
    return loadgen.Spec(rid=0, prompt=[1], max_new=1, greedy=False, **kw)


def test_truncation_keeps_what_the_contract_states():
    a = np.log([0.5, 0.3, 0.15, 0.05])
    # mass before each token: 0, .5, .8, .95
    assert check.kept(a, sampled(top_p=0.9)).tolist() == [1, 1, 1, 0]
    assert check.kept(a, sampled(top_k=2)).tolist() == [1, 1, 0, 0]
    # min-p 0.4: probability at least 0.2
    assert check.kept(a, sampled(min_p=0.4)).tolist() == [1, 1, 0, 0]
    # without top-k, top-p normalizes over the tokens not listed too
    assert check.kept(a[:3] + np.log(1 / 0.95), sampled(top_p=0.9),
                      tail=0.05 / 0.5).tolist() == [1, 1, 1]


def test_support_gap_against_hand_worked_numbers():
    z = np.array([3.0, 2.0, 0.0, -1.0])
    # greedy: the best logit minus the token's
    assert check.support_gap(z, loadgen.Spec(rid=0, prompt=[1], max_new=1,
                                             greedy=True), 2) == 3.0
    # top-2: token 2 needs +1 and token 1 -1 to tie for second place, a
    # spread of 2 whatever the temperature
    for t in (1.0, 0.5):
        assert check.support_gap(z, sampled(top_k=2, temperature=t), 2) \
            == pytest.approx(2.0, abs=1e-6)
        assert check.support_gap(z, sampled(top_k=2, temperature=t), 1) == 0
    # top-p 0.5 on probabilities .6 .3 .1 keeps token 0 alone; token 1 is
    # kept once .6 e^-h < .4 e^h, at a spread 2h = ln 1.5
    z = np.log([0.6, 0.3, 0.1])
    assert check.support_gap(z, sampled(top_p=0.5), 1) == \
        pytest.approx(np.log(1.5), abs=1e-6)
    assert check.support_gap(z, sampled(top_p=0.5), 0) == 0.0


def test_control_reads_its_widest_kept_token():
    z = np.log([0.6, 0.3, 0.1])
    zc = np.log([0.3, 0.3, 0.4])          # keeps token 2 first at top-p .5
    spec = sampled(top_p=0.5)
    assert check.widest_in_support(z, zc, spec) == \
        pytest.approx(check.support_gap(z, spec, 2))
    assert check.widest_in_support(z, z, spec) == 0.0


def test_decide_needs_every_number_within_its_limit():
    limits = {"greedy_gap": {"limit": 0.5}, "sampled_gap": {"limit": 0.4}}
    assert check.decide({"greedy_gap": 0.5, "sampled_gap": 0.0}, limits)
    assert not check.decide({"greedy_gap": 0.1, "sampled_gap": 0.41}, limits)
    assert not check.decide({"greedy_gap": 0.1, "sampled_gap": None}, limits)
    assert not check.decide({"control_greedy_gap": 0.1,
                             "control_sampled_gap": 2.0}, limits, "control_")


@pytest.mark.parametrize("name", ["tiny-llama", "tiny-qwen3"])
def test_float8_control_reads_far_above_float32(name):
    """The comparison tells the stated precision from the one below it:
    greedy tokens of the float32 reference itself read 0; the same
    positions read in float8 put other tokens first, well below the
    reference's best."""
    cfg = harness.load_json(DATA / f"{name}.json")
    params = make_weights(cfg, 33)
    rng = np.random.default_rng(1)
    samples = []
    for k in range(6):
        prompt = rng.integers(1, cfg["vocab_size"], 20).tolist()
        out = []
        for _ in range(12):           # greedy decode of the reference
            seq = prompt + out
            z = dense_decoder.logits(F32(params), cfg, seq, [len(seq) - 1])
            out.append(int(z[0].argmax()))
        spec = loadgen.Spec(rid=k, prompt=prompt, max_new=12, greedy=True)
        samples.append({"prompt": prompt, "output": out, "spec": spec})
    res = check.run(params, cfg, samples, pad_to=64, control=True)
    assert res["greedy_gap"] <= 1e-3
    assert res["control_greedy_gap"] > 0.05
