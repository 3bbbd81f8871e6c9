"""Traffic: the same work for every seed, in another order."""
import itertools

import pytest

from benchmarks.chip import harness, loadgen

CHAT = harness.traffic_file("chat-sampling")
BATCH = harness.traffic_file("batch-sampling")


def shape(specs):
    """Each property's multiset (their pairing is the seed's to shuffle)."""
    return [sorted(f(s) for s in specs) for f in (
        lambda s: len(s.prompt), lambda s: s.max_new, lambda s: s.greedy,
        lambda s: s.top_k, lambda s: len(s.bias))]


def test_every_seed_gets_the_same_multiset():
    a = list(loadgen.make_run(CHAT, 49152, 30.0, 1))
    b = list(loadgen.make_run(CHAT, 49152, 30.0, 2**31 + 12345))
    assert shape(a) == shape(b)
    assert [s.prompt for s in a] != [s.prompt for s in b]
    assert max(s.due for s in a) == pytest.approx(max(s.due for s in b))


def test_same_seed_same_requests():
    a = list(loadgen.make_run(CHAT, 49152, 10.0, 7))
    b = list(loadgen.make_run(CHAT, 49152, 10.0, 7))
    assert [(s.prompt, s.max_new, s.due, s.seed) for s in a] == \
        [(s.prompt, s.max_new, s.due, s.seed) for s in b]


def test_open_loop_arrivals_and_warm_population():
    specs = list(loadgen.make_run(CHAT, 49152, 30.0, 3))
    head = CHAT["initial_requests"]
    assert all(s.due == -CHAT["warmup_s"] for s in specs[:head])
    dues = [s.due for s in specs[head:]]
    assert dues == sorted(dues) and dues[0] > -CHAT["warmup_s"]
    span = CHAT["warmup_s"] + 30.0
    assert abs(dues[-1] - 30.0) < 0.2 * span
    lo, hi = CHAT["prompt_len"]["min"], CHAT["prompt_len"]["max"]
    assert all(lo <= len(s.prompt) <= hi for s in specs)
    assert all(0 < t < 49152 for s in specs for t in s.prompt)


def test_contract_shares():
    specs = loadgen.make_specs(CHAT, 49152, 400, 5)
    c = CHAT["contract"]
    assert sum(s.greedy for s in specs) == round(c["greedy"] * 400)
    assert sum(bool(s.bias) for s in specs) == \
        round(c["logit_bias"]["share"] * 400)
    assert all(s.repetition == c["repetition_penalty"] for s in specs)
    sampled = [s for s in specs if not s.greedy]
    assert all(c["top_p"][0] <= s.top_p <= c["top_p"][1] for s in sampled)
    assert all(s.top_k == 0 and s.min_p == 0.0 for s in specs if s.greedy)


def test_closed_loop_draws_blocks_without_end():
    it = loadgen.make_run(BATCH, 151936, 30.0, 9)
    specs = list(itertools.islice(it, 7 * BATCH["clients"]))[BATCH["clients"]:]
    rids = [s.rid for s in specs]
    assert len(set(rids)) == len(rids)
    block = 2 * BATCH["clients"]
    assert shape(specs[:block]) == shape(specs[block:2 * block])
    assert shape(specs[:block]) == shape(specs[2 * block:])


def test_reachable_prompt_buckets():
    assert loadgen.reachable_prompt_buckets(CHAT, 128, 2048) == \
        list(range(128, 1025, 128))
    assert loadgen.reachable_prompt_buckets(BATCH, 128, 1024) == \
        [128, 256, 384, 512]
