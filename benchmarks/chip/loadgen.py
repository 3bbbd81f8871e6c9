"""Traffic: the requests and arrival times of one run, from a mix file.

A mix (``traffic/<name>.json``) is data. This one generator reads it:

* ``kind``: ``open_poisson`` (independent users at ``rate_rps``; each
  request is due at its Poisson arrival, whatever the server is doing) or
  ``closed`` (``clients`` callers, each sending its next request the moment
  its last one finished).
* ``prompt_len`` / ``output_len``: lognormal (``median``, ``sigma``) clipped
  to ``[min, max]``.
* ``contract``: the share of requests carrying each sampling control.

Every seed gets the same multiset of lengths, gaps and contracts, in another
order: they are quantiles of the stated distributions, shuffled by the seed.
So a seed changes which request is long and when it arrives, never how much
work a run holds. Prompt token ids follow a Zipf law over token id (low ids
are frequent, as in a tokenizer's vocabulary) and are drawn from the seed.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List, Optional, Tuple

import numpy as np


@dataclass
class Spec:
    """One request as the load generator sends it."""

    rid: int
    prompt: List[int]
    max_new: int
    greedy: bool
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    min_p: float = 0.0
    repetition: float = 1.0
    presence: float = 0.0
    frequency: float = 0.0
    seed: int = 0
    bias: Tuple[Tuple[int, float], ...] = ()
    due: Optional[float] = None   # offset from the window's open


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int, rng) -> np.ndarray:
    """``n`` lengths: the quantiles of the clipped lognormal, shuffled."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    q = np.array([nd.inv_cdf(u) for u in _grid(n)])
    x = np.rint(dist["median"] * np.exp(dist["sigma"] * q))
    x = np.clip(x, dist["min"], dist["max"]).astype(np.int64)
    return rng.permutation(x)


def poisson_gaps(rate: float, n: int, rng) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process at ``rate`` per second:
    the exponential's quantiles, shuffled."""
    return rng.permutation(-np.log1p(-_grid(n)) / rate)


def _share(n: int, share: float, rng) -> np.ndarray:
    mask = np.zeros(n, bool)
    mask[:int(round(share * n))] = True
    return rng.permutation(mask)


def _span(lo_hi, n: int, rng) -> np.ndarray:
    lo, hi = lo_hi
    return rng.permutation(lo + (hi - lo) * _grid(n))


def zipf_tokens(vocab: int, exponent: float, n: int, rng) -> np.ndarray:
    """``n`` token ids in ``[1, vocab)`` with P(v) proportional to
    ``v ** -exponent``."""
    w = np.arange(1, vocab, dtype=np.float64) ** -exponent
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return 1 + np.searchsorted(cdf, rng.random(n), side="right").clip(
        0, vocab - 2)


def make_specs(traffic: dict, vocab: int, n: int, seed: int,
               rid0: int = 0) -> List[Spec]:
    """``n`` requests of the mix, from ``seed``."""
    rng = np.random.default_rng(seed)
    plen = lengths(traffic["prompt_len"], n, rng)
    olen = lengths(traffic["output_len"], n, rng)
    c = traffic["contract"]
    greedy = _share(n, c.get("greedy", 0.0), rng)
    ns = int(n - greedy.sum())            # sampled requests
    temp = _span(c["temperature"], ns, rng)
    top_p = _span(c["top_p"], ns, rng)
    tk = c.get("top_k")
    has_k = _share(ns, tk["share"], rng) if tk else np.zeros(ns, bool)
    ks = iter(np.rint(_span(tk["range"], int(has_k.sum()), rng)).astype(int)
              if tk else ())
    mp = c.get("min_p")
    has_mp = _share(ns, mp["share"], rng) if mp else np.zeros(ns, bool)
    pf = c.get("presence_frequency")
    has_pf = _share(n, pf["share"], rng) if pf else np.zeros(n, bool)
    lb = c.get("logit_bias")
    has_lb = _share(n, lb["share"], rng) if lb else np.zeros(n, bool)
    toks = zipf_tokens(vocab, traffic["prompt_token_zipf"], int(plen.sum()),
                       rng)
    seeds = rng.integers(0, 2**31 - 1, n)
    specs, at, j = [], 0, 0
    for i in range(n):
        s = Spec(rid=rid0 + i, prompt=toks[at:at + plen[i]].tolist(),
                 max_new=int(olen[i]), greedy=bool(greedy[i]),
                 repetition=float(c.get("repetition_penalty", 1.0)),
                 seed=int(seeds[i]))
        at += plen[i]
        if not s.greedy:
            s.temperature = float(temp[j])
            s.top_p = float(top_p[j])
            if has_k[j]:
                s.top_k = int(next(ks))
            if has_mp[j]:
                s.min_p = float(mp["value"])
            j += 1
        if has_pf[i]:
            s.presence = float(pf["presence"])
            s.frequency = float(pf["frequency"])
        if has_lb[i]:
            ids = rng.choice(lb["id_below"], size=len(lb["values"]),
                             replace=False)
            s.bias = tuple((int(t), float(b))
                           for t, b in zip(ids, lb["values"]))
        specs.append(s)
    return specs


def make_run(traffic: dict, vocab: int, seconds: float,
             seed: int) -> Iterator[Spec]:
    """The requests of one run, in the order they are sent.

    The first ones stand for work already in progress when the warm-up
    starts (``initial_requests`` in the open loop, each caller's first
    request in a closed loop): due at once, with a residual output length
    (a share of a full one, the shares a fixed grid), so the loop reaches
    its steady occupancy within the warm-up. Open loop: then one block of
    as many requests as arrive in the warm-up and the window, due at
    Poisson arrivals from ``-warmup_s`` on (offsets from the window's
    open). Closed loop: then blocks of twice the callers, for as long as
    it is asked; a caller whose request finished takes the next one. Each
    block holds the whole multiset of the mix."""
    kind = traffic["kind"]
    if kind == "open_poisson":
        head = traffic.get("initial_requests", 0)
    elif kind == "closed":
        head = traffic["clients"]
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    rng = np.random.default_rng([seed, 1 << 20])
    first = make_specs(traffic, vocab, head, [seed, 0]) if head else []
    full = sorted(s.max_new for s in first)
    share = np.random.default_rng(0).permutation(_grid(head))
    residual = [max(1, int(round(u * n))) for u, n in zip(share, full)]
    for s, n in zip(first, rng.permutation(residual)):
        s.max_new = int(n)
        s.due = -float(traffic["warmup_s"])
    yield from first
    if kind == "open_poisson":
        m = int(math.ceil(
            traffic["rate_rps"] * (traffic["warmup_s"] + seconds))) + 1
        rest = make_specs(traffic, vocab, m, [seed, 1], rid0=head)
        due = np.cumsum(poisson_gaps(traffic["rate_rps"], m, rng))
        for s, t in zip(rest, due):
            s.due = float(t) - traffic["warmup_s"]
        yield from rest
        return
    m = 2 * head
    for b in itertools.count(1):
        yield from make_specs(traffic, vocab, m, [seed, b],
                              rid0=head + (b - 1) * m)


def reachable_prompt_buckets(traffic: dict, bucket: int,
                             max_seq_len: int) -> List[int]:
    """The padded prompt lengths the engine's prefill can see for this mix
    (its ``prompt_bucket`` multiples covering ``[min, max]``)."""
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    first = max(bucket, -(-lo // bucket) * bucket)
    last = min(-(-hi // bucket) * bucket, max_seq_len)
    return list(range(first, last + 1, bucket))
