"""The comparison that decides ``correct``.

After the window has closed, two samples of the requests the run finished
are drawn from the seed: greedy requests and sampled ones, each holding the
longest of its kind and then others until it has some hundreds of served
tokens (the mix's ``check_tokens``). Each request runs through the
configuration's plain reference (``references/<name>.py``): the prompt and
the served tokens as one sequence, float32 logits at every position that
produced a served token. The sampling contract is then applied as it
states it, in plain numpy: the request's logit bias is added; the
repetition penalty divides positive logits and multiplies negative ones of
every token seen in the prompt or the output so far; presence and
frequency penalties are subtracted from the output's tokens; the logits are
divided by the temperature; top-k keeps the k best, top-p the shortest
prefix of the top-k-renormalized distribution whose mass before a token is
under p, min-p the tokens whose probability is at least ``min_p`` times the
best one's. A greedy token is the best logit.

The number compared is, for each served token, its ``gap``: the least
change to the reference's logits that puts the token inside that support,
as a spread in logit units (every logit moved by at most half of it). For
a greedy token it is the best logit minus the token's. Rounding in bfloat16
moves the support's edge by a little; a wrong mask, position, penalty,
temperature or token moves it by the logits' own scale.

* ``greedy_gap``: the widest gap over the greedy sample;
* ``sampled_gap``: the widest gap over the sampled sample.

The control (``control_*``, not run by the benchmark's own runs) reads the
same positions of the same sequences with the reference in float8 in the
program's place: the gap of the token it puts first (greedy), and the
widest gap of any token in its own support (sampled). It goes through the
same decision (``decide``) as the program.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Optional

import numpy as np

NUMBERS = ("greedy_gap", "sampled_gap")
TOP = 4096          # candidates sorted per position (the support lies there)


def reference_module(cfg: dict):
    return importlib.import_module(
        f"benchmarks.chip.references.{cfg['reference']}")


def contract_logits(logits: np.ndarray, prompt, output, spec,
                    vocab: int) -> np.ndarray:
    """Apply the contract's bias and penalties to (T, V) logits, row t
    seeing the prompt and ``output[:t]``."""
    z_all = np.asarray(logits, np.float64).copy()
    bias = np.zeros(vocab)
    for t, b in spec.bias:
        bias[t] += b
    prompt_seen = np.bincount(prompt, minlength=vocab) > 0
    out_counts = np.zeros(vocab)
    for t in range(z_all.shape[0]):
        z = z_all[t] + bias
        seen = prompt_seen | (out_counts > 0)
        f = np.where(seen, spec.repetition, 1.0)
        z = np.where(z > 0, z / f, z * f)
        z = z - spec.presence * (out_counts > 0) - spec.frequency * out_counts
        z_all[t] = z
        out_counts[output[t]] += 1
    return z_all


def gaps(z: np.ndarray, chosen) -> np.ndarray:
    """Per position: the best logit minus the chosen token's."""
    chosen = np.asarray(chosen)
    return z.max(axis=1) - z[np.arange(len(chosen)), chosen]


def kept(a: np.ndarray, spec, tail: float = 0.0) -> np.ndarray:
    """Which of the descending temperature-scaled logits ``a`` the
    truncation keeps. ``tail``: the summed ``exp(x - a[0])`` of the tokens
    not in ``a`` (all below it), which top-p without top-k normalizes over."""
    n = len(a)
    k = n if spec.top_k <= 0 else min(spec.top_k, n)
    w = np.exp(a - a[0])
    keep = np.arange(n) < k
    p = w / (w[:k].sum() + (tail if spec.top_k <= 0 else 0.0))
    keep &= (np.cumsum(p) - p) < spec.top_p
    keep &= p >= spec.min_p * p[0]
    return keep


def _moves(zs: np.ndarray, v: int, spec):
    """``inside(h)``: whether token ``v`` is kept once it is raised by ``h``
    and every token above it lowered by ``h`` (those below raised by
    ``h``): the most that a change of ``h`` to each scaled logit can do for
    it."""
    zv, m = zs[v], zs.max()
    top = np.argpartition(-zs, TOP - 1)[:TOP] if len(zs) > TOP \
        else np.arange(len(zs))
    if zv < zs[top].min():
        top = np.arange(len(zs))             # far outside: use every token
    x = zs[top[top != v]]
    rest = np.ones(len(zs), bool)
    rest[top] = False
    rest[v] = False
    tail = float(np.exp(zs[rest] - m).sum())     # all below v
    first = np.r_[0, np.ones(len(x))]            # v first among equals

    def inside(h: float) -> bool:
        vals = np.concatenate([[zv + h], np.where(x > zv, x - h, x + h)])
        order = np.lexsort((first, -vals))
        a = vals[order]
        keep = kept(a, spec, tail * np.exp(h + m - a[0]))
        return bool(keep[np.nonzero(order == 0)[0][0]])

    return inside


def support_gap(z: np.ndarray, spec, v: int) -> float:
    """The least spread of a change to the (V,) contract logits ``z`` (bias
    and penalties applied, not scaled) that puts ``v`` in the support."""
    if spec.greedy or spec.temperature <= 0:
        return float(z.max() - z[v])
    zs = z / spec.temperature
    inside = _moves(zs, v, spec)
    if inside(0.0):
        return 0.0
    lo, hi = 0.0, (zs.max() - zs[v]) / 2 + 1e-9
    for _ in range(40):
        mid = (lo + hi) / 2
        lo, hi = (lo, mid) if inside(mid) else (mid, hi)
    return float(2 * hi * spec.temperature)


def widest_in_support(z: np.ndarray, zc: np.ndarray, spec) -> float:
    """Control, one position: the widest gap against ``z`` of any token that
    the contract keeps on the control's logits ``zc``."""
    if spec.greedy or spec.temperature <= 0:
        return float(z.max() - z[int(zc.argmax())])
    zs = zc / spec.temperature
    top = np.argsort(-zs)[:TOP]
    a = zs[top]
    tail = float(np.exp(np.delete(zs, top) - a[0]).sum())
    members = top[kept(a, spec, tail)]
    return support_gap(z, spec, int(members[np.argmin(z[members])]))


def pick(sent: List, rng, tokens: int, greedy: bool) -> List:
    """Finished greedy (or sampled) requests: the longest, then others in an
    order drawn from ``rng``, until they hold ``tokens`` served tokens (or
    all are taken)."""
    done = [s for s in sent if s.spec.greedy == greedy and s.request.output
            and s.request.finish_reason == "length"]
    if not done:
        return []
    done.sort(key=lambda s: (-len(s.request.output), s.spec.rid))
    out = [done[0]]
    for i in rng.permutation(len(done) - 1):
        if sum(len(s.request.output) for s in out) >= tokens:
            break
        out.append(done[1 + i])
    return out


def run(params, cfg: dict, samples: List, pad_to: int,
        control: bool = False) -> Dict[str, Optional[float]]:
    """``samples``: dicts with ``prompt``, ``output`` and ``spec``.
    Returns ``greedy_gap`` and ``sampled_gap`` (None where the sample held
    no such token), with ``control`` also ``control_greedy_gap`` and
    ``control_sampled_gap``, and the tokens compared."""
    ref = reference_module(cfg)
    V = cfg["vocab_size"]
    res: Dict[str, Optional[float]] = {"tokens": 0, "near_ties": 0}
    worst: Dict[str, float] = {}

    def note(name, x):
        worst[name] = max(worst.get(name, 0.0), x)

    for s in samples:
        prompt, out, spec = list(s["prompt"]), list(s["output"]), s["spec"]
        kind = "greedy_gap" if spec.greedy else "sampled_gap"
        seq = prompt + out[:-1]
        at = np.arange(len(prompt) - 1, len(seq))
        z = contract_logits(ref.logits(params, cfg, seq, at, pad_to=pad_to),
                            prompt, out, spec, V)
        for t, v in enumerate(out):
            note(kind, support_gap(z[t], spec, v))
        res["tokens"] += len(out)
        top2 = np.sort(z, axis=1)[:, -2:]
        res["near_ties"] += int(np.sum(top2[:, 1] - top2[:, 0] < 0.5))
        if control:
            zc = contract_logits(
                ref.logits(params, cfg, seq, at, quant="fp8", pad_to=pad_to),
                prompt, out, spec, V)
            for t in range(len(out)):
                note("control_" + kind, widest_in_support(z[t], zc[t], spec))
    for name in NUMBERS:
        res[name] = worst.get(name)
        if control:
            res["control_" + name] = worst.get("control_" + name)
    return res


def decide(values: Dict[str, Optional[float]], limits: dict,
           prefix: str = "") -> bool:
    """``correct``: every number read, each within its limit."""
    for name in NUMBERS:
        x = values.get(prefix + name)
        limit = limits.get(name, {}).get("limit")
        if x is None or limit is None or not x <= limit:
            return False
    return True
