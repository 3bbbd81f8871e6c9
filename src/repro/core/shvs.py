"""Speculative Hot-Vocab Sampling with rejection correctness (paper §5.3).

Math (Eq. 6–9): with penalized/scaled logits z and stable weights
``w_v = exp(z_v − max z)`` split into hot set H and tail V∖H:

    α_b  = S_hot / (S_hot + S_tail)
    q    = w|_H / S_hot          (hot proposal)
    r    = w|_tail / S_tail      (tail proposal)
    draw ŷ ~ q; accept iff u ≤ α_b else y ~ r    ⇒  P[y = v] = p̃_v  exactly.

TPU adaptation (see DESIGN.md): on TPU the expensive decision-plane op is the
*sort* (top-k/top-p over V up to 202k), not the single streaming pass. SHVS
keeps one cheap O(V) vectorized pass (exp + segmented sums + tail max) and
confines all sort-based work to the H-sized hot block — except for rows whose
filter support leaves the hot set, which take the full-V truncation-first
fallback (the paper's S2) and its sort.

Filter interaction (beyond-paper refinement, §7 of DESIGN.md): with top-k /
top-p enabled, the hot fast path is provably exact iff the global filter
support is contained in H. Containment holds iff the k-th best hot logit
≥ max tail logit (checked from the same streaming pass). Rows that fail the
guard take the full-vocabulary truncation-first path; the paper reports
80–95% acceptance, and the guard preserves distributional exactness instead
of assuming it.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.sampler_backend import (DecisionStats, SamplerBackend,
                                        register_backend)
from repro.core.sampling import (SamplingParams, TruncResult, _inverse_cdf_draw,
                                 temperature_scale, truncation_first_sample)

NEG_INF = -1e30


class HotSet(NamedTuple):
    """Model-dependent hot vocabulary (built offline, §5.3)."""

    indices: jnp.ndarray    # (H,) int32 — token ids in the hot set
    mask: jnp.ndarray       # (V,) bool  — membership mask

    @property
    def size(self) -> int:
        return self.indices.shape[0]


def make_hot_set(indices: jnp.ndarray, vocab_size: int) -> HotSet:
    indices = jnp.asarray(indices, jnp.int32)
    mask = jnp.zeros((vocab_size,), bool).at[indices].set(True)
    return HotSet(indices=indices, mask=mask)


def default_hot_set(vocab_size: int, shvs=None) -> HotSet:
    """The hot set a backend takes when none is given: the contiguous
    low-id prefix (tokenizers assign low ids to frequent tokens) sized by
    the SHVS config. Real deployments pass a trace-built set."""
    from repro.config import SHVSConfig
    H = (shvs if shvs is not None else SHVSConfig()).resolve_hot_size(
        vocab_size)
    return make_hot_set(jnp.arange(H, dtype=jnp.int32), vocab_size)


class SHVSResult(NamedTuple):
    tokens: jnp.ndarray      # (B,) int32
    accepted: jnp.ndarray    # (B,) bool — fast path produced the token
    alpha: jnp.ndarray       # (B,) f32  — hot-vocab mass (Eq. 7)
    exact_fast: jnp.ndarray  # (B,) bool — containment guard passed
    needs_reference: jnp.ndarray  # (B,) bool — even the full-V truncation
    # fallback could not certify exactness (pathological nucleus); callers
    # wanting bit-exact semantics re-sample those rows with the oracle


def shvs_masses(z: jnp.ndarray, hot: HotSet):
    """The single streaming pass over V (Eq. 6–7): returns
    (m, S_hot, S_tail, tail_max) with shapes ((B,),...).

    The ``fused`` backend's kernel streams the hot and total masses tile
    by tile (``kernels/ref.streaming_mass_update``) inside its single pass.
    """
    m = jnp.max(z, axis=-1)
    w = jnp.exp(z - m[:, None])
    hotf = hot.mask.astype(z.dtype)[None, :]
    s_hot = jnp.sum(w * hotf, axis=-1)
    s_tot = jnp.sum(w, axis=-1)
    s_tail = s_tot - s_hot
    tail_max = jnp.max(jnp.where(hot.mask[None, :], NEG_INF, z), axis=-1)
    return m, s_hot, s_tail, tail_max


def shvs_sample(z: jnp.ndarray, params: SamplingParams, hot: HotSet,
                u_accept: jnp.ndarray, u_hot: jnp.ndarray,
                u_tail: jnp.ndarray, *, k_cap: int = 1024,
                force_full_fallback: bool = True) -> SHVSResult:
    """SHVS on penalized logits z (B, V).

    u_accept / u_hot / u_tail: (B,) uniforms (pre-generated, deterministic).
    ``k_cap``: truncation cap for the filtered hot fast path.

    Semantics by configuration:
    * no filters (top_k=0, top_p=1, min_p=0): the paper's exact rejection
      sampler — accept hot draw iff u ≤ α, else draw from the tail proposal.
    * filters on: fast path = truncation-first on the H hot columns, exact
      iff (a) the filter support is contained in H (k-th hot ≥ tail max) and
      (b) the truncation itself is exact; other rows fall back to the
      full-V truncation-first path.
    """
    B, V = z.shape
    zs = temperature_scale(z, params.temperature)
    m, s_hot, s_tail, tail_max = shvs_masses(zs, hot)
    alpha = s_hot / jnp.maximum(s_hot + s_tail, 1e-30)

    hot_z = zs[:, hot.indices]                            # (B, H) gather
    H = hot.indices.shape[0]
    kc = min(k_cap, H)
    s_tot = s_hot + s_tail

    # ---- filtered fast path: truncation-first on the hot block -----------
    trunc = truncation_first_sample(hot_z, params, u_hot, k_cap=kc,
                                    z_is_scaled=True, full_total=s_tot,
                                    full_max=m)
    fast_tokens = hot.indices[trunc.tokens]               # map back to V
    has_filter = (params.top_k > 0) | (params.top_p < 1.0) | (params.min_p > 0.0)

    # containment guards: the global filter support must provably live
    # inside the hot set (computed from the same streaming pass's tail_max).
    hot_sorted = jax.lax.top_k(hot_z, kc)[0]              # (B, kc) desc
    # (a) explicit top-k: the k-th best hot logit strictly beats every tail
    kk = jnp.where(params.top_k > 0, jnp.minimum(params.top_k, kc), kc)
    kth = jnp.take_along_axis(hot_sorted, kk[:, None] - 1, axis=-1)[:, 0]
    topk_contained = (params.top_k > 0) & (kth > tail_max)
    # (b) nucleus-only: the first hot prefix reaching mass top_p (under the
    # FULL normalizer) must consist of logits strictly above tail_max
    w_hot_top = jnp.exp(hot_sorted - m[:, None])
    cum_full = jnp.cumsum(w_hot_top, -1) / jnp.maximum(s_tot, 1e-30)[:, None]
    reach = cum_full >= (jnp.minimum(params.top_p, 1.0) - 1e-7)[:, None]
    jstar = jnp.argmax(reach, axis=-1)                    # first True (or 0)
    at_jstar = jnp.take_along_axis(hot_sorted, jstar[:, None], axis=-1)[:, 0]
    nucleus_contained = reach.any(-1) & (at_jstar > tail_max)
    # (c) min-p-only: every tail token must fail the min-p threshold
    minp_contained = (jnp.exp(tail_max - m) < params.min_p) & \
        (hot_sorted[:, 0] >= m - 1e-6)
    guard = jnp.where(params.top_k > 0, topk_contained,
                      jnp.where(params.top_p < 1.0, nucleus_contained,
                                minp_contained))
    exact_fast = jnp.where(has_filter, guard & trunc.exact,
                           jnp.ones((B,), bool))

    # ---- unfiltered exact rejection path (the paper's Eq. 8–9) -----------
    w_hot = jnp.exp(hot_z - m[:, None])
    hot_draw = hot.indices[_inverse_cdf_draw(w_hot, u_hot)]
    accept = u_accept <= alpha
    w_tail = jnp.exp(zs - m[:, None]) * (~hot.mask[None, :])
    tail_draw = _inverse_cdf_draw(w_tail, u_tail).astype(jnp.int32)
    nofilter_tokens = jnp.where(accept, hot_draw, tail_draw)

    tokens = jnp.where(has_filter, fast_tokens, nofilter_tokens)
    accepted = jnp.where(has_filter, exact_fast, accept)
    needs_reference = jnp.zeros((B,), bool)

    if force_full_fallback:
        # rows whose fast path is not provably exact re-sample on full V
        full = truncation_first_sample(zs, params, u_tail, k_cap=k_cap,
                                       z_is_scaled=True)
        need_full = has_filter & ~exact_fast
        tokens = jnp.where(need_full, full.tokens, tokens)
        needs_reference = need_full & ~full.exact

    greedy = jnp.argmax(zs, axis=-1).astype(jnp.int32)
    tokens = jnp.where(params.temperature <= 0.0, greedy, tokens)
    return SHVSResult(tokens=tokens.astype(jnp.int32), accepted=accepted,
                      alpha=alpha, exact_fast=exact_fast,
                      needs_reference=needs_reference)


@register_backend("shvs")
class SHVSBackend(SamplerBackend):
    """S2 + S3 — the full SIMPLE decision plane as a sampler backend.

    Registered here (not in ``sampler_backend``) so the backend lives next
    to the math it wraps. ``hot_set`` defaults to :func:`default_hot_set`.
    """

    name = "shvs"

    def __init__(self, *, vocab_size: int, k_cap: int = 1024, shvs=None,
                 hot_set: Optional[HotSet] = None, **_):
        self.hot_set = hot_set if hot_set is not None else \
            default_hot_set(vocab_size, shvs)
        self.k_cap = k_cap

    def step(self, z, params, uniforms):
        res = shvs_sample(z, params, self.hot_set, uniforms[:, 0],
                          uniforms[:, 1], uniforms[:, 2], k_cap=self.k_cap)
        stats = DecisionStats(res.accepted.mean(), res.alpha.mean(),
                              (~res.exact_fast).mean())
        return res.tokens, stats
