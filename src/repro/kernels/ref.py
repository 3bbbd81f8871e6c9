"""Pure-jnp oracle for the fused sampling kernel, and the tile helpers
kernel and oracle share.

``penalty_ref`` is Eq. 1 + temperature in its plain form;
``fused_sample_ref`` is the tile-faithful oracle the kernel must match bit
for bit (asserted by ``tests/test_kernels.py`` over shape/dtype sweeps in
interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def penalty_ref(logits, counts_p, counts_o, repetition, presence, frequency,
                temperature):
    """Fused penalties + temperature scale (paper §2.2 / Eq. 1).

    logits: (B, V) any float dtype; counts_*: (B, V) int32;
    repetition/presence/frequency/temperature: (B,) f32.
    Returns penalized, temperature-scaled logits (B, V) f32.
    """
    z = logits.astype(jnp.float32)
    seen = ((counts_p > 0) | (counts_o > 0)).astype(jnp.float32)
    f = 1.0 + (repetition[:, None] - 1.0) * seen
    z = jnp.where(z > 0, z / f, z * f)
    z = z - presence[:, None] * (counts_o > 0).astype(jnp.float32)
    z = z - frequency[:, None] * counts_o.astype(jnp.float32)
    return z / jnp.maximum(temperature, 1e-6)[:, None]


def _hash_uniform(seed, b, v):
    """Deterministic per-(seed,row,col) uniform in (0,1) via a 32-bit integer
    hash (xorshift-mix). Shared by the fused kernel's draw and its oracle so
    both produce bit-identical samples."""
    x = (b.astype(jnp.uint32) * jnp.uint32(2654435761) ^
         v.astype(jnp.uint32) * jnp.uint32(40503) ^
         jnp.uint32(seed))
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(2246822519)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(3266489917)
    x = x ^ (x >> jnp.uint32(16))
    # (0, 1): add 0.5 then scale so zero maps off the boundary
    return (_u32_to_f32(x) + 0.5) * (1.0 / 4294967296.0)


def _u32_to_f32(x):
    """uint32 -> float32 through exact int32 halves (Mosaic has no direct
    u32 <-> f32 cast). ``hi * 65536`` and ``lo`` are exact in f32, so the
    one rounding is the sum's: the same bits as ``x.astype(jnp.float32)``."""
    hi = (x >> jnp.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    lo = (x & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * 65536.0 + lo


# ---------------------------------------------------------------------------
# Fused single-pass sampler (DESIGN.md §14): penalties → temperature →
# streaming top-K + masses → truncation-first filter → restricted Gumbel draw.
#
# The helpers below are shared VERBATIM by the Pallas kernel body
# (``fused_kernel.py``) and the tile-faithful oracle ``fused_sample_ref`` so
# kernel and oracle are bit-identical by construction: both run the same jnp
# ops over the same (block_b, block_v) tile sequence.
# ---------------------------------------------------------------------------

# salts the fused draw's hash stream; its value keys every fused stream, so
# changing it moves them all
FUSED_DRAW_SALT = 0x46555345


def _u32_from_uniform(u):
    """Map a pre-generated uniform in [0, 1) to a 24-bit integer row seed.

    24 bits keeps the product exactly representable in f32 (no rounding up
    to 2^24 for u -> 1), so the seed is a pure function of the uniform's
    bits and identical across hosts/shards. The cast goes through int32,
    exact below 2^31: Mosaic lowers f32 -> i32 but not f32 -> u32.
    """
    return (u * 16777216.0).astype(jnp.int32).astype(jnp.uint32)


def streaming_mass_update(m, s_tot, s_hot, zs, hot_f):
    """One online-softmax tile step (the streaming form of
    ``core.shvs.shvs_masses``):
    carries (m, s_tot, s_hot) — running max and total/hot exp-sums in the
    basis exp(z − m), each a (bb, 1) column. zs: (bb, bv) scaled logits;
    hot_f: (1|bb, bv) f32.
    """
    tile_max = jnp.max(zs, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, tile_max)
    scale = jnp.exp(m - m_new)
    w = jnp.exp(zs - m_new)
    s_tot = s_tot * scale + jnp.sum(w, axis=-1, keepdims=True)
    s_hot = s_hot * scale + jnp.sum(w * hot_f, axis=-1, keepdims=True)
    return m_new, s_tot, s_hot


def topk_merge(vals, idx, tile_vals, tile_idx):
    """Merge a vocab tile into the running per-row top-K buffer.

    K rounds of max-extraction over the buffer-first concatenation: each
    round takes the largest remaining value and, among equal values, the
    lowest position. That is exactly a stable descending sort's first K
    entries, so ties resolve to the LOWEST vocabulary index (earlier tiles
    precede later ones, and in-tile ids ascend), matching ``jnp.argmax``
    tie-breaking — which is what makes the fused greedy path bit-identical
    to the reference backend's argmax. Only reductions, compares and
    selects, which Mosaic lowers (it has no sort). vals/idx: (bb, K);
    tile_vals/tile_idx: (bb, bv).
    """
    bb, K = vals.shape
    cat_v = jnp.concatenate([vals, tile_vals], axis=-1)
    cat_i = jnp.concatenate([idx, tile_idx], axis=-1)
    n = cat_v.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (bb, n), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (bb, K), 1)

    def extract(r, carry):
        # ``taken`` is int32, not bool: Mosaic cannot carry i1 vectors
        # through a loop
        taken, out_v, out_i = carry
        free = taken == 0
        m = jnp.max(jnp.where(free, cat_v, -jnp.inf), axis=-1,
                    keepdims=True)
        first = jnp.min(jnp.where((cat_v == m) & free, pos, n), axis=-1,
                        keepdims=True)
        hit = pos == first
        pick = jnp.sum(jnp.where(hit, cat_i, 0), axis=-1, keepdims=True)
        here = col == r
        return (jnp.where(hit, 1, taken), jnp.where(here, m, out_v),
                jnp.where(here, pick, out_i))

    init = (jnp.zeros((bb, n), jnp.int32), jnp.zeros_like(vals),
            jnp.zeros_like(idx))
    _, out_v, out_i = jax.lax.fori_loop(0, K, extract, init)
    return out_v, out_i


def trunc_gumbel_draw(vals, idx, s_tot, top_k, top_p, min_p, temperature,
                      row_seed):
    """Truncation-first filter + restricted Gumbel-max draw on the merged
    top-K buffer (the fused kernel's final-tile epilogue).

    vals/idx: (B, K) descending buffer (values are penalized AND
    temperature-scaled); s_tot: (B, 1) total exp-mass in the basis
    exp(z − vals[:, 0]) (the buffer head IS the global max); row_seed:
    (B, 1) uint32 per-row draw seeds; the per-row params are (B, 1)
    columns too. Filter semantics mirror
    ``core.sampling.truncation_first_sample`` — top-k / nucleus / min-p
    applied inside the truncated domain with the exclusive-prefix-mass
    nucleus rule — and the draw replaces inverse-CDF with
    argmax(vals + Gumbel) over the kept support, which samples the same
    renormalized distribution exactly (Gumbel-max on a restricted support)
    without a second normalization pass. Returns (tokens, exact, kept),
    each a (B, 1) column.
    """
    B, K = vals.shape
    w = jnp.exp(vals - vals[:, :1])
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, K), 1)
    kk = jnp.where(top_k > 0, jnp.minimum(top_k, K), K)
    keep = pos < kk
    subset_total = jnp.sum(w * keep, axis=-1, keepdims=True)
    # with an explicit top-k the kept subset IS the support; otherwise the
    # support is the full distribution, whose mass the streaming pass
    # already accumulated (this is what makes one pass sufficient)
    norm_total = jnp.where(top_k > 0, subset_total, s_tot)
    p = w * keep / jnp.maximum(norm_total, 1e-30)
    # inclusive prefix sum as a triangular matmul (Mosaic has no cumsum)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (K, K), 0) <=
           jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)).astype(jnp.float32)
    cum = jnp.dot(p, tri, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    keep &= (cum - p) < top_p
    keep &= p >= min_p * p[:, :1]
    # provable-exactness flags (same rules as truncation_first_sample)
    mass_at_cap = subset_total / jnp.maximum(norm_total, 1e-30)
    explicit_k = (top_k > 0) & (top_k <= K)
    nucleus_ok = (top_p < 1.0) & \
        (mass_at_cap >= jnp.minimum(top_p, 1.0) - 1e-7)
    p_last = w[:, K - 1:] / jnp.maximum(norm_total, 1e-30)
    minp_ok = (min_p > 0.0) & (p_last < min_p * p[:, :1])
    full_mass_ok = mass_at_cap >= 1.0 - 1e-7
    exact = explicit_k | nucleus_ok | minp_ok | full_mass_ok
    # restricted Gumbel-max: noise keyed on (salt, row seed, vocab id) only,
    # so the draw is invariant to batch composition and row sharding
    u = _hash_uniform(FUSED_DRAW_SALT, row_seed, idx)
    g = -jnp.log(-jnp.log(u))
    score = jnp.where(keep, vals + g, -jnp.inf)
    jwin = jnp.argmax(score, axis=-1, keepdims=True)
    tokens = jnp.sum(jnp.where(pos == jwin, idx, 0), axis=-1, keepdims=True)
    tokens = jnp.where(temperature <= 0.0, idx[:, :1], tokens)
    kept = jnp.sum(keep.astype(jnp.int32), axis=-1, keepdims=True)
    return tokens.astype(jnp.int32), exact, kept


def fused_pad(logits, counts_p, counts_o, repetition, presence, frequency,
              temperature, top_k, top_p, min_p, u_row, hot_mask, *,
              block_b, block_v):
    """Pad fused-sampler inputs to block multiples. Shared by the ops
    wrapper and the oracle so both see bit-identical padded operands.

    Padded vocab columns carry z=NEG_INF / counts=0 / cold hot-mask (zero
    mass, never sampled for any live row); padded batch rows carry neutral
    params. Returns (padded tuple, bb) with bb the resolved row block.
    """
    B, V = logits.shape
    bb = min(block_b, B) if B % min(block_b, B) == 0 else 1

    def padv(x, value):                      # vocab axis of (B, V) arrays
        pad = (-x.shape[1]) % block_v
        return x if pad == 0 else jnp.pad(x, ((0, 0), (0, pad)),
                                          constant_values=value)

    def padb(x, value):                      # batch axis of any leading-B
        pad = (-x.shape[0]) % bb
        if pad == 0:
            return x
        widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, widths, constant_values=value)

    z = padb(padv(logits.astype(jnp.float32), NEG_INF), NEG_INF)
    cp = padb(padv(jnp.asarray(counts_p, jnp.int32), 0), 0)
    co = padb(padv(jnp.asarray(counts_o, jnp.int32), 0), 0)
    hotpad = (-hot_mask.shape[0]) % block_v
    hot = jnp.asarray(hot_mask, jnp.int32)
    if hotpad:
        hot = jnp.pad(hot, (0, hotpad))
    return (z, cp, co,
            padb(repetition.astype(jnp.float32), 1.0),
            padb(presence.astype(jnp.float32), 0.0),
            padb(frequency.astype(jnp.float32), 0.0),
            padb(temperature.astype(jnp.float32), 1.0),
            padb(jnp.asarray(top_k, jnp.int32), 0),
            padb(top_p.astype(jnp.float32), 1.0),
            padb(min_p.astype(jnp.float32), 0.0),
            padb(u_row.astype(jnp.float32), 0.5),
            hot), bb


@functools.partial(jax.jit, static_argnames=("k_cap", "block_b", "block_v"))
def fused_sample_ref(logits, counts_p, counts_o, repetition, presence,
                     frequency, temperature, top_k, top_p, min_p, u_row,
                     hot_mask, *, k_cap, block_b=8, block_v=512):
    """Tile-faithful oracle for the fused single-pass sampler.

    This is the UNFUSED composition: ``penalty_ref`` materializes the full
    penalized/scaled (B, V) tensor, then separate passes build the top-K
    buffer and the streaming masses, then the shared epilogue filters and
    draws. It walks vocabulary tiles in the same (block_v) order as the
    kernel and calls the identical helper functions, so the two are
    bit-identical — floating-point accumulation order and all.

    logits: (B, V); counts_*: (B, V) int32; per-row params (B,); u_row:
    (B,) pre-generated uniforms (the decision plane's column 1); hot_mask:
    (V,) bool. Returns (tokens, exact, alpha, kept), each (B,).
    """
    B, V = logits.shape
    (z, cp, co, rep, pres, freq, temp, tk, tp, mp, u, hot), bb = fused_pad(
        logits, counts_p, counts_o, repetition, presence, frequency,
        temperature, top_k, top_p, min_p, u_row, hot_mask,
        block_b=block_b, block_v=block_v)
    Bp, Vp = z.shape
    K = min(k_cap, Vp)
    zs = penalty_ref(z, cp, co, rep, pres, freq, temp)
    m = jnp.full((Bp, 1), NEG_INF, jnp.float32)
    s_tot = jnp.zeros((Bp, 1), jnp.float32)
    s_hot = jnp.zeros((Bp, 1), jnp.float32)
    vals = jnp.full((Bp, K), -jnp.inf, jnp.float32)
    idx = jnp.full((Bp, K), Vp, jnp.int32)
    for j in range(Vp // block_v):
        sl = slice(j * block_v, (j + 1) * block_v)
        hot_f = hot[sl].astype(jnp.float32)[None, :]
        m, s_tot, s_hot = streaming_mass_update(m, s_tot, s_hot,
                                                zs[:, sl], hot_f)
        tile_idx = jnp.broadcast_to(
            jnp.arange(j * block_v, (j + 1) * block_v, dtype=jnp.int32),
            (Bp, block_v))
        vals, idx = topk_merge(vals, idx, zs[:, sl], tile_idx)
    # the streamed sums are in the basis exp(z − m) and the buffer head is
    # that same running max (identical float), so s_tot needs no re-basis
    col = lambda x: x[:, None]
    tokens, exact, kept = trunc_gumbel_draw(
        vals, idx, s_tot, col(tk), col(tp), col(mp), col(temp),
        _u32_from_uniform(col(u)))
    alpha = s_hot / jnp.maximum(s_tot, 1e-30)
    return (jnp.minimum(tokens[:B, 0], V - 1), exact[:B, 0], alpha[:B, 0],
            kept[:B, 0])
