"""Public jit'd wrappers for the Pallas kernels.

Handles padding to block multiples, dtype coercion, and the
interpret-vs-compiled choice, which follows the platform a call is lowered
for (:func:`_on_platform`): compiled by Mosaic on a TPU, run by the Pallas
interpreter on the CPU, where there is no Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import penalty_kernel, shvs_kernel, gumbel_kernel
from repro.kernels import fused_kernel
from repro.kernels import ref  # noqa: F401  (re-exported for convenience)

NEG_INF = -1e30


def _on_platform(kernel, *args, **static):
    """Call a Pallas ``kernel`` interpreted where the enclosing program is
    lowered for the CPU and compiled everywhere else. The choice is made
    per lowering, so one traced program is right on either backend."""
    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(kernel, interpret=True, **static),
        default=functools.partial(kernel, interpret=False, **static))


def _pad_axis(x, axis: int, mult: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), size


def fused_penalty_scale(logits, counts_p, counts_o, repetition, presence,
                        frequency, temperature, *, block_b: int = 8,
                        block_v: int = 512):
    """Fused penalties + temperature (kernel-backed, any (B, V))."""
    B, V = logits.shape
    bb = min(block_b, B) if B % min(block_b, B) == 0 else 1
    z, _ = _pad_axis(logits, 1, block_v)
    cp, _ = _pad_axis(counts_p, 1, block_v)
    co, _ = _pad_axis(counts_o, 1, block_v)
    zb, _ = _pad_axis(z, 0, bb)
    cpb, _ = _pad_axis(cp, 0, bb)
    cob, _ = _pad_axis(co, 0, bb)
    rep, _ = _pad_axis(repetition.astype(jnp.float32), 0, bb, 1.0)
    pres, _ = _pad_axis(presence.astype(jnp.float32), 0, bb)
    freq, _ = _pad_axis(frequency.astype(jnp.float32), 0, bb)
    temp, _ = _pad_axis(temperature.astype(jnp.float32), 0, bb, 1.0)
    out = _on_platform(
        penalty_kernel.penalty_scale, zb, cpb, cob, rep, pres, freq, temp,
        block_b=bb, block_v=min(block_v, zb.shape[1]))
    return out[:B, :V]


def fused_shvs_masses(z, hot_mask, *, block_b: int = 8, block_v: int = 512):
    """Fused SHVS streaming pass (m, s_hot, s_tail, tail_max)."""
    B, V = z.shape
    bb = min(block_b, B) if B % min(block_b, B) == 0 else 1
    zp, _ = _pad_axis(z.astype(jnp.float32), 1, block_v, NEG_INF)
    hm, _ = _pad_axis(hot_mask.astype(jnp.int32), 0, block_v, 1)
    # padded columns: hot & NEG_INF => contribute exp(-inf)=0 to s_hot and
    # never touch tail_max
    zp, _ = _pad_axis(zp, 0, bb, NEG_INF)
    m, s_hot, s_tail, tmax = _on_platform(
        shvs_kernel.shvs_masses, zp, hm, block_b=bb,
        block_v=min(block_v, zp.shape[1]))
    return m[:B], s_hot[:B], s_tail[:B], tmax[:B]


def fused_sample(logits, counts_p, counts_o, params, u_row, hot_mask, *,
                 k_cap: int, block_b: int = 8, block_v: int = 2048):
    """The fused single-pass sampling decision (kernel-backed, any (B, V)).

    penalties → temperature → streaming top-K/masses → truncation-first
    filter → Gumbel draw, in ONE read of the logits. ``params`` is the
    7-field ``SamplingParams`` core struct; ``u_row`` is the (B,) uniform
    column driving the draw. Oracle: ``ref.fused_sample_ref`` (bit-identical
    by shared tile math). Returns (tokens, exact(bool), alpha, kept).
    """
    B, V = logits.shape
    padded, bb = ref.fused_pad(
        logits, counts_p, counts_o, params.repetition_penalty,
        params.presence_penalty, params.frequency_penalty,
        params.temperature, params.top_k, params.top_p, params.min_p,
        u_row, hot_mask, block_b=block_b, block_v=block_v)
    z = padded[0]
    tokens, exact, alpha, kept = _on_platform(
        fused_kernel.fused_sample, *padded, k_cap=min(k_cap, z.shape[1]),
        block_b=bb, block_v=min(block_v, z.shape[1]))
    return (jnp.minimum(tokens[:B], V - 1), exact[:B] != 0, alpha[:B],
            kept[:B])


def fused_gumbel_argmax(z, seed, *, block_b: int = 8, block_v: int = 512):
    """Single-pass Gumbel-max categorical draw from softmax(z)."""
    B, V = z.shape
    bb = min(block_b, B) if B % min(block_b, B) == 0 else 1
    zp, _ = _pad_axis(z.astype(jnp.float32), 1, block_v, NEG_INF)
    zp, _ = _pad_axis(zp, 0, bb, NEG_INF)
    toks = _on_platform(
        gumbel_kernel.gumbel_argmax, zp, seed, block_b=bb,
        block_v=min(block_v, zp.shape[1]))
    return jnp.minimum(toks[:B], V - 1)
