"""The public wrapper for the fused sampling kernel.

Handles padding to block multiples, dtype coercion, and the
interpret-vs-compiled choice, which follows the platform a call is lowered
for (:func:`_on_platform`): compiled by Mosaic on a TPU, run by the Pallas
interpreter on the CPU, where there is no Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fused_kernel, ref


def _on_platform(kernel, *args, **static):
    """Call a Pallas ``kernel`` interpreted where the enclosing program is
    lowered for the CPU and compiled everywhere else. The choice is made
    per lowering, so one traced program is right on either backend."""
    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(kernel, interpret=True, **static),
        default=functools.partial(kernel, interpret=False, **static))


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
