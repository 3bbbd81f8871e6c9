"""Pallas TPU kernel: the fused single-pass sampling decision (DESIGN.md §14).

ONE HBM→VMEM streaming pass over vocabulary tiles performs the whole
decision-plane pipeline for a row shard:

  penalties (Eq. 1)  →  temperature  →  streaming top-K + exp-masses
                     →  truncation-first filter (§5.2)  →  Gumbel-max draw

Per (block_b, block_v) tile the kernel applies the penalty/temperature math
elementwise in VMEM, folds the tile into a per-row top-K candidate buffer
(stable merge, lowest-index tie-breaking) and into online-softmax running
sums (total + hot-set mass), then on the LAST vocab tile runs the filter +
restricted Gumbel-max epilogue on the (block_b, K) buffer. The (B, V)
logits are read once; nothing (B, V)-shaped is ever written — the unfused
composition reads/writes the logits tensor at every stage boundary
(see ``benchmarks/kernel_bench.py`` for the derived pass accounting).

Truncation-first is what makes a single pass possible at all: every filter
(top-k / nucleus / min-p) and the draw itself only ever look at the K best
logits plus O(1) streaming aggregates, so the epilogue's working set is
(block_b, K) regardless of V. The draw uses argmax(z + Gumbel) restricted
to the kept support — distribution-identical to normalize-then-inverse-CDF
but needs no second pass for the normalizer.

All tile math is shared verbatim with ``ref.fused_sample_ref`` (the
tile-faithful oracle), so kernel and oracle are bit-identical, including
float accumulation order. Grid: (B/block_b, V/block_v), vocab innermost
(sequential on TPU), accumulating into revisited output blocks. Per-row
operands and outputs travel as (B, 1) columns: Mosaic tiles a rank-1
block only by 128 or by the whole array, and a column block of
``block_b`` rows tiles for any B.

Mosaic compiles the kernel on a TPU (``ops`` decides by the platform the
call is lowered for; ``tests/test_tpu_compile.py`` compiles it for a
described v5e). The shared helpers use only what Mosaic lowers: the
top-K merge is K rounds of max-extraction instead of a sort, the prefix
sum a triangular matmul, and the u32 <-> f32 casts go through int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import (NEG_INF, _u32_from_uniform,
                               streaming_mass_update, topk_merge,
                               trunc_gumbel_draw)


def _fused_kernel(rep_ref, pres_ref, freq_ref, temp_ref, tk_ref, tp_ref,
                  mp_ref, u_ref, z_ref, cp_ref, co_ref, hot_ref,
                  tok_ref, exact_ref, alpha_ref, kept_ref,
                  vals_ref, idx_ref, m_ref, stot_ref, shot_ref,
                  *, block_v, vocab_padded):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    # -- penalties + temperature, elementwise in VMEM (== ref.penalty_ref) --
    z = z_ref[...].astype(jnp.float32)               # (bb, bv)
    cp = cp_ref[...]
    co = co_ref[...]
    seen = ((cp > 0) | (co > 0)).astype(jnp.float32)
    f = 1.0 + (rep_ref[...] - 1.0) * seen           # rows are (bb, 1)
    z = jnp.where(z > 0, z / f, z * f)
    z = z - pres_ref[...] * (co > 0).astype(jnp.float32)
    z = z - freq_ref[...] * co.astype(jnp.float32)
    zs = z / jnp.maximum(temp_ref[...], 1e-6)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        stot_ref[...] = jnp.zeros_like(stot_ref)
        shot_ref[...] = jnp.zeros_like(shot_ref)
        vals_ref[...] = jnp.full_like(vals_ref, -jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, vocab_padded)

    # -- streaming masses + top-K merge (shared helpers, same float order) --
    hot_f = (hot_ref[...] != 0).astype(jnp.float32)[None, :]
    m, s_tot, s_hot = streaming_mass_update(
        m_ref[...], stot_ref[...], shot_ref[...], zs, hot_f)
    m_ref[...] = m
    stot_ref[...] = s_tot
    shot_ref[...] = s_hot
    bb, K = vals_ref.shape
    # a tile whose every value is <= its row's K-th buffered value cannot
    # enter the buffer (ties keep the lower, buffered id), so its merge
    # would return the buffer unchanged: skip it
    enters = jnp.max(zs, axis=-1, keepdims=True) > vals_ref[:, K - 1:]

    @pl.when(jnp.max(enters.astype(jnp.int32)) > 0)
    def _merge():
        tile_idx = jax.lax.broadcasted_iota(jnp.int32, (bb, block_v), 1) \
            + j * block_v
        vals, idx = topk_merge(vals_ref[...], idx_ref[...], zs, tile_idx)
        vals_ref[...] = vals
        idx_ref[...] = idx

    # -- final vocab tile: filter + draw on the (bb, K) buffer --------------
    @pl.when(j == nv - 1)
    def _epilogue():
        tokens, exact, kept = trunc_gumbel_draw(
            vals_ref[...], idx_ref[...], s_tot, tk_ref[...], tp_ref[...],
            mp_ref[...], temp_ref[...], _u32_from_uniform(u_ref[...]))
        tok_ref[...] = tokens
        exact_ref[...] = exact.astype(jnp.int32)
        alpha_ref[...] = s_hot / jnp.maximum(s_tot, 1e-30)
        kept_ref[...] = kept


@functools.partial(jax.jit,
                   static_argnames=("k_cap", "block_b", "block_v", "interpret"))
def fused_sample(z, counts_p, counts_o, repetition, presence, frequency,
                 temperature, top_k, top_p, min_p, u_row, hot_mask, *,
                 k_cap: int, block_b: int = 8, block_v: int = 512,
                 interpret: bool = True):
    """The fused single-pass sampling kernel. See ``ref.fused_sample_ref``.

    z: (B, V); counts_*: (B, V) int32; per-row params (B,); u_row: (B,)
    uniforms; hot_mask: (V,) int32. B % block_b == 0 and V % block_v == 0
    are required (``ops.fused_sample`` pads via ``ref.fused_pad``).
    Returns (tokens i32, exact i32, alpha f32, kept i32), each (B,).
    """
    B, V = z.shape
    assert B % block_b == 0 and V % block_v == 0, (B, V, block_b, block_v)
    K = min(k_cap, V)
    grid = (B // block_b, V // block_v)
    tile = lambda: pl.BlockSpec((block_b, block_v), lambda i, j: (i, j),
                                memory_space=pltpu.VMEM)
    row = lambda: pl.BlockSpec((block_b, 1), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM)
    buf = lambda: pl.BlockSpec((block_b, K), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM)
    kernel = functools.partial(_fused_kernel, block_v=block_v,
                               vocab_padded=V)
    col = lambda x, dt: jnp.asarray(x, dt).reshape(B, 1)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row()] * 8 + [tile(), tile(), tile(),
                                pl.BlockSpec((block_v,), lambda i, j: (j,),
                                             memory_space=pltpu.VMEM)],
        out_specs=[row(), row(), row(), row(), buf(), buf(), row(), row(),
                   row()],
        out_shape=[jax.ShapeDtypeStruct((B, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, K), jnp.float32),
                   jax.ShapeDtypeStruct((B, K), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1), jnp.float32)],
        interpret=interpret,
    )(col(repetition, jnp.float32), col(presence, jnp.float32),
      col(frequency, jnp.float32), col(temperature, jnp.float32),
      col(top_k, jnp.int32), col(top_p, jnp.float32),
      col(min_p, jnp.float32), col(u_row, jnp.float32),
      z, jnp.asarray(counts_p, jnp.int32), jnp.asarray(counts_o, jnp.int32),
      jnp.asarray(hot_mask, jnp.int32))
    tokens, exact, alpha, kept = (o[:, 0] for o in out[:4])
    return tokens, exact, alpha, kept
