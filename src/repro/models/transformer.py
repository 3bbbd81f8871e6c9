"""Decoder stacks for every assigned family, with scan-over-layers.

All stacks share a uniform calling convention:

    apply_<family>_stack(params, x, positions, cfg, cache, mode) -> (y, cache, aux)

* ``mode``: "train" | "prefill" | "decode".
* ``cache`` is a dict pytree (see :func:`init_cache`); ``None`` in train mode.
* layer parameters are stacked along a leading L axis and consumed via
  ``lax.scan`` so HLO size (and compile time) is depth-independent.

KV caches support ring-buffer (sliding window) semantics: slot = pos % S_c.
SSM caches carry O(1) recurrent state; RWKV additionally carries the
token-shift inputs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout

from repro.config import ModelConfig
from repro.models import dist
from repro.models.attention import (attention_block, cross_attention_block,
                                    init_attention, project_enc_kv)
from repro.models.layers import (apply_mlp, init_mlp, layer_norm, rms_norm)
from repro.models.moe import apply_moe, init_moe
from repro.models.ssm import (init_mamba, init_rwkv, mamba_dims, mamba_seq,
                              rwkv_channel_mix_seq, rwkv_time_mix_seq)

# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------


def cache_len_for(cfg: ModelConfig, seq_len: int, window: Optional[int] = None) -> int:
    w = cfg.sliding_window if window is None else window
    return min(seq_len, w) if w else seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               window: Optional[int] = None, dtype=None):
    """Build the decode/prefill cache pytree for ``cfg``.

    ``seq_len`` is the maximum context length; sliding-window archs allocate
    only ``window`` slots (ring buffer).
    """
    dtype = jnp.dtype(dtype or cfg.dtype)
    hd = cfg.resolved_head_dim
    nkv = cfg.num_kv_heads
    d = cfg.d_model
    L = cfg.num_layers
    cache = {
        "len": jnp.zeros((batch,), jnp.int32),
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        Sc = cache_len_for(cfg, seq_len, window)
        cache["k"] = jnp.zeros((L, batch, Sc, nkv, hd), dtype)
        cache["v"] = jnp.zeros((L, batch, Sc, nkv, hd), dtype)
        if cfg.is_encdec:
            Se = cfg.encoder.num_frames
            cache["cross_k"] = jnp.zeros((L, batch, Se, nkv, hd), dtype)
            cache["cross_v"] = jnp.zeros((L, batch, Se, nkv, hd), dtype)
    elif cfg.family == "ssm":        # rwkv6
        hs = cfg.ssm.rwkv_head_size
        H = d // hs
        cache["ssm"] = jnp.zeros((L, batch, H, hs, hs), jnp.float32)
        cache["x_last_t"] = jnp.zeros((L, batch, d), dtype)
        cache["x_last_c"] = jnp.zeros((L, batch, d), dtype)
    elif cfg.family == "hybrid":     # zamba2: mamba states + shared-attn kv
        inner, nheads, headdim, N = mamba_dims(cfg)
        K = cfg.ssm.conv_size
        G = -(-L // cfg.hybrid.attn_every)   # number of shared-attn sites
        Sc = cache_len_for(cfg, seq_len, window or cfg.sliding_window or 4096)
        cache["ssm"] = jnp.zeros((L, batch, nheads, headdim, N), jnp.float32)
        cache["conv"] = jnp.zeros((L, batch, K - 1, inner), dtype)
        cache["k"] = jnp.zeros((G, batch, Sc, nkv, hd), dtype)
        cache["v"] = jnp.zeros((G, batch, Sc, nkv, hd), dtype)
    else:
        raise ValueError(cfg.family)
    return cache


def stage_bounds(num_layers: int, num_stages: int):
    """Balanced contiguous layer split for pipeline parallelism
    (DESIGN.md §12): stage s owns layers [lo, hi); earlier stages absorb
    the remainder so no stage is more than one layer heavier."""
    assert 1 <= num_stages <= num_layers, (num_stages, num_layers)
    base, rem = divmod(num_layers, num_stages)
    bounds, lo = [], 0
    for s in range(num_stages):
        hi = lo + base + (1 if s < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def slice_stage_params(stack_params: dict, lo: int, hi: int, *, last: bool):
    """Stage-slice a dense/moe stack's parameters: every stacked per-layer
    leaf keeps rows [lo, hi); ``final_ln`` ships only with the last stage
    (it runs after the full depth). ``lax.scan`` over the sliced tree
    composes bit-identically to one scan over the full stack — the
    pipeline engine's identity argument (DESIGN.md §12)."""
    out = {k: jax.tree_util.tree_map(lambda a: a[lo:hi], v)
           for k, v in stack_params.items() if k != "final_ln"}
    if last:
        out["final_ln"] = stack_params["final_ln"]
    return out


def slice_stage_cache(cache: dict, lo: int, hi: int):
    """Stage-slice a cache pytree: per-layer leaves (leading L axis — k/v
    slabs or paged pools) keep layers [lo, hi); per-sequence leaves
    (len/pos/block_table) are shared bookkeeping and pass through whole."""
    out = dict(cache)
    for k in ("k", "v", "k_pool", "v_pool"):
        if k in cache:
            out[k] = cache[k][lo:hi]
    return out


def _write_kv(cache_k_l, cache_v_l, k, v, lens, mode: str, mask=None):
    """Write new K/V into one layer's cache. Handles ring buffers.

    cache_k_l: (B, Sc, nkv, hd); k: (B, S_new, nkv, hd); lens: (B,) current
    per-sequence lengths (write positions). Prefill assumes fresh sequences
    (lens == 0 semantics; entries land at slots 0..S_new-1, ring-rotated).
    mode "chunk": slab write at per-row offsets, gated by ``mask`` (B,) —
    rows outside the mask keep their cache contents untouched.
    """
    Sc = cache_k_l.shape[1]
    S_new = k.shape[1]
    if mode == "decode":            # one token per row at slot lens[b] % Sc
        slot = (lens % Sc).astype(jnp.int32)

        def upd(c, x, s):
            return jax.lax.dynamic_update_slice(c, x, (s, 0, 0))

        ck = jax.vmap(upd)(cache_k_l, k, slot)
        cv = jax.vmap(upd)(cache_v_l, v, slot)
        return ck, cv
    if mode == "chunk":
        # C-wide slab at each row's offset, but ONLY for rows in the chunk
        # mask: the (B, C) program computes garbage K/V for co-resident
        # decode rows, and an unmasked slab write would clobber their valid
        # entries once lens[b] > Sc - C (dynamic_update_slice clamps the
        # start). Masked rows keep their slab via read-modify-write.
        mask = lens >= 0 if mask is None else mask
        slot = jnp.minimum(lens, Sc - S_new).astype(jnp.int32)

        def upd_masked(c, x, s, m):
            cur = jax.lax.dynamic_slice(c, (s, 0, 0), x.shape)
            return jax.lax.dynamic_update_slice(
                c, jnp.where(m, x, cur), (s, 0, 0))

        ck = jax.vmap(upd_masked)(cache_k_l, k, slot, mask)
        cv = jax.vmap(upd_masked)(cache_v_l, v, slot, mask)
        return ck, cv
    # prefill (fresh rows): keep the last Sc entries, rotated into ring order
    if S_new >= Sc:
        s0 = S_new % Sc
        return jnp.roll(k[:, -Sc:], s0, axis=1), jnp.roll(v[:, -Sc:], s0, axis=1)
    ck = jax.lax.dynamic_update_slice(cache_k_l, k, (0, 0, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v_l, v, (0, 0, 0, 0))
    return ck, cv


def _token_major(cache_k) -> bool:
    """Whether the default device lays out a stacked ``(L, B, Sc, nkv, hd)``
    cache with each token's ``(nkv, hd)`` block contiguous: the slot axis
    major to both head axes. A TPU makes the slot axis minor instead where
    ``(nkv, hd)`` would pad its tiles (smollm-360m's 5 x 64)."""
    dev = jax.devices()[0]
    layout = Layout.from_pjrt_layout(dev.client.get_default_layout(
        cache_k.dtype, cache_k.shape, dev))
    return set(layout.major_to_minor[-2:]) == {3, 4}


def _write_token(cache, layer, new, slot, token_major: bool):
    """Write one decode token per row, ``new`` ``(B, 1, nkv, hd)``, into
    layer ``layer`` of the stacked cache ``(L, B, Sc, nkv, hd)`` at slot
    ``slot[b]``, in place. Where a token is contiguous on the device
    (``token_major``) the B tokens are scattered. Where the slot axis is
    minor, a scatter along it would relayout the whole cache, so the
    layer's slab is written back whole with the tokens selected in."""
    if token_major:
        return cache.at[layer, jnp.arange(new.shape[0]), slot].set(
            new[:, 0], unique_indices=True)
    hit = jnp.arange(cache.shape[2])[None, :] == slot[:, None]
    slab = jnp.where(hit[:, :, None, None], new,
                     jax.lax.dynamic_index_in_dim(cache, layer, 0, False))
    return jax.lax.dynamic_update_index_in_dim(cache, slab, layer, 0)


# ---------------------------------------------------------------------------
# Dense / MoE / VLM decoder stack (also whisper decoder via cross_kv)
# ---------------------------------------------------------------------------


def init_dense_stack(key, cfg: ModelConfig):
    L = cfg.num_layers
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    p = {
        "ln1": jnp.ones((L, cfg.d_model), dtype),
        "ln2": jnp.ones((L, cfg.d_model), dtype),
        "attn": init_attention(ks[0], cfg, stacked=L),
        "final_ln": jnp.ones((cfg.d_model,), dtype),
    }
    if cfg.moe is not None:
        p["moe"] = init_moe(ks[1], cfg, stacked=L)
    else:
        p["mlp"] = init_mlp(ks[1], cfg, stacked=L)
    if cfg.is_encdec:
        p["ln_cross"] = jnp.ones((L, cfg.d_model), dtype)
        p["cross"] = init_attention(ks[2], cfg, stacked=L, cross=True)
    return p


def apply_dense_stack(params, x, positions, cfg: ModelConfig, cache, mode: str,
                      window: Optional[int] = None, remat: bool = False,
                      enc_out=None, chunk_mask=None, chunk_counts=None,
                      final_norm: bool = True):
    """x: (B, S, d). Returns (y, cache, aux_loss).

    ``final_norm=False`` skips the stack's closing norm — a pipeline stage
    that is not the last one hands its residual stream to the next stage
    raw (``params`` then need not carry ``final_ln``; DESIGN.md §12).

    For encoder-decoder models (whisper): pass ``enc_out`` in train/prefill
    mode; prefill stores the projected cross-K/V into the cache for decode.

    A *paged* cache (keys ``k_pool``/``v_pool``/``block_table`` — DESIGN.md
    §9) is accepted transparently in decode/chunk mode: each layer gathers
    its contiguous block view, runs the standard cached attention over it
    (bit-identical to the contiguous path when the view width matches), and
    the new K/V land in the pool via an out-of-bounds-dropping scatter.
    ``chunk_counts`` (B,) gives the valid tokens per row of a chunk slab
    (paged chunk writes only; the contiguous slab write doesn't need it).

    Decode on a *contiguous* cache carries the stacked ``k``/``v`` through
    the layer scan and writes each row's new token in place at
    ``(layer, b, len[b] % Sc)``; prefill, chunk and the paged pool pass
    their caches as scan inputs and take the written ones back as outputs
    (DESIGN.md §19).
    """
    use_ln = cfg.family == "audio"   # whisper uses LayerNorm (bias-free here)
    norm = (lambda h, w: layer_norm(h, w, jnp.zeros_like(w), cfg.rmsnorm_eps)) \
        if use_ln else (lambda h, w: rms_norm(h, w, cfg.rmsnorm_eps))
    win = cfg.sliding_window if window is None else window
    kv_len = None if cache is None else (
        cache["len"] + (1 if mode == "decode" else x.shape[1]))
    lens0 = None if cache is None else cache["len"]
    paged = cache is not None and "k_pool" in cache
    if paged:
        assert mode in ("decode", "chunk"), \
            "paged cache supports decode/chunk only (prefill rows are " \
            "scattered in by the engine)"
        from repro.models.attention import (flat_block_indices,
                                            gather_block_view,
                                            scatter_block_kv)
        bt = cache["block_table"]
        blk = cache["k_pool"].shape[2]
        nblocks = cache["k_pool"].shape[1]
        C = x.shape[1]
        if mode == "decode":
            pool_valid = jnp.ones((x.shape[0], C), bool)
        else:
            counts = chunk_counts if chunk_counts is not None \
                else jnp.full((x.shape[0],), C, jnp.int32)
            pool_valid = jnp.arange(C)[None, :] < counts[:, None]
            if chunk_mask is not None:
                pool_valid &= chunk_mask[:, None]
        # one (B, C) destination map shared by every layer's pool scatter
        pool_flat = flat_block_indices(bt, lens0, pool_valid, blk, nblocks)
    compute_cross = cfg.is_encdec and mode in ("train", "prefill")
    # a scan's xs and ys never share a buffer, so a contiguous cache passed
    # through them is sliced, copied and re-stacked whole on every step;
    # decode adds one token per row, so it carries the cache instead
    in_place = cache is not None and mode == "decode" and not paged
    if in_place:
        slot = (lens0 % cache["k"].shape[2]).astype(jnp.int32)
        token_major = _token_major(cache["k"])

    def body(carry, xs):
        x, aux, kv = carry
        lp = xs["layer"]
        h = norm(x, lp["ln1"])
        if mode == "train":
            attn_out, k, v = attention_block(lp["attn"], h, cfg, positions,
                                             mode="train", window=win)
            ck = cv = None
        else:
            if paged:
                # materialize this layer's contiguous view of the pool; the
                # standard decode/chunk attention below runs on it unchanged
                ck_in = gather_block_view(xs["kp"], bt, blk)
                cv_in = gather_block_view(xs["vp"], bt, blk)
            elif not in_place:
                ck_in, cv_in = xs["ck"], xs["cv"]
            if mode == "decode":
                # write first so the current token attends to itself
                _, k, v = attention_block(lp["attn"], h, cfg, positions,
                                          mode="train", window=win)  # project only
                if in_place:
                    kv = tuple(_write_token(c, xs["l"], t, slot, token_major)
                               for c, t in zip(kv, (k, v)))
                    ck, cv = (jax.lax.dynamic_index_in_dim(c, xs["l"], 0,
                                                           False) for c in kv)
                else:
                    ck, cv = _write_kv(ck_in, cv_in, k, v, lens0, "decode")
                attn_out, _, _ = attention_block(
                    lp["attn"], h, cfg, positions, cache_k=ck, cache_v=cv,
                    kv_len=kv_len, mode="decode", window=win)
            elif mode == "chunk":
                # chunked continue-prefill: write the chunk's K/V slab at
                # each row's current offset, then attend against the cache
                # with per-row causal masks (DESIGN.md §8)
                _, k, v = attention_block(lp["attn"], h, cfg, positions,
                                          mode="project", window=win)
                ck, cv = _write_kv(ck_in, cv_in, k, v, lens0, "chunk",
                                   chunk_mask)
                attn_out, _, _ = attention_block(
                    lp["attn"], h, cfg, positions, cache_k=ck, cache_v=cv,
                    kv_len=lens0, mode="chunk", window=win)
            else:  # prefill
                attn_out, k, v = attention_block(lp["attn"], h, cfg, positions,
                                                 mode="train", window=win)
                ck, cv = _write_kv(ck_in, cv_in, k, v, lens0, "prefill")
        x = x + attn_out
        if cfg.is_encdec:
            if compute_cross:
                cross_kv = project_enc_kv(lp["cross"], enc_out, cfg)
            else:
                cross_kv = (xs["cross_k"], xs["cross_v"])
            hc = norm(x, lp["ln_cross"])
            x = x + cross_attention_block(lp["cross"], hc, cross_kv, cfg)
            if compute_cross and cache is not None:
                ys_cross = cross_kv
            else:
                ys_cross = None
        h2 = norm(x, lp["ln2"])
        if cfg.moe is not None:
            ff, l_aux = apply_moe(lp["moe"], h2, cfg, train=(mode == "train"))
            aux = aux + l_aux
        else:
            ff = apply_mlp(lp["mlp"], h2, cfg.act)
        x = x + ff
        x = dist.constrain(x, dist.batch_spec_entry(), None, None)
        ys = {}
        if ck is not None and not in_place:
            if paged:
                # persist only the new tokens: scatter them into the pool
                # (the gathered view ck/cv was a per-iteration temporary)
                ys["kp"] = scatter_block_kv(xs["kp"], k, pool_flat)
                ys["vp"] = scatter_block_kv(xs["vp"], v, pool_flat)
            else:
                ys["ck"], ys["cv"] = ck, cv
        if cfg.is_encdec and compute_cross and cache is not None:
            ys["cross_k"], ys["cross_v"] = ys_cross
        return (x, aux, kv), ys

    if remat and mode == "train":
        body = jax.checkpoint(body)

    layer_tree = {k: v for k, v in params.items()
                  if k != "final_ln"}
    xs = {"layer": layer_tree}
    kv = None
    if cache is not None:
        if paged:
            xs["kp"], xs["vp"] = cache["k_pool"], cache["v_pool"]
        elif in_place:
            kv = (cache["k"], cache["v"])
            xs["l"] = jnp.arange(cache["k"].shape[0])
        else:
            xs["ck"], xs["cv"] = cache["k"], cache["v"]
        if cfg.is_encdec and not compute_cross:
            xs["cross_k"], xs["cross_v"] = cache["cross_k"], cache["cross_v"]

    (x, aux, kv), ys = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), kv), xs)
    if cache is not None and mode != "train":
        cache = dict(cache)
        if paged:
            cache["k_pool"], cache["v_pool"] = ys["kp"], ys["vp"]
        elif in_place:
            cache["k"], cache["v"] = kv
        else:
            cache["k"], cache["v"] = ys["ck"], ys["cv"]
        if "cross_k" in ys:
            cache["cross_k"], cache["cross_v"] = ys["cross_k"], ys["cross_v"]
        S_new = 1 if mode == "decode" else positions.shape[-1]
        cache["len"] = cache["len"] + S_new
        cache["pos"] = cache["pos"] + S_new
    if final_norm:
        x = norm(x, params["final_ln"])
    return x, cache, aux


# ---------------------------------------------------------------------------
# RWKV-6 stack
# ---------------------------------------------------------------------------


def init_rwkv_stack(key, cfg: ModelConfig):
    L = cfg.num_layers
    dtype = jnp.dtype(cfg.dtype)
    return {
        "ln1": jnp.ones((L, cfg.d_model), dtype),
        "ln2": jnp.ones((L, cfg.d_model), dtype),
        "layers": init_rwkv(key, cfg, stacked=L),
        "final_ln": jnp.ones((cfg.d_model,), dtype),
    }


def apply_rwkv_stack(params, x, positions, cfg: ModelConfig, cache, mode: str,
                     window=None, remat: bool = False):
    B = x.shape[0]
    hs = cfg.ssm.rwkv_head_size
    H = cfg.d_model // hs
    if cache is None:
        zstate = jnp.zeros((cfg.num_layers, B, H, hs, hs), jnp.float32)
        zlast = jnp.zeros((cfg.num_layers, B, cfg.d_model), x.dtype)
        ssm, xlt, xlc = zstate, zlast, zlast
    else:
        ssm, xlt, xlc = cache["ssm"], cache["x_last_t"], cache["x_last_c"]

    def body(carry, xs):
        x = carry
        lp, st, lt, lc = xs["lp"], xs["ssm"], xs["xlt"], xs["xlc"]
        h = rms_norm(x, xs["ln1"], cfg.rmsnorm_eps)
        tm, new_lt, new_st = rwkv_time_mix_seq(lp, h, lt, st, cfg)
        x = x + tm
        h2 = rms_norm(x, xs["ln2"], cfg.rmsnorm_eps)
        cm, new_lc = rwkv_channel_mix_seq(lp, h2, lc)
        x = x + cm
        x = dist.constrain(x, dist.batch_spec_entry(), None, None)
        return x, {"ssm": new_st, "xlt": new_lt, "xlc": new_lc}

    if remat and mode == "train":
        body = jax.checkpoint(body)
    xs = {"lp": params["layers"], "ssm": ssm, "xlt": xlt, "xlc": xlc,
          "ln1": params["ln1"], "ln2": params["ln2"]}
    x, ys = jax.lax.scan(body, x, xs)
    if cache is not None:
        cache = dict(cache)
        cache["ssm"], cache["x_last_t"], cache["x_last_c"] = (
            ys["ssm"], ys["xlt"], ys["xlc"])
        S_new = x.shape[1]
        cache["len"] = cache["len"] + S_new
        cache["pos"] = cache["pos"] + S_new
    x = rms_norm(x, params["final_ln"], cfg.rmsnorm_eps)
    return x, cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Zamba2 hybrid stack: groups of mamba layers + shared attention block
# ---------------------------------------------------------------------------


def init_zamba_stack(key, cfg: ModelConfig):
    L = cfg.num_layers
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "ln_m": jnp.ones((L, cfg.d_model), dtype),
        "mamba": init_mamba(ks[0], cfg, stacked=L),
        "shared_ln1": jnp.ones((cfg.d_model,), dtype),
        "shared_ln2": jnp.ones((cfg.d_model,), dtype),
        "shared_attn": init_attention(ks[1], cfg),
        "shared_mlp": init_mlp(ks[2], cfg),
        "final_ln": jnp.ones((cfg.d_model,), dtype),
    }


def apply_zamba_stack(params, x, positions, cfg: ModelConfig, cache, mode: str,
                      window: Optional[int] = None, remat: bool = False):
    L = cfg.num_layers
    every = cfg.hybrid.attn_every
    win = window if window is not None else (cfg.sliding_window or 4096)
    B = x.shape[0]
    inner, nheads, headdim, N = mamba_dims(cfg)
    K = cfg.ssm.conv_size
    if cache is None:
        conv = jnp.zeros((L, B, K - 1, inner), x.dtype)
        ssm = jnp.zeros((L, B, nheads, headdim, N), jnp.float32)
        kv_len = None
        lens0 = jnp.zeros((B,), jnp.int32)
    else:
        conv, ssm = cache["conv"], cache["ssm"]
        lens0 = cache["len"]
        kv_len = cache["len"] + (1 if mode == "decode" else x.shape[1])
    aux = jnp.zeros((), jnp.float32)
    new_conv, new_ssm = [], []
    new_k, new_v = [], []

    def mamba_group(x, lo, hi):
        lp = jax.tree_util.tree_map(lambda a: a[lo:hi], params["mamba"])
        lns = params["ln_m"][lo:hi]
        cv = conv[lo:hi]
        st = ssm[lo:hi]

        def body(x, xs):
            h = rms_norm(x, xs["ln"], cfg.rmsnorm_eps)
            out, c2, s2 = mamba_seq(xs["lp"], h, xs["conv"], xs["ssm"], cfg)
            x = x + out
            x = dist.constrain(x, dist.batch_spec_entry(), None, None)
            return x, {"conv": c2, "ssm": s2}

        if remat and mode == "train":
            body = jax.checkpoint(body)
        x, ys = jax.lax.scan(body, x, {"lp": lp, "ln": lns, "conv": cv, "ssm": st})
        return x, ys["conv"], ys["ssm"]

    g = 0
    lo = 0
    while lo < L:
        hi = min(lo + every, L)
        # shared attention block at each group boundary
        h = rms_norm(x, params["shared_ln1"], cfg.rmsnorm_eps)
        if mode == "train":
            attn_out, k, v = attention_block(params["shared_attn"], h, cfg,
                                             positions, mode="train", window=win)
        else:
            ck_in, cv_in = cache["k"][g], cache["v"][g]
            if mode == "decode":
                _, k, v = attention_block(params["shared_attn"], h, cfg,
                                          positions, mode="train", window=win)
                ck, cvv = _write_kv(ck_in, cv_in, k, v, lens0, "decode")
                attn_out, _, _ = attention_block(
                    params["shared_attn"], h, cfg, positions, cache_k=ck,
                    cache_v=cvv, kv_len=kv_len, mode="decode", window=win)
            else:
                attn_out, k, v = attention_block(params["shared_attn"], h, cfg,
                                                 positions, mode="train",
                                                 window=win)
                ck, cvv = _write_kv(ck_in, cv_in, k, v, lens0, "prefill")
            new_k.append(ck)
            new_v.append(cvv)
        x = x + attn_out
        h2 = rms_norm(x, params["shared_ln2"], cfg.rmsnorm_eps)
        x = x + apply_mlp(params["shared_mlp"], h2, cfg.act)
        # mamba group
        x, c2, s2 = mamba_group(x, lo, hi)
        new_conv.append(c2)
        new_ssm.append(s2)
        lo = hi
        g += 1

    if cache is not None:
        cache = dict(cache)
        cache["conv"] = jnp.concatenate(new_conv, axis=0)
        cache["ssm"] = jnp.concatenate(new_ssm, axis=0)
        cache["k"] = jnp.stack(new_k, axis=0)
        cache["v"] = jnp.stack(new_v, axis=0)
        S_new = 1 if mode == "decode" else x.shape[1]
        cache["len"] = cache["len"] + S_new
        cache["pos"] = cache["pos"] + S_new
    x = rms_norm(x, params["final_ln"], cfg.rmsnorm_eps)
    return x, cache, aux


# ---------------------------------------------------------------------------
# Whisper encoder
# ---------------------------------------------------------------------------


def init_encoder(key, cfg: ModelConfig):
    Le = cfg.encoder.num_layers
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    return {
        "pos": (0.02 * jax.random.normal(
            ks[0], (cfg.encoder.num_frames, cfg.d_model), jnp.float32)).astype(dtype),
        "ln1": jnp.ones((Le, cfg.d_model), dtype),
        "ln2": jnp.ones((Le, cfg.d_model), dtype),
        "attn": init_attention(ks[1], cfg, stacked=Le),
        "mlp": init_mlp(ks[2], cfg, stacked=Le),
        "final_ln": jnp.ones((cfg.d_model,), dtype),
    }


def apply_encoder(params, frames, cfg: ModelConfig):
    """frames: (B, S_enc, d) precomputed stub embeddings."""
    from repro.models.attention import attend_full, _project_qkv, _expand_gqa
    x = frames + params["pos"][None, :frames.shape[1]].astype(frames.dtype)
    zeros = jnp.zeros_like

    def body(x, lp):
        h = layer_norm(x, lp["ln1"], zeros(lp["ln1"]), cfg.rmsnorm_eps)
        q, k, v = _project_qkv(lp["attn"], h, h, cfg,
                               jnp.arange(x.shape[1])[None], rope=False)
        qg = _expand_gqa(q, cfg.num_kv_heads)
        out = attend_full(qg, k, v, causal=False, window=0)
        out = out.reshape(x.shape[0], x.shape[1], -1)
        out = jnp.einsum("bsh,hd->bsd", out, lp["attn"]["w_o"],
                         preferred_element_type=jnp.float32).astype(x.dtype)
        x = x + out
        h2 = layer_norm(x, lp["ln2"], zeros(lp["ln2"]), cfg.rmsnorm_eps)
        x = x + apply_mlp(lp["mlp"], h2, cfg.act)
        return x, None

    x, _ = jax.lax.scan(body, x, {k: params[k] for k in ("ln1", "ln2", "attn", "mlp")})
    return layer_norm(x, params["final_ln"], zeros(params["final_ln"]),
                      cfg.rmsnorm_eps)
