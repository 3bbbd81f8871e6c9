"""Plain float32 forward of a dense decoder: Llama and Qwen3 layers.

Written from the published architecture, not from the program: token
embedding; per layer RMSNorm, GQA attention with rotary position
embeddings (rotate-half form, ``theta ** (-2i / head_dim)``) and, for
Qwen3, RMSNorm over each head's query and key before the rotation; a
residual add; RMSNorm, a SwiGLU MLP (``down(silu(gate x) * up x)``) and a
residual add; a final RMSNorm and the LM head (the embedding's transpose
where tied). Every product is float32 at ``HIGHEST`` matmul precision.

It runs one sequence at a time and one layer per call, so it fits beside
nothing else on the chip. ``quant="fp8"`` is the control: every matrix
product takes both operands rounded to float8 (e4m3) with a scale per
output channel (weights) and per token (activations), the precision below
the bfloat16 the configurations serve in.

Weights are read by name from the tree ``weights.make_weights`` builds.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0


class Spec(NamedTuple):
    d: int
    H: int
    kv: int
    hd: int
    eps: float
    theta: float
    qk_norm: bool
    tied: bool


def spec_of(cfg: dict) -> Spec:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return Spec(d=d, H=h, kv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                qk_norm=cfg["model_type"] == "qwen3",
                tied=bool(cfg["tie_word_embeddings"]))


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, quant):
    """x (S, i) @ w (i, o) in float32, or both rounded to fp8 first."""
    w = w.astype(F32)
    if quant == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, pos, theta):
    """x (S, heads, hd): rotate-half rotary embedding at positions pos."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd // 2, dtype=F32) * 2.0 / hd)
    ang = pos[:, None].astype(F32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("sp", "quant"))
def _layer(x, stack, i, sp: Spec, quant):
    lw = jax.tree_util.tree_map(lambda a: a[i], stack)
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, lw["ln1"], sp.eps)
    a = lw["attn"]
    q = _mm(h, a["w_q"], quant).reshape(S, sp.H, sp.hd)
    k = _mm(h, a["w_k"], quant).reshape(S, sp.kv, sp.hd)
    v = _mm(h, a["w_v"], quant).reshape(S, sp.kv, sp.hd)
    if sp.qk_norm:
        q = _rms(q, a["q_norm"], sp.eps)
        k = _rms(k, a["k_norm"], sp.eps)
    q, k = _rope(q, pos, sp.theta), _rope(k, pos, sp.theta)
    group = sp.H // sp.kv
    k = jnp.repeat(k, group, axis=1)          # query head j reads kv j // g
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(sp.hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(S, -1)
    x = x + _mm(o, a["w_o"], quant)
    h = _rms(x, lw["ln2"], sp.eps)
    m = lw["mlp"]
    u = jax.nn.silu(_mm(h, m["w_gate"], quant)) * _mm(h, m["w_up"], quant)
    return x + _mm(u, m["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("sp", "quant"))
def _head(x, final_ln, head, sp: Spec, quant):
    h = _rms(x, final_ln, sp.eps)
    return _mm(h, head, quant)


@jax.jit
def _embed(tok, ids):
    return jnp.take(tok, ids, axis=0).astype(F32)


def logits(params, cfg: dict, tokens, read_at, quant: Optional[str] = None,
           pad_to: Optional[int] = None) -> np.ndarray:
    """Float32 logits (len(read_at), V) of the sequence ``tokens`` at the
    positions ``read_at`` (each predicts the token after it).

    ``pad_to`` pads the sequence (causal attention keeps the pad from
    reaching earlier positions) and ``read_at`` to fixed sizes, so every
    sequence of a run uses one compiled program."""
    sp = spec_of(cfg)
    tokens = np.asarray(tokens, np.int32)
    read_at = np.asarray(read_at, np.int32)
    S = pad_to or len(tokens)
    ids = np.zeros(S, np.int32)
    ids[:len(tokens)] = tokens
    T = len(read_at)
    rows = np.zeros(max(T, S) if pad_to else T, np.int32)
    rows[:T] = read_at
    stack = {k: v for k, v in params["stack"].items() if k != "final_ln"}
    with jax.default_matmul_precision("highest"):
        x = _embed(params["emb"]["tok"], jnp.asarray(ids))
        for i in range(stack["ln1"].shape[0]):
            x = _layer(x, stack, jnp.int32(i), sp, quant)
        head = params["emb"]["tok"].T if sp.tied else params["emb"]["head"]
        out = _head(x[jnp.asarray(rows)], params["stack"]["final_ln"], head,
                    sp, quant)
    return np.asarray(out)[:T]
