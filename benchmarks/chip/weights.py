"""Random weights for a dense decoder, made on the device from the seed.

One jitted call builds the whole parameter tree in the type it is served in,
in the layout the engine's dense model reads (``emb``/``stack`` trees). Per
layer and per block of vocabulary rows the normals are drawn inside a
``lax.map``, so no float32 copy of a whole leaf ever exists.

The output head follows the configuration's ``head_zipf`` profile: the row
of token ``v`` has std ``top_logit_std * (v + 1) ** -exponent / sqrt(d)``,
so against a unit-RMS final hidden state the logit of token ``v`` has std
``top_logit_std * (v + 1) ** -exponent``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NORM_JITTER = 0.05


def root_key(seed: int):
    """A PRNG key from any non-negative seed, including ones above 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def head_row_std(cfg: dict) -> np.ndarray:
    """(V,) std of each output-head row, from the configuration's profile."""
    z = cfg["weights"]["head_zipf"]
    v = np.arange(cfg["vocab_size"], dtype=np.float64)
    return (z["top_logit_std"] * (v + 1.0) ** -z["exponent"]
            / np.sqrt(cfg["hidden_size"]))


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"L": cfg["num_hidden_layers"], "d": d, "H": h,
            "kv": cfg["num_key_value_heads"], "hd": hd,
            "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "qk_norm": cfg["model_type"] == "qwen3",
            "tied": bool(cfg["tie_word_embeddings"])}


def _row_blocks(V: int) -> int:
    for n in (128, 64, 32, 16, 8, 4, 2):
        if V % n == 0:
            return n
    return 1


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _norm_weight(key, shape, dtype):
    return (1.0 + NORM_JITTER * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def _rows(key, V, d, std_rows, dtype):
    """(V, d) rows, row v drawn with std ``std_rows[v]``, in blocks."""
    n = _row_blocks(V)
    keys = jax.random.split(key, n)
    stds = std_rows.reshape(n, V // n, 1)

    def block(args):
        k, s = args
        return (jax.random.normal(k, (V // n, d), jnp.float32) * s
                ).astype(dtype)

    return jax.lax.map(block, (keys, stds)).reshape(V, d)


def _build(key, cfg_items, head_std):
    cfg = dict(cfg_items)
    g = dims(cfg)
    dtype = jnp.dtype(cfg["serve_dtype"])
    L, d, H, kv, hd, f, V = (g[k] for k in ("L", "d", "H", "kv", "hd", "f",
                                            "V"))
    k_layers, k_tok, k_head, k_final = jax.random.split(key, 4)

    def layer(k):
        ks = jax.random.split(k, 11)
        p = {
            "ln1": _norm_weight(ks[0], (d,), dtype),
            "ln2": _norm_weight(ks[1], (d,), dtype),
            "attn": {
                "w_q": _normal(ks[2], (d, H * hd), d ** -0.5, dtype),
                "w_k": _normal(ks[3], (d, kv * hd), d ** -0.5, dtype),
                "w_v": _normal(ks[4], (d, kv * hd), d ** -0.5, dtype),
                "w_o": _normal(ks[5], (H * hd, d), (H * hd) ** -0.5, dtype),
            },
            "mlp": {
                "w_gate": _normal(ks[6], (d, f), d ** -0.5, dtype),
                "w_up": _normal(ks[7], (d, f), d ** -0.5, dtype),
                "w_down": _normal(ks[8], (f, d), f ** -0.5, dtype),
            },
        }
        if g["qk_norm"]:
            p["attn"]["q_norm"] = _norm_weight(ks[9], (hd,), dtype)
            p["attn"]["k_norm"] = _norm_weight(ks[10], (hd,), dtype)
        return p

    stack = jax.lax.map(layer, jax.random.split(k_layers, L))
    stack["final_ln"] = _norm_weight(k_final, (d,), dtype)
    if g["tied"]:
        emb = {"tok": _rows(k_tok, V, d, head_std, dtype)}
    else:
        emb = {"tok": _rows(k_tok, V, d, jnp.full((V,), 0.02, jnp.float32),
                            dtype),
               "head": _rows(k_head, V, d, head_std, dtype).T}
    return {"emb": emb, "stack": stack}


def _hashable(cfg: dict):
    keep = ("model_type", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "vocab_size", "tie_word_embeddings", "serve_dtype")
    return tuple((k, cfg.get(k)) for k in keep)


@functools.lru_cache(maxsize=None)
def _jitted_build(cfg_items):
    return jax.jit(functools.partial(_build, cfg_items=cfg_items))


def make_weights(cfg: dict, seed: int):
    """The parameter tree for configuration ``cfg`` (a config file's JSON)
    from ``seed``, on the default device, in ``serve_dtype``."""
    head_std = jnp.asarray(head_row_std(cfg), jnp.float32)
    return _jitted_build(_hashable(cfg))(root_key(seed), head_std=head_std)
