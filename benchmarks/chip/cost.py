"""Operations and bytes a dense decoder's work needs, from its shapes.

These are the yardstick's counts, not the program's: what the algorithm
must do, not what the program happens to do. A decode step reads every
weight it multiplies once, each active row's K/V up to that row's own
length (not the padded cache), and once the (B, V) logits and the two
(B, V) int32 penalty histograms; it performs 2 FLOPs per multiply-add of
every matrix product on its active rows plus attention over each row's
own context. Prefill counts its prompt tokens the same way with causal
attention, and the LM head for the last position only.
"""
from __future__ import annotations

from typing import Iterable, NamedTuple, Tuple

from benchmarks.chip.weights import dims


class Cost(NamedTuple):
    flops: float
    bytes: float


def layer_matmul_params(cfg: dict) -> int:
    """Matrix entries of one decoder layer (q, k, v, o; gate, up, down)."""
    g = dims(cfg)
    d, H, kv, hd, f = g["d"], g["H"], g["kv"], g["hd"], g["f"]
    return d * H * hd + 2 * d * kv * hd + H * hd * d + 3 * d * f


def matmul_params(cfg: dict) -> int:
    """Matrix entries a token is multiplied through: every layer plus the
    LM head (the input embedding is a lookup, not a product)."""
    g = dims(cfg)
    return g["L"] * layer_matmul_params(cfg) + g["V"] * g["d"]


def param_count(cfg: dict) -> int:
    """Every parameter the configuration holds."""
    g = dims(cfg)
    d, L, V, hd = g["d"], g["L"], g["V"], g["hd"]
    norms = L * 2 * d + d + (L * 2 * hd if g["qk_norm"] else 0)
    emb = V * d if g["tied"] else 2 * V * d
    return L * layer_matmul_params(cfg) + norms + emb


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    g = dims(cfg)
    return 2 * g["L"] * g["kv"] * g["hd"] * dtype_bytes


def attention_flops(cfg: dict, queries: int, context: float) -> float:
    """QK^T and PV for ``queries`` tokens each seeing ``context`` keys."""
    g = dims(cfg)
    return 4.0 * g["L"] * g["H"] * g["hd"] * queries * context


def decode_step(cfg: dict, contexts: Iterable[int], batch: int,
                dtype_bytes: int = 2) -> Cost:
    """One decode step over rows with the given context lengths, in a
    program of ``batch`` slots."""
    g = dims(cfg)
    ctx = list(contexts)
    n = len(ctx)
    flops = 2.0 * matmul_params(cfg) * n + sum(
        attention_flops(cfg, 1, c) for c in ctx)
    weights = (g["L"] * layer_matmul_params(cfg) + g["V"] * g["d"]) \
        * dtype_bytes
    norms = (g["L"] * 2 * g["d"] + g["d"]) * dtype_bytes
    kv = kv_bytes_per_token(cfg, dtype_bytes) * sum(ctx)
    emb_rows = n * g["d"] * dtype_bytes
    logits_and_state = 3 * batch * g["V"] * 4
    return Cost(flops, weights + norms + kv + emb_rows + logits_and_state)


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Model FLOPs of one prompt: every layer for every token, causal
    attention (token i sees i + 1 keys), the LM head for the last one."""
    g = dims(cfg)
    n = prompt_len
    return (2.0 * g["L"] * layer_matmul_params(cfg) * n
            + attention_flops(cfg, 1, n * (n + 1) / 2)
            + 2.0 * g["V"] * g["d"])


def decode_token_flops(cfg: dict, context: int) -> float:
    """Model FLOPs of one generated token at ``context``."""
    return 2.0 * matmul_params(cfg) + attention_flops(cfg, 1, context)


def least_time(cost: Cost, peaks) -> Tuple[float, str]:
    """The least time the chip could take for ``cost``, and which of its
    peaks bounds it (``"compute"`` or ``"memory"``)."""
    t_c = cost.flops / peaks.flops_per_s
    t_m = cost.bytes / peaks.bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
