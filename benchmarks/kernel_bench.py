"""Kernel-level benchmark: the fused single-pass sampling kernel vs the
unfused composition — wall time, analytic HBM-pass accounting, and
bytes-per-token-decision (the trajectory in ``BENCH_kernels.json``).

The decision plane is memory-bound (paper §2.1: O(1) FLOPs/byte), so HBM
passes over the (B, V) logits-row footprint ARE the roofline. Pass counts
are DERIVED from the kernel configuration (which penalties are enabled,
which truncation mode runs, whether SHVS splits hot/tail masses) — never
hard-coded — so the roofline column cannot drift from what the kernels
actually stream (``tests/test_kernel_bench.py`` pins the derivation).

Interpret-mode wall times are reported for trend-tracking only: Pallas
interpret mode emulates the grid on CPU, so the analytic pass counts, not
the wall clock, are the architecture-relevant numbers (DESIGN.md §14).
"""
from __future__ import annotations

import json
import time
from dataclasses import asdict, dataclass

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_jitted, zipf_logits
from repro.kernels import ops, ref

#: v5e HBM bandwidth (bytes/s) for the analytic pass -> time conversion.
V5E_HBM_BPS = 819e9


@dataclass(frozen=True)
class KernelConfig:
    """The knobs that change what the sampling pipeline streams from HBM.

    Only (B, V)-row-sized operands count as passes; (B,)-sized params and
    the (V,) hot mask are O(1/B) of a pass and ignored.
    """

    repetition: bool = True        # Eq. 1 repetition penalty (reads BOTH
    #                                prompt and output count rows)
    presence: bool = True          # presence penalty (output counts)
    frequency: bool = True         # frequency penalty (output counts)
    truncation: str = "truncation_first"   # or "full_softmax" (reference)
    hot_set: bool = False          # SHVS hot/tail mass split (Eq. 6)

    @property
    def any_penalty(self) -> bool:
        return self.repetition or self.presence or self.frequency


def hbm_passes_unfused(cfg: KernelConfig) -> float:
    """Row-footprint passes of the UNFUSED composition, by stage:

    * penalty+temperature stage: read z, write penalized z' (always — the
      temperature scale alone still streams the row), plus the count-row
      reads its enabled penalties need;
    * truncation_first: the top-K scan and the streaming-mass pass each
      re-read z' (separate kernels);
    * full_softmax: max pass + exp-sum pass + probs write + CDF-draw read;
    * SHVS adds one more z' read: the hot/tail mass split runs as its own
      kernel in the unfused pipeline.
    """
    passes = 2.0                               # z read + z' write
    if cfg.repetition:
        passes += 1.0                          # prompt-count rows
    if cfg.any_penalty:
        passes += 1.0                          # output-count rows
    if cfg.truncation == "truncation_first":
        passes += 2.0                          # top-K scan + mass pass
    else:
        passes += 4.0                          # max, exp-sum, probs, draw
    if cfg.hot_set:
        passes += 1.0                          # separate hot-mass kernel
    return passes


def hbm_passes_fused(cfg: KernelConfig) -> float:
    """The fused kernel reads each needed row operand exactly once and
    writes only (B,)-sized outputs: 1 pass over z, plus the count rows its
    enabled penalties require. Truncation mode and the hot-set split ride
    in the same stream — they add NOTHING (that is the point of the
    kernel: DESIGN.md §14)."""
    passes = 1.0                               # the single z read
    if cfg.repetition:
        passes += 1.0
    if cfg.any_penalty:
        passes += 1.0
    return passes


def bytes_per_token_decision(passes: float, vocab: int) -> float:
    """HBM bytes streamed per sampled token (one batch row), f32 rows."""
    return passes * vocab * 4.0


#: the accounting sweep: named configs the trajectory tracks.
CONFIGS = [
    ("default", KernelConfig()),
    ("no_penalties", KernelConfig(repetition=False, presence=False,
                                  frequency=False)),
    ("presence_only", KernelConfig(repetition=False, frequency=False)),
    ("full_softmax", KernelConfig(truncation="full_softmax")),
    ("shvs_hot_set", KernelConfig(hot_set=True)),
]


def _accounting_rows(vocab: int) -> list:
    rows = []
    for name, cfg in CONFIGS:
        unf, fus = hbm_passes_unfused(cfg), hbm_passes_fused(cfg)
        rows.append({
            "config": name, **asdict(cfg),
            "passes_unfused": unf, "passes_fused": fus,
            "traffic_cut": unf / fus,
            "bytes_per_token_unfused": bytes_per_token_decision(unf, vocab),
            "bytes_per_token_fused": bytes_per_token_decision(fus, vocab),
            "v5e_us_unfused": unf * vocab * 4.0 / V5E_HBM_BPS * 1e6,
            "v5e_us_fused": fus * vocab * 4.0 / V5E_HBM_BPS * 1e6,
        })
    return rows


def _exec_modes() -> dict:
    """Execution mode of each timed side. The fused kernel runs under
    Pallas (interpret-mode grid emulation on CPU unless compiled); the
    unfused composition is ordinary XLA. The two are DIFFERENT execution
    substrates, so their wall clocks are separate per-mode trend columns —
    never a cross-mode ratio (the PR-6 trajectory point compared them
    directly and "showed" the fused kernel 14% slower, an artifact of
    interpret-mode emulation, not the kernel)."""
    return {"fused_exec_mode":
            "pallas_interpret" if jax.default_backend() == "cpu"
            else "pallas_compiled",
            "unfused_exec_mode": "xla"}


def _wall_times(B: int, V: int, k_cap: int, hot_size: int) -> dict:
    """Fused Pallas pass and the unfused ``kernels/ref.py`` composition on
    identical operands (the differential-identity pair from
    ``tests/test_kernels.py``) — each timed ONLY against its own past
    points (see :func:`_exec_modes`), median wall time per call at the
    ``time_jitted`` default iteration count (the old iters=3/warmup=1
    run was noise-dominated on top of being cross-mode)."""
    z = zipf_logits(B, V)
    rng = np.random.default_rng(0)
    cp = jnp.asarray(rng.integers(0, 2, (B, V)), jnp.int32)
    co = jnp.asarray(rng.integers(0, 2, (B, V)), jnp.int32)
    rep = jnp.full((B,), 1.1)
    pres = jnp.full((B,), 0.1)
    freq = jnp.full((B,), 0.1)
    temp = jnp.full((B,), 0.8)
    tk = jnp.full((B,), 16, jnp.int32)
    tp = jnp.full((B,), 0.95)
    mp = jnp.zeros((B,))
    u = jnp.asarray(rng.random(B), jnp.float32)
    hot = jnp.asarray(np.arange(V) < hot_size)

    from repro.core.sampling import SamplingParams
    params = SamplingParams(temperature=temp, top_k=tk, top_p=tp, min_p=mp,
                            repetition_penalty=rep, presence_penalty=pres,
                            frequency_penalty=freq)

    def fused():
        return ops.fused_sample(z, cp, co, params, u, hot, k_cap=k_cap)

    def unfused():
        return ref.fused_sample_ref(z, cp, co, rep, pres, freq, temp, tk,
                                    tp, mp, u, hot, k_cap=k_cap,
                                    block_v=2048)

    t_fus = time_jitted(fused)
    t_unf = time_jitted(unfused)
    return {"B": B, "V": V, "k_cap": k_cap, "hot_size": hot_size,
            **_exec_modes(),
            "fused_wall_us": t_fus * 1e6, "unfused_wall_us": t_unf * 1e6}


def write_trajectory(rows: list, timing: dict,
                     out: str = "BENCH_kernels.json") -> dict:
    """Append one trajectory point (accounting sweep + timed shapes) to
    ``out`` — the kernel bench history future PRs diff against."""
    point = {
        # schema 2: timing carries {fused,unfused}_exec_mode and the two
        # wall clocks are per-mode trend columns (no cross-mode ratio)
        "bench": "kernel_bench", "schema": 2,
        "completed_unix": int(time.time()),
        "timing": timing,
        "results": rows,
    }
    try:
        with open(out) as f:
            doc = json.load(f)
        assert isinstance(doc.get("trajectory"), list)
    except (OSError, ValueError, AssertionError):
        doc = {"bench": "kernel_bench", "trajectory": []}
    doc["trajectory"].append(point)
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    return point


def run(emit_fn=emit, smoke: bool = False,
        out: str = "BENCH_kernels.json") -> list:
    B, V = (4, 4096) if smoke else (8, 49_152)
    rows = _accounting_rows(V)
    for r in rows:
        emit_fn(f"kernel.passes.{r['config']}", r["passes_fused"],
                f"unfused {r['passes_unfused']:.0f} -> fused "
                f"{r['passes_fused']:.0f} "
                f"({r['traffic_cut']:.1f}x HBM traffic cut; "
                f"{r['bytes_per_token_fused'] / 1e3:.0f} KB/token fused)")
        assert r["passes_fused"] <= r["passes_unfused"] / 2.0, \
            f"{r['config']}: fused must halve the unfused pass count"
    timing = _wall_times(B, V, k_cap=64 if smoke else 1024,
                         hot_size=min(V // 4, 16_384))
    emit_fn("kernel.fused_wall_us", timing["fused_wall_us"],
            f"{timing['fused_exec_mode']}, B={B} V={V} — per-mode trend "
            f"column, NOT comparable to unfused_wall_us (different "
            f"execution substrate; see passes.* for the roofline)")
    emit_fn("kernel.unfused_wall_us", timing["unfused_wall_us"],
            f"{timing['unfused_exec_mode']} "
            f"(ref.fused_sample_ref composition), B={B} V={V} — per-mode "
            f"trend column")
    default = rows[0]
    emit_fn("kernel.v5e_hbm_passes", default["passes_fused"],
            f"unfused {default['passes_unfused']:.0f} passes "
            f"({default['v5e_us_unfused']:.1f}us/token on v5e) -> fused "
            f"{default['passes_fused']:.0f} "
            f"({default['v5e_us_fused']:.1f}us/token): "
            f"{default['traffic_cut']:.1f}x decision-plane HBM traffic cut")
    if out:
        write_trajectory(rows, timing, out)
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args()
    run(smoke=args.smoke, out=args.out)
