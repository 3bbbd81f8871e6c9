"""Measured pipeline bubbles: baseline vs disaggregated sampling on the
EXECUTABLE pipeline engine (DESIGN.md §12).

Where ``benchmarks/pipeline_sim.py`` *models* the paper's Eq. 4 with
assumed stage/sampling constants, this benchmark *measures* it: a real
``p``-stage microbatched decode (stage-sliced params, per-stage KV,
cycle clock) with the decision plane either

* ``baseline``      — sampled synchronously right after the last stage's
                      forward (t_sampling on every cycle's critical path);
* ``disaggregated`` — device_get to the host sampler pool, committed at
                      the microbatch's stage-1 re-entry, (M−p) cycles of
                      slack to hide in.

The model is tiny but the vocabulary is large (full-V ``reference``
backend), so the sampling epilogue is material relative to a stage's
forward — the regime of the paper's Fig. 1b.

``--validate`` cross-checks the analytic simulator: the measured per-stage
forward time, sampling time, and sampler-pool rate are fed into
``pipeline_sim``'s cycle formulas and the predicted steady-state cycle is
compared against the measured one (relative error reported per mode).

    PYTHONPATH=src python -m benchmarks.fig_pipeline [--validate]
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit
from repro.config import ModelConfig, SamplingConfig
from repro.engine import PipelineConfig, PipelineEngine, Request
from repro.models.model import Model

ROWS = 4           # rows per microbatch
MAX_NEW = 24
VOCAB = 8192       # big vocab -> material sampling epilogue (Fig. 1b regime)

_CACHE: dict = {}


def _bench_model() -> ModelConfig:
    return ModelConfig(name="pipe-bench", family="dense", num_layers=4,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                       vocab_size=VOCAB)


def _params(cfg: ModelConfig):
    if "params" not in _CACHE:
        _CACHE["params"] = Model(cfg).init(jax.random.PRNGKey(0))
    return _CACHE["params"]


def measure(stages: int, microbatches: int, mode: str, samplers: int = 2,
            algorithm: str = "reference") -> dict:
    """One closed-loop run (every slot occupied, uniform max_new) on the
    executable pipeline; returns ``pipeline_report()`` plus TPOT
    percentiles. Steady-state only: the report's full-cycle filter drops
    the fill/drain ramp."""
    cfg = _bench_model()
    params = _params(cfg)
    B = ROWS * microbatches
    eng = PipelineEngine(cfg, params, PipelineConfig(
        max_batch=B, max_seq_len=64, algorithm=algorithm,
        k_cap=min(256, cfg.vocab_size), prompt_bucket=8,
        stages=stages, microbatches=microbatches, samplers=samplers,
        sampler_mode=mode))
    rng = np.random.default_rng(0)
    reqs = [Request(
        request_id=i,
        prompt=rng.integers(1, cfg.vocab_size, 8).tolist(),
        max_new_tokens=MAX_NEW,
        sampling=SamplingConfig(temperature=0.9, top_k=40, top_p=0.95,
                                repetition_penalty=1.1))
        for i in range(B)]
    eng.submit(reqs)
    # warmup: one full traversal compiles every stage + the sampler step
    for _ in range(microbatches + stages + 2):
        eng.step()
    eng.cycle_log.clear()
    done = eng.run(max_steps=50_000)
    eng.close()
    assert len(done) == B, f"{len(done)}/{B} finished"
    rep = eng.pipeline_report()
    tpot = []
    for r in done:
        if len(r.token_times) > 1:
            tpot.extend(np.diff(r.token_times))
    rep["tpot_p50_ms"] = float(np.percentile(tpot, 50) * 1e3) if tpot else 0.0
    rep["tpot_p95_ms"] = float(np.percentile(tpot, 95) * 1e3) if tpot else 0.0
    rep["rows_per_mb"] = ROWS
    return rep


def validate(stages: int, microbatches: int, emit_fn) -> None:
    """Cross-check ``pipeline_sim``'s analytic cycle against measurement.

    The simulator's inputs are taken FROM the measured run — mean stage
    forward time, mean on-stage sampling time, per-row sampler-pool time —
    so the comparison isolates the cycle *structure* (Eq. 4 vs the slack
    formula), not the constants."""
    from benchmarks.pipeline_sim import SimConfig, _cycle
    base = measure(stages, microbatches, "device")
    simple = measure(stages, microbatches, "host")
    # measured components (s): forward = mean stage busy NET of sampling
    t_stage = (np.mean(base["stage_util"]) * base["mean_cycle_ms"]
               - base["sample_ms_mean"] / stages) * 1e-3
    # the pool's per-row cost is fetch + CPU sampling: pipeline_report
    # splits them (transfer_ms_mean vs sampler_ms_mean, DESIGN.md §13) but
    # the simulator's t_sampler_row models the whole host-side path
    scfg = SimConfig(num_stages=stages, num_microbatches=microbatches,
                     t_stage=t_stage,
                     t_sampling_gpu=base["sample_ms_mean"] * 1e-3,
                     t_sampler_row=((simple["sampler_ms_mean"]
                                     + simple["transfer_ms_mean"]) * 1e-3
                                    / max(ROWS, 1)),
                     num_samplers=1, batch_slots=ROWS * microbatches,
                     jitter=0.0)
    rng = np.random.default_rng(0)
    for mode, rep in (("baseline", base), ("simple", simple)):
        C_pred, _, _ = _cycle(scfg, mode, ROWS, rng)
        C_meas = rep["mean_cycle_ms"] * 1e-3
        err = abs(C_pred - C_meas) / C_meas
        emit_fn(f"fig_pipeline.validate.p{stages}.{mode}", err * 100,
                f"analytic C={C_pred * 1e3:.3f}ms measured="
                f"{C_meas * 1e3:.3f}ms rel_err={err:.1%}")


def run(emit_fn=emit) -> None:
    for p, M in ((2, 4), (4, 8)):
        base = measure(p, M, "device")
        simple = measure(p, M, "host")
        tag = f"p{p}_m{M}"
        emit_fn(f"fig_pipeline.bubble.{tag}.baseline",
                base["bubble_frac"] * 1e6,
                f"bubble={base['bubble_frac']:.1%} "
                f"cycle={base['mean_cycle_ms']:.2f}ms "
                f"sample={base['sample_ms_mean']:.2f}ms "
                f"tpot_p50={base['tpot_p50_ms']:.1f}ms (paper: 22-40%)")
        emit_fn(f"fig_pipeline.bubble.{tag}.disaggregated",
                simple["bubble_frac"] * 1e6,
                f"bubble={simple['bubble_frac']:.1%} "
                f"cycle={simple['mean_cycle_ms']:.2f}ms "
                f"stall={simple['stall_ms_mean']:.2f}ms "
                f"sampler={simple['sampler_ms_mean']:.2f}ms "
                f"xfer={simple['transfer_ms_mean']:.2f}ms "
                f"tpot_p50={simple['tpot_p50_ms']:.1f}ms")
        # headline: pipeline-cycle gain (Eq. 4's C — in a real PP
        # deployment tokens/s scales with 1/C). Wall-clock TPOT is also
        # reported but on this ONE-device emulation it penalizes the
        # disaggregated mode: the host sampler workers contend with every
        # stage's compute for the same few cores, whereas deployed stages
        # are separate accelerators and the pool is otherwise-idle host CPU.
        gain = (base["mean_cycle_ms"] / simple["mean_cycle_ms"] - 1) \
            if simple["mean_cycle_ms"] else 0.0
        emit_fn(f"fig_pipeline.gain.{tag}", gain * 100,
                f"cycle {base['mean_cycle_ms']:.2f}->"
                f"{simple['mean_cycle_ms']:.2f}ms (+{gain:.1%} pipeline "
                f"frequency); bubble {base['bubble_frac']:.1%}->"
                f"{simple['bubble_frac']:.1%}; emulation TPOT p50 "
                f"{base['tpot_p50_ms']:.1f}->{simple['tpot_p50_ms']:.1f}ms")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--validate", action="store_true",
                    help="cross-check pipeline_sim's analytic cycle "
                         "predictions against measured cycles")
    args = ap.parse_args()
    if args.validate:
        validate(2, 4, emit)
        validate(4, 8, emit)
    else:
        run(emit)
