"""Model FLOPs of every prompt and output token processed in the traced
window (``cost.prefill_flops``, ``cost.decode_token_flops`` at each row's
real context) over the window times the chip's peak, in %."""
from benchmarks.chip import cost, trace


def read(r):
    flops = sum(sum(cost.decode_token_flops(r.cfg, c) for c in s.decode_ctx)
                + sum(cost.prefill_flops(r.cfg, p) for p in s.admitted_prompts)
                for s in r.steps)
    w = trace.window_s(r.red)
    if not flops or w <= 0 or r.peaks is None:
        return None
    return 100.0 * flops / (w * r.peaks.flops_per_s)
