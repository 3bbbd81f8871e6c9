"""The decode program's share of its roofline, in %: the least time the
chip could take for a step (``cost.decode_step`` on each step's real row
lengths: weights once, each row's K/V to its own length, the logits and
penalty state once), over the measured device time of a step."""
from benchmarks.chip import cost, trace


def read(r):
    calls = trace.module_calls(r.red, "jit__decode_impl")
    steps = [s for s in r.steps if s.decode_ctx]
    if not calls or not steps or r.peaks is None:
        return None
    least = [cost.least_time(cost.decode_step(r.cfg, s.decode_ctx, r.batch),
                             r.peaks)[0] for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(calls) / len(calls))
