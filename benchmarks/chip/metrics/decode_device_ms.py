"""Device time of one execution of the decode program (forward and the
fused decision plane), mean over the traced window, in ms."""
from benchmarks.chip import trace


def read(r):
    calls = trace.module_calls(r.red, "jit__decode_impl")
    return 1e3 * sum(calls) / len(calls) if calls else None
