"""Device time of the prefill and prompt-chunk programs per 1000 prompt
tokens admitted in the traced window, in ms."""
from benchmarks.chip import trace


def read(r):
    t = sum(trace.module_calls(r.red, "jit__prefill_impl")
            + trace.module_calls(r.red, "jit__chunk_impl"))
    tokens = sum(sum(s.admitted_prompts) for s in r.steps)
    return 1e3 * t / (tokens / 1e3) if t > 0 and tokens else None
