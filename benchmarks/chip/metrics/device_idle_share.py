"""Share of the traced window in which no op ran on the device, in %."""
from benchmarks.chip import trace


def read(r):
    w = trace.window_s(r.red)
    if not r.red["devices"] or w <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(r.red) / w)
