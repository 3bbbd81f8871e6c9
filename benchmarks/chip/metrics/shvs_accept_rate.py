"""Share of rows whose token came from SHVS's hot-vocabulary fast path, in
%, over the decode steps in which every slot was active. The engine's
``StepRecord.accept_rate`` averages over every slot of the batch, active or
not, so only a full step's rate is a rate over requests."""
import math


def read(r):
    full = [rec.accept_rate for rec in r.records
            if rec.batch == r.batch and math.isfinite(rec.accept_rate)]
    return 100.0 * sum(full) / len(full) if full else None
