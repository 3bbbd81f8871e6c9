"""Mean rows per committed decode step (``StepRecord.batch``)."""


def read(r):
    rows = [rec.batch for rec in r.records if rec.batch > 0]
    return sum(rows) / len(rows) if rows else None
