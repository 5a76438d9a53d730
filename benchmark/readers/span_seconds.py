"""Seconds the program spent in the spans called ``names`` (their durations
summed; they do not nest in one another), per span called ``per``: the step's
root span, one a job."""

from .. import spans as S


def read(summary, ctx, names, per):
    spans = S.of(ctx)
    jobs = len(S.named(spans, per))
    found = S.named(spans, *names)
    if not jobs or not found:
        return None
    return sum(s.end_ns - s.start_ns for s in found) / 1e9 / jobs
