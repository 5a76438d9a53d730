"""Device time of the ops matching ``pattern`` as a share of busy time, %."""


def read(summary, ctx, pattern):
    if summary is None or summary.busy_s <= 0:
        return None
    t = summary.op_seconds(pattern)
    return 100.0 * t / summary.busy_s if t > 0 else None
