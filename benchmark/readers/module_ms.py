"""Device time of the programs (``XLA Modules``) matching ``pattern``, in ms,
divided by how often ``per_pattern`` ran (ops or modules) over ``per_div``,
or the median duration of one launch with ``stat: median``."""

import statistics


def read(summary, ctx, pattern, per_pattern=None, per_kind="module", per_div=1.0,
         per_div_param=None, stat="mean"):
    if summary is None:
        return None
    durs = [(e - s) / 1e6 for _, _, s, e in summary.module_events(pattern)]
    if not durs:
        return None
    if stat == "median":
        return statistics.median(durs)
    if per_pattern is None:
        return sum(durs) / len(durs)
    events = summary.module_events(per_pattern) if per_kind == "module" \
        else summary.op_events(per_pattern)
    n = sum(1 for _ in events)
    if per_div_param:
        per_div = float(ctx.counters["params"][per_div_param])
    if n == 0:
        return None
    return sum(durs) / (n / per_div)
