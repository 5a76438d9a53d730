"""A count the driver took from the program, optionally per ``per`` (another counter)."""


def read(summary, ctx, name, per=None, scale=1.0):
    value = ctx.counters.get(name)
    if value is None:
        return None
    if per is not None:
        denom = ctx.counters.get(per)
        if not denom:
            return None
        value = value / denom
    return scale * value
