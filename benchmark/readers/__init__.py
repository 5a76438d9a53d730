"""Per-layer metrics: one small reader per kind of source, found by the
name in the metric's ``layer_metrics/<metric>.json``.

A reader is ``read(summary, ctx, **args)``: ``summary`` is the traced
window reduced (``benchmark.trace.Summary``, or None when nothing was
traced), ``ctx.counters`` the counts the driver took from the program.  A
reader that finds nothing to read returns None and the metric is left out
of the line.
"""

import importlib


def read_metric(doc: dict, summary, ctx):
    mod = importlib.import_module("benchmark.readers." + doc["reader"])
    value = mod.read(summary, ctx, **doc.get("args", {}))
    return None if value is None else float(value)
