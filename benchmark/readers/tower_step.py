"""The tower's training step, from the device trace: the ``tower_step``
programs on the ``XLA Modules`` line and, inside them, the ``XLA Ops`` by the
``jax.named_scope`` they were traced under.  A trace names an op by its HLO
instruction and carries no scope: the program's ``op_scopes`` event (the
driver hands it on as ``ctx.counters['op_scopes']``) maps instruction ->
scope; ``also`` adds the instructions XLA renames on the way (its ragged-dot
kernels lose their ``op_name``).  Without the map every ``what`` but ``mfu``
returns None.

``what: mfu`` — model operations of a step (``benchmark/costs_tower.py``: the
parameters a position really uses, allowed score pairs only, no
recomputation) over the chip's peak, over the step program's device time.
``what: share`` — the scopes' device time as a share of the step's, %.
``what: roofline`` — ``cost`` (a function of ``costs_tower``) of one layer x
layers: the larger of operations / peak and bytes / peak over the scopes'
device time a step.  Recomputed forward passes are in the time and not in
the operations, so a share stays under what the kernels alone would read.
"""

import re

from .. import costs, costs_tower
from ..trace import WRAPPERS, short_name, total, union


def _steps(summary, pattern):
    plane = summary.planes[0]
    steps = sorted((s, e) for _, _, s, e in summary.module_events(pattern, plane))
    if not steps:
        return plane, []
    durs = sorted(e - s for s, e in steps)
    return plane, [(s, e) for s, e in steps if e - s >= 0.5 * durs[len(durs) // 2]]   # not cut by the trace's edge


def _scope_ns(summary, plane, steps, names, also):
    """Device ns of the ops called ``names`` (or matching ``also``) inside the steps."""
    rx = re.compile(also) if also else None
    hits, j = [], 0
    for name, s, e in sorted(summary.ops[plane], key=lambda o: o[1]):
        while j < len(steps) and steps[j][1] < s:
            j += 1
        if j == len(steps):
            break
        sn = short_name(name)
        if s >= steps[j][0] and e <= steps[j][1] and not WRAPPERS.match(sn) and \
                (sn in names or (rx is not None and rx.search(sn))):
            hits.append((s, e))
    return total(union(hits))


def read(summary, ctx, what, pattern="tower_step", scopes=(), also=None, cost=None):
    p = ctx.counters.get("params")
    if summary is None or not summary.planes or not p or "cfg" not in p:
        return None
    plane, steps = _steps(summary, pattern)
    if not steps:
        return None
    step_ns = sum(e - s for s, e in steps) / len(steps)
    peaks = costs.peaks_for(ctx.device_kind)
    if what == "mfu":
        flops = costs_tower.step_model_flops(p["cfg"], p["rows"], p["seq"], p["block"],
                                             p["pairs_per_layer"])
        ctx.say(f"tower_step: {len(steps)} steps, {step_ns / 1e6:.2f} ms of device time a step, "
                f"{flops / 1e12:.3f} model TFLOP a step")
        return 100.0 * flops / peaks["flops_per_s"] / (step_ns / 1e9)
    table = ctx.counters.get("op_scopes")
    if not table:
        return None
    names = set(n for s in scopes for n in table.get(s, ()))
    scope_ns = _scope_ns(summary, plane, steps, names, also) / len(steps)
    if scope_ns <= 0:
        return None
    if what == "share":
        return 100.0 * scope_ns / step_ns
    layers = p["cfg"]["num_hidden_layers"]
    one = {"attn": lambda: costs_tower.attn_cost(p["cfg"], p["rows"], p["seq"], p["block"]),
           "experts": lambda: costs_tower.experts_cost(p["cfg"], p["pairs_per_layer"])}[cost]()
    least, bound = costs.min_seconds({k: layers * v for k, v in one.items()}, peaks)
    ctx.say(f"{cost}: {scope_ns / 1e6:.2f} ms a step in {list(scopes)}, least {least * 1e3:.2f} ms ({bound}-bound)")
    return 100.0 * least / (scope_ns / 1e9)
