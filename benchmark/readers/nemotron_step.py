"""The ``nemotron_h`` tower's training step, from the device trace: as
``readers/tower_step.py`` (the ``tower_step`` programs on ``XLA Modules``, the
``XLA Ops`` inside them by the ``jax.named_scope`` they were traced under,
through the program's ``op_scopes`` event) with ``benchmark/costs_nemotron.py``
for the operations and bytes.  A program that records no such counters or
scopes gives None.

``what: mfu`` — model operations of a step over the chip's peak, over the step
program's device time.  ``what: share`` — the scopes' device time as a share
of the step's, %.  ``what: roofline`` — ``cost`` of one layer x the trunk's
layers of that kind: the larger of operations / peak and bytes / peak over the
scopes' device time a step.  The MTP module's own layers are traced under
``tower/mtp``, which takes their ops, so a kernel's scope holds the trunk's
layers only — except XLA's ``ragged-dot`` kernels, which lose their
``op_name`` and are added by name (``also``), the MTP layer's among them: their
time is in ``latent_experts_roofline`` and their operations are not, so that
share reads low, never high.  Recomputed forward passes are in the time and
not in the operations.
"""

from .. import costs, costs_nemotron
from .tower_step import _scope_ns, _steps


def read(summary, ctx, what, pattern="tower_step", scopes=(), also=None, cost=None):
    p = ctx.counters.get("params")
    if summary is None or not summary.planes or not p or p.get("tower") != "nemotron_h":
        return None
    plane, steps = _steps(summary, pattern)
    if not steps:
        return None
    cfg, rows, seq = p["cfg"], p["rows"], p["seq"]
    step_ns = sum(e - s for s, e in steps) / len(steps)
    peaks = costs.peaks_for(ctx.device_kind)
    if what == "mfu":
        flops = costs_nemotron.step_model_flops(cfg, rows, seq, p["pairs_per_layer"])
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
    pattern_ = cfg["hybrid_override_pattern"]
    one, layers = {
        "scan": lambda: (costs_nemotron.scan_cost(cfg, rows * (seq - 1)), pattern_.count("M")),
        "attn": lambda: (costs_nemotron.attn_cost(cfg, rows, seq - 1), pattern_.count("*")),
        "experts": lambda: (costs_nemotron.experts_cost(cfg, p["pairs_per_layer"]),
                            pattern_.count("E"))}[cost]()
    least, bound = costs.min_seconds({k: layers * v for k, v in one.items()}, peaks)
    ctx.say(f"{cost}: {scope_ns / 1e6:.2f} ms a step in {list(scopes)}, least {least * 1e3:.3f} ms "
            f"({bound}-bound) over {layers} layers")
    return 100.0 * least / (scope_ns / 1e9)
