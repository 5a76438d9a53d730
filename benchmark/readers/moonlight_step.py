"""The ``deepseek_v3`` tower's training step, from the device trace: as
``readers/lfm2_step.py`` (the ``tower_step`` programs on ``XLA Modules``, the
``XLA Ops`` inside them by the ``jax.named_scope`` they were traced under,
through the program's ``op_scopes`` event) with ``benchmark/costs_mla.py``
for the operations and bytes.  A program that records no such counters or
scopes gives None.

``what: mfu`` — model operations of a step over the chip's peak, over the step
program's device time.  ``what: share`` — the scopes' device time as a share
of the step's, %.  ``what: roofline`` — ``cost`` of one layer x the layers
that have it: the larger of operations / peak and bytes / peak over the
scopes' device time a step.  ``tower/attn/full`` holds the attention kernels
alone (forward, forward again, ``dq``, ``dk``/``dv``), whose q and k run at 256
lanes for 192 channels: the zero lanes are in the time, not in the
operations.  XLA's ``ragged-dot`` kernels lose their ``op_name`` and are added
by name (``also``).  Recomputed forward passes are in the time and not in the
operations or bytes: a share reads low, never high.
"""

from .. import costs, costs_mla
from .tower_step import _scope_ns, _steps


def read(summary, ctx, what, pattern="tower_step", scopes=(), also=None, cost=None):
    p = ctx.counters.get("params")
    if summary is None or not summary.planes or not p or p.get("tower") != "deepseek_v3":
        return None
    plane, steps = _steps(summary, pattern)
    if not steps:
        return None
    cfg, seqs, seq = p["cfg"], p["sequences"], p["seq"]
    step_ns = sum(e - s for s, e in steps) / len(steps)
    peaks = costs.peaks_for(ctx.device_kind)
    if what == "mfu":
        flops = costs_mla.step_model_flops(cfg, seqs, seq, p["pairs_per_layer"])
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
    one, layers = {
        "attn": lambda: (costs_mla.attn_cost(cfg, seqs, seq), cfg["num_hidden_layers"]),
        "experts": lambda: (costs_mla.experts_cost(cfg, p["pairs_per_layer"]),
                            cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])}[cost]()
    least, bound = costs.min_seconds({k: layers * v for k, v in one.items()}, peaks)
    ctx.say(f"{cost}: {scope_ns / 1e6:.2f} ms a step in {list(scopes)}, least {least * 1e3:.3f} ms "
            f"({bound}-bound) over {layers} layers")
    return 100.0 * least / (scope_ns / 1e9)
