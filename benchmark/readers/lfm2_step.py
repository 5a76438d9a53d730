"""The ``lfm2_moe`` tower's training step, from the device trace: as
``readers/afmoe_step.py`` (the ``tower_step`` programs on ``XLA Modules``, the
``XLA Ops`` inside them by the ``jax.named_scope`` they were traced under,
through the program's ``op_scopes`` event) with ``benchmark/costs_lfm2.py``
for the operations and bytes.  A program that records no such counters or
scopes gives None.

``what: mfu`` — model operations of a step over the chip's peak, over the step
program's device time.  ``what: share`` — the scopes' device time as a share
of the step's, %.  ``what: roofline`` — ``cost`` of one layer x the layers of
that kind: the larger of operations / peak and bytes / peak over the scopes'
device time a step.  ``tower/conv/mix`` holds the convolution's gates and
taps, forward, forward again and backward; ``tower/attn/full`` the attention
kernels alone; XLA's ``ragged-dot`` kernels lose their ``op_name`` and are
added by name (``also``).  Recomputed forward passes are in the time and not
in the operations or bytes: a share reads low, never high.
"""

from .. import costs, costs_lfm2
from .tower_step import _scope_ns, _steps


def read(summary, ctx, what, pattern="tower_step", scopes=(), also=None, cost=None):
    p = ctx.counters.get("params")
    if summary is None or not summary.planes or not p or p.get("tower") != "lfm2_moe":
        return None
    plane, steps = _steps(summary, pattern)
    if not steps:
        return None
    cfg, seqs, seq = p["cfg"], p["sequences"], p["seq"]
    step_ns = sum(e - s for s, e in steps) / len(steps)
    peaks = costs.peaks_for(ctx.device_kind)
    if what == "mfu":
        flops = costs_lfm2.step_model_flops(cfg, seqs, seq, p["pairs_per_layer"])
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
    kinds = cfg["layer_types"]
    one, layers = {
        "conv_mix": lambda: (costs_lfm2.conv_mix_cost(cfg, seqs, seq), kinds.count(costs_lfm2.KINDS[0])),
        "attn": lambda: (costs_lfm2.attn_cost(cfg, seqs, seq), kinds.count(costs_lfm2.KINDS[1])),
        "experts": lambda: (costs_lfm2.experts_cost(cfg, p["pairs_per_layer"]),
                            cfg["num_hidden_layers"] - cfg["num_dense_layers"])}[cost]()
    least, bound = costs.min_seconds({k: layers * v for k, v in one.items()}, peaks)
    ctx.say(f"{cost}: {scope_ns / 1e6:.2f} ms a step in {list(scopes)}, least {least * 1e3:.3f} ms "
            f"({bound}-bound) over {layers} layers")
    return 100.0 * least / (scope_ns / 1e9)
