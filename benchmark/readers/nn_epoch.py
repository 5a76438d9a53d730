"""NN training per epoch, from the programs on the device's ``XLA Modules``
line.  ``what: device`` — device time of the step and validation programs
over the epochs traced.  ``what: gap`` — within a job (the benchmark's
``bench:job`` spans; the whole window where there is none): (end of its last
such program - start of its first) / epochs, less the device time: what the
host spends between and around an epoch's programs.  ``what: roofline`` —
forward + backward operations of the step program from the widths over the
chip's peak, over the step program's device time."""

from .. import costs


def read(summary, ctx, what, step_pattern, eval_pattern=None):
    if summary is None:
        return None
    steps = sorted((s, e) for _, _, s, e in summary.module_events(step_pattern))
    if not steps:
        return None
    # a program cut by the trace's edge is no epoch
    durs = sorted(e - s for s, e in steps)
    steps = [(s, e) for s, e in steps if e - s >= 0.5 * durs[len(durs) // 2]]
    evals = sorted((s, e) for _, _, s, e in summary.module_events(eval_pattern)) \
        if eval_pattern else []
    n_planes = max(len(summary.planes), 1)
    epochs = len(steps) / n_planes
    device_ms = sum(e - s for s, e in steps + evals) / n_planes / 1e6 / epochs
    if what == "device":
        return device_ms
    if what == "roofline":
        p = ctx.counters["params"]
        peaks = costs.peaks_for(ctx.device_kind)
        least = costs.mlp_train_flops(p["macs_per_row"], p["train_rows"]) / peaks["flops_per_s"]
        return 100.0 * least / (sum(e - s for s, e in steps) / n_planes / 1e9 / epochs)
    spans = [(a, b) for n, a, b in summary.spans if n == "bench:job"] or \
        [(summary.lo_ns, summary.hi_ns)]
    total_ns = n = 0
    for a, b in spans:
        inside = [(s, e) for s, e in steps + evals if s >= a and e <= b]
        k = sum(1 for s, e in steps if s >= a and e <= b)
        if k >= 2:
            total_ns += max(e for _, e in inside) - min(s for s, _ in inside)
            n += k / n_planes
    if n == 0:
        return None
    return total_ns / 1e6 / n - device_ms
