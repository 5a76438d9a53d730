"""Roofline share of the histogram kernel, %: for every traced launch the
least time the chip could take (``costs.hist_kernel_cost`` of the launch's
own shapes against ``peaks.json``), summed, over the kernel's device time.

The shapes are read from the launch's HLO text in the trace: the output is
``f32[C_pad, S, K, lanes]`` and the first operand ``s32[C_pad, rows]``.  The
columns and bins counted are the configuration's own (padding is no work the
algorithm needs)."""

import re

from .. import costs

OUT = re.compile(r"= f32\[(\d+),(\d+),(\d+),(\d+)\]")
ROWS = re.compile(r"custom-call\(s32\[(\d+),(\d+)\]")


def read(summary, ctx, pattern, n_feat, n_bins):
    if summary is None:
        return None
    launches = list(summary.op_events(pattern))
    if not launches:
        return None
    peaks = costs.peaks_for(ctx.device_kind)
    least = spent = 0.0
    bound = {"flops": 0, "bytes": 0}
    for _, name, s, e in launches:
        out, rows = OUT.search(name), ROWS.search(name)
        if not out or not rows:
            continue
        cost = costs.hist_kernel_cost(int(rows.group(2)), int(n_feat), int(n_bins),
                                      int(out.group(3)), n_stats=int(out.group(2)))
        t, which = costs.min_seconds(cost, peaks)
        least += t
        bound[which] += 1
        spent += (e - s) / 1e9
    if spent <= 0:
        return None
    ctx.say(f"hist_roofline: {sum(bound.values())} launches, bound by {bound}, "
            f"least {least:.4f}s of {spent:.4f}s")
    return 100.0 * least / spent
