"""Host time of an epoch, ms: over the program's ``span`` spans (one an
epoch), the mean of duration less the device-busy time inside the span.  The
inside counterpart of ``nn_epoch_gap_ms``, which sees the same loop from the
device's programs — but from the first program on, where this one counts a
job's first epoch whole: the programs built anew, and the wait for the plane
still on its way up.  An earlier line gives the first epoch and the median of
the others apart."""

import statistics

from .. import spans as S


def read(summary, ctx, span):
    epochs = S.named(S.of(ctx), span)
    if summary is None or not summary.planes or not epochs:
        return None
    host = [((s.end_ns - s.start_ns) - S.busy_inside(summary, s.start_ns, s.end_ns)) / 1e6
            for s in epochs]
    ctx.say(f"{span}: {len(host)} spans, host ms of the first {host[0]:.2f}, median of the "
            f"others {statistics.median(host[1:] or host):.2f}")
    return sum(host) / len(host)
