"""Of the traced window's device-idle time, the share (%) during which the
program's innermost open span is none, or one of ``containers`` (the step's
root and the phases that only hold other spans): idle time that no span names.
Mean over chips.  An earlier line gives the idle seconds by innermost span.

Whether the spans are enough is this number: under a tenth, and the idle share
can be read off by name."""

from .. import spans as S
from ..trace import subtract, total, union


def read(summary, ctx, containers):
    spans = S.of(ctx)
    if summary is None or not summary.planes or not spans:
        return None
    named = union((s.start_ns, s.end_ns) for s in spans if s.name not in containers)
    idle = [subtract([(summary.lo_ns, summary.hi_ns)], summary.busy[p]) for p in summary.planes]
    shares = [100.0 * total(subtract(i, named)) / total(i) for i in idle if total(i) > 0]
    if not shares:
        return None
    outside = total(subtract(idle[0], union((s.start_ns, s.end_ns) for s in spans))) / 1e9
    ctx.say(f"idle {total(idle[0]) / 1e9:.3f}s of a {summary.window_s:.3f}s window, by innermost "
            "span: " + ", ".join(f"{n} {t:.3f}" for n, t in S.seconds_by_name(spans, idle[0])[:14]) +
            f"; outside any span {outside:.3f}")
    return sum(shares) / len(shares)
