"""The program's own spans, read from the traced job's ``.xplane.pb``.

Since telemetry schema 15 every live span of ``shifu_tpu.obs`` is also a
``shifu:<name>`` annotation on the profiler's ``/host:CPU`` plane, with the
span's ``id``, its ``parent`` and its numeric attrs as stats: the same
clock as the device's ``XLA Ops`` and ``XLA Modules`` lines, so a reader can
lay host phases over device time without moving either.  ``benchmark/
trace.py`` keeps only its own ``bench:`` markers; this module opens the file
under ``ctx.work/trace`` itself.

An ``xla.build`` annotation is a zero-length marker written when jax ended
a trace, a lowering or a backend build; it carries ``secs`` and stands for
the interval [marker - secs, marker].

A program that writes no such annotation (a commit before schema 15) gives
an empty list, and every reader built on it returns None.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from .trace import clip, subtract, total, union

PREFIX = "shifu:"
BUILD = "xla.build"
Interval = Tuple[float, float]


class Span(NamedTuple):
    name: str                   # without the prefix
    start_ns: float
    end_ns: float
    id: Optional[int]
    parent: Optional[int]
    attrs: dict                 # the other stats


def from_event(name: str, start_ns: float, dur_ns: float, stats: dict) -> Span:
    """One ``shifu:`` annotation as a span; a build marker gets its interval back."""
    stats = dict(stats)
    sid, parent = stats.pop("id", None), stats.pop("parent", None)
    name = name[len(PREFIX):]
    end = start_ns + dur_ns
    if name == BUILD:
        start_ns, end = start_ns - float(stats.get("secs", 0.0)) * 1e9, start_ns
    return Span(name, float(start_ns), float(end), sid, parent, stats)


def extract(xplane_path: str) -> List[Span]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(from_event(ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats)))
    return sorted(out, key=lambda s: (s.start_ns, -s.end_ns))


def of(ctx) -> List[Span]:
    """The traced job's program spans, read once a run (a test sets
    ``ctx.program_spans`` itself)."""
    spans = getattr(ctx, "program_spans", None)
    if spans is None:
        files = sorted(glob.glob(os.path.join(ctx.work, "trace", "**", "*.xplane.pb"),
                                 recursive=True))
        spans = ctx.program_spans = extract(files[-1]) if files else []
    return spans


def named(spans: Iterable[Span], *names: str) -> List[Span]:
    return [s for s in spans if s.name in names]


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that merged ``b`` covers."""
    return subtract(a, subtract(a, b))


def self_intervals(spans: List[Span]) -> List[Tuple[Span, List[Interval]]]:
    """Each span with its own time: its interval less its children's."""
    kids: Dict[int, List[Interval]] = defaultdict(list)
    for s in spans:
        if s.parent is not None:
            kids[s.parent].append((s.start_ns, s.end_ns))
    return [(s, subtract([(s.start_ns, s.end_ns)], union(kids.get(s.id, [])))) for s in spans]


def descendants(spans: List[Span], root: Span) -> List[Span]:
    """``root`` and every span below it."""
    kids: Dict[int, List[Span]] = defaultdict(list)
    for s in spans:
        if s.parent is not None:
            kids[s.parent].append(s)
    out, todo = [], [root]
    while todo:
        s = todo.pop()
        out.append(s)
        todo.extend(kids.get(s.id, []))
    return out


def seconds_by_name(spans: List[Span], within: List[Interval]) -> List[Tuple[str, float]]:
    """[name, seconds] of the spans' own time inside ``within`` (merged),
    largest first: which span the host was innermost in, and for how long.
    Spans of one name are united first: jax reports the traces inside a
    trace as builds of their own, beside the outer one."""
    own: Dict[str, List[Interval]] = defaultdict(list)
    for s, mine in self_intervals(spans):
        own[s.name].extend(mine)
    acc = {name: total(intersect(union(iv), within)) / 1e9 for name, iv in own.items()}
    return sorted(((n, t) for n, t in acc.items() if t > 0), key=lambda kv: -kv[1])


def busy_inside(summary, lo: float, hi: float) -> float:
    """Device-busy ns inside [lo, hi], mean over chips."""
    per = [total(clip(summary.busy[p], lo, hi)) for p in summary.planes]
    return sum(per) / max(len(per), 1)
