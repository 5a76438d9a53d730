"""From a profiler trace to numbers: capture, extraction, reduction.

The reduction works on plain events ``(plane, line, name, start_ns,
dur_ns)`` so that it can be tested on a recorded fixture
(``tests/fixtures/*.tsv``) without a chip.  Pitfalls of the TPU's trace,
all seen in real files: the ``XLA Ops`` line holds an enclosing ``%while``
*and* its body ops (durations sum to twice the window), ``Async XLA Ops``
overlap compute, and the ``XLA Modules`` line spans whole programs.  So
busy time is a **union of intervals**, never a sum; the per-op table leaves
out control-flow wrappers; async ops are kept apart.
"""

from __future__ import annotations

import glob
import os
import re
import threading
import time
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, str, str, float, float]      # plane, line, name, start_ns, dur_ns

OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
WRAPPERS = re.compile(r"^%?(while|conditional|call)[.\d]*\b")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
BENCH_SPAN = "bench:"


# ------------------------------------------------------------------ capture
class Capture:
    """``jax.profiler`` around the first ``seconds`` of a window: started by
    the caller, stopped by a timer thread (the program's own call blocks)."""

    def __init__(self, out_dir: str, seconds: float):
        self.dir, self.seconds = out_dir, seconds
        self._timer: Optional[threading.Timer] = None
        self._lock = threading.Lock()
        self._on = False
        self.host_spans: List[Tuple[str, float, float]] = []   # name, perf_counter start, end

    def start(self) -> None:
        import jax
        os.makedirs(self.dir, exist_ok=True)
        # device and TraceMe events only: the python tracer slows the host
        # it is meant to observe and fills the file
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._on, self._t0 = True, time.perf_counter()
        with jax.profiler.TraceAnnotation(BENCH_SPAN + "trace_start"):
            pass
        self._timer = threading.Timer(self.seconds, self.stop)
        self._timer.daemon = True
        self._timer.start()

    def stop(self) -> None:
        import jax
        with self._lock:
            if not self._on:
                return
            self._on = False
            with jax.profiler.TraceAnnotation(BENCH_SPAN + "trace_stop"):
                pass
            jax.profiler.stop_trace()
        if self._timer is not None and threading.current_thread() is not self._timer:
            self._timer.cancel()

    def reduce(self) -> Optional["Summary"]:
        self.stop()
        files = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True))
        if not files:
            return None
        events = list(extract(files[-1]))
        # the caller's own spans, timed by perf_counter, moved onto the
        # profiler's clock through the start marker (a span still open when
        # the trace stops would otherwise be lost)
        mark = next((st for _, _, n, st, _ in events if n == BENCH_SPAN + "trace_start"), None)
        if mark is not None:
            for name, a, b in self.host_spans:
                events.append(("/host:CPU", "bench", name, mark + (a - self._t0) * 1e9,
                               (b - a) * 1e9))
        return Summary(events)


def extract(xplane_path: str) -> Iterable[Event]:
    """Device lines and the host's python lines of an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        host = plane.name == "/host:CPU"
        if not device and not host:
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE, ASYNC_LINE):
                continue
            for ev in line.events:
                if host and not ev.name.startswith(BENCH_SPAN):
                    continue
                yield (plane.name, line.name, ev.name, float(ev.start_ns), float(ev.duration_ns))


def write_tsv(events: Iterable[Event], path: str, name_limit: int = 400) -> None:
    with open(path, "w") as f:
        for plane, line, name, start, dur in events:
            name = name.replace("\t", " ").replace("\n", " ")[:name_limit]
            f.write(f"{plane}\t{line}\t{start:.0f}\t{dur:.0f}\t{name}\n")


def read_tsv(path: str) -> List[Event]:
    out = []
    with open(path) as f:
        for row in f:
            plane, line, start, dur, name = row.rstrip("\n").split("\t", 4)
            out.append((plane, line, name, float(start), float(dur)))
    return out


# ---------------------------------------------------------------- intervals
def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Tuple[float, float]], b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(merged: List[Tuple[float, float]], lo: float, hi: float):
    """Merged intervals clipped to [lo, hi]."""
    return [(max(a, lo), min(b, hi)) for a, b in merged if b > lo and a < hi]


def short_name(hlo: str) -> str:
    """``%fusion.355 = f32[...] fusion(...)`` -> ``fusion.355``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


# ------------------------------------------------------------------ summary
class Summary:
    """One traced window, reduced.  Times in seconds unless named ``_ns``."""

    def __init__(self, events: List[Event]):
        self.ops: Dict[str, List[Tuple[str, float, float]]] = defaultdict(list)
        self.async_ops: Dict[str, List[Tuple[str, float, float]]] = defaultdict(list)
        self.modules: Dict[str, List[Tuple[str, float, float]]] = defaultdict(list)
        self.spans: List[Tuple[str, float, float]] = []
        for plane, line, name, start, dur in events:
            if DEVICE_PLANE.match(plane):
                target = {OPS_LINE: self.ops, ASYNC_LINE: self.async_ops,
                          MODULES_LINE: self.modules}.get(line)
                if target is not None:
                    target[plane].append((name, start, start + dur))
            elif name.startswith(BENCH_SPAN):
                self.spans.append((name, start, start + dur))
        self.planes = sorted(set(self.ops) | set(self.modules))
        device_times = [t for p in self.planes for _, s, e in self.ops[p] + self.modules[p]
                        for t in (s, e)]
        # the traced window: between the capture's own markers (host and
        # device share the profiler's clock), else first-to-last device op
        marks = {n: s for n, s, _ in self.spans}
        lo = marks.get(BENCH_SPAN + "trace_start", min(device_times, default=0.0))
        hi = marks.get(BENCH_SPAN + "trace_stop", max(device_times, default=0.0))
        self.spans = [(n, max(s, lo), min(e, hi)) for n, s, e in self.spans
                      if not n.startswith(BENCH_SPAN + "trace_") and s < hi and e > lo]
        self.lo_ns, self.hi_ns = lo, hi
        self.window_s = (hi - lo) / 1e9
        self.busy = {p: clip(union((s, e) for _, s, e in self.ops[p]), lo, hi)
                     for p in self.planes}
        self.busy_s = (sum(total(b) for b in self.busy.values()) / max(len(self.planes), 1)) / 1e9

    # --- per-op numbers (all chips together; callers divide by chips)
    def op_events(self, pattern: str, plane: Optional[str] = None):
        rx = re.compile(pattern)
        for p in ([plane] if plane else self.planes):
            for name, s, e in self.ops[p]:
                if rx.search(short_name(name)):
                    yield p, name, s, e

    def op_seconds(self, pattern: str) -> float:
        """Device time of the ops matching ``pattern``: union per chip,
        mean over chips."""
        per = [total(union((s, e) for _, _, s, e in self.op_events(pattern, p)))
               for p in self.planes]
        return sum(per) / max(len(per), 1) / 1e9

    def module_events(self, pattern: str, plane: Optional[str] = None):
        rx = re.compile(pattern)
        for p in ([plane] if plane else self.planes):
            for name, s, e in self.modules[p]:
                if rx.search(name):
                    yield p, name, s, e

    def module_seconds(self, pattern: str) -> float:
        per = [sum(e - s for _, _, s, e in self.module_events(pattern, p)) for p in self.planes]
        return sum(per) / max(len(per), 1) / 1e9

    def op_table(self, top: int = 10) -> List[list]:
        """[name, seconds] of the ops that took most device time (mean over
        chips), control-flow wrappers left out, instances of one op summed."""
        acc: Dict[str, float] = defaultdict(float)
        for p in self.planes:
            for name, s, e in self.ops[p]:
                sn = short_name(name)
                if not WRAPPERS.match(sn):
                    acc[sn] += (e - s)
        n = max(len(self.planes), 1)
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n / 1e9] for k, v in rows]

    def idle_gaps(self, top: int = 10) -> List[list]:
        """[what the host was in, seconds] for the longest idle gaps of the
        first chip: the benchmark's span the gap begins in and the device
        programs before and after it."""
        if not self.planes:
            return []
        p = self.planes[0]
        busy = self.busy[p]
        gaps = subtract([(self.lo_ns, self.hi_ns)], busy)
        mods = sorted(self.modules[p], key=lambda m: m[1])
        acc: Dict[str, float] = defaultdict(float)
        for s, e in gaps:
            span = next((n for n, a, b in self.spans if a <= s < b), "outside any span")
            before = next((n for n, a, b in reversed(mods) if b <= s + 1), "start")
            after = next((n for n, a, b in mods if a >= e - 1), "end")
            clean = lambda n: n.split("(")[0]
            acc[f"{span}: {clean(before)} -> {clean(after)}"] += e - s
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[k[:120], v / 1e9] for k, v in rows]

    def breakdown(self) -> dict:
        return {"device_ops": self.op_table(10), "idle_gaps": self.idle_gaps(10)}
