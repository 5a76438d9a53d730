"""Span tracer — nested wall-clock spans with a thread-safe collector.

The in-process analogue of the reference's per-step wall-clock log lines
and MR job counters: every pipeline step runs under a root span, phases
and trainer epochs nest inside it, and the whole trace lands as JSONL
under ``<modelset>/telemetry/`` for ``analysis --telemetry`` to render.

JSONL schema (``SCHEMA_VERSION``) — one JSON object per line, keyed by
``kind``:

- ``meta``:   ``{kind, schema_version, step, ts, pid}`` — opens a flush
  block (one per step run / bench flush);
- ``span``:   ``{kind, name, id, parent, ts, dur_s, attrs}`` — ``parent``
  is the enclosing span's ``id`` (``null`` for roots); ``ts`` is epoch
  seconds at entry; durations come from ``time.perf_counter``;
- ``event``:  ``{kind, name, ts, parent, attrs}`` — a point-in-time
  record (per-epoch trainer metrics, early stops, profile captures);
- ``metric``: one registry instrument snapshot (see
  :mod:`shifu_tpu.obs.registry`).

One clock: a live :class:`Span` also holds a
``jax.profiler.TraceAnnotation`` named ``shifu:<name>`` for its lifetime
(stats: ``id``, ``parent``, the numeric attrs).  Inside a ``jax.profiler``
session (``--profile``) the span therefore lies in the ``.xplane.pb`` on
``/host:CPU``, on the clock of the device's ``XLA Ops`` / ``XLA Modules``
lines; outside a session an annotation costs an atomic load.

Zero-cost when disabled: :func:`span` returns a shared no-op singleton
(one function call + one branch per call site) and builds no annotation,
:func:`event` returns immediately.
"""

from __future__ import annotations

import json
import numbers
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional

# v2: ingest instrumentation (ingest.bytes_read / windows_emitted /
# h2d_wait_seconds / disk_passes / spill_hits / spill_misses counters;
# the report's "ingest stall fraction" line derives from them)
# v3: variable-selection plane instrumentation (varsel.host_syncs /
# mask_batches / windows counters, varsel.rows_per_sec / candidates
# gauges; bench varsel_* extras ride the same version)
# v4: disk-tail super-batch instrumentation (train.tail_sweeps /
# tail_repairs / tail_repair_levels counters; the report's tail-plane
# "tail sweeps" + ingest-stall lines and bench tail_* extras —
# disk_passes / bytes_read per tree, dual-schedule rates — derive
# from them)
# v5: observability plane v2 — span/event records carry ``tid`` (the
# recording thread's name: ingest-prep spans land on their own timeline
# track), live-span registry for heartbeats (obs/health), ingest.window_
# prep / ingest.h2d_wait spans, drift.* gauges (streaming PSI monitor),
# OpenMetrics snapshot names derive from the same registry records
# v6: device cost-attribution plane — ``{"kind": "cost"}`` records per
# named executable (flops / bytes_accessed / memory / compiles /
# launches, keyed by abstract input signature; obs/costs), the flush
# meta carries ``backend`` (platform + device_kind, resolving the peak
# table for the utilization report), xla.recompiles / xla.launches and
# ingest.rows_padded counters, timeline span args annotated with
# flops/bytes
# v7: online serving plane — serve.* instruments (requests / batches /
# rows_padded / flush_full / flush_deadline / request_errors / swaps
# counters, queue_depth / bucket_occupancy gauges, batch_latency_ms
# histogram) and the per-bucket ``serve.score.<key>.g<gen>.b<bucket>``
# cost records the AOT scorer registers (the recompile sentinel's
# serving beat)
# v8: request/SLO observability plane — sampled ``serve.request`` /
# ``serve.batch`` span records (tid ``shifu-serve``: per-request
# queue/deadline/pad/launch/device decomposition, batch spans linking
# member trace ids — the timeline's shifu-serve track), histogram
# metric records carry ``p50``/``p99`` (fixed-bin log sketch, also the
# metrics.prom quantile lines), ``slo.*`` gauges + the
# ``serve.trace_sampled`` counter, SERVE heartbeats may carry
# ``queue_depth`` / ``queue_buildup`` / ``slo`` extras, and monitor /
# timeline learn multi-dir (cross-process) aggregation
# v9: roofline speed round — ``serve.bucket_occupancy`` is a HISTOGRAM
# (was a last-batch gauge; p50/p99 quantile lines land in metrics.prom),
# ``serve.bucket_rungs_added`` counter (occupancy-driven ladder
# refinement), ``pallas.tree_traverse`` analytic cost records (the
# quantized uint8 traversal kernel is opaque to XLA cost analysis), and
# the bench emits ``nn_train_mixed_*`` / ``serve_quantized_*`` extras
# (mixed-precision ladder + quantized serving scorer)
# v10: elastic multi-controller plane — ``dcn.*`` instruments
# (connect_retries / steps_closed / step_timeouts / step_wait_seconds /
# late_applied / late_dropped / catchup_steps / rejoins counters,
# membership_epoch / live_members gauges), the ``dcn.step`` span, the
# monitor's ``quorum_lost`` summary field (aggregate + single-dir), and
# the bench's ``multihost_*`` extras (1→2→4 scaling + time-to-recover)
# v11: model-quality observability plane — sampled score-log segments
# under ``telemetry/scorelog/`` (``scorelog.*`` counters), the
# delayed-label join (``quality.outcomes`` / ``quality.outcomes_late``),
# the ``telemetry/posttrain.json`` training-time score snapshot eval
# persists, the ``telemetry/quality.json`` live-quality table
# (``quality.*`` gauges: per-generation live AUC / ECE / score-PSI),
# SERVE heartbeats may carry a ``quality`` extra, the refresh
# controller's third trigger source (``source: "quality"``), and the
# bench's ``--plane quality`` extras (``serve_scorelog_qps_frac`` +
# ``quality_label_flip_detect_s``, the lower-is-better ``*_detect_s``
# compare class)
# v12: raw-record serving + fleet — ``serve.raw_requests`` /
# ``serve.raw_rows`` / ``serve.raw_rejects`` counters (the fused
# transform's ingest beat: per-record coded rejection, never the
# batch), per-bucket ``serve.score.<key>.raw.b<bucket>`` cost records
# (the raw family of AOT executables under the same recompile
# sentinel), ``serve.fleet_replicas_up`` gauge + ``serve.fleet_drains``
# / ``serve.fleet_requeues`` / ``serve.fleet_swaps`` counters (the
# router's balancing/death/coordinated-swap beat), fleet worker
# heartbeats ride proc ``serve-<key>-<replica>``, and the bench's
# ``serve_raw_qps_frac`` + ``--plane fleet`` extras
# v13: overload protection — ``serve.shed_overload`` /
# ``serve.shed_expired`` / ``serve.cancelled`` counters (every shed is
# a coded fast-fail, never a silent drop), ``serve.mode`` gauge +
# ``serve.brownouts`` counter (brownout degradation, also a SERVE
# heartbeat ``mode`` extra and the monitor's ``<< BROWNOUT`` flag),
# ``serve.fleet_hedges`` / ``serve.fleet_breaker_opens`` /
# ``serve.fleet_retry_denied`` counters (the router's hedged-dispatch /
# circuit-breaker / retry-budget beat), SLO summaries carry a ``shed``
# total OUTSIDE availability burn, and the bench's ``--plane overload``
# extras (``serve_overload_goodput`` tracked via the new ``*_goodput``
# throughput suffix, ``serve_overload_p99_ms``, shed fractions)
# v14: one-parse offline pipeline — ``rawcache.hits`` / ``rawcache.
# misses`` / ``rawcache.bytes_written`` counters (the columnar raw-parse
# cache shared across stats/norm/eval), the ``ingest.parse_stall_frac``
# gauge (parse-pool consumer stall; the report's parse-stall line),
# ``ingest.disk_passes`` now also counts raw string-plane traversals
# (``DataSource.iter_chunks``) so the cold-vs-cached e2e delta is
# telemetry-backed, and the bench's ``--plane ingest`` extras
# (``stats_throughput`` / ``norm_throughput`` serial-vs-pooled) +
# ``pipeline_e2e_wall_s`` / ``pipeline_e2e_disk_passes`` on ``--plane
# e2e``
# v15: spans on the profiler's clock (``shifu:`` TraceAnnotations), the
# NN train job's load-path spans (``data.*``, ``train.split``, ``nn.*``)
# and ``xla.build`` records
SCHEMA_VERSION = 15

# the prefix of every annotation the program writes into a profiler
# session (``bench:`` belongs to the benchmark's own markers)
ANNOTATION_PREFIX = "shifu:"

_TRUE = ("1", "true", "on", "yes")

# tri-state enable: explicit set_enabled() override > cached env/property
# lookup.  The cache keeps enabled() at one global read + branch on the
# hot path; reset_for_tests()/set_enabled(None) clears it.
_enabled_override: Optional[bool] = None
_enabled_cache: Optional[bool] = None


def _truthy(v: Optional[str]) -> bool:
    return v is not None and str(v).strip().lower() in _TRUE


def _lookup(env_key: str, *prop_keys: str) -> bool:
    v = os.environ.get(env_key)
    if v is None:
        from ..config import environment
        for k in prop_keys:
            v = environment.get_property(k)
            if v is not None:
                break
    return _truthy(v)


def enabled() -> bool:
    """Is telemetry on?  env ``SHIFU_TPU_TELEMETRY`` / property
    ``shifu.telemetry`` / :func:`set_enabled` (CLI ``--telemetry``)."""
    if _enabled_override is not None:
        return _enabled_override
    global _enabled_cache
    if _enabled_cache is None:
        _enabled_cache = _lookup("SHIFU_TPU_TELEMETRY",
                                 "shifu.telemetry", "shifu.tpu.telemetry")
    return _enabled_cache


def set_enabled(value: Optional[bool]) -> None:
    """Programmatic override (CLI flag, tests); ``None`` restores the
    env/property lookup."""
    global _enabled_override, _enabled_cache
    _enabled_override = value
    _enabled_cache = None


# ------------------------------------------------------------- collector
class _Collector:
    """Thread-safe record buffer + per-thread span stack."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._records: List[Dict[str, Any]] = []
        self._tls = threading.local()
        self._next_id = 0
        # id -> (name, thread name, entry ts) for spans currently OPEN —
        # the heartbeat thread (obs/health) reads this to report what
        # each thread is doing *right now*, between record flushes
        self._live: Dict[int, tuple] = {}

    def new_id(self) -> int:
        with self._lock:
            self._next_id += 1
            return self._next_id

    @property
    def stack(self) -> List[int]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_parent(self) -> Optional[int]:
        st = self.stack
        return st[-1] if st else None

    def add(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            self._records.append(rec)

    def span_opened(self, span_id: int, name: str, ts: float) -> None:
        with self._lock:
            self._live[span_id] = (name, threading.current_thread().name,
                                   ts)

    def span_closed(self, span_id: int) -> None:
        with self._lock:
            self._live.pop(span_id, None)

    def live_spans(self) -> List[Dict[str, Any]]:
        """Currently-open spans, oldest first (heartbeat surface)."""
        with self._lock:
            return [{"id": i, "name": n, "thread": t, "ts": ts}
                    for i, (n, t, ts) in sorted(self._live.items())]

    def drain(self) -> List[Dict[str, Any]]:
        with self._lock:
            out, self._records = self._records, []
            return out

    def peek(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._records)

    def clear(self) -> None:
        with self._lock:
            self._records.clear()
            self._live.clear()
        self._tls = threading.local()


_collector = _Collector()


def _stats(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """The attrs an annotation can carry: numbers only (a string could
    hold the separators of the annotation's own ``name#k=v,...#`` form)."""
    return {k: v for k, v in attrs.items() if isinstance(v, numbers.Real)}


def _annotation(name: str, span_id: int, parent: Optional[int],
                stats: Dict[str, Any]):
    """The ``shifu:<name>`` annotation of one span; ``stats`` is owned."""
    from jax.profiler import TraceAnnotation
    stats["id"] = span_id
    if parent is not None:
        stats["parent"] = parent
    return TraceAnnotation(ANNOTATION_PREFIX + name, **stats)


class Span:
    """A live span; use via ``with span("name", k=v) as sp:``.  Extra
    attributes attach with :meth:`set`.  Entered and left on ONE thread:
    the annotation it holds is that thread's."""

    __slots__ = ("name", "attrs", "id", "parent", "_ts", "_t0", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.id = _collector.new_id()
        self.parent: Optional[int] = None
        self._ts = 0.0
        self._t0 = 0.0
        self._ann = None

    def under(self, parent) -> "Span":
        """Name ``parent`` (a span, live on another thread) as this span's
        parent: the stack that links spans is a thread's own."""
        self.parent = parent.id
        return self

    def __enter__(self) -> "Span":
        if self.parent is None:
            self.parent = _collector.current_parent()
        _collector.stack.append(self.id)
        self._ts = time.time()
        _collector.span_opened(self.id, self.name, self._ts)
        self._ann = _annotation(self.name, self.id, self.parent,
                                _stats(self.attrs))
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        dur = time.perf_counter() - self._t0
        st = _collector.stack
        if st and st[-1] == self.id:
            st.pop()
        _collector.span_closed(self.id)
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        _collector.add({"kind": "span", "name": self.name, "id": self.id,
                        "parent": self.parent, "ts": round(self._ts, 3),
                        "dur_s": round(dur, 6),
                        "tid": threading.current_thread().name,
                        "attrs": self.attrs})
        return False

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**_stats(attrs))
        return self


class _NullSpan:
    """Shared no-op span: the disabled path allocates nothing."""

    __slots__ = ()
    id = None
    parent = None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs: Any) -> "_NullSpan":
        return self

    def under(self, parent) -> "_NullSpan":
        return self


_NULL_SPAN = _NullSpan()


def span(name: str, /, **attrs: Any):
    """Open a (nested) span.  No-op singleton when telemetry is off."""
    if not enabled():
        return _NULL_SPAN
    return Span(name, attrs)


def event(name: str, /, **attrs: Any) -> None:
    """Record a point-in-time event under the current span (per-epoch
    trainer metrics, early stops, ...)."""
    if not enabled():
        return
    _collector.add({"kind": "event", "name": name,
                    "ts": round(time.time(), 3),
                    "parent": _collector.current_parent(),
                    "tid": threading.current_thread().name, "attrs": attrs})


def record_span(name: str, ts: float, dur_s: float,
                attrs: Optional[Dict[str, Any]] = None,
                tid: Optional[str] = None,
                parent: Optional[int] = None) -> Optional[int]:
    """Record an externally-timed span.  Producers whose spans start and
    end on DIFFERENT threads (the serve plane: a request enters on the
    caller's thread and completes on the batcher worker) measure with
    their own perf counters and emit the finished span here; ``tid``
    overrides the track label (e.g. ``shifu-serve``).  Returns the span
    id, or None (no allocation) when telemetry is off."""
    if not enabled():
        return None
    sid = _collector.new_id()
    _collector.add({"kind": "span", "name": name, "id": sid,
                    "parent": parent, "ts": round(float(ts), 6),
                    "dur_s": round(float(dur_s), 6),
                    "tid": tid or threading.current_thread().name,
                    "attrs": dict(attrs or {})})
    return sid


_UNSAFE = re.compile(r"[^\w.<>-]")      # kept out of an annotation's stats


def record_build(stage: str, secs: float, program: str = "") -> None:
    """One ``xla.build`` span: jax traced (``stage`` ``trace``), lowered
    (``lower``) or built (``compile``: compiled, or loaded from the
    compile cache) ``program`` in the ``secs`` that have just ended, on
    this thread.  The ``jax.monitoring`` listener learns of the work when
    it ends and an annotation cannot be back-dated, so on the profiler's
    clock this is a zero-length marker that carries ``secs``: a reader
    rebuilds the interval [end - secs, end]."""
    if not enabled():
        return
    parent = _collector.current_parent()
    sid = record_span("xla.build", time.time() - secs, secs,
                      {"stage": stage, "program": program}, parent=parent)
    stats: Dict[str, Any] = {"secs": secs, "stage": stage}
    if program:
        stats["program"] = _UNSAFE.sub("_", program)
    with _annotation("xla.build", sid, parent, stats):
        pass


def pending_records() -> List[Dict[str, Any]]:
    """Snapshot of not-yet-flushed records (tests, bench)."""
    return _collector.peek()


def live_spans() -> List[Dict[str, Any]]:
    """Spans currently open across ALL threads (the heartbeat's 'what is
    this process doing right now' surface).  Empty when disabled."""
    if not enabled():
        return []
    return _collector.live_spans()


def flush(path: str, step: Optional[str] = None,
          extra_meta: Optional[Dict[str, Any]] = None) -> bool:
    """Append the buffered spans/events plus a registry snapshot to
    ``path`` as one JSONL block opened by a ``meta`` line, then clear
    both.  Returns False (and writes nothing) when telemetry is off."""
    if not enabled():
        return False
    from . import costs, registry
    records = _collector.drain()
    metrics = registry.snapshot(reset=True)
    cost_recs = costs.cost_snapshot(reset=True)
    meta: Dict[str, Any] = {"kind": "meta", "schema_version": SCHEMA_VERSION,
                            "step": step, "ts": round(time.time(), 3),
                            "pid": os.getpid(),
                            "backend": costs.backend_info()}
    if extra_meta:
        meta.update(extra_meta)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    # append-only trace sink BY DESIGN: each flush appends a block;
    # every reader (report/timeline) skips a torn final line
    with open(path, "a") as f:  # shifu-lint: disable=atomic-write
        for rec in [meta] + records + metrics + cost_recs:
            f.write(json.dumps(rec) + "\n")
    return True


def reset_for_tests() -> None:
    from . import costs
    from .registry import get_registry
    set_enabled(None)
    _collector.clear()
    get_registry().reset()
    costs.reset_for_tests()
