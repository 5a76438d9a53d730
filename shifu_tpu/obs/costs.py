"""Device cost-attribution plane — FLOPs/bytes accounting per executable.

The obs plane's span tree (PR 1/7) says *where* wall-clock goes; this
module says *whether that time was well spent*: every named executable
records its XLA-estimated FLOPs and bytes accessed
(``lowered.cost_analysis()``), its compiled memory footprint
(``compiled.memory_analysis()``), and compile/launch counts, keyed by
``(name, abstract input shapes/dtypes)``.  The utilization report
(:mod:`obs.utilization`) joins these against span wall times to
report achieved FLOP/s, bytes/s, percent-of-peak and a roofline verdict
per plane — the per-op cost visibility the TF system paper ties its
performance story to.

Three entry layers:

- :func:`costed_jit` — ``jax.jit`` replacement for a NAMED entry point.
  Dispatches through its own AOT cache (``lower()`` → ``compile()`` →
  call the compiled executable), so cost capture never double-compiles;
  any AOT oddity falls back to the plain jitted path per call, so the
  wrapper can slow a run down but never break it.  When telemetry is
  disabled at wrap time it returns the BARE ``jax.jit`` result — no
  wrapper frames, no registry writes.  ``lazy=True`` is the form for
  module-scope executables: the telemetry check moves to call time (one
  branch), because module import happens before the CLI's
  ``--telemetry`` flips the switch.
- :func:`record_executable` — the lower-level hook for code that
  already holds a ``(lowered, compiled)`` pair.
- :func:`register_cost_model` / :func:`record_model_launch` — analytic
  FLOP/byte models for Pallas kernels, which XLA's cost analysis cannot
  see through (a ``pallas_call`` is an opaque custom call); the hand
  models in :mod:`shifu_tpu.ops.hist_pallas` / :mod:`shifu_tpu.ops.tree`
  register here and land in the same registry.

THE SHAPE-CHURN SENTINEL: a second *distinct* signature under one name
bumps the ``xla.recompiles`` counter and logs a warn-once per name —
silent recompiles from shape churn are exactly the hazard the
padded-bucket serving plane must stay free of.

Cost records flush into the telemetry JSONL as ``{"kind": "cost", ...}``
lines (schema v6) alongside spans and metrics, so ``analysis
--telemetry --utilization`` can join them post-hoc.
"""

from __future__ import annotations

import contextlib
import inspect
import logging
import os
import re
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import tracer

log = logging.getLogger(__name__)


# ------------------------------------------------------------ peak table
# Per-device peak compute (bf16/matmul FLOP/s) and HBM bandwidth (B/s),
# matched by substring against jax's device_kind (lowercased).  Public
# spec-sheet numbers for the TPU generations.  A device_kind that is not
# in the table has NO peak: the report says "peak unknown" and prints no
# percent-of-peak or MFU (set SHIFU_TPU_PEAK_FLOPS / SHIFU_TPU_PEAK_BW to
# supply one).
DEVICE_PEAKS: Tuple[Tuple[str, float, float], ...] = (
    ("tpu v6", 918e12, 1640e9),
    ("tpu v5p", 459e12, 2765e9),
    ("tpu v5 lite", 197e12, 819e9),
    ("tpu v5e", 197e12, 819e9),
    ("tpu v4", 275e12, 1228e9),
    ("tpu v3", 123e12, 900e9),
    ("tpu v2", 46e12, 700e9),
)
PEAK_UNKNOWN = "peak unknown"


def backend_info() -> Dict[str, str]:
    """(platform, device_kind) of local device 0 — stamped into the
    flush meta so a post-hoc report resolves the right peak row."""
    import jax
    d = jax.local_devices()[0]
    return {"platform": str(d.platform), "device_kind": str(d.device_kind)}


def resolve_peaks(backend: Optional[Dict[str, str]] = None
                  ) -> Tuple[Optional[float], Optional[float], str]:
    """(peak FLOP/s, peak B/s, provenance label) for the backend's
    ``device_kind``; ``(None, None, PEAK_UNKNOWN)`` when the table has no
    row for it.  Env overrides beat the table: ``SHIFU_TPU_PEAK_FLOPS`` /
    ``SHIFU_TPU_PEAK_BW`` (floats, per-device)."""
    backend = backend or backend_info()
    kind = str(backend.get("device_kind") or "").lower()
    flops = bw = None
    label = PEAK_UNKNOWN
    for sub, f, b in DEVICE_PEAKS:
        if sub in kind:
            flops, bw, label = f, b, sub
            break
    for env, idx in (("SHIFU_TPU_PEAK_FLOPS", 0), ("SHIFU_TPU_PEAK_BW", 1)):
        v = os.environ.get(env)
        if v:
            try:
                if idx == 0:
                    flops = float(v)
                else:
                    bw = float(v)
                label += f" +{env}"
            except ValueError:
                log.warning("ignoring unparseable %s=%r", env, v)
    return flops, bw, label


# -------------------------------------------------------------- registry
class _Entry:
    """One (name, signature) executable's accumulated accounting."""

    __slots__ = ("name", "signature", "flops", "bytes_accessed", "memory",
                 "analytic", "compiles", "launches", "total_launches")

    def __init__(self, name: str, signature: str, flops: Optional[float],
                 bytes_accessed: Optional[float],
                 memory: Optional[Dict[str, int]], analytic: bool):
        self.name = name
        self.signature = signature
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.memory = memory
        self.analytic = analytic
        self.compiles = 0          # since the last snapshot(reset=True)
        self.launches = 0          # since the last snapshot(reset=True)
        self.total_launches = 0    # process lifetime

    def to_record(self) -> Dict[str, Any]:
        rec: Dict[str, Any] = {
            "kind": "cost", "name": self.name, "signature": self.signature,
            "flops": self.flops, "bytes_accessed": self.bytes_accessed,
            "compiles": self.compiles, "launches": self.launches,
            "analytic": self.analytic,
        }
        if self.memory is not None:
            rec["memory"] = self.memory
        return rec


class CostRegistry:
    """Process-wide executable cost table; thread-safe (the streamed
    window loop launches from the main thread while the heartbeat /
    exporter threads snapshot)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, Any], _Entry] = {}
        self._seen_sigs: Dict[str, set] = {}
        self._recompile_warned: set = set()

    def record(self, name: str, key: Any, signature: str,
               flops: Optional[float], bytes_accessed: Optional[float],
               memory: Optional[Dict[str, int]],
               analytic: bool = False) -> _Entry:
        """Register a freshly-built executable (one compile) under
        ``(name, key)`` and run the recompile sentinel."""
        recompiled = False
        with self._lock:
            ent = self._entries.get((name, key))
            if ent is None:
                ent = self._entries[(name, key)] = _Entry(
                    name, signature, flops, bytes_accessed, memory,
                    analytic)
            ent.compiles += 1
            sigs = self._seen_sigs.setdefault(name, set())
            if key not in sigs:
                if sigs:                       # a PRIOR different signature
                    recompiled = True
                sigs.add(key)
            warn = recompiled and name not in self._recompile_warned
            if warn:
                self._recompile_warned.add(name)
        if recompiled:
            from . import registry
            registry.counter("xla.recompiles").inc()
            if warn:
                # warn-once per name: the first shape-churn recompile is
                # the signal; per-occurrence logs would bury it
                log.warning(
                    "executable %r recompiled for a new input signature "
                    "%s — shape churn defeats the compile cache (pad/"
                    "bucket inputs to stable shapes); further recompiles "
                    "of this executable count in xla.recompiles silently",
                    name, signature)
        return ent

    def has_entry(self, name: str, key: Any) -> bool:
        with self._lock:
            return (name, key) in self._entries

    def launch(self, name: str, key: Any) -> None:
        with self._lock:
            ent = self._entries.get((name, key))
            if ent is None:
                return
            ent.launches += 1
            ent.total_launches += 1
        from . import registry
        registry.counter("xla.launches").inc()

    def snapshot(self, reset: bool = False) -> List[Dict[str, Any]]:
        """Cost records with activity since the last reset, stable-sorted
        by (name, signature) so the trace is diff-friendly."""
        with self._lock:
            ents = [e for _, e in sorted(self._entries.items(),
                                         key=lambda kv: (kv[1].name,
                                                         kv[1].signature))
                    if e.launches or e.compiles]
            recs = [e.to_record() for e in ents]
            if reset:
                for e in ents:
                    e.launches = 0
                    e.compiles = 0
        return recs

    def entries(self) -> List[_Entry]:
        with self._lock:
            return [e for _, e in sorted(self._entries.items(),
                                         key=lambda kv: (kv[1].name,
                                                         kv[1].signature))]

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self._seen_sigs.clear()
            self._recompile_warned.clear()


_registry = CostRegistry()


def get_cost_registry() -> CostRegistry:
    return _registry


def cost_snapshot(reset: bool = False) -> List[Dict[str, Any]]:
    return _registry.snapshot(reset=reset)


def reset_for_tests() -> None:
    # the analytic-model table is NOT cleared: models register at kernel-
    # module import (like the metric manifest), not per run
    _registry.reset()


# ------------------------------------------------------------ signatures
def _leaf_sig(x: Any) -> str:
    """'f32[8,64]'-style abstract signature for one leaf (weak-typed
    python scalars keyed apart from committed arrays)."""
    import jax
    aval = jax.typeof(x)
    s = aval.str_short()
    if getattr(aval, "weak_type", False):
        s += "~"
    return s


def _split_static(fn: Callable, jit_kwargs: Dict[str, Any]
                  ) -> Tuple[set, set]:
    """(static positional indices, static kwarg names) a call must be
    partitioned by — mirrors how jax.jit resolves static_argnums /
    static_argnames against the wrapped function's signature."""
    nums = jit_kwargs.get("static_argnums") or ()
    if isinstance(nums, int):
        nums = (nums,)
    names = jit_kwargs.get("static_argnames") or ()
    if isinstance(names, str):
        names = (names,)
    idx = set(nums)
    try:
        params = list(inspect.signature(fn).parameters)
        for n in names:
            if n in params:
                idx.add(params.index(n))
    except (TypeError, ValueError):
        pass
    return idx, set(names)


def _signature(args: tuple, kwargs: dict, static_idx: set,
               static_names: set):
    """(hashable cache key, human signature string, dynamic args,
    dynamic kwargs, has_tracer) for one call."""
    import jax
    dyn_args = tuple(a for i, a in enumerate(args) if i not in static_idx)
    dyn_kwargs = {k: v for k, v in kwargs.items() if k not in static_names}
    statics = tuple(sorted(
        [(f"#{i}", repr(args[i])) for i in static_idx if i < len(args)]
        + [(k, repr(v)) for k, v in kwargs.items() if k in static_names]))
    leaves, treedef = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
    has_tracer = any(isinstance(x, jax.core.Tracer) for x in leaves)
    if has_tracer:
        return None, "", dyn_args, dyn_kwargs, True
    sigs = tuple(_leaf_sig(x) for x in leaves)
    key = (treedef, sigs, statics)
    return key, ",".join(sigs), dyn_args, dyn_kwargs, False


def _cost_numbers(lowered) -> Tuple[Optional[float], Optional[float]]:
    """(flops, bytes accessed) from ``lowered.cost_analysis()`` — shapes
    vary by backend (dict / list-of-dict / None); absent keys are None,
    never a crash."""
    try:
        ca = lowered.cost_analysis()
    except Exception:
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None, None
    flops = ca.get("flops")
    bya = ca.get("bytes accessed")
    return (float(flops) if flops is not None else None,
            float(bya) if bya is not None else None)


def _memory_numbers(compiled) -> Optional[Dict[str, int]]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out: Dict[str, int] = {}
    for field, key in (("argument_size_in_bytes", "args"),
                       ("output_size_in_bytes", "out"),
                       ("temp_size_in_bytes", "temp"),
                       ("generated_code_size_in_bytes", "code")):
        v = getattr(ma, field, None)
        if v is not None:
            out[key] = int(v)
    return out or None


def record_executable(name: str, lowered, compiled,
                      signature: Optional[str] = None,
                      key: Optional[Any] = None) -> None:
    """Lower-level hook: register an already-built ``(lowered,
    compiled)`` pair under ``name``.  Derives the abstract input
    signature from the lowering when not supplied."""
    if signature is None:
        try:
            import jax
            avals = jax.tree_util.tree_leaves(lowered.in_avals)
            signature = ",".join(a.str_short() for a in avals)
        except Exception:
            signature = "unknown"
    flops, bya = _cost_numbers(lowered)
    _registry.record(name, key if key is not None else signature,
                     signature, flops, bya, _memory_numbers(compiled))


_HLO_OP = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*metadata=\{op_name="([^"]*)"')


def op_scopes(hlo_text: str, scopes: Sequence[str]) -> Dict[str, List[str]]:
    """scope -> the HLO instructions whose ``op_name`` passes through it
    (``jax.named_scope`` puts the scope's name there).  A device trace names
    its ops by instruction and carries no ``op_name``: with this map a
    reader sums an executable's device time by scope.  The first scope of
    ``scopes`` that an ``op_name`` contains takes the instruction."""
    out: Dict[str, List[str]] = {s: [] for s in scopes}
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            hit = next((s for s in scopes if s in m.group(2)), None)
            if hit is not None:
                out[hit].append(m.group(1))
    return out


# ------------------------------------------------------------ costed_jit
@contextlib.contextmanager
def _keyed_with_metadata():
    """jax keys its persistent compile cache on a program without its
    ``op_name`` metadata, so a source that only renamed its scopes is handed
    the executable an older source built, under that source's names.  What
    telemetry builds is read by those names (:meth:`CostedJit.hlo_text` ->
    :func:`op_scopes`), so it is keyed with them; a plain ``jax.jit`` (telemetry
    off) keeps jax's key."""
    import jax
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, was)


class CostedJit:
    """A named, cost-attributed jitted callable (see module docs).

    Dispatch: per distinct ``(dynamic avals, static values)`` signature,
    ``lower()`` + ``compile()`` ONCE through jax's AOT path (cost and
    memory analyses come from exactly that lowering — no second compile)
    and launch the compiled executable directly afterwards.  Tracer
    inputs (the rare call from inside another trace) and any AOT
    failure fall through to the plain jitted path.
    """

    def __init__(self, name: str, fn: Callable, jit_kwargs: Dict[str, Any],
                 lazy: bool = False):
        import jax
        self.name = name
        self._jitted = jax.jit(fn, **jit_kwargs)
        self._static_idx, self._static_names = _split_static(fn, jit_kwargs)
        self._compiled: Dict[Any, Any] = {}
        self._lazy = lazy
        self._broken = False

    def __call__(self, *args, **kwargs):
        if self._broken or not tracer.enabled():
            return self._jitted(*args, **kwargs)
        try:
            key, sig, dyn_args, dyn_kwargs, has_tracer = _signature(
                args, kwargs, self._static_idx, self._static_names)
        except Exception:
            log.debug("costed_jit %r signature derivation failed; "
                      "falling back to plain jit", self.name, exc_info=True)
            self._broken = True
            return self._jitted(*args, **kwargs)
        if has_tracer:
            return self._jitted(*args, **kwargs)
        compiled = self._compiled.get(key)
        if compiled is None:
            try:
                lowered = self._jitted.lower(*args, **kwargs)
                with _keyed_with_metadata():
                    compiled = lowered.compile()
            except Exception:
                log.debug("costed_jit %r AOT build failed; falling back "
                          "to plain jit", self.name, exc_info=True)
                self._broken = True
                return self._jitted(*args, **kwargs)
            flops, bya = _cost_numbers(lowered)
            _registry.record(self.name, key, sig, flops, bya,
                             _memory_numbers(compiled))
            self._compiled[key] = compiled
        _registry.launch(self.name, key)
        try:
            return compiled(*dyn_args, **dyn_kwargs)
        except Exception:
            # a dispatch-layer mismatch (committed-device or layout
            # corner) — the plain path is always correct
            log.debug("costed_jit %r AOT dispatch failed; using plain "
                      "jit for this call", self.name, exc_info=True)
            return self._jitted(*args, **kwargs)

    def hlo_text(self) -> Optional[str]:
        """The optimized HLO of the executable built last (None before the
        first costed call): what :func:`op_scopes` reads."""
        if not self._compiled:
            return None
        try:
            return list(self._compiled.values())[-1].as_text()
        except Exception:
            return None

    # parity with jax.jit's AOT surface, so call sites can still lower
    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)


def costed_jit(name: str, fn: Optional[Callable] = None, *,
               lazy: bool = False, **jit_kwargs):
    """``jax.jit`` with cost attribution under ``name`` (usable as
    ``costed_jit("plane.fn", fn, static_argnames=...)`` or as a
    decorator ``@costed_jit("plane.fn")``).

    Telemetry disabled at wrap time ⇒ returns the BARE ``jax.jit(fn)``
    — no wrapper frames, no registry writes, indistinguishable from
    un-instrumented code.  ``lazy=True`` defers the check to call time
    (one branch per call): required for module-scope executables, whose
    wrap runs at import, before ``--telemetry`` can flip the switch.
    """
    if fn is None:
        return lambda f: costed_jit(name, f, lazy=lazy, **jit_kwargs)
    if not lazy and not tracer.enabled():
        import jax
        return jax.jit(fn, **jit_kwargs)
    return CostedJit(name, fn, jit_kwargs, lazy=lazy)


# ------------------------------------------------------- analytic models
# Pallas kernels have no cost_analysis (XLA sees an opaque custom call):
# the kernel modules register small hand-derived FLOP/byte models here
# and the host launch loops record launches with the live shapes.
_models: Dict[str, Callable[..., Dict[str, float]]] = {}


def register_cost_model(name: str,
                        fn: Callable[..., Dict[str, float]]) -> None:
    """Register an analytic model: ``fn(**shape_kwargs)`` must return a
    dict with ``flops`` and ``bytes_accessed``."""
    _models[name] = fn


def cost_models() -> Dict[str, Callable[..., Dict[str, float]]]:
    return dict(_models)


def record_model_launch(name: str, **shape_kwargs) -> None:
    """Record one launch of an analytically-modeled kernel.  Entries key
    by the shape kwargs (the model's own signature space), count
    launches like compiled executables, and ride the same recompile
    sentinel.  No-op when telemetry is off or the model is unknown."""
    if not tracer.enabled():
        return
    model = _models.get(name)
    if model is None:
        log.debug("no cost model registered under %r", name)
        return
    key = tuple(sorted(shape_kwargs.items()))
    sig = ",".join(f"{k}={v}" for k, v in key)
    if not _registry.has_entry(name, key):
        try:
            est = model(**shape_kwargs)
        except Exception:
            log.debug("cost model %r failed for %r", name, shape_kwargs,
                      exc_info=True)
            return
        _registry.record(name, key, sig, float(est.get("flops") or 0.0),
                         float(est.get("bytes_accessed") or 0.0), None,
                         analytic=True)
    _registry.launch(name, key)
