"""AOT device-resident ensemble scorer — one executable per batch bucket.

The eval plane's :class:`~shifu_tpu.eval.scorer.Scorer` dispatches
per-model on every call (stacked NN groups on device, tree/WDL/SVM
columns through host ``np.asarray`` round trips).  For serving that
dispatch is pure per-request overhead, so :class:`AOTScorer` builds ONE
fused traceable function over the whole ensemble — every model's scores
as device sub-expressions of a single graph, no host hop between the
models of a bag — and ``lower()→compile()``s it ONCE per batch bucket at
startup.  A request batch then costs: pad to
the smallest covering bucket, one compiled launch, trim.

Every bucket executable registers with the cost-attribution plane
(:func:`shifu_tpu.obs.costs.record_executable`) under its own name
(``serve.score.<tag>.b<bucket>``), so the shape-churn sentinel
(``xla.recompiles``) police the central hazard of this design: a warmed
server must NEVER compile again, whatever request sizes arrive.
"""

from __future__ import annotations

import logging
import threading
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..eval.scorer import SCORE_SCALE, Scorer
from ..obs import costs

log = logging.getLogger(__name__)

# geometric bucket ladder default: one executable per rung; request
# batches pad to the smallest covering rung (``-Dshifu.serve.buckets``)
DEFAULT_BUCKETS = (1, 8, 64, 512)


def bucket_ladder() -> Tuple[int, ...]:
    """The configured bucket ladder, ascending and deduplicated
    (property ``shifu.serve.buckets`` = comma-separated sizes)."""
    from ..config import environment
    spec = environment.get_property("shifu.serve.buckets")
    if not spec:
        return DEFAULT_BUCKETS
    try:
        sizes = sorted({int(s) for s in spec.split(",") if s.strip()})
        if not sizes or any(s <= 0 for s in sizes):
            raise ValueError(spec)
        return tuple(sizes)
    except ValueError:
        log.warning("ignoring unparseable shifu.serve.buckets=%r", spec)
        return DEFAULT_BUCKETS


def covering_bucket(buckets: Sequence[int], n: int) -> int:
    """Smallest rung >= n (the largest rung when n exceeds the ladder —
    the caller chunks oversize batches)."""
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


def refine_ladder(buckets: Sequence[int], size_counts: dict,
                  max_extra: int = 2, min_share: float = 0.2,
                  occupancy_target: float = 0.8,
                  multiple: int = 8) -> Tuple[int, ...]:
    """Occupancy-driven rung refinement: given the observed distribution
    of real batch row-counts (``size_counts``: rows -> batches), propose
    intermediate rungs under rungs that systematically pad.

    A rung qualifies when it carries at least ``min_share`` of observed
    batches AND the p95 of its real batch sizes — rounded up to
    ``multiple`` — lands below ``occupancy_target`` of the rung: most of
    its traffic then pads to the tighter rung instead.  At most
    ``max_extra`` rungs are added per refinement (bounded compile
    budget) and existing rungs are NEVER removed, so every in-flight
    ``covering_bucket`` decision stays valid and already-compiled
    executables keep serving — the zero-recompile contract is untouched
    because a new rung compiles (a NEW executable name, first
    signature) before any batch pads to it."""
    buckets = tuple(sorted(set(int(b) for b in buckets)))
    total = sum(size_counts.values())
    if not total:
        return buckets
    per_rung: dict = {b: [] for b in buckets}
    for n, cnt in size_counts.items():
        per_rung[covering_bucket(buckets, int(n))].append((int(n), cnt))
    proposals = []
    for b, sizes in per_rung.items():
        if b == buckets[0]:
            continue                    # nothing tighter to offer
        carried = sum(c for _, c in sizes)
        if carried / total < min_share:
            continue
        cum, p95 = 0, b
        for n, c in sorted(sizes):
            cum += c
            if cum >= 0.95 * carried:
                p95 = n
                break
        rung = min(b, ((p95 + multiple - 1) // multiple) * multiple)
        if 0 < rung < occupancy_target * b and rung not in buckets:
            proposals.append((carried, rung))
    extra = sorted(r for _, r in
                   sorted(proposals, reverse=True)[:max_extra])
    return tuple(sorted(set(buckets) | set(extra)))


def infer_dims(models: Sequence) -> Tuple[int, int]:
    """(n_features, n_bin_cols) the ensemble's inputs must provide,
    derived from the saved specs — what startup warming compiles
    against.  ``n_bin_cols`` is 0 when no model consumes bins."""
    n_feat = 0
    n_bins_cols = 0
    for m in models:
        kind = getattr(m, "input_kind", "norm")
        name = type(m).__name__
        if name == "IndependentNNModel":
            n_feat = max(n_feat, int(m.spec.input_dim))
        elif name == "IndependentSVMModel":
            n_feat = max(n_feat, int(m.sv_x.shape[1]))
        elif name == "IndependentTreeModel":
            feats = max((int(np.max(t.split_feat)) for t in m.trees),
                        default=-1)
            n_bins_cols = max(n_bins_cols, feats + 1)
        elif kind == "both":                       # WDL: index lists
            nf = (getattr(m.spec, "extra", None) or {}).get(
                "num_feat_idx") or []
            cf = (getattr(m.spec, "extra", None) or {}).get(
                "cat_col_idx") or []
            if nf:
                n_feat = max(n_feat, max(nf) + 1)
            if cf:
                n_bins_cols = max(n_bins_cols, max(cf) + 1)
    return n_feat, n_bins_cols


def _tree_column(m) -> Callable:
    """Device-traceable score column for a saved forest — the jnp twin of
    ``IndependentTreeModel.compute`` (same f32 link math, no host hop).

    The traversal is the QUANTIZED one by default (``ops.tree_quant``):
    bins walk in their uint8 wire dtype with f32 only at the leaf
    accumulate — bit-identical scores, 1/4 the bytes on serving's
    dominant operand, and the Pallas kernel on TPU loads each row block
    once for the whole forest instead of once per (tree, level)."""
    import jax
    import jax.numpy as jnp

    from ..ops import tree_quant as tq
    from ..ops.tree import predict_forest_stacked, stack_forest

    depth = m.trees[0].depth
    spec = m.spec
    quant = tq.quant_scoring() and tq.bins_fit_uint8(spec.n_bins)
    if quant:
        qarrays = tq.stack_forest_quant(m.trees)
    else:
        stacked = stack_forest(m.trees)

    def col(x, bins):
        if quant:
            b = bins if bins.dtype == jnp.uint8 else bins.astype(jnp.uint8)
            preds = tq.predict_forest_quant(*qarrays, b, depth)
        else:
            preds = predict_forest_stacked(*stacked, bins, depth)
        if spec.algorithm == "GBT":
            f = spec.init_score + spec.learning_rate * preds.sum(axis=0)
            if spec.loss == "log":
                return 1.0 / (1.0 + jnp.exp(-f))
            return jnp.clip(f, 0.0, 1.0)
        out = preds.mean(axis=0)        # RF mean vote
        return out[:, 0] if out.ndim > 1 else out
    return col


def _wdl_column(m) -> Callable:
    """Device-traceable WDL column: the index slicing of
    ``compute_full`` moved inside the trace.

    The serve copy of the categorical plane is picked ONCE at build time
    (``shifu.wdl.serveCopy`` — see :func:`train.wdl_shard.
    build_serve_forward`): tables too big for one device score through a
    row-sharded gather inside this same traced graph (replicated
    activations, one psum per lookup plane — never an all-gather of a
    table), a hot-rows copy squashes the cold tail, and small tables keep
    the classic replicated forward.  All modes trace to fixed shapes, so
    the per-bucket AOT contract (zero recompiles) is untouched.  Hashed-ID
    columns fold in-graph (``apply_hash_device``) — bit-identical to the
    trainer's host hashing."""
    import jax.numpy as jnp

    from ..models.wdl import apply_hash_device, forward
    from ..train.wdl_shard import build_serve_forward

    nf = tuple((m.spec.extra or {}).get("num_feat_idx") or ())
    cf = tuple((m.spec.extra or {}).get("cat_col_idx") or ())
    spec, params = m.spec, m.params
    mode, sharded_fwd = build_serve_forward(spec, params)
    if mode != "full":
        log.info("WDL serve column: %s table copy", mode)

    def col(x, bins):
        x_num = x[:, np.asarray(nf, np.int32)] if nf \
            else jnp.zeros((x.shape[0], 0), jnp.float32)
        x_cat = bins[:, np.asarray(cf, np.int32)].astype(jnp.int32) if cf \
            else jnp.zeros((x.shape[0], 0), jnp.int32)
        x_cat = apply_hash_device(spec, x_cat)
        if sharded_fwd is not None:
            return sharded_fwd(x_num, x_cat)[:, 0]
        return forward(params, spec, x_num, x_cat)[:, 0]
    return col


def build_ensemble_fn(scorer: Scorer) -> Tuple[Callable, bool]:
    """One pure traceable ``fn(x[, bins]) -> raw [n, M]`` over the whole
    ensemble (scores already scaled), plus whether it consumes bins.

    Same dispatch rules as :meth:`Scorer.score_device` — same-shape NN
    models ride the stacked-group vmap, everything else contributes its
    own device sub-expression — but as ONE graph XLA fuses end to end.
    """
    from ..models.nn import forward as nn_forward

    models = scorer.models
    groups = scorer._stacked_nn_groups()
    grouped = {i for idxs, _, _ in groups for i in idxs}
    needs_bins = any(getattr(m, "input_kind", "norm") in ("bins", "both")
                     for m in models)

    cols: List[Optional[Callable]] = [None] * len(models)
    for i, m in enumerate(models):
        if i in grouped:
            continue
        kind = getattr(m, "input_kind", "norm")
        if kind == "bins":
            cols[i] = _tree_column(m)
        elif kind == "both":
            cols[i] = _wdl_column(m)
        elif type(m).__name__ == "IndependentNNModel":
            cols[i] = (lambda sp, ps: lambda x, bins:
                       nn_forward(ps, sp, x)[:, 0])(m.spec, m.params)
        elif type(m).__name__ == "IndependentSVMModel":
            cols[i] = (lambda mm: lambda x, bins:
                       mm._decision(x)[:, 0])(m)
        else:
            raise TypeError(f"cannot build a device column for "
                            f"{type(m).__name__}")

    scale = scorer.scale

    def fn(x, bins=None):
        import jax.numpy as jnp
        out = [None] * len(models)
        for idxs, stacked, fwd in groups:
            g = fwd(stacked, x)                      # [M, n, out]
            for pos, i in enumerate(idxs):
                out[i] = g[pos][:, 0]
        for i, col in enumerate(cols):
            if col is not None:
                out[i] = col(x, bins)
        return jnp.stack(out, axis=1) * scale
    return fn, needs_bins


def serve_recompile_count(prefix: str = "serve.score") -> int:
    """Distinct-signature recompiles observed across all serve
    executables — the telemetry-independent read of the shape-churn
    sentinel (``record_executable`` feeds the cost registry whether or
    not telemetry is on).  A warmed server must report 0."""
    by_name: dict = {}
    for e in costs.get_cost_registry().entries():
        if e.name.startswith(prefix):
            by_name.setdefault(e.name, set()).add(e.signature)
    return sum(len(sigs) - 1 for sigs in by_name.values())


class AOTScorer:
    """The modelset's ensemble, pinned in HBM, behind per-bucket AOT
    executables (see module docs).

    ``warm()`` compiles every rung of the ladder up front;
    :meth:`score_batch` then pads to the covering rung, launches the
    compiled executable (the pad copy is the only host-side byte
    movement), and trims.  Thread-safe: the batcher
    worker launches while a hot-swap builds the NEXT scorer instance
    elsewhere; one instance's executables are immutable after warm.
    """

    def __init__(self, models: Sequence, scale: float = SCORE_SCALE,
                 buckets: Optional[Sequence[int]] = None,
                 name: str = "serve.score", transform=None):
        import jax

        from ..ops import tree_quant as tq
        self.scorer = Scorer(models, scale)
        self.buckets = tuple(sorted(set(buckets or bucket_ladder())))
        self.name = name
        self.n_features, self.n_bins_cols = infer_dims(models)
        # requests carry bins in the narrowest dtype the ensemble admits
        # (uint8 wire contract) — quant off pins the old int32 signature
        self.bins_dtype = tq.ensemble_bins_dtype(models) \
            if tq.quant_scoring() else np.dtype(np.int32)
        # analytic kernel launches for the cost plane: the Pallas
        # traversal is opaque to XLA's cost analysis, so each scored
        # bucket records one model launch per quant-kernel forest
        # (serving MFU rows stay honest — the hist_kernel_cost pattern)
        self._quant_kernel_shapes = []
        if tq.quant_scoring():
            for m in models:
                if type(m).__name__ != "IndependentTreeModel" \
                        or not tq.bins_fit_uint8(m.spec.n_bins):
                    continue
                t0 = m.trees[0]
                if tq.quant_lowering(None, t0.n_nodes,
                                     np.ndim(t0.leaf_value) + 1) == "pallas":
                    self._quant_kernel_shapes.append(dict(
                        n_feat=self.n_bins_cols, n_bins=m.spec.n_bins,
                        n_nodes=t0.n_nodes, depth=t0.depth,
                        n_trees=len(m.trees)))
        fn, self.needs_bins = build_ensemble_fn(self.scorer)
        # AOT template only — never launched directly; every bucket's
        # executable registers with record_executable in _ensure_compiled.
        # Inputs are NOT donated: no [n, M] score output can alias an
        # [n, features] / uint8 bins / packed-wire input, and on the chip
        # XLA says so once per bucket ("donated buffers were not usable")
        self._jitted = jax.jit(fn)  # shifu-lint: disable=recompile-hazard
        self._compiled: dict = {}
        self._compiled_raw: dict = {}
        # raw-record family: the norm transform fused as a jnp prelude of
        # the SAME ensemble graph — one executable per rung, wire format
        # [n, 3C] (serve/transform.py), bins minted in-graph in the
        # narrow wire dtype so tree_quant stays uint8
        self.transform = transform
        self.accepts_raw = transform is not None
        self._jitted_raw = None
        if transform is not None:
            if transform.width < self.n_features:
                raise ValueError(
                    f"transform emits {transform.width} features but the "
                    f"ensemble consumes {self.n_features} — the ColumnConfig "
                    "snapshot does not match the models")
            if transform.n_columns < self.n_bins_cols:
                raise ValueError(
                    f"transform emits {transform.n_columns} bin columns but "
                    f"the ensemble consumes {self.n_bins_cols}")
            nfeat, nbc = self.n_features, self.n_bins_cols
            bdt, needs_bins = self.bins_dtype, self.needs_bins

            def raw_fn(packed):
                xx, bb = transform.apply_device(packed)
                xx = xx[:, :nfeat]
                if not needs_bins:
                    return fn(xx)
                return fn(xx, bb[:, :nbc].astype(bdt))
            # AOT template only — per-bucket executables register below
            self._jitted_raw = jax.jit(raw_fn)  # shifu-lint: disable=recompile-hazard
        self._lock = threading.Lock()
        self._pin_params()

    @property
    def models(self) -> List:
        return self.scorer.models

    def _pin_params(self) -> None:
        """Force every param/forest leaf onto the device ONCE — scoring
        must never pay a lazy host->HBM transfer mid-request."""
        import jax
        for idxs, stacked, _ in self.scorer._stacked_nn_groups():
            jax.block_until_ready(stacked)
        for m in self.models:
            for leaf in jax.tree_util.tree_leaves(
                    getattr(m, "params", None)):
                jax.block_until_ready(jax.device_put(leaf))

    # ------------------------------------------------------------ compile
    def _avals(self, bucket: int):
        import jax
        x = jax.ShapeDtypeStruct((bucket, self.n_features), np.float32)
        if not self.needs_bins:
            return (x,)
        return (x, jax.ShapeDtypeStruct((bucket, self.n_bins_cols),
                                        self.bins_dtype))

    def _avals_raw(self, bucket: int):
        import jax
        return (jax.ShapeDtypeStruct((bucket, self.transform.wire_width),
                                     self.transform.wire_dtype),)

    def _ensure_compiled(self, bucket: int, raw: bool = False):
        cache = self._compiled_raw if raw else self._compiled
        ent = cache.get(bucket)
        if ent is not None:
            return ent
        with self._lock:
            ent = cache.get(bucket)
            if ent is not None:
                return ent
            import jax
            jitted = self._jitted_raw if raw else self._jitted
            avals = self._avals_raw(bucket) if raw else self._avals(bucket)
            lowered = jitted.lower(*avals)
            exe = lowered.compile()
            try:
                sig = ",".join(a.str_short() for a in
                               jax.tree_util.tree_leaves(lowered.in_avals))
            except Exception:
                sig = f"b{bucket}"
            # per-bucket name: each rung has exactly ONE legal signature,
            # so ANY second signature under it is real shape churn and
            # trips the xla.recompiles sentinel
            # bounded shape-keyed family: ONE name per ladder rung by
            # design, so the per-name dedup stays meaningful
            suffix = ".raw" if raw else ""
            costs.record_executable(f"{self.name}{suffix}.b{bucket}",  # shifu-lint: disable=recompile-hazard
                                    lowered, exe, signature=sig)
            ent = cache[bucket] = (exe, sig)
        return ent

    def warm(self, launch: bool = True) -> None:
        """Compile every rung; ``launch=True`` additionally runs each
        executable once so first-request latency pays no dispatch-path
        lazy init either."""
        for b in self.buckets:
            self._warm_one(b, launch)

    def _warm_one(self, bucket: int, launch: bool = True) -> None:
        exe, _ = self._ensure_compiled(bucket)
        if launch:
            args = [np.zeros((bucket, self.n_features), np.float32)]
            if self.needs_bins:
                args.append(np.zeros((bucket, self.n_bins_cols),
                                     self.bins_dtype))
            import jax
            jax.block_until_ready(exe(*args))
        if not self.accepts_raw:
            return
        rexe, _ = self._ensure_compiled(bucket, raw=True)
        if launch:
            import jax
            # a zero wire row decodes as all-missing — a legal record
            jax.block_until_ready(rexe(np.zeros(
                (bucket, self.transform.wire_width),
                self.transform.wire_dtype)))

    def extend_buckets(self, new_buckets: Sequence[int]) -> int:
        """Grow the ladder with occupancy-refined rungs (see
        :func:`refine_ladder`).  Every new rung compiles AND launches
        once BEFORE it is published, so the first real batch that pads
        to it pays a warm dispatch — compiling ahead of use is what
        keeps the zero-recompile contract intact.  Existing rungs are
        never removed.  Returns the number of rungs added."""
        add = [int(b) for b in sorted(set(new_buckets))
               if int(b) > 0 and int(b) not in self.buckets]
        for b in add:
            self._warm_one(b)
        if add:
            with self._lock:
                self.buckets = tuple(sorted(set(self.buckets) | set(add)))
            from .. import obs
            obs.counter("serve.bucket_rungs_added").inc(len(add))
            log.info("%s: ladder refined to %s", self.name, self.buckets)
        return len(add)

    # the batcher's request tracer may pass ``timings=`` (duck-checked —
    # test doubles wrapping this class need not support it)
    supports_timings = True

    # ------------------------------------------------------------- score
    def score_batch(self, x: np.ndarray,
                    bins: Optional[np.ndarray] = None,
                    timings: Optional[dict] = None) -> np.ndarray:
        """raw scaled scores [n, M] for a request batch; pads to the
        covering bucket, chunks batches beyond the top rung.  Returns a
        host array (the serving response crosses the link by
        definition — ONE fetch per launch).

        ``timings`` (sampled request tracing only) accumulates the
        launch decomposition in place: ``pad_s`` the host pad copy,
        ``device_s`` the executable call (device compute on the
        synchronous CPU/TPU-AOT dispatch path), ``launch_s`` argument
        prep + the host fetch around it."""
        import time as _time
        n = len(x)
        top = self.buckets[-1]
        if n > top:
            return np.concatenate(
                [self.score_batch(x[s:s + top],
                                  None if bins is None else bins[s:s + top],
                                  timings=timings)
                 for s in range(0, n, top)], axis=0)
        t0 = _time.perf_counter() if timings is not None else 0.0
        bucket = covering_bucket(self.buckets, n)
        pad = bucket - n
        if pad:
            x = np.concatenate(
                [x, np.zeros((pad, x.shape[1]), x.dtype)], axis=0)
            if bins is not None:
                bins = np.concatenate(
                    [bins, np.zeros((pad, bins.shape[1]), bins.dtype)],
                    axis=0)
        if timings is not None:
            t1 = _time.perf_counter()
            timings["pad_s"] = timings.get("pad_s", 0.0) + (t1 - t0)
        exe, sig = self._ensure_compiled(bucket)
        args = [np.ascontiguousarray(x, np.float32)]
        if self.needs_bins:
            if bins is None:
                raise ValueError("ensemble contains bin-consuming models "
                                 "— requests must carry bins")
            args.append(np.ascontiguousarray(bins, self.bins_dtype))
        costs.get_cost_registry().launch(f"{self.name}.b{bucket}", sig)
        for kw in self._quant_kernel_shapes:
            costs.record_model_launch("pallas.tree_traverse",
                                      rows=bucket, **kw)
        if timings is None:
            return np.asarray(exe(*args))[:n]
        t2 = _time.perf_counter()
        out = exe(*args)
        t3 = _time.perf_counter()
        raw = np.asarray(out)
        t4 = _time.perf_counter()
        timings["device_s"] = timings.get("device_s", 0.0) + (t3 - t2)
        timings["launch_s"] = timings.get("launch_s", 0.0) \
            + (t2 - t1) + (t4 - t3)
        return raw[:n]

    def score_batch_raw(self, packed: np.ndarray,
                        timings: Optional[dict] = None) -> np.ndarray:
        """raw scaled scores [n, M] for PACKED raw-record rows (the
        ``serve/transform.py`` wire format): the fused executable norms
        in-graph and scores in one launch.  Same pad/chunk/trim contract
        as :meth:`score_batch`; pad rows are all-missing and cost
        nothing beyond the rung."""
        import time as _time
        if not self.accepts_raw:
            raise ValueError("this scorer was built without a norm "
                             "transform — raw records need the "
                             "ColumnConfig snapshot")
        n = len(packed)
        top = self.buckets[-1]
        if n > top:
            return np.concatenate(
                [self.score_batch_raw(packed[s:s + top], timings=timings)
                 for s in range(0, n, top)], axis=0)
        t0 = _time.perf_counter() if timings is not None else 0.0
        bucket = covering_bucket(self.buckets, n)
        pad = bucket - n
        if pad:
            packed = np.concatenate(
                [packed, np.zeros((pad, packed.shape[1]), packed.dtype)],
                axis=0)
        if timings is not None:
            t1 = _time.perf_counter()
            timings["pad_s"] = timings.get("pad_s", 0.0) + (t1 - t0)
        exe, sig = self._ensure_compiled(bucket, raw=True)
        arg = np.ascontiguousarray(packed, self.transform.wire_dtype)
        costs.get_cost_registry().launch(f"{self.name}.raw.b{bucket}", sig)
        for kw in self._quant_kernel_shapes:
            costs.record_model_launch("pallas.tree_traverse",
                                      rows=bucket, **kw)
        if timings is None:
            return np.asarray(exe(arg))[:n]
        t2 = _time.perf_counter()
        out = exe(arg)
        t3 = _time.perf_counter()
        raw = np.asarray(out)
        t4 = _time.perf_counter()
        timings["device_s"] = timings.get("device_s", 0.0) + (t3 - t2)
        timings["launch_s"] = timings.get("launch_s", 0.0) \
            + (t2 - t1) + (t4 - t3)
        return raw[:n]

    def score_mean(self, x: np.ndarray,
                   bins: Optional[np.ndarray] = None) -> np.ndarray:
        """Per-row ensemble mean — the serving response column."""
        return self.score_batch(x, bins).mean(axis=1)
