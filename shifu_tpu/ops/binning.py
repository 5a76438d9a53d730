"""Device-side streaming binning + per-column stats accumulation.

TPU-native replacement for the reference's stats data path (SURVEY.md §3.2):
the SPDT/MunroPat streaming-sketch binning (``core/binning/``) plus the
``UpdateBinningInfo`` MR second pass become two SPMD passes over columnar
chunks:

  pass 1 (moments): per-column count/min/max + centered moments M2..M4
          (Chan et al. pairwise combine, so f32 device sums stay accurate),
  pass 2 (sketch):  a fine equal-width histogram per column (pos/neg counts
          and weighted counts via one scatter-add ``segment_sum``).

Bin boundaries for every binning method (EqualPositive/Total/Negative/
Interval + weighted variants, ``ModelStatsConf.java:34-35``) are read off the
fine histogram's cumulative sums; final per-bin pos/neg counts are exact
segment-sums of fine buckets (boundaries always land on fine-bucket edges).
Categorical bins are exact dict aggregations.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config.model_config import BinningMethod
from ..obs import costs as obs_costs

# merged-category group separator (reference uses \u0001 in CategoricalBinInfo)
CATEGORY_GROUP_SEP = "\x01"

NEG_INF = float("-inf")


# ----------------------------------------------------------------- kernels
# Stats-plane executables are cost-attributed (obs/costs) so the
# utilization report can say whether the fused sweep is compute- or
# bandwidth-bound; ``lazy=True`` because these wrap at module import,
# before the CLI's --telemetry flips the telemetry switch.
@obs_costs.costed_jit("stats.moments_kernel", lazy=True)
def _moments_kernel(x: jnp.ndarray, valid: jnp.ndarray):
    """Per-column count/sum/min/max + centered M2/M3/M4 for one chunk.

    x: [R, C] float32 with arbitrary values where invalid; valid: [R, C] bool.
    Centering by the chunk mean keeps f32 power sums small enough for TPU.
    """
    v = valid.astype(x.dtype)
    cnt = v.sum(axis=0)
    safe_cnt = jnp.maximum(cnt, 1.0)
    xv = jnp.where(valid, x, 0.0)
    s1 = xv.sum(axis=0)
    mean = s1 / safe_cnt
    d = jnp.where(valid, x - mean, 0.0)
    m2 = (d * d).sum(axis=0)
    m3 = (d * d * d).sum(axis=0)
    m4 = (d * d * d * d).sum(axis=0)
    big = jnp.asarray(jnp.finfo(x.dtype).max, x.dtype)
    mn = jnp.where(valid, x, big).min(axis=0)
    mx = jnp.where(valid, x, -big).max(axis=0)
    return cnt, mean, m2, m3, m4, mn, mx


def _stat_channels(target, weight, unit_weight: bool):
    """Per-row stat channels + their bf16-exactness flags: [pos, neg]
    (0/1 indicators, exact) or [pos, neg, w_pos, w_neg] — the ONE place
    that knows the channel order (histogram and missing-bin aggregation
    must never disagree on it)."""
    R = target.shape[0]
    is_pos = (target >= 0.5)[:, None]
    ones = jnp.ones((R, 1), jnp.float32)
    pos_i = jnp.where(is_pos, ones, 0.0)
    neg_i = jnp.where(is_pos, 0.0, ones)
    if unit_weight:
        return jnp.concatenate([pos_i, neg_i], axis=1), (True, True)
    w = weight[:, None]
    return jnp.concatenate(
        [pos_i, neg_i, jnp.where(is_pos, w, 0.0),
         jnp.where(is_pos, 0.0, w)], axis=1), (True, True, False, False)


@obs_costs.costed_jit("stats.histogram_kernel", lazy=True,
                      static_argnames=("num_buckets", "use_pallas",
                                       "unit_weight", "expand", "mesh"))
def _histogram_kernel(x: jnp.ndarray, valid: jnp.ndarray, target: jnp.ndarray,
                      weight: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                      num_buckets: int, use_pallas: bool = False,
                      unit_weight: bool = False, expand: bool = True,
                      mesh=None):
    """Fine-histogram for one chunk.

    Returns [C, num_buckets, 4]: (#pos, #neg, w_pos, w_neg) per fine bucket.
    Two lowerings, the tree-histogram story replayed for the ETL plane:
    ``use_pallas=True`` → the two-level one-hot MXU kernel
    (:func:`shifu_tpu.ops.hist_pallas.stats_histograms_pallas` — the TPU
    serializes scatter-adds, and at north-star widths the scatter path
    cannot keep up with object-storage IO); default → one flattened
    ``segment_sum``, the reference's per-(column,bin) reducer accumulation.

    ``unit_weight=True`` (no weight column configured — the common case)
    computes only the two 0/1 count channels and mirrors them into the
    weighted slots: half the accumulation work, and both channels are
    bf16-exact so the MXU path runs a single dot per column pair.
    ``expand=False`` skips the mirroring and returns the raw [C, B, 2] —
    for device-side accumulators whose drain pays link bandwidth per
    channel (the host expands after the fetch).
    """
    R, C = x.shape
    scale = num_buckets / jnp.maximum(hi - lo, 1e-30)
    idx = jnp.clip(((x - lo) * scale), 0, num_buckets - 1).astype(jnp.int32)
    vals, exact = _stat_channels(target, weight, unit_weight)
    if use_pallas:
        from .hist_pallas import (stats_histograms_pallas,
                                  stats_histograms_sharded, target_platform)
        cidx = jnp.where(valid, idx, -1)     # invalid cell -> matches no bin
        interp = target_platform(mesh) != "tpu"
        if mesh is not None and mesh.size > 1:
            h = stats_histograms_sharded(cidx, vals, num_buckets, mesh,
                                         interpret=interp, exact=exact)
        else:
            h = stats_histograms_pallas(cidx, vals, num_buckets,
                                        interpret=interp, exact=exact)
    else:
        S = vals.shape[1]
        flat = idx + jnp.arange(C, dtype=jnp.int32) * num_buckets
        flat = jnp.where(valid, flat, C * num_buckets)  # overflow slot
        data = jnp.broadcast_to(vals[:, None, :], (R, C, S)).reshape(R * C, S)
        seg = jax.ops.segment_sum(data, flat.reshape(-1),
                                  num_segments=C * num_buckets + 1)
        h = seg[:-1].reshape(C, num_buckets, S)
    if unit_weight and expand:               # w_pos = #pos, w_neg = #neg
        h = jnp.concatenate([h, h], axis=2)
    return h


# ------------------------------------------------------- moment combination
def _combine_moments(a: dict, b: Tuple[np.ndarray, ...]) -> dict:
    """Chan et al. pairwise combination of (count, mean, M2, M3, M4)."""
    cb, mb, M2b, M3b, M4b, mnb, mxb = [np.asarray(t, np.float64) for t in b]
    if not a:
        return {"count": cb, "mean": mb, "M2": M2b, "M3": M3b, "M4": M4b,
                "min": mnb, "max": mxb}
    ca, ma, M2a, M3a, M4a = a["count"], a["mean"], a["M2"], a["M3"], a["M4"]
    n = ca + cb
    safe_n = np.maximum(n, 1.0)
    delta = mb - ma
    mean = ma + delta * cb / safe_n
    M2 = M2a + M2b + delta ** 2 * ca * cb / safe_n
    M3 = (M3a + M3b + delta ** 3 * ca * cb * (ca - cb) / safe_n ** 2
          + 3 * delta * (ca * M2b - cb * M2a) / safe_n)
    M4 = (M4a + M4b
          + delta ** 4 * ca * cb * (ca ** 2 - ca * cb + cb ** 2) / safe_n ** 3
          + 6 * delta ** 2 * (ca ** 2 * M2b + cb ** 2 * M2a) / safe_n ** 2
          + 4 * delta * (ca * M3b - cb * M3a) / safe_n)
    return {"count": n, "mean": np.where(n > 0, mean, 0.0), "M2": M2, "M3": M3,
            "M4": M4, "min": np.minimum(a["min"], mnb),
            "max": np.maximum(a["max"], mxb)}


@obs_costs.costed_jit("stats.missing_agg", lazy=True,
                      static_argnames=("unit_weight", "expand"))
def _missing_agg_kernel(valid, target, weight, live=None,
                        unit_weight: bool = False, expand: bool = True):
    """[C, 4] (pos/neg/w_pos/w_neg) sums over INVALID cells — the
    missing-bin aggregation as one device matmul instead of four host
    passes over the [R, C] mask.  HIGHEST precision keeps f32-faithful
    accumulation (counts are exact integers below 2^24; the bounded
    drain in :class:`NumericAccumulator` keeps them there).

    ``live`` [R] bool marks real rows: mesh-sharded chunks pad rows to
    the data-axis extent, and a padded all-invalid row must NOT count as
    missing (every other kernel drops invalid cells on its own)."""
    inval = (~valid).astype(jnp.float32)               # [R, C]
    if live is not None:
        inval = inval * live.astype(jnp.float32)[:, None]
    vals, _ = _stat_channels(target, weight, unit_weight)
    magg = jax.lax.dot_general(inval, vals, (((0,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)  # [C, S]
    if unit_weight and expand:
        magg = jnp.concatenate([magg, magg], axis=1)
    return magg


def _method_weight_col(hist, method_value: str, nch: int):
    """[C, K] per-fine-bucket weight measure for a binning method (the
    channel mix ``compute_boundaries`` reads off the histogram)."""
    pos, neg = hist[..., 0], hist[..., 1]
    wpos = hist[..., 2] if nch == 4 else pos
    wneg = hist[..., 3] if nch == 4 else neg
    return {
        "EqualPositive": pos,
        "EqualNegtive": neg,
        "WeightEqualTotal": wpos + wneg,
        "WeightEqualPositive": wpos,
        "WeightEqualNegative": wneg,
    }.get(method_value, pos + neg)


@obs_costs.costed_jit("stats.refine_prov", lazy=True,
                      static_argnames=("num_buckets",))
def _refine_prov_kernel(prov, plo, phi, lo, hi, num_buckets: int):
    """Re-bin a PROVISIONAL-grid fine histogram onto the exact final grid,
    on device (the fused one-pass sweep's refinement step — see
    :class:`shifu_tpu.ops.sketches.RangeSketch`).

    Each provisional bucket lands whole in the final bucket its center
    falls in: counts are conserved exactly; placement error is bounded by
    one provisional bucket width ((phi-plo)/K — with the sketch margin,
    ~1.5/K of the value range, far inside the fine-sketch resolution the
    boundaries are read at anyway)."""
    kk = jnp.arange(num_buckets, dtype=jnp.float32)
    centers = plo[:, None] + (phi - plo)[:, None] * \
        (kk[None, :] + 0.5) / num_buckets                     # [C, K]
    scale = num_buckets / jnp.maximum(hi - lo, 1e-30)
    idx = jnp.clip((centers - lo[:, None]) * scale[:, None],
                   0, num_buckets - 1).astype(jnp.int32)      # [C, K]
    return jax.vmap(
        lambda p, i: jax.ops.segment_sum(p, i,
                                         num_segments=num_buckets))(
        prov, idx)


@functools.partial(jax.jit, static_argnames=("method_value", "max_bins",
                                             "num_buckets", "nch",
                                             "interval"))
def _finalize_sketch_kernel(hist, magg, lo, hi, method_value: str,
                            max_bins: int, num_buckets: int, nch: int,
                            interval: bool = False):
    """The whole sketch→ColumnStats reduction ON DEVICE, one packed fetch.

    Replaces the host path (drain the [C, 4096, ch] fine histogram —
    8-16 MB of fetch — then per-column numpy cumsums) with
    device math whose output is only [C, max_bins]-sized.  The
    fine-bucket→final-bin reduction needs no scatter: boundaries are
    nondecreasing, so each final bin is a contiguous fine-bucket range
    and per-bin sums are differences of the channel cumsum gathered at
    the range ends (the ``UpdateBinningInfoReducer.java:57`` aggregation,
    reformulated prefix-sum style).

    Returns (boundaries [C, max_bins] incl. leading -inf and possible
    duplicates — the host dedupes; agg [C, max_bins+1, nch] aligned to
    the UNdeduped boundaries, missing bin last; pct [C, 3]; distinct [C];
    totals [C] of the method measure — zero-total columns fall back to
    the reference's single-bin shape host-side).
    """
    C = hist.shape[0]
    weight_col = _method_weight_col(hist, method_value, nch)     # [C, K]
    edges = lo[:, None] + (hi - lo)[:, None] * \
        jnp.arange(num_buckets + 1, dtype=jnp.float32) / num_buckets
    cum = jnp.cumsum(weight_col, axis=1)                         # [C, K]
    total = cum[:, -1]                                           # [C]
    frac = jnp.arange(1, max_bins, dtype=jnp.float32) / max_bins
    if interval:                                 # EqualInterval: width, not
        bnd = lo[:, None] + (hi - lo)[:, None] * frac      # population
    else:
        targets = total[:, None] * frac                          # [C, B-1]
        pos = jax.vmap(lambda c, t: jnp.searchsorted(c, t, side="left"))(
            cum, targets)                                        # [C, B-1]
        bnd = jnp.take_along_axis(edges, pos + 1, axis=1)        # [C, B-1]
    bnd_full = jnp.concatenate(
        [jnp.full((C, 1), NEG_INF, jnp.float32), bnd], axis=1)   # [C, B]
    # fine bucket k belongs to final bin searchsorted(bnd, edge_k, right)-1;
    # the assignment is nondecreasing in k, so bin b covers fine buckets
    # [hi_idx[b-1], hi_idx[b]) where hi_idx[b] = #buckets assigned <= b
    bucket_bin = jnp.clip(
        jax.vmap(lambda b, e: jnp.searchsorted(b, e, side="right"))(
            bnd_full, edges[:, :-1]) - 1, 0, max_bins - 1)       # [C, K]
    bins_iota = jnp.arange(max_bins)
    # per-bin sums via a masked reduction rather than cumsum differences:
    # large-minus-large f32 prefixes put ~1e-5 x TOTAL error on every bin;
    # direct per-bin summation keeps the error proportional to the bin
    onehot = (bucket_bin[:, :, None] == bins_iota[None, None, :]) \
        .astype(hist.dtype)                                      # [C, K, B]
    agg_bins = jnp.einsum('ckb,cks->cbs', onehot, hist,
                          precision=jax.lax.Precision.HIGHEST)
    agg = jnp.concatenate([agg_bins, magg[:, None, :]], axis=1)  # [C,B+1,ch]
    # percentiles (count measure) to fine-bucket resolution; the count
    # cumsum is exact (integer sums below 2^24)
    cnt_cum = jnp.cumsum(hist[..., 0] + hist[..., 1], axis=1)    # [C, K]
    q = jnp.asarray([0.25, 0.5, 0.75], jnp.float32)
    qpos = jax.vmap(lambda c, t: jnp.searchsorted(c, t, side="left"))(
        cnt_cum, cnt_cum[:, -1:] * q[None, :])
    pct = jnp.take_along_axis(
        edges, jnp.minimum(qpos + 1, num_buckets), axis=1)       # [C, 3]
    distinct = (hist.sum(axis=2) > 0).sum(axis=1)                # [C]
    return jnp.concatenate([
        bnd_full.reshape(-1), agg.reshape(-1), pct.reshape(-1),
        distinct.astype(jnp.float32), total])


# ------------------------------------------------------------- accumulators
@dataclass
class NumericAccumulator:
    """Streaming accumulator over numeric columns (both passes).

    Device-side accumulation: per-chunk kernel outputs stay in HBM and
    drain to host float64 in ONE packed fetch per pass (or per ~8M-row
    super-chunk, which keeps f32 bucket counts integer-exact).  A host
    fetch is a full device round trip, so a per-chunk ``np.asarray``
    would serialize the whole stats plane behind fetch latency."""
    n_cols: int
    num_buckets: int = 4096
    unit_weight: bool = False       # no weight column: w channels mirror counts
    # (ensemble, data) mesh: chunk rows shard over the data axis and the
    # per-chunk reductions psum on ICI — the reference's up-to-999 stats
    # reducers (``MapReducerStatsWorker.java:111-139``); None or a 1-device
    # mesh keeps the single-chip layout
    mesh: Optional[object] = None
    moments: dict = field(default_factory=dict)
    total_rows: int = 0
    missing: Optional[np.ndarray] = None
    hist: Optional[np.ndarray] = None          # [C, K, 4] float64
    missing_agg: Optional[np.ndarray] = None   # [C, 4] pos/neg/wpos/wneg of missing
    lo: Optional[np.ndarray] = None
    hi: Optional[np.ndarray] = None
    # exact mode (MunroPat): keep per-column (valid values, pos flag,
    # weight) so boundaries land on TRUE quantiles instead of sketch-bucket
    # edges (reference ``core/binning/MunroPatBinning.java:29`` materializes
    # the column sample the same way).  Memory is O(valid values) — the
    # exact path is for LOCAL-scale runs; the sketch remains the default.
    exact: bool = False
    _exact_cols: Optional[list] = None     # [C] lists of (vals, pos, w)
    _pend_moments: list = field(default_factory=list)  # [7, C] device chunks
    _pend_moment_rows: int = 0
    _hist_dev: Optional[object] = None     # [C, K, 4] f32 on device
    _magg_dev: Optional[object] = None     # [C, 4] f32 on device
    _pend_hist_rows: int = 0
    _lo_d: Optional[object] = None
    _hi_d: Optional[object] = None
    # fused one-pass sweep state (update_fused/finalize_fused): chunks
    # ship H2D ONCE and stay device-resident up to ``fused_budget`` bytes;
    # past it, chunks accumulate into a PROVISIONAL-range histogram that
    # refines onto the exact grid at finalize (ops/sketches.RangeSketch)
    fused_budget: int = 1 << 30
    _fused_chunks: list = field(default_factory=list)
    _fused_bytes: int = 0
    _prov_hist_dev: Optional[object] = None
    _prov_magg_dev: Optional[object] = None
    _prov_lo_d: Optional[object] = None
    _prov_hi_d: Optional[object] = None

    # f32 histogram counts are exact integers up to 2^24; drain to host
    # float64 well before that so TB-scale streams lose nothing
    DRAIN_ROWS = 8_000_000

    def __post_init__(self):
        # the fine-histogram bucket axis must stay MXU-tile-aligned: the
        # two-level one-hot stats kernel factors bucket ids as hi*64+lo
        # (64 sublanes x 64 lanes per dot tile) and caps at 4096 — a
        # misaligned count would silently fall off the kernel path onto
        # the serialized scatter lowering
        if self.num_buckets % 64 != 0 or not \
                (64 <= self.num_buckets <= 4096):
            raise ValueError(
                f"num_buckets={self.num_buckets} is not MXU-tile-aligned: "
                "the stats fine histogram requires a multiple of 64 in "
                "[64, 4096] (ops/hist_pallas.stats_histograms_pallas)")

    def _data_size(self) -> int:
        return int(self.mesh.shape["data"]) if self.mesh is not None else 1

    def _put_rows(self, *arrays):
        """Chunk rows onto the mesh (padded, data-axis sharded) — see
        :func:`shifu_tpu.parallel.mesh.shard_chunk_rows`.  Padded rows are
        all-invalid with weight/target 0."""
        from ..parallel.mesh import shard_chunk_rows
        return shard_chunk_rows(self.mesh, *arrays)

    # ---- pass 1
    def update_moments(self, x: np.ndarray, valid: np.ndarray) -> None:
        if self._data_size() <= 1:
            # jnp.asarray: a device-resident chunk stays put (np.asarray
            # would round-trip it through the host — catastrophic over a
            # remote-device link)
            xd, vd = jnp.asarray(x, jnp.float32), jnp.asarray(valid)
        else:
            xd, vd, _ = self._put_rows(np.asarray(x, np.float32),
                                       np.asarray(valid))
        out = _moments_kernel(xd, vd)
        self._pend_moments.append(jnp.stack(out))      # [7, C], stays on device
        self.total_rows += x.shape[0]
        self._pend_moment_rows += x.shape[0]
        if self._pend_moment_rows >= self.DRAIN_ROWS:  # bound the pending
            self._drain_moments()                      # list and its HBM

    def _drain_moments(self) -> None:
        if not self._pend_moments:
            return
        chunks = np.asarray(jnp.stack(self._pend_moments), np.float64)
        self._pend_moments.clear()
        self._pend_moment_rows = 0
        for m in chunks:                               # Chan combine in f64
            self.moments = _combine_moments(self.moments, tuple(m))
        # invalid cells among processed rows = rows - valid count
        self.missing = self.total_rows - self.moments["count"]

    def finalize_range(self) -> None:
        self._drain_moments()
        mn, mx = self.moments["min"].copy(), self.moments["max"].copy()
        empty = self.moments["count"] == 0
        mn[empty], mx[empty] = 0.0, 1.0
        same = mx <= mn
        mx[same] = mn[same] + 1.0
        self.lo, self.hi = mn, mx
        self._lo_d = jnp.asarray(self.lo, jnp.float32)
        self._hi_d = jnp.asarray(self.hi, jnp.float32)

    # ---- pass 2
    def update_histogram(self, x: np.ndarray, valid: np.ndarray,
                         target: np.ndarray, weight: np.ndarray) -> None:
        assert self.lo is not None, "call finalize_range() after pass 1"
        from .hist_pallas import pallas_available
        up = (pallas_available(self.mesh) and self.num_buckets % 64 == 0
              and self.num_buckets <= 4096)
        if self._data_size() <= 1:     # see update_moments on jnp.asarray
            xd = jnp.asarray(x, jnp.float32)
            vd = jnp.asarray(valid)
            td = jnp.asarray(target, jnp.float32)
            wd = jnp.asarray(weight, jnp.float32)
            live = None
        else:
            xd, vd, td, wd, live = self._put_rows(
                np.asarray(x, np.float32), np.asarray(valid),
                np.asarray(target, np.float32),
                np.asarray(weight, np.float32))
        h = _histogram_kernel(xd, vd, td, wd, self._lo_d, self._hi_d,
                              self.num_buckets, use_pallas=up,
                              unit_weight=self.unit_weight, expand=False,
                              mesh=self.mesh if self._data_size() > 1
                              else None)
        magg = _missing_agg_kernel(vd, td, wd, live,
                                   unit_weight=self.unit_weight,
                                   expand=False)
        self._hist_dev = h if self._hist_dev is None else self._hist_dev + h
        self._magg_dev = (magg if self._magg_dev is None
                          else self._magg_dev + magg)
        self._pend_hist_rows += x.shape[0]
        if self._pend_hist_rows >= self.DRAIN_ROWS:
            self._drain_hist()
        if self.exact:
            if self._exact_cols is None:
                self._exact_cols = [[] for _ in range(self.n_cols)]
            pos_r = np.asarray(target, np.float64) >= 0.5
            w64 = np.asarray(weight, np.float64)
            for c in range(self.n_cols):
                v = valid[:, c]
                self._exact_cols[c].append(
                    (np.asarray(x[v, c], np.float64), pos_r[v], w64[v]))

    # ---- fused one-pass sweep (moments + histogram in ONE disk pass)
    def _kernel_gate(self) -> bool:
        from .hist_pallas import pallas_available
        return bool(pallas_available(self.mesh))

    def update_fused(self, x: np.ndarray, valid: np.ndarray,
                     target: np.ndarray, weight: np.ndarray) -> None:
        """One-pass chunk update: moments accumulate as in pass 1 AND the
        chunk's device arrays are RETAINED (up to ``fused_budget`` bytes)
        so :meth:`finalize_fused` can build the exact-range fine histogram
        without re-reading or re-shipping the chunk — each shard window is
        read, parsed and put H2D ONCE (the two-pass plane paid all three
        twice).  Chunks past the budget accumulate immediately into a
        PROVISIONAL-range histogram (sketch-first boundaries,
        :class:`shifu_tpu.ops.sketches.RangeSketch`) refined on device at
        finalize.  Resident-path results are BIT-identical to the
        two-pass sweep (same kernels, same inputs, same order)."""
        assert not self.exact, \
            "fused sweep serves the sketch path; exact (MunroPat) " \
            "binning keeps the two-pass flow"
        if self._data_size() <= 1:
            xd, vd = jnp.asarray(x, jnp.float32), jnp.asarray(valid)
            td = jnp.asarray(target, jnp.float32)
            wd = jnp.asarray(weight, jnp.float32)
            live = None
        else:
            xd, vd, td, wd, live = self._put_rows(
                np.asarray(x, np.float32), np.asarray(valid),
                np.asarray(target, np.float32),
                np.asarray(weight, np.float32))
        self._pend_moments.append(jnp.stack(_moments_kernel(xd, vd)))
        self.total_rows += x.shape[0]
        self._pend_moment_rows += x.shape[0]
        if self._pend_moment_rows >= self.DRAIN_ROWS:
            self._drain_moments()
        nbytes = x.shape[0] * (5 * self.n_cols + 8)   # f32 x + bool v + t/w
        if self._fused_bytes + nbytes <= self.fused_budget:
            self._fused_chunks.append((xd, vd, td, wd, live, x.shape[0]))
            self._fused_bytes += nbytes
            return
        if self._prov_lo_d is None:
            self._freeze_provisional()     # ONE sync, at first overflow
        h = _histogram_kernel(xd, vd, td, wd, self._prov_lo_d,
                              self._prov_hi_d, self.num_buckets,
                              use_pallas=self._kernel_gate(),
                              unit_weight=self.unit_weight, expand=False,
                              mesh=self.mesh if self._data_size() > 1
                              else None)
        magg = _missing_agg_kernel(vd, td, wd, live,
                                   unit_weight=self.unit_weight,
                                   expand=False)
        self._prov_hist_dev = h if self._prov_hist_dev is None \
            else self._prov_hist_dev + h
        self._prov_magg_dev = magg if self._prov_magg_dev is None \
            else self._prov_magg_dev + magg

    def _freeze_provisional(self) -> None:
        """Freeze the provisional fine-histogram range from the running
        range sketch — drains pending moments (the single host sync the
        overflow path pays, once per job)."""
        from .sketches import RangeSketch
        self._drain_moments()
        rs = RangeSketch(self.n_cols)
        rs.update(self.moments["min"], self.moments["max"])
        plo, phi = rs.provisional_bounds()
        self._prov_lo_d = jnp.asarray(plo, jnp.float32)
        self._prov_hi_d = jnp.asarray(phi, jnp.float32)

    def finalize_fused(self) -> None:
        """Close the fused sweep: exact [lo, hi] from the drained moments,
        then the retained device chunks replay through the histogram
        kernel on the exact grid (zero disk reads, zero H2D) and the
        provisional overflow histogram re-bins onto the exact grid ON
        DEVICE.  Afterwards the accumulator is in the same state pass 2
        would have left — ``finalize_sketch`` / ``compute_boundaries``
        work unchanged."""
        self.finalize_range()
        up = self._kernel_gate()
        for xd, vd, td, wd, live, rows in self._fused_chunks:
            h = _histogram_kernel(xd, vd, td, wd, self._lo_d, self._hi_d,
                                  self.num_buckets, use_pallas=up,
                                  unit_weight=self.unit_weight,
                                  expand=False,
                                  mesh=self.mesh if self._data_size() > 1
                                  else None)
            magg = _missing_agg_kernel(vd, td, wd, live,
                                       unit_weight=self.unit_weight,
                                       expand=False)
            self._hist_dev = h if self._hist_dev is None \
                else self._hist_dev + h
            self._magg_dev = magg if self._magg_dev is None \
                else self._magg_dev + magg
            self._pend_hist_rows += rows
            if self._pend_hist_rows >= self.DRAIN_ROWS:
                self._drain_hist()
        self._fused_chunks.clear()
        self._fused_bytes = 0
        if self._prov_hist_dev is not None:
            refined = _refine_prov_kernel(
                self._prov_hist_dev, self._prov_lo_d, self._prov_hi_d,
                self._lo_d, self._hi_d, self.num_buckets)
            self._hist_dev = refined if self._hist_dev is None \
                else self._hist_dev + refined
            self._magg_dev = self._prov_magg_dev \
                if self._magg_dev is None \
                else self._magg_dev + self._prov_magg_dev
            self._prov_hist_dev = None
            self._prov_magg_dev = None

    # ---- mid-sweep checkpointing (stats-step crash resume)
    def spill_resident(self) -> None:
        """Migrate the device-resident fused chunks into the PROVISIONAL
        histogram (freezing provisional bounds on first use) so the
        fused-sweep state becomes a few host-serializable arrays instead
        of a dataset-sized chunk list.  Afterwards the budget is zeroed:
        every later chunk accumulates provisionally too, which keeps an
        uninterrupted checkpointing run and a crash-resumed one on the
        SAME numeric path (both refine the identical provisional grid at
        finalize)."""
        if self._prov_lo_d is None:
            self._freeze_provisional()
        up = self._kernel_gate()
        for xd, vd, td, wd, live, _rows in self._fused_chunks:
            h = _histogram_kernel(xd, vd, td, wd, self._prov_lo_d,
                                  self._prov_hi_d, self.num_buckets,
                                  use_pallas=up,
                                  unit_weight=self.unit_weight,
                                  expand=False,
                                  mesh=self.mesh if self._data_size() > 1
                                  else None)
            magg = _missing_agg_kernel(vd, td, wd, live,
                                       unit_weight=self.unit_weight,
                                       expand=False)
            self._prov_hist_dev = h if self._prov_hist_dev is None \
                else self._prov_hist_dev + h
            self._prov_magg_dev = magg if self._prov_magg_dev is None \
                else self._prov_magg_dev + magg
        self._fused_chunks.clear()
        self._fused_bytes = 0
        self.fused_budget = 0

    def checkpoint_state(self) -> Dict[str, np.ndarray]:
        """Host-serializable snapshot of the fused-sweep accumulation
        (moments + provisional histogram).  Restoring it and replaying
        the remaining chunks reproduces an uninterrupted checkpointing
        run exactly (f32 provisional counts round-trip bit-identically)."""
        assert not self.exact, "exact (MunroPat) stats do not checkpoint"
        self.spill_resident()
        self._drain_moments()
        out: Dict[str, np.ndarray] = {
            "total_rows": np.asarray(self.total_rows, np.int64)}
        for k, v in self.moments.items():
            out[f"m_{k}"] = np.asarray(v)
        out["prov_lo"] = np.asarray(self._prov_lo_d)
        out["prov_hi"] = np.asarray(self._prov_hi_d)
        if self._prov_hist_dev is not None:
            out["prov_hist"] = np.asarray(self._prov_hist_dev)
            out["prov_magg"] = np.asarray(self._prov_magg_dev)
        return out

    def restore_checkpoint(self, state: Dict[str, np.ndarray]) -> None:
        self.total_rows = int(state["total_rows"])
        self.moments = {k[2:]: np.asarray(state[k], np.float64)
                        for k in state if k.startswith("m_")}
        if "count" in self.moments:
            self.missing = self.total_rows - self.moments["count"]
        self._prov_lo_d = jnp.asarray(state["prov_lo"], jnp.float32)
        self._prov_hi_d = jnp.asarray(state["prov_hi"], jnp.float32)
        if "prov_hist" in state:
            self._prov_hist_dev = jnp.asarray(state["prov_hist"],
                                              jnp.float32)
            self._prov_magg_dev = jnp.asarray(state["prov_magg"],
                                              jnp.float32)
        self._fused_chunks.clear()
        self._fused_bytes = 0
        self.fused_budget = 0          # continue in provisional mode

    def _drain_hist(self) -> None:
        if self._hist_dev is None:
            return
        # ONE packed fetch for both accumulators (two would be two trips;
        # with no weight column only the 2 count channels cross to the
        # host — the fetch is priced by its bytes)
        nch = 2 if self.unit_weight else 4
        packed = np.asarray(jnp.concatenate(
            [self._hist_dev.reshape(-1), self._magg_dev.reshape(-1)]),
            np.float64)
        self._hist_dev = None
        self._magg_dev = None
        self._pend_hist_rows = 0
        n_h = self.n_cols * self.num_buckets * nch
        h = packed[:n_h].reshape(self.n_cols, self.num_buckets, nch)
        magg = packed[n_h:].reshape(self.n_cols, nch)
        if self.unit_weight:                 # w_pos = #pos, w_neg = #neg
            h = np.concatenate([h, h], axis=2)
            magg = np.concatenate([magg, magg], axis=1)
        self.hist = h if self.hist is None else self.hist + h
        self.missing_agg = (magg if self.missing_agg is None
                            else self.missing_agg + magg)

    # ---- device-side finalize (the default stats path)
    def finalize_sketch(self, method: BinningMethod, max_bins: int):
        """Boundaries + per-bin stats + percentiles + distinct counts for
        EVERY column in one small packed fetch — the fine histogram never
        crosses the link (the drain path moves 8-16 MB at link bandwidth;
        this moves [C, max_bins]-sized results).

        Returns (boundaries: list of deduped [nb] arrays,
        aggs: list of [nb+1, 4] bin stats incl. trailing missing bin,
        pct: [C, 3] p25/median/p75, distinct: [C] ints) — element-exact
        with ``compute_boundaries`` + ``bin_counts`` + ``percentile`` +
        ``distinct_estimate`` (the parity test pins it)."""
        if self.hist is not None:
            # a mid-pass drain already moved counts to host float64 (>8M
            # rows); re-uploading as f32 would round counts past 2^24 —
            # stay on the exact host path for these TB-scale runs
            self._drain_hist()
            boundaries = self.compute_boundaries(method, max_bins)
            aggs = [self.bin_counts(c, boundaries[c])
                    for c in range(self.n_cols)]
            pct = np.stack([self.percentile(c, [0.25, 0.5, 0.75])
                            for c in range(self.n_cols)])
            distinct = np.array([self.distinct_estimate(c)
                                 for c in range(self.n_cols)])
            return boundaries, aggs, pct, distinct
        nch = 2 if self.unit_weight else 4
        hist_d = self._hist_dev
        magg_d = self._magg_dev
        assert hist_d is not None, "finalize_sketch needs pass-2 data"
        C, B = self.n_cols, max_bins
        interval = method == BinningMethod.EqualInterval
        packed = np.asarray(_finalize_sketch_kernel(
            hist_d, magg_d, self._lo_d, self._hi_d, method.value,
            B, self.num_buckets, nch, interval), np.float64)
        bnd_all, agg_all, pct, distinct, totals = np.split(
            packed, np.cumsum([C * B, C * (B + 1) * nch, C * 3, C]))
        bnd_all = bnd_all.reshape(C, B)
        agg_all = agg_all.reshape(C, B + 1, nch)
        pct = pct.reshape(C, 3)
        # all-missing columns have no percentiles (host path returns NaN,
        # serialized as null — not the empty-range fallback edge value)
        pct[np.asarray(self.moments["count"]) <= 0] = np.nan
        if nch == 2:                  # w_pos/w_neg mirror the counts
            agg_all = np.concatenate([agg_all, agg_all], axis=2)
        boundaries, aggs = [], []
        for c in range(C):
            if totals[c] <= 0 and not interval:
                # reference single-bin shape for a zero-measure column
                boundaries.append(np.array([NEG_INF]))
                agg = np.zeros((2, 4))
                agg[0] = agg_all[c, :B].sum(axis=0)
                agg[1] = agg_all[c, B]
                aggs.append(agg)
                continue
            bnds = bnd_all[c]
            keep = np.ones(B, bool)
            keep[1:] = np.diff(bnds) > 0              # _dedupe semantics
            # undeduped bin j collapses onto the last kept boundary <= j
            dd = np.cumsum(keep) - 1
            nb = int(keep.sum())
            agg = np.zeros((nb + 1, 4))
            np.add.at(agg, dd, agg_all[c, :B])
            agg[nb] = agg_all[c, B]
            boundaries.append(bnds[keep])
            aggs.append(agg)
        return boundaries, aggs, pct, distinct.astype(np.int64)

    # ---- boundary derivation
    def bucket_edges(self, col: int) -> np.ndarray:
        return np.linspace(self.lo[col], self.hi[col], self.num_buckets + 1)

    def compute_boundaries(self, method: BinningMethod, max_bins: int) -> List[np.ndarray]:
        """Per-column bin boundaries; element 0 is -inf like the reference's
        ``binBoundary`` (value v falls in bin i when b[i] <= v < b[i+1])."""
        self._drain_hist()
        assert self.hist is not None
        out = []
        for c in range(self.n_cols):
            h = self.hist[c]  # [K, 4]
            if method == BinningMethod.EqualInterval:
                inner = np.linspace(self.lo[c], self.hi[c], max_bins + 1)[:-1]
                bnds = np.concatenate([[NEG_INF], inner[1:]])
                out.append(_dedupe(bnds))
                continue
            # same channel mix as the device finalize (one mapping)
            weight_col = _method_weight_col(h[None], method.value, 4)[0]
            total = weight_col.sum()
            if total <= 0:
                out.append(np.array([NEG_INF]))
                continue
            cum = np.cumsum(weight_col)
            targets = total * np.arange(1, max_bins) / max_bins
            # first fine-bucket index where cum >= target -> boundary at its right edge
            pos = np.searchsorted(cum, targets, side="left")
            edges = self.bucket_edges(c)
            bnds = np.concatenate([[NEG_INF], edges[pos + 1]])
            out.append(_dedupe(bnds))
        return out

    def _exact_col(self, col: int):
        chunks = self._exact_cols[col]
        return (np.concatenate([c[0] for c in chunks]) if chunks
                else np.empty(0),
                np.concatenate([c[1] for c in chunks]) if chunks
                else np.empty(0, bool),
                np.concatenate([c[2] for c in chunks]) if chunks
                else np.empty(0))

    @staticmethod
    def _measure(method: BinningMethod):
        """Weight measure of one (pos, w) row set for a binning method —
        selected ONCE, not rebuilt per column."""
        return {
            BinningMethod.EqualTotal: lambda p, w: np.ones(len(p)),
            BinningMethod.EqualPositive: lambda p, w: p.astype(np.float64),
            BinningMethod.EqualNegtive: lambda p, w: (~p).astype(np.float64),
            BinningMethod.WeightEqualTotal: lambda p, w: w,
            BinningMethod.WeightEqualPositive: lambda p, w: w * p,
            BinningMethod.WeightEqualNegative: lambda p, w: w * ~p,
        }.get(method, lambda p, w: np.ones(len(p)))

    def compute_boundaries_exact(self, method: BinningMethod,
                                 max_bins: int) -> List[np.ndarray]:
        """Exact equal-frequency boundaries from the materialized values —
        the MunroPat path (reference ``MunroPatBinning.java:29`` exact
        quantiles): boundaries are TRUE data quantiles of the method's
        weight measure, not sketch-bucket edges.  Pair with
        :meth:`bin_counts_exact` — the sketch-based :meth:`bin_counts`
        assumes boundaries on bucket edges and would misassign rows tied
        at a mid-bucket boundary."""
        assert self._exact_cols is not None, \
            "exact boundaries need exact=True collection during pass 2"
        measure = self._measure(method)
        out = []
        for c in range(self.n_cols):
            vals, pos, ws = self._exact_col(c)
            if vals.size == 0:
                out.append(np.array([NEG_INF]))
                continue
            if method == BinningMethod.EqualInterval:
                inner = np.linspace(vals.min(), vals.max(), max_bins + 1)[:-1]
                out.append(_dedupe(np.concatenate([[NEG_INF], inner[1:]])))
                continue
            wrow = measure(pos, ws)
            order = np.argsort(vals, kind="stable")
            sv, sw = vals[order], wrow[order]
            cum = np.cumsum(sw)
            total = cum[-1]
            if total <= 0:
                out.append(np.array([NEG_INF]))
                continue
            targets = total * np.arange(1, max_bins) / max_bins
            pos_idx = np.searchsorted(cum, targets, side="left")
            pos_idx = np.minimum(pos_idx, len(sv) - 1)
            bnds = np.concatenate([[NEG_INF], sv[pos_idx]])
            out.append(_dedupe(bnds))
        return out

    def bin_counts_exact(self, col: int, boundaries: np.ndarray) -> np.ndarray:
        """Per-bin (pos, neg, wpos, wneg) from the EXACT materialized rows,
        with the same assignment rule scoring uses (``ColumnBinner
        .bin_numeric``: b[i] <= v < b[i+1]); trailing missing bin from the
        missing aggregation.  The sketch-based :meth:`bin_counts` is only
        exact when boundaries sit on fine-bucket edges — exact-quantile
        boundaries don't."""
        self._drain_hist()
        vals, pos, ws = self._exact_col(col)
        nb = len(boundaries)
        idx = np.clip(np.searchsorted(boundaries, vals, side="right") - 1,
                      0, nb - 1)
        agg = np.zeros((nb + 1, 4))
        np.add.at(agg, (idx, 0), pos.astype(np.float64))
        np.add.at(agg, (idx, 1), (~pos).astype(np.float64))
        np.add.at(agg, (idx, 2), ws * pos)
        np.add.at(agg, (idx, 3), ws * ~pos)
        if self.missing_agg is not None:
            agg[nb] = self.missing_agg[col]
        return agg

    def bin_counts(self, col: int, boundaries: np.ndarray) -> np.ndarray:
        """Exact per-bin (pos, neg, wpos, wneg) counts incl. trailing missing
        bin, derived by segment-summing fine buckets."""
        self._drain_hist()
        edges = self.bucket_edges(col)
        # fine bucket k covers [edges[k], edges[k+1]); assign to final bin
        bucket_bin = np.searchsorted(boundaries, edges[:-1], side="right") - 1
        bucket_bin = np.clip(bucket_bin, 0, len(boundaries) - 1)
        n_bins = len(boundaries)
        agg = np.zeros((n_bins + 1, 4))
        np.add.at(agg, bucket_bin, self.hist[col])
        if self.missing_agg is not None:
            agg[n_bins] = self.missing_agg[col]
        return agg

    def percentile(self, col: int, q: Sequence[float]) -> np.ndarray:
        """Approximate percentiles (to fine-bucket resolution) from the sketch."""
        self._drain_hist()
        h = self.hist[col][:, 0] + self.hist[col][:, 1]
        total = h.sum()
        if total <= 0:
            return np.full(len(q), np.nan)
        cum = np.cumsum(h)
        edges = self.bucket_edges(col)
        pos = np.searchsorted(cum, np.asarray(q) * total, side="left")
        return edges[np.minimum(pos + 1, self.num_buckets)]

    def distinct_estimate(self, col: int) -> int:
        """Lower-bound distinct estimate = occupied fine buckets (the
        reference uses HyperLogLog; this is the sketch-native analogue)."""
        self._drain_hist()
        return int((self.hist[col].sum(axis=1) > 0).sum())


def _dedupe(bnds: np.ndarray) -> np.ndarray:
    keep = np.ones(len(bnds), dtype=bool)
    keep[1:] = np.diff(bnds) > 0
    return bnds[keep]


@dataclass
class CategoricalAccumulator:
    """Exact per-category pos/neg/weight aggregation (dict-based, streamed)."""
    stats: Dict[str, Dict[str, np.ndarray]] = field(default_factory=dict)

    def update(self, col_name: str, values: np.ndarray, valid: np.ndarray,
               target: np.ndarray, weight: np.ndarray,
               stripped: bool = False) -> None:
        """``values`` may be pre-stripped (``stripped=True`` skips the
        string pass).  One factorize + four weighted bincounts per chunk —
        the per-chunk DataFrame/groupby this replaces was the host
        bottleneck on categorical-heavy (fraud-style) datasets
        (reference reducers are column-parallel,
        ``MapReducerStatsWorker.java:111-139``)."""
        import pandas as pd
        d = self.stats.setdefault(col_name, {})
        is_pos = target >= 0.5
        if not stripped:
            values = pd.Series(values, dtype=str).str.strip().to_numpy()
        codes, cats = pd.factorize(values)           # C hash table
        k = len(cats)
        # factorize codes NaN/None as -1; route them (and invalid rows) to
        # the missing slot rather than letting bincount see a negative
        idx = np.where(valid & (codes >= 0), codes, k)
        posf = is_pos.astype(np.float64)
        w = np.asarray(weight, np.float64)
        stacked = np.stack([
            np.bincount(idx, weights=posf, minlength=k + 1),
            np.bincount(idx, weights=1.0 - posf, minlength=k + 1),
            np.bincount(idx, weights=w * posf, minlength=k + 1),
            np.bincount(idx, weights=w * (1.0 - posf), minlength=k + 1)],
            axis=1)                                  # [k+1, 4]
        for i, cat in enumerate(cats):
            row = stacked[i]
            if not row.any():          # a missing-marker string: all rows
                continue               # of this category were invalid
            prev = d.get(cat)
            d[cat] = row if prev is None else prev + row
        m = stacked[k]
        if m.any():
            prev = d.get(_MISSING_KEY)
            d[_MISSING_KEY] = m if prev is None else prev + m

    def state_lists(self):
        """(meta, arrays) host snapshot for mid-sweep checkpoints: per
        column a category list (JSON side) + a [k, 4] count matrix."""
        meta, arrays = {}, {}
        for i, (col, d) in enumerate(self.stats.items()):
            cats = list(d.keys())
            meta[col] = {"i": i, "cats": cats}
            arrays[f"cat_{i}"] = (np.stack([d[c] for c in cats])
                                  if cats else np.zeros((0, 4)))
        return meta, arrays

    def load_state(self, meta, arrays) -> None:
        self.stats = {
            col: {c: np.asarray(arrays[f"cat_{m['i']}"][j], np.float64)
                  for j, c in enumerate(m["cats"])}
            for col, m in meta.items()}

    def finalize(self, col_name: str, max_cates: int = 0):
        """Return (categories, counts[cats+1, 4], n_distinct, n_missing) —
        last counts row = missing bin.  Categories ordered frequency desc; if
        ``max_cates``>0, overflow categories are folded into the missing bin
        (the reference caps via ``cateMaxNumBin``).  ``n_distinct`` /
        ``n_missing`` are the PRE-cap truths (the reference computes
        distinctCount from the raw value set, not the capped bin list)."""
        d = self.stats.get(col_name, {})
        items = [(k, v) for k, v in d.items() if k != _MISSING_KEY]
        n_distinct = len(items)
        items.sort(key=lambda kv: (-(kv[1][0] + kv[1][1]), kv[0]))
        missing = d.get(_MISSING_KEY, np.zeros(4))
        n_missing = int(missing[0] + missing[1])
        if max_cates and len(items) > max_cates:
            for _, v in items[max_cates:]:
                missing = missing + v
            items = items[:max_cates]
        cats = [k for k, _ in items]
        counts = np.stack([v for _, v in items] + [missing]) if items else \
            missing[None, :]
        return cats, counts, n_distinct, n_missing


_MISSING_KEY = "\x00__missing__"


# ----------------------------------------------------------------- binner
class ColumnBinner:
    """Maps raw column values -> bin indices given finalized binning.

    Numeric: searchsorted over binBoundary (boundary[0] = -inf); categorical:
    exact category index; missing/unseen -> ``num_bins`` (the trailing missing
    bin), matching reference ``BinUtils.getBinNum`` semantics.
    """

    def __init__(self, boundaries: Optional[np.ndarray] = None,
                 categories: Optional[List[str]] = None):
        assert (boundaries is None) != (categories is None)
        self.boundaries = None if boundaries is None else np.asarray(boundaries, np.float64)
        self.categories = categories
        if categories is None:
            self.cat_index = None
        else:
            # a bin label may be a merged group of raw categories joined by
            # CATEGORY_GROUP_SEP (dynamic rebin; reference CategoricalBinInfo)
            self.cat_index = {}
            for i, c in enumerate(categories):
                for member in c.split(CATEGORY_GROUP_SEP):
                    self.cat_index[member] = i

    @property
    def num_bins(self) -> int:
        if self.boundaries is not None:
            return len(self.boundaries)
        return len(self.categories)

    def bin_numeric(self, x: np.ndarray, valid: np.ndarray) -> np.ndarray:
        idx = np.searchsorted(self.boundaries, x, side="right") - 1
        idx = np.clip(idx, 0, self.num_bins - 1)
        return np.where(valid, idx, self.num_bins).astype(np.int32)

    def bin_categorical(self, values: np.ndarray) -> np.ndarray:
        import pandas as pd
        s = pd.Series(values, dtype=str).str.strip()
        idx = s.map(self.cat_index).fillna(self.num_bins).to_numpy(dtype=np.int64)
        return idx.astype(np.int32)
