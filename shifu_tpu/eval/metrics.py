"""Eval metrics: confusion-matrix sweep, ROC/PR/gain curves, AUC.

Reference ``core/ConfusionMatrix.java:62,553`` sorts scores descending and
walks thresholds accumulating unit + weighted tp/fp/tn/fn per bucket;
``core/eval/AreaUnderCurve.java:61-97`` integrates ROC by trapezoid;
``PerformanceEvaluator.java`` assembles the report.  Here the whole sweep is
one vectorized sort + cumsum — every threshold at once — and buckets are
sampled from the full curve afterwards.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np


@dataclass
class PerformancePoint:
    """One row of the reference's per-bucket report
    (``PerformanceResult``/``ConfusionMatrixObject``)."""
    binLowestScore: float
    tp: float
    fp: float
    fn: float
    tn: float
    precision: float
    recall: float            # catch rate / TPR
    fpr: float               # action rate on goods
    actionRate: float        # share of population at/above threshold
    liftUnit: float          # recall / actionRate
    weightedTp: float = 0.0
    weightedFp: float = 0.0
    weightedFn: float = 0.0
    weightedTn: float = 0.0
    weightedPrecision: float = 0.0
    weightedRecall: float = 0.0
    weightedFpr: float = 0.0


@dataclass
class PerformanceResult:
    areaUnderRoc: float
    weightedAuc: float
    areaUnderPr: float
    points: List[PerformancePoint] = field(default_factory=list)
    modelCount: int = 1
    recordCount: int = 0
    posCount: float = 0.0
    negCount: float = 0.0

    def to_dict(self) -> Dict:
        def clean(v):
            # NaN is not legal JSON — degenerate (single-class) sweeps
            # serialize as null
            return None if isinstance(v, float) and np.isnan(v) else v
        return {
            "areaUnderRoc": clean(self.areaUnderRoc),
            "weightedAuc": clean(self.weightedAuc),
            "areaUnderPr": clean(self.areaUnderPr),
            "recordCount": self.recordCount,
            "posCount": self.posCount,
            "negCount": self.negCount,
            "modelCount": self.modelCount,
            "performance": [vars(p) for p in self.points],
        }


def auc_trapezoid(fpr: np.ndarray, tpr: np.ndarray) -> float:
    """Trapezoid AUC over a monotone curve (reference
    ``AreaUnderCurve.java:61-97``)."""
    order = np.argsort(fpr, kind="stable")
    return float(np.trapezoid(tpr[order], fpr[order]))


@dataclass
class SweepCurves:
    """Full-resolution cumulative curves, scores descending."""
    thresholds: np.ndarray
    tp: np.ndarray
    fp: np.ndarray
    wtp: np.ndarray
    wfp: np.ndarray
    pos_total: float
    neg_total: float
    wpos_total: float
    wneg_total: float


def sweep(scores: np.ndarray, targets: np.ndarray,
          weights: Optional[np.ndarray] = None) -> SweepCurves:
    """Sort-desc + cumsum over every threshold at once.

    Tied scores collapse to one curve point (the end of the tie block): a
    threshold can only sit between distinct score values, so keeping
    intra-tie prefixes would make AUC depend on input row order.  The
    trapezoid over block ends integrates the diagonal across each tie."""
    scores = np.asarray(scores, np.float64)
    targets = np.asarray(targets, np.float64)
    w = np.ones_like(scores) if weights is None else np.asarray(weights, np.float64)
    order = np.argsort(-scores, kind="stable")
    s, t, ww = scores[order], targets[order], w[order]
    tp = np.cumsum(t)
    fp = np.cumsum(1.0 - t)
    wtp = np.cumsum(t * ww)
    wfp = np.cumsum((1.0 - t) * ww)
    if len(s):
        ends = np.flatnonzero(np.diff(s) != 0)
        keep = np.concatenate([ends, [len(s) - 1]])
        s, tp, fp, wtp, wfp = s[keep], tp[keep], fp[keep], wtp[keep], wfp[keep]
    return SweepCurves(thresholds=s, tp=tp, fp=fp, wtp=wtp, wfp=wfp,
                       pos_total=float(tp[-1]) if len(tp) else 0.0,
                       neg_total=float(fp[-1]) if len(fp) else 0.0,
                       wpos_total=float(wtp[-1]) if len(wtp) else 0.0,
                       wneg_total=float(wfp[-1]) if len(wfp) else 0.0)


CURVE_POINTS = 1024     # device-sweep downsample resolution (charts/buckets)


def _sweep_device_impl(s, t, w, points: int):
    """Whole confusion sweep ON DEVICE; one packed fetch.

    The host sweep (above) argsorts fetched scores, so a full-set fetch
    precedes the sort.  Here sort, cumsums and the tie-group reductions
    all run on device and only ``5*points + 7`` floats cross to the
    host.

    Deliberately scatter-free (TPU serializes scatters): tie groups are
    resolved with cummax/cummin scans + gathers —
      start_idx[i] = index of row i's tie-group start (forward cummax)
      end_idx[i]   = index of its group end (reverse cummin)
    AUC/wAUC use the tie-corrected Mann-Whitney sum, which equals the
    trapezoid over the tie-collapsed curve exactly; PR-AUC accumulates
    per-group trapezoid contributions at group-end rows.
    """
    import jax
    import jax.numpy as jnp

    n = s.shape[0]
    # f64 when x64 is live (checked, not assumed: .astype(f64) under
    # disabled x64 truncates with a warning per call)
    f = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    tiny = 1e-12
    neg_s, t, w = jax.lax.sort(
        (-s.astype(f), t.astype(f), w.astype(f)), num_keys=1,
        is_stable=True)
    s = -neg_s
    idx = jnp.arange(n)
    tp = jnp.cumsum(t)
    fp = jnp.cumsum(1.0 - t)
    wtp = jnp.cumsum(t * w)
    wfp = jnp.cumsum((1.0 - t) * w)
    pos, neg, wpos, wneg = tp[-1], fp[-1], wtp[-1], wfp[-1]

    newg = jnp.concatenate([jnp.ones(1, bool), s[1:] != s[:-1]])
    is_end = jnp.concatenate([s[1:] != s[:-1], jnp.ones(1, bool)])
    start_idx = jax.lax.cummax(jnp.where(newg, idx, -1))
    end_idx = jnp.flip(jax.lax.cummin(
        jnp.flip(jnp.where(is_end, idx, n - 1))))
    j_prev = start_idx - 1                      # end of the previous group
    jp = jnp.maximum(j_prev, 0)
    has_prev = j_prev >= 0

    fp_end, wfp_end = fp[end_idx], wfp[end_idx]
    fp_before = jnp.where(has_prev, fp[jp], 0.0)
    wfp_before = jnp.where(has_prev, wfp[jp], 0.0)
    # exact tie-corrected AUC: per positive row, negatives strictly below
    # + half the negatives tied with it
    auc = jnp.sum(t * ((neg - fp_end) + 0.5 * (fp_end - fp_before))) \
        / jnp.maximum(pos * neg, tiny)
    wauc = jnp.sum((t * w) * ((wneg - wfp_end)
                              + 0.5 * (wfp_end - wfp_before))) \
        / jnp.maximum(wpos * wneg, tiny)

    # PR-AUC trapezoid over group ends (r_{-1}=0, p_{-1}=p_0, matching
    # the host evaluate_curves integration)
    tp_end = tp[end_idx]
    prec_end = tp_end / jnp.maximum(tp_end + fp_end, tiny)
    rec_end = tp_end / jnp.maximum(pos, tiny)
    prev_tp = jnp.where(has_prev, tp[jp], 0.0)
    prev_fp = jnp.where(has_prev, fp[jp], 0.0)
    prev_prec = jnp.where(
        has_prev, prev_tp / jnp.maximum(prev_tp + prev_fp, tiny), prec_end)
    prev_rec = prev_tp / jnp.maximum(pos, tiny)
    pr_auc = jnp.sum(jnp.where(
        is_end, (rec_end - prev_rec) * (prec_end + prev_prec) * 0.5, 0.0))

    # downsampled curve: 'points' equal-population rows snapped to their
    # tie-group end (cumulative population at row i is exactly i+1)
    rows = jnp.clip((jnp.arange(1, points + 1) * n) // points - 1, 0, n - 1)
    e = end_idx[rows]
    packed = jnp.concatenate([
        s[e], tp[e], fp[e], wtp[e], wfp[e],
        jnp.stack([auc, wauc, pr_auc, pos, neg, wpos, wneg])])
    return packed


_sweep_device_jit = None      # lazily jitted (keeps jax import lazy here)


def sweep_device(scores, targets, weights=None,
                 points: int = CURVE_POINTS):
    """Device-side :func:`sweep`: returns ``(SweepCurves, exact_aucs)``.

    ``scores``/``targets``/``weights`` may live on device already (the
    scorer's resident plane) — nothing but the packed curve crosses the
    link.  ``exact_aucs`` is ``(auc, wauc, pr_auc)`` at full resolution;
    the curves are downsampled to ``points`` for charts/buckets.
    """
    import jax
    import jax.numpy as jnp

    n = int(scores.shape[0])
    if n == 0:
        return sweep(np.zeros(0), np.zeros(0)), (float("nan"),) * 3
    if weights is None:
        weights = jnp.ones(n, jnp.float32)
    global _sweep_device_jit
    if _sweep_device_jit is None:
        _sweep_device_jit = jax.jit(_sweep_device_impl,
                                    static_argnames=("points",))
    packed = np.asarray(_sweep_device_jit(
        jnp.asarray(scores), jnp.asarray(targets), jnp.asarray(weights),
        min(points, n)))
    p = min(points, n)
    thr, tp, fp, wtp, wfp = (packed[i * p:(i + 1) * p] for i in range(5))
    auc, wauc, pr_auc, pos, neg, wpos, wneg = packed[5 * p:]
    if p > 1:     # collapse duplicate group snaps (ties / n < points)
        keep = np.concatenate([np.flatnonzero(np.diff(thr) != 0),
                               [p - 1]])
        thr, tp, fp, wtp, wfp = (a[keep] for a in (thr, tp, fp, wtp, wfp))
    curves = SweepCurves(thresholds=thr, tp=tp, fp=fp, wtp=wtp, wfp=wfp,
                         pos_total=float(pos), neg_total=float(neg),
                         wpos_total=float(wpos), wneg_total=float(wneg))
    return curves, (float(auc), float(wauc), float(pr_auc))


def evaluate_scores_device(scores, targets, weights=None,
                           buckets: int = 10,
                           points: int = CURVE_POINTS):
    """Device-plane :func:`evaluate_scores`: returns ``(curves, result)``
    with AUC/wAUC/PR-AUC computed exactly on device (the bucket rows come
    from the downsampled curve — boundary error ≤ 1/points of the
    population, the reference's own bucket granularity is 1/10)."""
    curves, (auc, wauc, pr_auc) = sweep_device(scores, targets, weights,
                                               points)
    result = evaluate_curves(curves, buckets)
    if not np.isnan(result.areaUnderRoc):
        result.areaUnderRoc = auc
        result.weightedAuc = wauc
        result.areaUnderPr = pr_auc
    return curves, result


def evaluate_scores(scores: np.ndarray, targets: np.ndarray,
                    weights: Optional[np.ndarray] = None,
                    buckets: int = 10) -> PerformanceResult:
    """Full eval report: AUC (unit + weighted), PR AUC, per-bucket confusion
    rows at ``buckets`` equal-population thresholds (reference
    ``performanceBucketNum``, default 10)."""
    return evaluate_curves(sweep(scores, targets, weights), buckets)


def evaluate_curves(c: SweepCurves, buckets: int = 10) -> PerformanceResult:
    """Report from precomputed curves — callers that also render charts
    (``eval/report.py``) sweep ONCE and share."""
    n = len(c.thresholds)           # distinct thresholds (ties collapsed)
    total = int(c.pos_total + c.neg_total)
    if n == 0 or c.pos_total == 0 or c.neg_total == 0:
        return PerformanceResult(float("nan"), float("nan"), float("nan"),
                                 recordCount=total, posCount=c.pos_total,
                                 negCount=c.neg_total)
    tpr = c.tp / c.pos_total
    fpr = c.fp / c.neg_total
    wtpr = c.wtp / max(c.wpos_total, 1e-12)
    wfpr = c.wfp / max(c.wneg_total, 1e-12)
    precision = c.tp / np.maximum(c.tp + c.fp, 1e-12)

    auc = auc_trapezoid(np.concatenate([[0.0], fpr, [1.0]]),
                        np.concatenate([[0.0], tpr, [1.0]]))
    wauc = auc_trapezoid(np.concatenate([[0.0], wfpr, [1.0]]),
                         np.concatenate([[0.0], wtpr, [1.0]]))
    # PR AUC over recall axis
    pr_auc = float(np.trapezoid(
        np.concatenate([[precision[0]], precision]),
        np.concatenate([[0.0], tpr])))

    points = []
    cum_pop = c.tp + c.fp
    for b in range(1, buckets + 1):
        # bucket boundary = threshold closest to b/buckets population share
        i = min(n - 1, int(np.searchsorted(cum_pop, b * total / buckets)))
        tp_, fp_ = float(c.tp[i]), float(c.fp[i])
        fn_, tn_ = c.pos_total - tp_, c.neg_total - fp_
        wtp_, wfp_ = float(c.wtp[i]), float(c.wfp[i])
        wfn_, wtn_ = c.wpos_total - wtp_, c.wneg_total - wfp_
        action = float(cum_pop[i]) / total
        points.append(PerformancePoint(
            binLowestScore=float(c.thresholds[i]),
            tp=tp_, fp=fp_, fn=fn_, tn=tn_,
            precision=tp_ / max(tp_ + fp_, 1e-12),
            recall=tp_ / max(c.pos_total, 1e-12),
            fpr=fp_ / max(c.neg_total, 1e-12),
            actionRate=action,
            liftUnit=(tp_ / max(c.pos_total, 1e-12)) / max(action, 1e-12),
            weightedTp=wtp_, weightedFp=wfp_, weightedFn=wfn_, weightedTn=wtn_,
            weightedPrecision=wtp_ / max(wtp_ + wfp_, 1e-12),
            weightedRecall=wtp_ / max(c.wpos_total, 1e-12),
            weightedFpr=wfp_ / max(c.wneg_total, 1e-12)))
    return PerformanceResult(
        areaUnderRoc=auc, weightedAuc=wauc, areaUnderPr=pr_auc, points=points,
        recordCount=total, posCount=c.pos_total, negCount=c.neg_total)


def gain_chart_rows(result: PerformanceResult) -> List[Dict]:
    """Gain-chart table (reference ``core/eval/GainChart.java`` csv body)."""
    return [{"actionRate": p.actionRate, "recall": p.recall,
             "precision": p.precision, "lift": p.liftUnit,
             "weightedRecall": p.weightedRecall, "score": p.binLowestScore}
            for p in result.points]


def evaluate_multiclass(class_scores: np.ndarray, targets: np.ndarray,
                        weights: Optional[np.ndarray] = None) -> Dict:
    """Multi-class eval report: weighted accuracy (argmax vote, reference
    ``MultiClsTagPredictor.predictTag``), per-class one-vs-rest AUC, macro
    AUC, and the K x K weighted confusion matrix.

    class_scores: [n, K]; targets: [n] class indices.
    """
    class_scores = np.asarray(class_scores, np.float64)
    t = np.asarray(targets).astype(int)
    n, k = class_scores.shape
    w = np.ones(n) if weights is None else np.asarray(weights, np.float64)
    pred = class_scores.argmax(axis=1)
    acc = float((w * (pred == t)).sum() / max(w.sum(), 1e-12))
    conf = np.zeros((k, k))
    np.add.at(conf, (t, pred), w)
    aucs = []
    for ci in range(k):
        c = sweep(class_scores[:, ci], (t == ci).astype(float), w)
        if c.pos_total > 0 and c.neg_total > 0:
            aucs.append(auc_trapezoid(c.fp / c.neg_total, c.tp / c.pos_total))
        else:
            aucs.append(float("nan"))
    finite = [a for a in aucs if np.isfinite(a)]
    return {"nClasses": k, "recordCount": int(n),
            "accuracy": acc, "errorRate": 1.0 - acc,
            "perClassAuc": [float(a) for a in aucs],
            "macroAuc": float(np.mean(finite)) if finite else float("nan"),
            "classCounts": np.bincount(t, minlength=k).tolist(),
            "confusionMatrix": conf.tolist()}
