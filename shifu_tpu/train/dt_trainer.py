"""GBT / RF distributed trainers — reference ``DTMaster``/``DTWorker``
(``core/dtrain/dt/``, 8.5k LoC) as device-side histogram + scan loops.

- GBT (``DTWorker.java:582-686`` residual update, ``DTMaster.java:392-435``
  tree switching): sequential trees; per-tree gradients (squared: y − f,
  log: y − sigmoid(f)) refit by a variance/Friedman tree; shrinkage
  ``learning_rate``; moving-average early stop
  (``dt/DTEarlyStopDecider.java``).
- RF (``DTWorker`` Poisson bagging + oob-as-validation): independent trees
  over Poisson row weights, entropy/gini impurity, per-tree feature
  subsetting (featureSubsetStrategy ALL/HALF/SQRT/LOG2/ONETHIRD/TWOTHIRDS).
- Whole-tree growth is ONE jitted program per round (``ops.tree.
  grow_tree_jit``); residuals/oob accumulators stay device-resident across
  trees — one host sync per tree (errors + the tiny tree arrays), not per
  level (the reference syncs worker↔master stats every level).
- On a mesh, rows shard over the ``data`` axis and XLA's psum aggregates the
  [nodes, C, B, S] histograms — the ``DTWorker``→``DTMaster`` merge
  (``DTMaster.java:274-533``) on ICI.
- Streaming mode (dataset > memory budget): per-level histogram accumulation
  over ``ShardStream`` windows; per-row residual/oob state lives in compact
  host caches (rows × 8B, ~100× smaller than the binned matrix).
- Mid-forest checkpointing every N trees + ``train -resume`` (reference
  ``DTMaster.doCheckPoint``, ``:637``); per-tree stateless RNG keys make a
  resumed run bit-identical to an uninterrupted one.
- Feature importance accumulates realized split GAINS (reference GainInfo
  aggregation), not split counts.
"""

from __future__ import annotations

import json
import logging
import os
from dataclasses import dataclass, field, replace
from functools import lru_cache, partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import faults, ioutil, obs
from ..config.model_config import Algorithm
from ..data.shards import Shards
from ..models import tree as tree_model
from ..ops.tree import (TreeArrays, _left_child_index, _level_leaf_raw,
                        best_splits, build_histograms,
                        build_histograms_batch, build_path_histograms,
                        cap_splits_by_leaves, grow_forest_jit,
                        grow_tree_jit, leaf_values_from_raw, n_tree_nodes,
                        node_index_at_level, predict_tree)
from .early_stop import GBTEarlyStopDecider
from .sampling import validation_split

log = logging.getLogger(__name__)


@dataclass
class DTSettings:
    n_trees: int = 100
    depth: int = 7
    impurity: str = "variance"
    loss: str = "squared"
    learning_rate: float = 0.05          # GBT shrinkage
    min_instances: float = 1.0
    min_gain: float = 0.0
    feature_subset: str = "ALL"
    valid_rate: float = 0.2
    bagging_rate: float = 1.0            # RF Poisson rate
    poisson_bagging: bool = True         # False: plain single tree (DT)
    early_stop: bool = False
    seed: int = 0
    checkpoint_dir: str = ""             # "" disables mid-forest checkpoints
    checkpoint_every: int = 25           # trees between checkpoints
    resume: bool = False
    n_classes: int = 0                   # >2: RF multiclass NATIVE mode
    max_leaves: int = 0                  # >0: leaf-wise node budget
    stats_exact: bool = False            # weights promised small-integer
                                         # (no weight column): RF hist
                                         # kernel skips f32-recovery dots
    tree_batch: int = 0                  # RF same-round trees grown per
                                         # batched device program; 0 = auto
                                         # (RF_TREE_BATCH)
    early_stop_check: int = 8            # trees between early-stop
                                         # decisions (device-accumulated
                                         # errors fetch in bulk)
    tail_tree_batch: int = 0             # RF disk-tail super-batch: trees
                                         # fed by one tail re-stream; 0 =
                                         # auto (budget-derived, see
                                         # _tail_super_batch)


def settings_from_params(params: Dict[str, Any], train_conf,
                         alg: Algorithm) -> DTSettings:
    """Reference train#params tree keys (``DTMaster.java:91`` init region):
    TreeNum / MaxDepth / Impurity / Loss / LearningRate /
    FeatureSubsetStrategy / MinInstancesPerNode / MinInfoGain."""
    p = params or {}
    default_impurity = "variance" if alg == Algorithm.GBT else "entropy"
    return DTSettings(
        n_trees=int(p.get("TreeNum", 10 if alg != Algorithm.DT else 1)),
        depth=int(p.get("MaxDepth", 7)),
        impurity=str(p.get("Impurity", default_impurity)).lower(),
        loss=str(p.get("Loss", "squared")).lower(),
        learning_rate=float(p.get("LearningRate", 0.05)),
        min_instances=float(p.get("MinInstancesPerNode", 1)),
        min_gain=float(p.get("MinInfoGain", 0.0)),
        feature_subset=str(p.get("FeatureSubsetStrategy", "ALL")).upper(),
        max_leaves=max(0, int(p.get("MaxLeaves", -1))),
        valid_rate=float(train_conf.validSetRate),
        bagging_rate=float(train_conf.baggingSampleRate),
        poisson_bagging=alg != Algorithm.DT,  # plain DT = one tree, full data
        early_stop=bool(train_conf.earlyStopEnable),
        seed=int(p.get("Seed", 0)),
        checkpoint_every=int(p.get("CheckpointInterval", 25)),
        tree_batch=int(p.get("TreeBatch", 0)),
        early_stop_check=max(1, int(p.get("EarlyStopCheckInterval", 8))),
        tail_tree_batch=int(p.get("TailTreeBatch", 0)))


def subset_count(strategy: str, c: int) -> int:
    s = strategy.upper()
    if s == "ALL":
        return c
    if s == "HALF":
        return max(1, c // 2)
    if s == "SQRT":
        return max(1, int(np.sqrt(c)))
    if s == "LOG2":
        return max(1, int(np.log2(max(c, 2))))
    if s == "ONETHIRD":
        return max(1, c // 3)
    if s == "TWOTHIRDS":
        return max(1, 2 * c // 3)
    return c


def _tree_rng(seed: int, tree_idx: int) -> np.random.Generator:
    """Stateless per-tree RNG: resume from tree k reproduces the exact
    feature subsets / bags an uninterrupted run would draw."""
    return np.random.default_rng([seed, tree_idx])


def _feat_subset(settings: DTSettings, c: int, tree_idx: int) -> np.ndarray:
    k = subset_count(settings.feature_subset, c)
    fa = np.zeros(c, bool)
    fa[_tree_rng(settings.seed, tree_idx).choice(c, size=k, replace=False)] = True
    return fa


@dataclass
class ForestResult:
    trees: List[TreeArrays]
    spec_kwargs: Dict[str, Any]
    train_error: float
    valid_error: float
    feature_importance: np.ndarray       # [C] summed split gains
    trees_built: int = 0
    history: List[Tuple[float, float]] = field(default_factory=list)
    disk_passes: int = 0                 # streamed mode: cold stream sweeps taken
    tail_sweeps: int = 0                 # streamed mode: disk-tail re-streams
                                         # (the super-batch schedule's guard
                                         # metric)
    bytes_read: int = 0                  # streamed mode: bytes this train
                                         # run pulled off disk (host-side
                                         # stream accounting, telemetry-
                                         # independent)


# ---------------------------------------------------------------- jitted rounds
def _loss_grad(y, f, loss: str):
    if loss == "log":
        return y - jax.nn.sigmoid(f)
    if loss == "absolute":
        return jnp.sign(y - f)
    return y - f


def _per_row_loss(y, f, loss: str):
    if loss == "log":
        p = jnp.clip(jax.nn.sigmoid(f), 1e-9, 1 - 1e-9)
        return -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
    if loss == "absolute":
        return jnp.abs(y - f)
    return (y - f) ** 2


def _gbt_round_impl(bins, y, tw, vw, f, fa, cat, lr, min_instances,
                    min_gain, n_bins: int, depth: int, impurity: str,
                    loss: str, use_pallas: bool = False,
                    max_leaves: int = 0, has_cat: bool = True, mesh=None):
    """One GBT tree end-to-end on device: residual grad → grow → predict →
    score update → train/valid error sums.  Only the tree arrays and two
    scalars cross to the host."""
    grad = _loss_grad(y, f, loss)
    stats = jnp.stack([tw, tw * grad], axis=1).astype(jnp.float32)
    sf, lm, lv, gfi, leaf_glob = grow_tree_jit(
        bins, stats, cat, fa, n_bins, depth, impurity, min_instances,
        min_gain, use_pallas=use_pallas, max_leaves=max_leaves,
        has_cat=has_cat, mesh=mesh)
    pred = jnp.take(lv, leaf_glob, axis=0)   # growth already walked the
    f2 = f + lr * pred                       # rows to their leaves
    per = _per_row_loss(y, f2, loss)
    tr = (per * tw).sum() / jnp.maximum(tw.sum(), 1e-9)
    va = (per * vw).sum() / jnp.maximum(vw.sum(), 1e-9)
    return sf, lm, lv, gfi, f2, tr, va



def _gbt_forest_impl(bins, y, tw, vw, f, fa_all, cat, lr, min_instances,
                     min_gain, n_bins: int, depth: int, impurity: str,
                     loss: str, n_trees: int, use_pallas: bool = False,
                     max_leaves: int = 0, has_cat: bool = True, mesh=None):
    """A whole chunk of the GBT forest as ONE executable (``lax.scan`` over
    trees).  The per-tree loop costs one program execution and one host
    fetch per tree, each with a fixed latency next to sub-ms tree compute,
    so the forest scans on device and crosses to the host once.  This is the
    natural end point of the reference's master/worker iteration collapse
    (``DTMaster.java:274-533`` per-iteration sync → zero syncs)."""
    del n_trees    # shape comes from fa_all; static arg keys the cache

    def body(f, fa):
        sf, lm, lv, gfi, f2, tr, va = _gbt_round_impl(
            bins, y, tw, vw, f, fa, cat, lr, min_instances, min_gain,
            n_bins, depth, impurity, loss, use_pallas, max_leaves,
            has_cat, mesh)
        return f2, _pack_tree_impl(sf, lm, lv, gfi, tr, va)

    f_out, packed = jax.lax.scan(body, f, fa_all)
    return f_out, packed


# cost-attributed (obs/costs, lazy: wrapped at import, telemetry flips
# later): the resident whole-forest executable — the gbt plane's main
# cost entry for the utilization report
_gbt_forest = obs.costed_jit("gbt.forest", _gbt_forest_impl, lazy=True,
                             static_argnames=(
    "n_bins", "depth", "impurity", "loss", "n_trees", "use_pallas",
    "max_leaves", "has_cat", "mesh"))


@lru_cache(maxsize=None)
def _gbt_forest_multi(n_bins: int, depth: int, impurity: str, loss: str,
                      n_trees: int, use_pallas: bool, max_leaves: int,
                      has_cat: bool, mesh=None):
    """vmapped :func:`_gbt_forest_impl` over a leading member axis —
    bagging members / same-structure grid trials train as ONE executable
    (reference queues one Guagua job per bag/combo,
    ``TrainModelProcessor.java:768-945``).  Members vary in weights,
    scores, feature subsets and the traced scalar hypers (lr /
    min_instances / min_gain); ``bins``/``y``/``cat`` broadcast."""
    def one(bins, y, tw, vw, f, fa_all, cat, lr, mi, mg):
        return _gbt_forest_impl(bins, y, tw, vw, f, fa_all, cat, lr, mi,
                                mg, n_bins, depth, impurity, loss, n_trees,
                                use_pallas, max_leaves, has_cat, mesh)
    return obs.costed_jit(
        "gbt.forest_bagged",
        jax.vmap(one, in_axes=(None, None, 0, 0, 0, 0, None, 0, 0,
                               0)))


def _stats_bf16_exact(w) -> bool:
    """True when every weight is a small non-negative integer, so RF stat
    channels (Poisson bag counts x weights x 0/1 targets) are exactly
    representable in bfloat16 and the histogram kernel may skip its
    f32-recovery dots (``ops/hist_pallas._hist_kernel``, ~1.6x).  Bag
    counts cap at 16, so w <= 16 keeps products <= 256 (bf16-exact)."""
    w = np.asarray(w)
    return bool(w.size and (w >= 0).all() and (w <= 16).all()
                and (np.mod(w, 1) == 0).all())


def _rf_round_impl(bins, y, w, key, bag_rate, oob_sum, oob_cnt, fa, cat,
                   min_instances, min_gain, n_bins: int, depth: int,
                   impurity: str, loss: str, poisson: bool,
                   n_classes: int = 0, use_pallas: bool = False,
                   max_leaves: int = 0, has_cat: bool = True, mesh=None,
                   stats_exact: bool = False):
    """One RF tree on device: Poisson bag → grow → oob accumulate →
    loss-consistent oob validation error (reference oob-as-validation,
    ``DTWorker.java:582-616``; round 1 hardcoded squared error).

    Multiclass NATIVE (``n_classes > 2``): per-class stat channels, leaf
    class distributions, misclassification-rate errors (reference
    ``dt/Impurity.java:368,553`` multiclass Entropy/Gini)."""
    n = bins.shape[0]
    bag = jax.random.poisson(key, bag_rate, (n,)).astype(jnp.float32) \
        if poisson else jnp.ones(n, jnp.float32)
    return _rf_round_from_bag(bins, y, w, bag, oob_sum, oob_cnt, fa, cat,
                              min_instances, min_gain, n_bins, depth,
                              impurity, loss, n_classes, use_pallas,
                              max_leaves, has_cat, mesh, stats_exact)


def _rf_stats_from_bag(y, w, bag, n_classes: int):
    """Per-row stat channels of one RF tree's bag — the ONE place the
    channel layout lives (per-tree, batched and streamed paths must never
    drift)."""
    bw = w * bag
    if n_classes > 2:
        return bw[:, None] * jax.nn.one_hot(y.astype(jnp.int32), n_classes,
                                            dtype=jnp.float32)
    return jnp.stack([bw, bw * y], axis=1).astype(jnp.float32)


def _rf_oob_update(pred, y, w, bag, oob_sum, oob_cnt, loss: str,
                   n_classes: int):
    """Out-of-bag vote accumulation + loss-consistent errors for ONE grown
    tree (reference oob-as-validation, ``DTWorker.java:582-616``) —
    shared by the per-tree round and the tree-batched round so their
    error streams stay bit-identical.  Returns (oob_sum, oob_cnt, tr, va).
    """
    oob = (bag == 0) & (w > 0)
    if n_classes > 2:
        yi = y.astype(jnp.int32)
        oob_sum = oob_sum + jnp.where(oob[:, None], pred, 0.0)
        oob_cnt = oob_cnt + oob.astype(oob_cnt.dtype)
        seen = oob_cnt > 0
        per_v = (jnp.argmax(oob_sum, axis=-1) != yi).astype(jnp.float32)
        per_t = (jnp.argmax(pred, axis=-1) != yi).astype(jnp.float32)
        wv = w * seen
        va = (per_v * wv).sum() / jnp.maximum(wv.sum(), 1e-9)
        tr = (per_t * w).sum() / jnp.maximum(w.sum(), 1e-9)
        return oob_sum, oob_cnt, tr, va
    oob_sum = oob_sum + jnp.where(oob, pred, 0.0)
    oob_cnt = oob_cnt + oob.astype(oob_cnt.dtype)
    seen = oob_cnt > 0
    oob_pred = oob_sum / jnp.maximum(oob_cnt, 1.0)
    # RF votes average probabilities; log loss needs them clipped, not logit
    if loss == "log":
        p = jnp.clip(oob_pred, 1e-9, 1 - 1e-9)
        per_v = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
    else:
        per_v = _per_row_loss(y, oob_pred, loss)
    wv = w * seen
    va = (per_v * wv).sum() / jnp.maximum(wv.sum(), 1e-9)
    per_t = _per_row_loss(y, pred, loss) if loss != "log" else \
        -(y * jnp.log(jnp.clip(pred, 1e-9, 1 - 1e-9))
          + (1 - y) * jnp.log(jnp.clip(1 - pred, 1e-9, 1 - 1e-9)))
    tr = (per_t * w).sum() / jnp.maximum(w.sum(), 1e-9)
    return oob_sum, oob_cnt, tr, va


def _rf_round_from_bag(bins, y, w, bag, oob_sum, oob_cnt, fa, cat,
                       min_instances, min_gain, n_bins: int, depth: int,
                       impurity: str, loss: str, n_classes: int = 0,
                       use_pallas: bool = False, max_leaves: int = 0,
                       has_cat: bool = True, mesh=None,
                       stats_exact: bool = False):
    """RF round body given a PRECOMPUTED bag — shared by the resident
    path (Poisson drawn in-graph above) and the streamed mega path
    (hash bags replayed on device, ``ops/hashing.py``)."""
    stats = _rf_stats_from_bag(y, w, bag, n_classes)
    sf, lm, lv, gfi, leaf_glob = grow_tree_jit(
        bins, stats, cat, fa, n_bins, depth, impurity, min_instances,
        min_gain, n_classes, use_pallas, max_leaves, has_cat, mesh,
        stats_exact)
    pred = jnp.take(lv, leaf_glob, axis=0)         # [n, K] mc, [n] binary
    oob_sum, oob_cnt, tr, va = _rf_oob_update(
        pred, y, w, bag, oob_sum, oob_cnt, loss, n_classes)
    return sf, lm, lv, gfi, oob_sum, oob_cnt, tr, va



def _mask_nbytes(total: int, n_bins: int) -> int:
    return (total * n_bins + 7) // 8


def _pack_mask_bits(lm):
    """left_mask bits packed 8-per-byte-value (f32-exact 0..255) for the
    host fetch — the mask is ~96%% of a packed tree's floats, so bit
    packing shrinks every tree transfer ~8x on the wire.  MSB-first to
    match ``np.unpackbits`` in :func:`_unpack_mask_bits`."""
    flat = lm.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % 8
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.float32)])
    w = jnp.asarray([128, 64, 32, 16, 8, 4, 2, 1], jnp.float32)
    return flat.reshape(-1, 8) @ w


def _unpack_mask_bits(vals: np.ndarray, total: int, n_bins: int):
    bits = np.unpackbits(np.asarray(np.rint(vals), np.uint8))
    return bits[:total * n_bins].reshape(total, n_bins) > 0


def _pack_tree_impl(sf, lm, lv, gfi, tr, va):
    """Flatten one round's outputs into a single f32 vector so the host
    fetches the whole tree in ONE transfer: every device→host fetch pays
    a fixed cost regardless of size, and unbatched per-array fetches
    scale that cost with arrays x trees."""
    return jnp.concatenate([
        sf.astype(jnp.float32), _pack_mask_bits(lm),
        lv.reshape(-1).astype(jnp.float32), gfi.astype(jnp.float32),
        jnp.stack([tr, va]).astype(jnp.float32)])


_pack_tree = jax.jit(_pack_tree_impl)  # shifu-lint: disable=recompile-hazard

# RF same-round trees grown per batched device program in the RESIDENT
# path (``grow_forest_jit``): each level's TB histograms build in ONE
# kernel launch with the bins one-hot shared across the batch.  8 matches
# the tail-sweep batch and the progress burst size.
RF_TREE_BATCH = 8


def _effective_tree_batch(settings: DTSettings) -> int:
    """The RF resident tree-batch width: ``TreeBatch`` train param /
    ``SHIFU_TREE_BATCH`` env; 0 = auto (:data:`RF_TREE_BATCH`)."""
    env = os.environ.get("SHIFU_TREE_BATCH")
    if env:
        return max(1, int(env))
    return settings.tree_batch if settings.tree_batch > 0 \
        else RF_TREE_BATCH


def _rf_forest_impl(bins, y, w, base_key, tree_ids, bag_rate, oob_sum,
                    oob_cnt, fa_all, cat, min_instances, min_gain,
                    n_bins: int, depth: int, impurity: str, loss: str,
                    poisson: bool, n_classes: int, n_trees: int,
                    use_pallas: bool = False, max_leaves: int = 0,
                    has_cat: bool = True, mesh=None,
                    stats_exact: bool = False, tree_batch: int = 1):
    """A chunk of the RF forest as ONE executable (see :func:`_gbt_forest`).
    Per-tree keys fold the tree id into the base key on device — identical
    draws to the per-tree path, so resumed and scanned runs agree.

    ``tree_batch > 1``: RF trees are mutually independent, so the scan
    grows TB same-round trees per step through :func:`grow_forest_jit` —
    each level's TB histograms build in ONE kernel launch (the reference's
    ``DTMaster`` grows all RF trees of a round simultaneously,
    ``DTMaster.java:91`` toDoQueue).  Bags/keys/oob votes replay the exact
    per-tree stream (bags are per-tree key folds; oob votes chain through
    the batch in tree order), so results are bit-identical to
    ``tree_batch=1``; a chunk remainder past the last full batch runs the
    per-tree scan."""
    del n_trees
    n = bins.shape[0]

    def one_tree(carry, fa, ti):
        oob_sum, oob_cnt = carry
        key = jax.random.fold_in(base_key, ti)
        sf, lm, lv, gfi, oob_sum2, oob_cnt2, tr, va = _rf_round_impl(
            bins, y, w, key, bag_rate, oob_sum, oob_cnt, fa, cat,
            min_instances, min_gain, n_bins, depth, impurity, loss,
            poisson, n_classes, use_pallas, max_leaves, has_cat, mesh,
            stats_exact)
        return (oob_sum2, oob_cnt2), _pack_tree_impl(sf, lm, lv, gfi, tr, va)

    def body(carry, inp):
        fa, ti = inp
        return one_tree(carry, fa, ti)

    def body_batched(carry, inp):
        oob_sum, oob_cnt = carry
        fa_b, ti_b = inp                       # [TB, C], [TB]
        keys = jax.vmap(lambda t: jax.random.fold_in(base_key, t))(ti_b)
        if poisson:
            bags = jax.vmap(lambda k: jax.random.poisson(
                k, bag_rate, (n,)).astype(jnp.float32))(keys)
        else:
            bags = jnp.ones((tree_batch, n), jnp.float32)
        stats_b = jax.vmap(
            lambda bag: _rf_stats_from_bag(y, w, bag, n_classes))(bags)
        sf_b, lm_b, lv_b, gfi_b, lg_b = grow_forest_jit(
            bins, stats_b, cat, fa_b, n_bins, depth, impurity,
            min_instances, min_gain, n_classes, use_pallas, max_leaves,
            has_cat, mesh, stats_exact)
        packed = []
        for j in range(tree_batch):            # oob votes chain in order
            pred = jnp.take(lv_b[j], lg_b[j], axis=0)
            oob_sum, oob_cnt, tr, va = _rf_oob_update(
                pred, y, w, bags[j], oob_sum, oob_cnt, loss, n_classes)
            packed.append(_pack_tree_impl(sf_b[j], lm_b[j], lv_b[j],
                                          gfi_b[j], tr, va))
        return (oob_sum, oob_cnt), jnp.stack(packed)

    t_total = fa_all.shape[0]
    tb = max(1, tree_batch)
    main = (t_total // tb) * tb if tb > 1 else 0
    parts = []
    carry = (oob_sum, oob_cnt)
    if main:
        fa_g = fa_all[:main].reshape(main // tb, tb, fa_all.shape[1])
        ti_g = tree_ids[:main].reshape(main // tb, tb)
        carry, packed_g = jax.lax.scan(body_batched, carry, (fa_g, ti_g))
        parts.append(packed_g.reshape(main, -1))
    if main < t_total:
        carry, packed_r = jax.lax.scan(
            body, carry, (fa_all[main:], tree_ids[main:]))
        parts.append(packed_r)
    oob_sum, oob_cnt = carry
    packed = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return oob_sum, oob_cnt, packed


_rf_forest = obs.costed_jit("rf.forest", _rf_forest_impl, lazy=True,
                            static_argnames=(
    "n_bins", "depth", "impurity", "loss", "poisson", "n_classes",
    "n_trees", "use_pallas", "max_leaves", "has_cat",
    "mesh", "stats_exact", "tree_batch"))


@lru_cache(maxsize=None)
def _rf_forest_multi(n_bins: int, depth: int, impurity: str, loss: str,
                     poisson: bool, n_classes: int, n_trees: int,
                     use_pallas: bool, max_leaves: int, has_cat: bool,
                     mesh=None, stats_exact: bool = False):
    """vmapped :func:`_rf_forest_impl` over a leading member axis (see
    :func:`_gbt_forest_multi`); members vary in weights, keys, oob state,
    feature subsets, bag rate and the traced scalar hypers."""
    def one(bins, y, w, base_key, tree_ids, bag_rate, oob_sum, oob_cnt,
            fa_all, cat, mi, mg):
        return _rf_forest_impl(bins, y, w, base_key, tree_ids, bag_rate,
                               oob_sum, oob_cnt, fa_all, cat, mi, mg,
                               n_bins, depth, impurity, loss, poisson,
                               n_classes, n_trees, use_pallas, max_leaves,
                               has_cat, mesh, stats_exact)
    return obs.costed_jit(
        "rf.forest_bagged",
        jax.vmap(one, in_axes=(None, None, 0, 0, None, 0, 0, 0, 0,
                               None, 0, 0)))


def _unpack_tree(vec: np.ndarray, total: int, n_bins: int, c: int,
                 depth: int, n_classes: int = 0):
    """Host-side inverse of :func:`_pack_tree`."""
    k = n_classes if n_classes > 2 else 1
    sizes = [total, _mask_nbytes(total, n_bins), total * k, c, 2]
    parts = np.split(vec, np.cumsum(sizes)[:-1])
    lv = parts[2].astype(np.float32)
    if k > 1:
        lv = lv.reshape(total, k)
    tree = TreeArrays(split_feat=parts[0].astype(np.int32),
                      left_mask=_unpack_mask_bits(parts[1], total, n_bins),
                      leaf_value=lv, depth=depth)
    return tree, parts[3].astype(np.float64), float(parts[4][0]), \
        float(parts[4][1])


def _fetch(x) -> np.ndarray:
    """Device→host materialization of packed trainer results — the ONE
    counted host-sync point.  The telemetry counter lets tests (and
    ``analysis --telemetry``) pin that syncs scale with checkpoint/progress
    intervals, not with trees (tentpole: sync-free growth)."""
    obs.counter("train.host_syncs").inc()
    return np.asarray(x)


def _use_pallas(mesh) -> bool:
    """MXU histogram kernel dispatch.  On a multi-device mesh the kernel
    runs per-shard under ``shard_map`` with a psum merge over the data
    axis (``ops.hist_pallas.build_histograms_sharded``) — the trainers
    thread their mesh down so ``build_histograms`` can place it; a single
    device takes the plain kernel.  Gated on the MESH devices' platform
    (a CPU mesh on a TPU-backed host must not take the Mosaic path)."""
    from ..ops.hist_pallas import pallas_available
    return pallas_available(mesh)


def _hist_mesh(mesh):
    """The mesh build_histograms should shard_map over: only a real
    multi-device mesh matters (None keeps jit caches unified)."""
    return mesh if (mesh is not None and mesh.size > 1) else None


def _wire_bins_dtype(n_bins: int):
    """Narrowest host→device wire dtype that holds bin ids 0..n_bins-1
    (``data.shards.bins_wire_dtype`` — uint8 for <=256 bins).  The
    transfer is a real cost (PCIe bytes), and the reference itself stores
    worker rows as short[] bin ids (``DTWorker.java:100``) — int32 on
    the wire is pure waste."""
    from ..data.shards import bins_wire_dtype
    return bins_wire_dtype(n_bins)


def _put_bins(mesh, bins, n_bins: int):
    """bins → device in the compact wire dtype — and KEPT narrow in HBM
    (4x more resident windows per cache budget at uint8); the tree
    kernels widen to int32 in-graph (``ops.tree.build_histograms``).
    Spill-cache windows already arrive in the wire dtype, so the put is a
    zero-copy read straight out of the mmap."""
    bins = np.asarray(bins)
    wire = _wire_bins_dtype(n_bins)
    if wire != bins.dtype and bins.size:
        # a stale clean dir / re-binned ColumnConfig mismatch must fail
        # loudly, not wrap ids into negatives via the narrowing cast
        lo, hi = int(bins.min()), int(bins.max())
        if lo < 0 or hi >= n_bins:
            raise ValueError(
                f"bin ids [{lo}, {hi}] out of range for n_bins={n_bins} — "
                "the materialized clean data does not match the current "
                "ColumnConfig binning; re-run `norm`")
        bins = bins.astype(wire)
    [b] = _device_put_rows(mesh, bins)
    return b


def _device_put_rows(mesh, *arrays):
    """Shard row-indexed arrays over the mesh's data axis (padding rows with
    zeros so the extent divides; padded rows carry zero weight by
    construction of the weight arrays)."""
    if mesh is None:
        return [jnp.asarray(a) for a in arrays]
    from jax.sharding import NamedSharding, PartitionSpec as P
    data_size = mesh.shape["data"]
    n = arrays[0].shape[0]
    extra = (-n) % data_size
    out = []
    for a in arrays:
        a = np.asarray(a)
        if extra:
            pad = np.zeros((extra,) + a.shape[1:], a.dtype)
            a = np.concatenate([a, pad])
        spec = P("data") if a.ndim == 1 else P("data", None)
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return out


def train_gbt(bins, y, w, n_bins: int, cat_mask, settings: DTSettings,
              progress=None, init_trees: Optional[List[TreeArrays]] = None,
              init_score: Optional[float] = None, mesh=None,
              checkpoint_fn: Optional[Callable] = None,
              start_history: Optional[List] = None,
              init_scores: Optional[np.ndarray] = None) -> ForestResult:
    n, c = bins.shape
    vmask = validation_split(n, settings.valid_rate, settings.seed)
    wt = np.asarray(w, np.float64) * ~vmask
    wv = np.asarray(w, np.float64) * vmask
    y64 = np.asarray(y, np.float64)

    if init_score is None:  # continuous runs reuse the saved forest's prior
        prior = float((y64 * wt).sum() / max(wt.sum(), 1e-9))
        if settings.loss == "log":
            prior = np.clip(prior, 1e-6, 1 - 1e-6)
            init_score = float(np.log(prior / (1 - prior)))
        else:
            init_score = prior

    bins_d = _put_bins(mesh, bins, n_bins)
    y_d, tw_d, vw_d = _device_put_rows(
        mesh, y64.astype(np.float32),
        wt.astype(np.float32), wv.astype(np.float32))
    cat = jnp.asarray(cat_mask if cat_mask is not None else np.zeros(c, bool))
    hc = bool(np.asarray(cat).any())

    trees: List[TreeArrays] = list(init_trees or [])
    if trees and init_scores is not None and len(init_scores) == n:
        # checkpointed per-row scores: restore f BYTE-exact.  Replaying
        # trees eagerly is only f32-equivalent — XLA fuses the in-scan
        # `f + lr * predict` differently (FMA), so a replayed f can flip
        # borderline splits and break the bit-identical-resume contract
        [f] = _device_put_rows(mesh,
                               np.asarray(init_scores, np.float32))
    else:
        f = jnp.full(bins_d.shape[0], init_score, jnp.float32)
        from ..ops import tree_quant as tq
        if trees and tq.quant_scoring() and tq.bins_fit_uint8(n_bins) \
                and len({t.depth for t in trees}) == 1:
            # continuous-training replay: ONE batched quantized traversal
            # over the uint8-resident plane instead of a per-tree predict
            # loop; the per-tree adds keep the eager loop's summation
            # order, so the restored f stays bit-identical to it
            preds = tq.predict_forest_quant(
                *tq.stack_forest_quant(trees), bins_d, trees[0].depth)
            for i in range(len(trees)):
                f = f + settings.learning_rate * preds[i]
        else:
            for t in trees:  # heterogeneous depths: per-tree replay
                f = f + settings.learning_rate * predict_tree(
                    jnp.asarray(t.split_feat), jnp.asarray(t.left_mask),
                    jnp.asarray(t.leaf_value), bins_d, t.depth)

    stopper = GBTEarlyStopDecider()
    history: List[Tuple[float, float]] = list(start_history or [])
    replay_stopped = False
    for tr_prev, va_prev in history:
        # a restored forest that already hit its stop must not grow —
        # the checkpointed trees ARE the truncated early-stop forest
        if stopper.add(va_prev) and settings.early_stop:
            replay_stopped = True
    fi = np.zeros(c)
    total = n_tree_nodes(settings.depth)
    imp = "friedmanmse" if settings.impurity == "friedmanmse" else "variance"
    up = _use_pallas(mesh)
    ckpt = settings.checkpoint_every if (checkpoint_fn and
                                         settings.checkpoint_every) else 0

    # whole-forest scan: one executable + one fetch per chunk — zero
    # per-tree host round-trips.  A progress consumer gets its lines in
    # bursts of 8 trees (the progress file is a tail surface, and a
    # per-tree fetch is a full device round-trip).
    # Early stop no longer forces a per-tree sync either: errors
    # accumulate ON DEVICE inside the scan and the stop decision is
    # checked every ``early_stop_check`` trees on the bulk-fetched error
    # history; a mid-chunk trigger truncates the forest to the exact tree
    # the per-tree loop would have stopped at (trees are a prefix), so
    # results stay bit-identical at 1/K the syncs.
    ti = len(trees)
    stopped = replay_stopped
    while ti < settings.n_trees and not stopped:
        chunk = settings.n_trees - ti
        if ckpt:
            chunk = min(chunk, ((ti // ckpt) + 1) * ckpt - ti)
        if progress:
            chunk = min(chunk, 8)
        if settings.early_stop:
            chunk = min(chunk, settings.early_stop_check)
        fa_all = jnp.asarray(np.stack(
            [_feat_subset(settings, c, t)
             for t in range(ti, ti + chunk)]))
        f, packed = _gbt_forest(
            bins_d, y_d, tw_d, vw_d, f, fa_all, cat,
            settings.learning_rate, settings.min_instances,
            settings.min_gain, n_bins, settings.depth, imp,
            settings.loss, chunk, up, settings.max_leaves, hc,
            _hist_mesh(mesh))
        for j, vec in enumerate(_fetch(packed)):
            tree, gfi, tr_err, va_err = _unpack_tree(
                vec, total, n_bins, c, settings.depth)
            trees.append(tree)
            fi += gfi
            history.append((tr_err, va_err))
            if progress:
                progress(ti + j, tr_err, va_err)
            if settings.early_stop and stopper.add(va_err):
                # ignore the chunk tail past the trigger — exactly the
                # forest (and FI/history) the per-tree decision loop
                # would have kept
                obs.event("early_stop", trainer="gbt", tree=ti + j + 1)
                log.info("GBT early stop after %d trees", ti + j + 1)
                stopped = True
                break
        ti += chunk
        if ckpt:
            # TreeBatch-boundary checkpointing: every chunk is a commit
            # point (checkpoint_every stays the upper bound via the chunk
            # cap above); an early-stopped chunk checkpoints its
            # TRUNCATED forest so a crash before the final model write
            # resumes to the identical stop state.  Scores ride along so
            # resume restores f byte-exact (None after a stop: f holds
            # the dropped tail trees' updates, and a stopped forest
            # never grows again anyway)
            checkpoint_fn(trees, history, init_score,
                          None if stopped else np.asarray(f)[:n])
    return ForestResult(
        trees=trees,
        spec_kwargs={"algorithm": "GBT", "loss": settings.loss,
                     "learning_rate": settings.learning_rate,
                     "init_score": init_score},
        train_error=history[-1][0] if history else float("nan"),
        valid_error=history[-1][1] if history else float("nan"),
        feature_importance=fi,
        trees_built=len(trees), history=history)


def train_rf(bins, y, w, n_bins: int, cat_mask, settings: DTSettings,
             progress=None, mesh=None,
             checkpoint_fn: Optional[Callable] = None,
             init_trees: Optional[List[TreeArrays]] = None,
             start_history: Optional[List] = None) -> ForestResult:
    """Independent Poisson-bagged trees; out-of-bag rows score validation
    with the configured loss."""
    n, c = bins.shape
    se = settings.stats_exact or _stats_bf16_exact(w)
    bins_d = _put_bins(mesh, bins, n_bins)
    y_d, w_d = _device_put_rows(
        mesh, np.asarray(y, np.float32), np.asarray(w, np.float32))
    cat = jnp.asarray(cat_mask if cat_mask is not None else np.zeros(c, bool))
    hc = bool(np.asarray(cat).any())
    mc = settings.n_classes > 2
    oob_shape = (bins_d.shape[0], settings.n_classes) if mc \
        else (bins_d.shape[0],)
    oob_sum = jnp.zeros(oob_shape, jnp.float32)
    oob_cnt = jnp.zeros(bins_d.shape[0], jnp.float32)
    trees: List[TreeArrays] = list(init_trees or [])
    history: List[Tuple[float, float]] = list(start_history or [])
    fi = np.zeros(c)
    base_key = jax.random.PRNGKey(settings.seed)
    start = len(trees)
    if start:  # rebuild oob state by replaying stored trees with their bags
        for ti, t_old in enumerate(trees):
            key = jax.random.fold_in(base_key, ti)
            bag = jax.random.poisson(key, settings.bagging_rate,
                                     (bins_d.shape[0],)).astype(jnp.float32) \
                if settings.poisson_bagging else jnp.ones(bins_d.shape[0])
            pred = predict_tree(jnp.asarray(t_old.split_feat),
                                jnp.asarray(t_old.left_mask),
                                jnp.asarray(t_old.leaf_value), bins_d,
                                t_old.depth)
            oob = (bag == 0) & (w_d > 0)
            oob_sum = oob_sum + jnp.where(oob[:, None] if mc else oob,
                                          pred, 0.0)
            oob_cnt = oob_cnt + oob.astype(jnp.float32)
    total = n_tree_nodes(settings.depth)
    up = _use_pallas(mesh)
    ckpt = settings.checkpoint_every if (checkpoint_fn and
                                         settings.checkpoint_every) else 0

    def absorb(flat: np.ndarray, with_history: bool):
        nonlocal fi
        for vec in flat:
            tree, gfi, tr_err, va_err = _unpack_tree(
                vec, total, n_bins, c, settings.depth, settings.n_classes)
            trees.append(tree)
            fi += gfi
            if with_history:
                history.append((tr_err, va_err))

    # whole-forest scan (see _gbt_forest): one executable + one fetch per
    # chunk; progress consumers get their lines in bursts of 8 trees
    ti = start
    while ti < settings.n_trees:
        chunk = settings.n_trees - ti
        if ckpt:
            chunk = min(chunk, ((ti // ckpt) + 1) * ckpt - ti)
        if progress:
            chunk = min(chunk, 8)
        fa_all = jnp.asarray(np.stack(
            [_feat_subset(settings, c, t)
             for t in range(ti, ti + chunk)]))
        tree_ids = jnp.arange(ti, ti + chunk, dtype=jnp.uint32)
        oob_sum, oob_cnt, packed = _rf_forest(
            bins_d, y_d, w_d, base_key, tree_ids,
            settings.bagging_rate, oob_sum, oob_cnt, fa_all, cat,
            settings.min_instances, settings.min_gain, n_bins,
            settings.depth, settings.impurity, settings.loss,
            settings.poisson_bagging, settings.n_classes, chunk, up,
            settings.max_leaves, hc, _hist_mesh(mesh), se,
            _effective_tree_batch(settings))
        before = len(history)
        absorb(_fetch(packed), with_history=True)
        if progress:
            for j, (tr_err, va_err) in enumerate(history[before:],
                                                 start=ti):
                progress(j, tr_err, va_err)
        ti += chunk
        if ckpt:                       # TreeBatch-boundary checkpointing
            checkpoint_fn(trees, history, None)
    spec_kwargs: Dict[str, Any] = {"algorithm": "RF"}
    if mc:
        spec_kwargs["extra"] = {"n_classes": settings.n_classes}
    return ForestResult(
        trees=trees, spec_kwargs=spec_kwargs,
        train_error=history[-1][0] if history else float("nan"),
        valid_error=history[-1][1] if history else float("nan"),
        feature_importance=fi,
        trees_built=len(trees), history=history)


# ------------------------------------------------- bagged / grid members
def _device_put_members(mesh, *arrays):
    """Shard [B, rows] member matrices over the mesh's data axis (rows =
    axis 1; members replicate)."""
    if mesh is None:
        return [jnp.asarray(a) for a in arrays]
    from jax.sharding import NamedSharding, PartitionSpec as P
    data_size = mesh.shape["data"]
    out = []
    for a in arrays:
        a = np.asarray(a)
        extra = (-a.shape[1]) % data_size
        if extra:
            pad = np.zeros((a.shape[0], extra) + a.shape[2:], a.dtype)
            a = np.concatenate([a, pad], axis=1)
        out.append(jax.device_put(
            a, NamedSharding(mesh, P(None, "data"))))
    return out


def _check_member_structure(settings_list: List[DTSettings]) -> DTSettings:
    s0 = settings_list[0]
    for s in settings_list[1:]:
        same = (s.n_trees == s0.n_trees and s.depth == s0.depth
                and s.impurity == s0.impurity and s.loss == s0.loss
                and s.feature_subset == s0.feature_subset
                and s.max_leaves == s0.max_leaves
                and s.n_classes == s0.n_classes
                and s.poisson_bagging == s0.poisson_bagging)
        if not same:
            raise ValueError("bagged tree members must share structural "
                             "params (TreeNum/MaxDepth/Impurity/Loss/...)")
    return s0


def _member_results(packed_bt, settings_list, total, n_bins, c, alg,
                    n_classes=0) -> List[ForestResult]:
    """Unpack a [B, T, L] stacked-forest fetch into per-member results."""
    out = []
    for b, s in enumerate(settings_list):
        trees, fi = [], np.zeros(c)
        history = []
        for vec in packed_bt[b]:
            tree, gfi, tr_err, va_err = _unpack_tree(
                vec, total, n_bins, c, s.depth, n_classes)
            trees.append(tree)
            fi += gfi
            history.append((tr_err, va_err))
        kw: Dict[str, Any] = {"algorithm": alg}
        if alg == "GBT":
            kw.update({"loss": s.loss, "learning_rate": s.learning_rate})
        if n_classes > 2:
            kw["extra"] = {"n_classes": n_classes}
        out.append(ForestResult(
            trees=trees, spec_kwargs=kw,
            train_error=history[-1][0] if history else float("nan"),
            valid_error=history[-1][1] if history else float("nan"),
            feature_importance=fi, trees_built=len(trees),
            history=history))
    return out


def train_gbt_bagged(bins, y, tw_m, vw_m, n_bins: int, cat_mask,
                     settings_list: List[DTSettings], mesh=None,
                     progress=None) -> List[ForestResult]:
    """B independent GBT forests as ONE vmapped executable (reference
    bagging/grid fan-out, ``TrainModelProcessor.java:768-945``, one Guagua
    job per member).  Members share structure (TreeNum/MaxDepth/...) and
    vary in row weights ``tw_m``/``vw_m`` [B, n], seeds (feature subsets)
    and the traced scalars LearningRate / MinInstancesPerNode /
    MinInfoGain.  Early stop / checkpointing are per-run features of
    :func:`train_gbt`; callers fall back to sequential runs for those."""
    s0 = _check_member_structure(settings_list)
    n, c = bins.shape
    tw_m = np.asarray(tw_m, np.float32)
    vw_m = np.asarray(vw_m, np.float32)
    y64 = np.asarray(y, np.float64)

    init_scores = []
    for b, s in enumerate(settings_list):
        prior = float((y64 * tw_m[b]).sum() / max(tw_m[b].sum(), 1e-9))
        if s.loss == "log":
            prior = float(np.clip(prior, 1e-6, 1 - 1e-6))
            init_scores.append(float(np.log(prior / (1 - prior))))
        else:
            init_scores.append(prior)

    bins_d = _put_bins(mesh, bins, n_bins)
    y_d, = _device_put_rows(mesh, y64.astype(np.float32))
    tw_d, vw_d = _device_put_members(mesh, tw_m, vw_m)
    n_pad = bins_d.shape[0]
    f = jnp.asarray(np.repeat(np.asarray(init_scores, np.float32)[:, None],
                              n_pad, axis=1))
    cat = jnp.asarray(cat_mask if cat_mask is not None else np.zeros(c, bool))
    hc = bool(np.asarray(cat).any())
    fa_all = jnp.asarray(np.stack(
        [[_feat_subset(s, c, t) for t in range(s0.n_trees)]
         for s in settings_list]))                       # [B, T, C]
    # f32 pins the vmapped scan carry dtype under JAX_ENABLE_X64 rigs
    lr = jnp.asarray([s.learning_rate for s in settings_list],
                     jnp.float32)
    mi = jnp.asarray([s.min_instances for s in settings_list],
                     jnp.float32)
    mg = jnp.asarray([s.min_gain for s in settings_list], jnp.float32)
    imp = "friedmanmse" if s0.impurity == "friedmanmse" else "variance"
    fn = _gbt_forest_multi(n_bins, s0.depth, imp, s0.loss, s0.n_trees,
                           _use_pallas(mesh), s0.max_leaves, hc,
                           _hist_mesh(mesh))
    _, packed = fn(bins_d, y_d, tw_d, vw_d, f, fa_all, cat, lr, mi, mg)
    total = n_tree_nodes(s0.depth)
    results = _member_results(np.asarray(packed), settings_list, total,
                              n_bins, c, "GBT")
    for b, (res, s) in enumerate(zip(results, settings_list)):
        res.spec_kwargs["init_score"] = init_scores[b]
        if progress:
            for ti, (tr, va) in enumerate(res.history):
                progress(b, ti, tr, va)
    return results


def train_rf_bagged(bins, y, w_m, n_bins: int, cat_mask,
                    settings_list: List[DTSettings], mesh=None,
                    progress=None) -> List[ForestResult]:
    """B independent RF/DT forests as ONE vmapped executable (see
    :func:`train_gbt_bagged`).  ``w_m`` [B, n]: per-member row weights
    (the bagging sample); validation is per-member out-of-bag."""
    s0 = _check_member_structure(settings_list)
    n, c = bins.shape
    B = len(settings_list)
    mc = s0.n_classes if s0.n_classes > 2 else 0
    bins_d = _put_bins(mesh, bins, n_bins)
    y_d, = _device_put_rows(mesh, np.asarray(y, np.float32))
    w_d, = _device_put_members(mesh, np.asarray(w_m, np.float32))
    n_pad = bins_d.shape[0]
    cat = jnp.asarray(cat_mask if cat_mask is not None else np.zeros(c, bool))
    hc = bool(np.asarray(cat).any())
    oob_shape = (B, n_pad, s0.n_classes) if mc else (B, n_pad)
    oob_sum = jnp.zeros(oob_shape, jnp.float32)
    oob_cnt = jnp.zeros((B, n_pad), jnp.float32)
    base_key = jnp.stack([jax.random.PRNGKey(s.seed)
                          for s in settings_list])
    tree_ids = jnp.arange(s0.n_trees, dtype=jnp.uint32)
    bag_rate = jnp.asarray([s.bagging_rate for s in settings_list],
                           jnp.float32)
    fa_all = jnp.asarray(np.stack(
        [[_feat_subset(s, c, t) for t in range(s0.n_trees)]
         for s in settings_list]))
    mi = jnp.asarray([s.min_instances for s in settings_list],
                     jnp.float32)
    mg = jnp.asarray([s.min_gain for s in settings_list], jnp.float32)
    fn = _rf_forest_multi(n_bins, s0.depth, s0.impurity, s0.loss,
                          s0.poisson_bagging, s0.n_classes, s0.n_trees,
                          _use_pallas(mesh), s0.max_leaves, hc,
                          _hist_mesh(mesh),
                          s0.stats_exact or _stats_bf16_exact(w_m))
    _, _, packed = fn(bins_d, y_d, w_d, base_key, tree_ids, bag_rate,
                      oob_sum, oob_cnt, fa_all, cat, mi, mg)
    total = n_tree_nodes(s0.depth)
    results = _member_results(np.asarray(packed), settings_list, total,
                              n_bins, c, "RF", s0.n_classes)
    if progress:
        for b, res in enumerate(results):
            for ti, (tr, va) in enumerate(res.history):
                progress(b, ti, tr, va)
    return results


# ------------------------------------------------------------- streaming
# streamed/tail executables are cost-attributed under the gbt./rf.
# planes (obs/costs, lazy: module-scope wrap precedes --telemetry)
@partial(obs.costed_jit, "gbt.window_hist", lazy=True,
         static_argnames=("n_nodes", "n_bins", "level", "loss",
                          "use_pallas", "mesh", "left"))
def _gbt_window_hist(hist, bins_w, y_w, tw_w, f_w, sf, lm, n_nodes: int,
                     n_bins: int, level: int, loss: str,
                     use_pallas: bool = False, mesh=None,
                     left: bool = False):
    """Streamed level step: window rows find their level-local node by
    walking the partial tree, then scatter residual-gradient stats.  With
    mesh-sharded window rows the [nodes, C, B, S] sum is XLA's psum over
    the data axis — the DTWorker→DTMaster merge on ICI.

    ``left=True`` accumulates only the LEFT-child histograms of the level
    (parent-slot indexed, ``n_nodes`` halved) — the streamed side of the
    resident grow's histogram subtraction: right children derive as
    parent - left once the level's windows are summed
    (:func:`_derive_level`), halving every re-stream sweep's kernel work.

    ``hist`` (the running accumulator) is an INPUT so consecutive window
    programs chain by data dependency: XLA's CPU in-process collectives
    deadlock when two independent mesh programs overlap on a thread pool
    smaller than 2x the device count (each program's ranks block in the
    rendezvous holding pool threads the other program needs) — chained
    programs can never overlap, on any backend."""
    node_idx = node_index_at_level(sf, lm, bins_w, level)
    if left:
        node_idx = _left_child_index(node_idx)
    grad = _loss_grad(y_w, f_w, loss)
    stats = jnp.stack([tw_w, tw_w * grad], axis=1).astype(jnp.float32)
    return hist + build_histograms(bins_w, node_idx, stats, n_nodes,
                                   n_bins, use_pallas, mesh)


@obs.costed_jit("tree.derive_level", lazy=True,
                static_argnames=("n_nodes",))
def _derive_level(full_prev, hl, feat_prev, n_nodes: int):
    """Full level histogram from the parent level + accumulated
    left-child sums: right child = parent - left where the parent split,
    zero where it froze — the cross-window form of the subtraction in
    :func:`shifu_tpu.ops.tree.grow_tree_jit`."""
    split_ok = feat_prev >= 0
    hr = jnp.where(split_ok[:, None, None, None], full_prev - hl, 0.0)
    return jnp.stack([hl, hr], axis=1).reshape(
        n_nodes, hl.shape[1], hl.shape[2], hl.shape[3])


@partial(obs.costed_jit, "gbt.window_leaf_raw", lazy=True,
         static_argnames=("depth", "loss"))
def _gbt_window_leaf_raw(acc, bins_w, y_w, tw_w, f_w, sf, lm, depth: int,
                         loss: str):
    """Bottom-level raw leaf stat sums for one window — replaces the full
    [2^depth, C, B, S] histogram sweep of the deepest level with one
    [S, N] x [N, 2^depth] dot (the resident grow's leaf-sum bottom level,
    streamed)."""
    node_idx = node_index_at_level(sf, lm, bins_w, depth)
    grad = _loss_grad(y_w, f_w, loss)
    stats = jnp.stack([tw_w, tw_w * grad], axis=1).astype(jnp.float32)
    return acc + _level_leaf_raw(stats, node_idx, 1 << depth)


@obs.costed_jit("tree.set_bottom_leaves", lazy=True,
                static_argnames=("depth",))
def _set_bottom_leaves(lv, raw, depth: int):
    return lv.at[(1 << depth) - 1:].set(leaf_values_from_raw(raw))


# ------------------------------------------- coarse-to-fine disk tail
@partial(obs.costed_jit, "gbt.tail_head", lazy=True,
         static_argnames=("n_bins", "depth", "impurity", "loss",
                          "use_pallas", "max_leaves", "has_cat",
                          "mesh", "has_prev", "cand_k"))
def _gbt_tail_head(bins, y, tw, vw, f, sf_p, lm_p, lv_p, fa, cat, lr, mi,
                   mg, tail_extra, valid_upto, n_bins: int, depth: int,
                   impurity: str, loss: str, use_pallas: bool = False,
                   max_leaves: int = 0, has_cat: bool = True, mesh=None,
                   has_prev: bool = True, cand_k: int = 0):
    """The coarse-to-fine tree's RESIDENT head as ONE executable: apply
    the previous tree's score update to the coalesced resident block
    (+ its error sums), then grow the COARSE tree on the resident prefix
    alone — recording its per-level left histograms and bottom leaf sums,
    which ARE the resident contribution to the exact totals along the
    speculated structure (zero recomputation when the speculation holds).

    With ``cand_k > 0`` also picks the top-K candidate features (coarse
    realized gains, coarse split features forced in, indices sorted so
    K >= C degenerates to the identity gather) and narrows the recorded
    histograms to them — the bounded-candidate scan.

    ``tail_extra`` ([depth, half, C, B, S]) is the previous pass's exact
    tail-only evidence (:func:`_tail_extras`) with ``valid_upto`` = the
    level through which the previous speculation was confirmed; the
    coarse grow adds it to each level's split decision while this tree's
    structure still bit-matches the previous tree's (``sf_p``/``lm_p``
    double as the structure reference — they ARE the previous tree), so
    speculated splits pin to near-full-data optima instead of the
    resident prefix's.  One tree stale; exactness comes from the
    verify/repair pass, not from the evidence."""
    if has_prev:
        f = f + lr * predict_tree(sf_p, lm_p, lv_p, bins, depth)
        per = _per_row_loss(y, f, loss)
        sums = jnp.stack([(per * tw).sum(), tw.sum(),
                          (per * vw).sum(), vw.sum()])
    else:
        sums = jnp.zeros(4, jnp.float32)
    grad = _loss_grad(y, f, loss)
    stats = jnp.stack([tw, tw * grad], axis=1).astype(jnp.float32)
    sf_c, lm_c, _, gfi_c, _, hist_left, leaf_raw = grow_tree_jit(
        bins, stats, cat, fa, n_bins, depth, impurity, mi, mg,
        use_pallas=use_pallas, max_leaves=max_leaves, has_cat=has_cat,
        mesh=mesh, record_hists=True, tail_extra=tail_extra,
        prev_sf=sf_p, prev_lm=lm_p, valid_upto=valid_upto)
    if cand_k > 0:
        forced = jnp.zeros(bins.shape[1], jnp.float32).at[
            jnp.maximum(sf_c, 0)].add(
            jnp.where(sf_c >= 0, jnp.float32(1e30), jnp.float32(0.0)))
        _, cand_idx = jax.lax.top_k(gfi_c + forced, cand_k)
        cand_idx = jnp.sort(cand_idx).astype(jnp.int32)
        hist_left = jnp.take(hist_left, cand_idx, axis=2)
    else:
        cand_idx = jnp.zeros(0, jnp.int32)
    return sf_c, lm_c, hist_left, leaf_raw, f, sums, cand_idx


@obs.costed_jit("gbt.tail_extras", lazy=True,
                static_argnames=("c", "cand"))
def _tail_extras(hl_acc, hl_res, cand_idx, c: int, cand: bool = False):
    """The pass's exact TAIL-only evidence ([depth, half, C, B, S], full
    feature width): accumulated totals minus the resident head's recorded
    contribution, scattered back from the candidate set when the scan was
    bounded.  Level 0's slot is the full tail root (routing-free); level
    l is the tail left-child histograms routed along this pass's
    speculated structure — valid next pass exactly up to the level this
    pass CONFIRMED (the caller carries that as ``valid_upto``)."""
    tail = hl_acc - hl_res
    if cand:
        full = jnp.zeros(hl_acc.shape[:2] + (c,) + hl_acc.shape[3:],
                         hl_acc.dtype)
        return full.at[:, :, cand_idx].set(tail)
    return tail


@partial(obs.costed_jit, "gbt.tail_window_pass", lazy=True,
         static_argnames=("n_bins", "depth", "loss", "use_pallas",
                          "mesh", "has_prev", "cand"))
def _gbt_tail_window_pass(hist_left, leaf_raw, sums, bins_w, y_w, tw_w,
                          vw_w, f_w, sf_p, lm_p, lv_p, sf_c, lm_c,
                          cand_idx, lr, n_bins: int, depth: int, loss: str,
                          use_pallas: bool = False, mesh=None,
                          has_prev: bool = True, cand: bool = False):
    """ONE disk pass feeds everything, per tail window: the previous
    tree's score update + its error sums + EVERY level's histograms of
    the current tree along the speculated coarse structure + the bottom
    leaf sums, in a single executable — the O(depth x trees) tail
    re-stream schedule collapses to one re-stream per tree."""
    if has_prev:
        f_w = f_w + lr * predict_tree(sf_p, lm_p, lv_p, bins_w, depth)
        per = _per_row_loss(y_w, f_w, loss)
        sums = sums + jnp.stack([(per * tw_w).sum(), tw_w.sum(),
                                 (per * vw_w).sum(), vw_w.sum()])
    grad = _loss_grad(y_w, f_w, loss)
    stats = jnp.stack([tw_w, tw_w * grad], axis=1).astype(jnp.float32)
    hist_bins = jnp.take(bins_w, cand_idx, axis=1) if cand else None
    hl, lraw = build_path_histograms(bins_w, stats, sf_c, lm_c, depth,
                                     n_bins, use_pallas, mesh,
                                     hist_bins=hist_bins)
    return hist_left + hl, leaf_raw + lraw, sums, f_w


@partial(obs.costed_jit, "gbt.tail_select", lazy=True,
         static_argnames=("n_bins", "depth", "impurity",
                          "max_leaves", "has_cat", "cand"))
def _gbt_tail_select(hist_left, leaf_raw, sf_c, lm_c, cand_idx, cat, fa,
                     mi, mg, n_bins: int, depth: int, impurity: str,
                     max_leaves: int = 0, has_cat: bool = True,
                     cand: bool = False):
    """Exact split selection from the accumulated (resident + tail)
    per-level histograms, verifying the speculation: runs the level steps
    top-down with right-children derived by subtraction, compares each
    level's exact choice against the coarse structure, and reports the
    FIRST level where they diverge (``depth`` = fully confirmed; deeper
    histograms are mis-routed past a divergence and the caller repairs
    those levels with exact per-level sweeps).

    Returns (sf, lm, lv, fi_levels [depth, C], cnt_levels [depth],
    mismatch, full_levels [depth, half, K, B, S]) — per-level FI/
    leaf-budget state plus the exact FULL per-level histograms, so the
    caller can resume a repair from the divergence point without
    trusting the garbage tail AND seed the repair's subtraction chain
    with the exact level-``mis`` parent (bit-parity with the pure exact
    schedule requires the repair to derive right children the same way).
    """
    c_full = fa.shape[0]
    cat_h = jnp.take(cat, cand_idx) if cand else cat
    fa_h = jnp.take(fa, cand_idx) if cand else fa
    total = n_tree_nodes(depth)
    sf = jnp.full(total, -1, jnp.int32)
    lm = jnp.zeros((total, n_bins), bool)
    lv = jnp.zeros(total, jnp.float32)
    nodes_cnt = jnp.int32(1)
    fi_levels, cnt_levels = [], []
    full_hists = []               # exact FULL per-level hists (padded out;
                                  # the repair path's subtraction parents)
    mismatch = jnp.int32(depth)
    full_prev = None
    feat_prev = None
    for level in range(depth):
        n_nodes = 1 << level
        if level == 0:
            hist = hist_left[0][:1]
        else:
            hl = hist_left[level][:n_nodes // 2]
            hist = _derive_level(full_prev, hl, feat_prev, n_nodes)
        full_hists.append(hist)
        gain, feat_l, lmask, leaf, _ = best_splits(
            hist, cat_h, fa_h, impurity, mi, mg, has_cat=has_cat)
        feat = jnp.where(feat_l >= 0,
                         cand_idx[jnp.maximum(feat_l, 0)] if cand
                         else feat_l, -1).astype(jnp.int32)
        if max_leaves > 0:
            feat, lmask, nodes_cnt = cap_splits_by_leaves(
                gain, feat, lmask, nodes_cnt, max_leaves)
        base = n_nodes - 1
        sf = sf.at[base:base + n_nodes].set(feat)
        lm = lm.at[base:base + n_nodes].set(lmask)
        lv = lv.at[base:base + n_nodes].set(leaf)
        fi_levels.append(jax.ops.segment_sum(
            jnp.where(feat >= 0, jnp.maximum(gain, 0.0),
                      0.0).astype(jnp.float32),
            jnp.maximum(feat, 0), num_segments=c_full))
        cnt_levels.append(nodes_cnt)
        diff = jnp.any(feat != jax.lax.dynamic_slice_in_dim(
            sf_c, base, n_nodes)) | jnp.any(
            lmask != jax.lax.dynamic_slice_in_dim(lm_c, base, n_nodes,
                                                  axis=0))
        mismatch = jnp.where((mismatch == depth) & diff,
                             jnp.int32(level), mismatch)
        full_prev = hist
        feat_prev = feat
    lv = _set_bottom_leaves(lv, leaf_raw, depth)
    half = max(1 << (depth - 1), 1)
    full_levels = jnp.stack([
        jnp.concatenate([h, jnp.zeros((half - h.shape[0],) + h.shape[1:],
                                      h.dtype)]) if h.shape[0] < half
        else h
        for h in full_hists])
    return sf, lm, lv, jnp.stack(fi_levels), jnp.stack(cnt_levels), \
        mismatch, full_levels


# tiny packed-fetch glue: ~zero FLOPs, one shape per run — cost
# attribution would only add registry noise
@jax.jit  # shifu-lint: disable=recompile-hazard
def _pack_c2f(sf, lm, lv, fi):
    """[sf, mask-bits, lv, fi] packed fetch for a coarse-to-fine tree —
    errors travel separately (they land one pass later, fused into the
    NEXT tree's tail pass)."""
    return jnp.concatenate([sf.astype(jnp.float32), _pack_mask_bits(lm),
                            lv, fi])


@jax.jit  # shifu-lint: disable=recompile-hazard
def _pack_small(sums, mismatch):
    """The per-tree tiny fetch: [tr_sum, tw, va_sum, vw, mismatch]."""
    return jnp.concatenate([sums, mismatch[None].astype(jnp.float32)])


def _rf_tail_bags(idx_hi, idx_lo, khi_b, klo_b, thi, tlo, n: int,
                  poisson: bool):
    """[TB, n] Poisson bags hashed ON DEVICE for a tail super-batch —
    bit-identical to the host ``_hash_poisson`` stream
    (``ops/hashing.py``), so the wire carries two [n] uint32 index halves
    per window instead of a [TB, n] f32 bag plane (the put that dominated
    tail prep as TB grew).  Rows past ``n_valid`` need no masking here:
    the RF prep hook zeroes ``w`` there, and every consumer multiplies or
    gates by ``w``."""
    if not poisson:
        return jnp.ones((khi_b.shape[0], n), jnp.float32)
    from ..ops.hashing import hash_poisson_traced
    return jax.vmap(lambda kh, kl: hash_poisson_traced(
        idx_hi, idx_lo, kh, kl, thi, tlo))(khi_b, klo_b)


def _rf_stats_batch(y_w, w_w, bags_b, n_classes: int):
    bw_b = w_w[None, :] * bags_b
    if n_classes > 2:      # NATIVE multiclass: per-class weight channels
        return bw_b[:, :, None] * jax.nn.one_hot(
            y_w.astype(jnp.int32), n_classes, dtype=jnp.float32)[None]
    return jnp.stack([bw_b, bw_b * y_w[None, :]], axis=2) \
        .astype(jnp.float32)


@partial(obs.costed_jit, "rf.window_hist_batch", lazy=True,
         static_argnames=("n_nodes", "n_bins", "level",
                          "use_pallas", "mesh", "n_classes",
                          "stats_exact", "left", "poisson"))
def _rf_window_hist_batch(hist_b, bins_w, y_w, w_w, idx_hi, idx_lo,
                          khi_b, klo_b, thi, tlo, sf_b, lm_b,
                          n_nodes: int, n_bins: int, level: int,
                          use_pallas: bool = False, mesh=None,
                          n_classes: int = 0, stats_exact: bool = False,
                          left: bool = False, poisson: bool = True):
    """Super-batch histogram sweep for ONE window as ONE executable — and,
    since the multi-tree kernel round, ONE kernel launch: the TB trees'
    level histograms build through :func:`build_histograms_batch` (the
    bins one-hot is shared across the batch) instead of TB stacked
    single-tree kernels.  Bags hash on device (:func:`_rf_tail_bags`);
    ``left=True`` accumulates left children only for the subtraction
    derivation (:func:`_derive_level_batch`).

    The per-tree histograms of a tail batch are mutually independent, and
    independent mesh programs that overlap deadlock XLA:CPU's in-process
    collectives (see :func:`_gbt_window_hist`) — dispatching them as TB
    separate programs was the round-4 SIGABRT.  The single program keeps
    every collective in one totally-ordered executable and chains across
    windows via the stacked ``hist_b`` accumulator input."""
    bags_b = _rf_tail_bags(idx_hi, idx_lo, khi_b, klo_b, thi, tlo,
                           w_w.shape[0], poisson)
    node_b = jax.vmap(
        lambda sf, lm: node_index_at_level(sf, lm, bins_w, level))(
        sf_b, lm_b)
    if left:
        node_b = jax.vmap(_left_child_index)(node_b)
    stats_b = _rf_stats_batch(y_w, w_w, bags_b, n_classes)
    return hist_b + build_histograms_batch(bins_w, node_b, stats_b,
                                           n_nodes, n_bins, use_pallas,
                                           mesh, stats_exact)


@obs.costed_jit("tree.derive_level_batch", lazy=True,
                static_argnames=("n_nodes",))
def _derive_level_batch(full_prev_b, hl_b, feat_prev_b, n_nodes: int):
    """Batched :func:`_derive_level` (per-tree parent - left)."""
    return jax.vmap(
        lambda fp, hl, f: _derive_level(fp, hl, f, n_nodes))(
        full_prev_b, hl_b, feat_prev_b)


@partial(obs.costed_jit, "rf.window_leaf_batch", lazy=True,
         static_argnames=("depth", "n_classes", "poisson"))
def _rf_window_leaf_batch(raw_b, bins_w, y_w, w_w, idx_hi, idx_lo, khi_b,
                          klo_b, thi, tlo, sf_b, lm_b, depth: int,
                          n_classes: int = 0, poisson: bool = True):
    """Super-batch bottom-level raw leaf sums for one window — the
    leaf-sum bottom level, streamed and tree-batched (the deepest, widest
    histogram sweep of the old schedule becomes one dot per tree)."""
    bags_b = _rf_tail_bags(idx_hi, idx_lo, khi_b, klo_b, thi, tlo,
                           w_w.shape[0], poisson)
    stats_b = _rf_stats_batch(y_w, w_w, bags_b, n_classes)
    node_b = jax.vmap(
        lambda sf, lm: node_index_at_level(sf, lm, bins_w, depth))(
        sf_b, lm_b)
    return raw_b + jax.vmap(
        lambda st, ni: _level_leaf_raw(st, ni, 1 << depth))(stats_b,
                                                            node_b)


@obs.costed_jit("tree.set_bottom_leaves_batch", lazy=True,
                static_argnames=("depth", "n_classes"))
def _set_bottom_leaves_batch(lv_b, raw_b, depth: int, n_classes: int = 0):
    base = (1 << depth) - 1
    vals = jax.vmap(lambda r: leaf_values_from_raw(r, n_classes))(raw_b)
    return lv_b.at[:, base:].set(vals)


@partial(obs.costed_jit, "gbt.window_update", lazy=True,
         static_argnames=("depth", "loss"))
def _gbt_window_update(sums_in, bins_w, y_w, tw_w, vw_w, f_w, sf, lm, lv,
                       lr, depth: int, loss: str):
    """``sums_in`` accumulator as input — see :func:`_gbt_window_hist` on
    why window programs must chain."""
    pred = predict_tree(sf, lm, lv, bins_w, depth)
    f2 = f_w + lr * pred
    per = _per_row_loss(y_w, f2, loss)
    sums = jnp.stack([(per * tw_w).sum(), tw_w.sum(),
                      (per * vw_w).sum(), vw_w.sum()])
    return f2, sums_in + sums


@obs.costed_jit("rf.window_oob_update", lazy=True,
                static_argnames=("depth", "loss", "n_classes"))
def _rf_window_update(sums_in, bins_w, y_w, w_w, bag_w, oob_sum_w,
                      oob_cnt_w, sf, lm, lv, depth: int, loss: str,
                      n_classes: int = 0):
    """RF per-window oob accumulate + loss-consistent error sums on device
    (the round-2 host-numpy loop, jitted).  Multiclass (``n_classes > 2``):
    class-distribution votes + misclassification-rate errors, matching
    :func:`_rf_round_impl`."""
    pred = predict_tree(sf, lm, lv, bins_w, depth)
    oob = (bag_w == 0) & (w_w > 0)
    if n_classes > 2:
        oob_sum2 = oob_sum_w + jnp.where(oob[:, None], pred, 0.0)
        oob_cnt2 = oob_cnt_w + oob.astype(oob_cnt_w.dtype)
        seen = oob_cnt2 > 0
        yi = y_w.astype(jnp.int32)
        per_v = (jnp.argmax(oob_sum2, axis=-1) != yi).astype(jnp.float32)
        per_t = (jnp.argmax(pred, axis=-1) != yi).astype(jnp.float32)
        wv = w_w * seen
        sums = jnp.stack([(per_v * wv).sum(), wv.sum(),
                          (per_t * w_w).sum(), w_w.sum()])
        return oob_sum2, oob_cnt2, sums_in + sums
    oob_sum2 = oob_sum_w + jnp.where(oob, pred, 0.0)
    oob_cnt2 = oob_cnt_w + oob.astype(oob_cnt_w.dtype)
    seen = oob_cnt2 > 0
    oob_pred = oob_sum2 / jnp.maximum(oob_cnt2, 1.0)
    if loss == "log":
        p = jnp.clip(oob_pred, 1e-9, 1 - 1e-9)
        per_v = -(y_w * jnp.log(p) + (1 - y_w) * jnp.log(1 - p))
        pt = jnp.clip(pred, 1e-9, 1 - 1e-9)
        per_t = -(y_w * jnp.log(pt) + (1 - y_w) * jnp.log(1 - pt))
    else:
        per_v = _per_row_loss(y_w, oob_pred, loss)
        per_t = _per_row_loss(y_w, pred, loss)
    wv = w_w * seen
    sums = jnp.stack([(per_v * wv).sum(), wv.sum(),
                      (per_t * w_w).sum(), w_w.sum()])
    return oob_sum2, oob_cnt2, sums_in + sums


@partial(obs.costed_jit, "rf.window_update_batch", lazy=True,
         static_argnames=("depth", "loss", "n_classes", "poisson"))
def _rf_window_update_batch(sums_b, bins_w, y_w, w_w, idx_hi, idx_lo,
                            khi_b, klo_b, thi, tlo, oob_sum_w, oob_cnt_w,
                            sf_b, lm_b, lv_b, depth: int, loss: str,
                            n_classes: int = 0, poisson: bool = True):
    """Super-batch oob/error sweep for ONE window as ONE executable — the
    oob vote caches chain through the batch in tree order exactly as the
    per-tree sequence would (a ``lax.scan`` over the tree axis, so a
    budget-sized super-batch doesn't unroll into a giant program), and
    the single program keeps the row-sum AllReduces totally ordered (see
    :func:`_rf_window_hist_batch`)."""
    bags_b = _rf_tail_bags(idx_hi, idx_lo, khi_b, klo_b, thi, tlo,
                           w_w.shape[0], poisson)

    def body(carry, x):
        osw, ocw = carry
        s_j, bag_j, sf_j, lm_j, lv_j = x
        osw, ocw, s2 = _rf_window_update(
            s_j, bins_w, y_w, w_w, bag_j, osw, ocw, sf_j, lm_j, lv_j,
            depth, loss, n_classes)
        return (osw, ocw), s2

    (osw, ocw), sums = jax.lax.scan(
        body, (oob_sum_w, oob_cnt_w), (sums_b, bags_b, sf_b, lm_b, lv_b))
    return osw, ocw, sums




def _unpack_streamed(packed: np.ndarray, total: int, n_bins: int, c: int,
                     depth: int, n_classes: int = 0):
    """Host-side inverse of the fused/streamed packed layout
    [sf, lm, lv, fi, sums] — the ONE place that knows it."""
    k = n_classes if n_classes > 2 else 1
    sf_h, lm_h, lv_h, fi_h, sums = np.split(
        packed,
        np.cumsum([total, _mask_nbytes(total, n_bins), total * k, c]))
    lv = lv_h.astype(np.float32)
    if k > 1:
        lv = lv.reshape(total, k)
    tree = TreeArrays(split_feat=sf_h.astype(np.int32),
                      left_mask=_unpack_mask_bits(lm_h, total, n_bins),
                      leaf_value=lv, depth=depth)
    return tree, fi_h.astype(np.float32), sums


def _tree_level_step(hist, cat, fa, impurity: str, min_instances,
                     min_gain, has_cat: bool, level: int, depth: int,
                     max_leaves: int, sf, lm, lv, nodes_cnt, fi_add,
                     n_classes: int = 0):
    """One level of streamed tree growth from an aggregated histogram —
    the single implementation behind both the fused-resident executable
    and the disk-tail window loop (they must never drift)."""
    n_nodes = 1 << level
    gain, feat, lmask, leaf, _ = best_splits(
        hist, cat, fa, impurity, min_instances, min_gain,
        n_classes=n_classes, has_cat=has_cat)
    base = n_nodes - 1
    if level == depth:
        feat = jnp.full(n_nodes, -1, jnp.int32)
        lmask = jnp.zeros((n_nodes, hist.shape[2]), bool)
    elif max_leaves > 0:
        feat, lmask, nodes_cnt = cap_splits_by_leaves(
            gain, feat, lmask, nodes_cnt, max_leaves)
    sf = sf.at[base:base + n_nodes].set(feat)
    lm = lm.at[base:base + n_nodes].set(lmask)
    lv = lv.at[base:base + n_nodes].set(leaf)
    fi_add = fi_add + jax.ops.segment_sum(
        jnp.where(feat >= 0, jnp.maximum(gain, 0.0),
                  0.0).astype(jnp.float32),
        jnp.maximum(feat, 0), num_segments=hist.shape[1])
    return sf, lm, lv, nodes_cnt, fi_add


@obs.costed_jit("tree.level_step_batch", lazy=True,
                static_argnames=("impurity", "has_cat", "level", "depth",
                                 "max_leaves", "n_classes"))
def _tree_level_step_batch(hist_b, cat, fa_b, impurity: str, min_instances,
                           min_gain, has_cat: bool, level: int, depth: int,
                           max_leaves: int, sf_b, lm_b, lv_b, cnt_b, fi_b,
                           n_classes: int = 0):
    """Tail-batch level step as ONE executable (one dispatch per level
    for the whole batch; see :func:`_rf_window_hist_batch` on why the
    trees must not run as independent programs).  vmapped over the tree
    axis so a budget-sized super-batch traces once, not SB times."""
    def one(h, fa, sf, lm, lv, cnt, fi):
        return _tree_level_step(h, cat, fa, impurity, min_instances,
                                min_gain, has_cat, level, depth,
                                max_leaves, sf, lm, lv, cnt, fi,
                                n_classes)
    return jax.vmap(one)(hist_b, fa_b, sf_b, lm_b, lv_b, cnt_b, fi_b)




@lru_cache(maxsize=None)
def _row_unstack(k: int):
    return jax.jit(lambda d: tuple(d[i] for i in range(k)))  # shifu-lint: disable=recompile-hazard


def _put_row_floats(mesh, cols: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """A window's per-row f32 columns in ONE wire transfer: host-stack to
    [K, W], put, unstack on device (slices propagate the data sharding).
    Every host→device put pays a fixed dispatch cost on top of bandwidth
    — per-column puts made streamed-window prep transfer-bound."""
    keys = list(cols)
    stacked = np.stack([np.asarray(cols[k], np.float32) for k in keys])
    if mesh is None:
        d = jnp.asarray(stacked)
    else:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        d = jax.device_put(stacked, NamedSharding(mesh, P(None, "data")))
    return dict(zip(keys, _row_unstack(len(keys))(d)))


def _require_divisible(stream, mesh) -> None:
    if mesh is not None and stream.window_rows % mesh.shape["data"] != 0:
        raise ValueError(
            f"window_rows {stream.window_rows} must divide the mesh data "
            f"axis ({mesh.shape['data']}) — round it up at the call site")


def _default_cache_budget() -> int:
    from ..config import environment
    return environment.get_int("shifu.train.deviceCacheBytes", 1 << 30)


def _pipeline_depth(mesh) -> Optional[int]:
    """See :func:`data.streaming.pipeline_depth_for` — the shared
    single-device-only pipelined-prep rule (XLA:CPU in-process rendezvous
    deadlock, see :func:`_gbt_window_hist`)."""
    from ..data.streaming import pipeline_depth_for
    return pipeline_depth_for(mesh)


# floor on trees grown per disk-tail sweep in streamed RF.  The actual
# super-batch is budget-derived (:func:`_tail_super_batch`): as many
# trees as the per-level histogram state affords, so disk passes per tree
# scale as (depth+2)/SB instead of the old fixed /8.
RF_TAIL_TREE_BATCH = 8

# hard cap on the tail super-batch: past ~128 trees the batched level
# steps' compile time and the [SB, K, C, B, S] state stop paying for the
# marginal disk-pass amortization
RF_TAIL_SUPER_BATCH_MAX = 128


def _tail_super_batch(settings: DTSettings, c: int, n_bins: int,
                      n_stats: int) -> int:
    """Trees fed by ONE disk pass over the tail in streamed RF — the
    super-batch SB.  ``TailTreeBatch`` train param / ``SHIFU_TAIL_TREE_
    BATCH`` env override; auto derives from ``shifu.tree.
    tailSuperBatchBytes`` (default 256 MiB) against the deepest level's
    histogram state (~2x [SB, 2^(depth-1), C, B, S] f32 for the running
    accumulator + the previous level kept for subtraction, plus the
    per-window [SB, W] bag/stat planes)."""
    env = os.environ.get("SHIFU_TAIL_TREE_BATCH")
    if env:
        return max(1, int(env))
    if settings.tail_tree_batch > 0:
        return settings.tail_tree_batch
    from ..config import environment
    budget = environment.get_int("shifu.tree.tailSuperBatchBytes", 1 << 28)
    width = 1 << max(settings.depth - 1, 0)
    per_tree = 2 * width * c * n_bins * n_stats * 4
    return int(min(RF_TAIL_SUPER_BATCH_MAX,
                   max(RF_TAIL_TREE_BATCH, budget // max(per_tree, 1))))


def _tail_coarse_to_fine() -> bool:
    """The disk-tail coarse-to-fine schedule knob: ``SHIFU_TREE_TAIL_C2F``
    env / ``-Dshifu.tree.tailCoarseToFine`` property.

    Default: ON on accelerator backends, OFF on CPU.  The fused one-pass
    schedule trades recomputation (repair sweeps re-derive diverged
    levels) for disk passes — the winning trade exactly when per-pass
    overhead (H2D puts, dispatch latency, real disk) dominates, i.e. on
    a TPU/GPU driving an out-of-core tail.  On a CPU backend a "pass"
    over the mmap spill cache is nearly free while the repair compute is
    not, so the exact per-level super-batch schedule is faster (measured
    ~40k vs ~29k rows*trees/s on the CI rig at 50% repair rate).  Both
    schedules produce bit-identical forests; only the pass/compute mix
    differs."""
    env = os.environ.get("SHIFU_TREE_TAIL_C2F")
    if env is not None:
        return env.lower() not in ("0", "off", "false")
    from ..config import environment
    default = jax.default_backend() != "cpu"
    return environment.get_bool("shifu.tree.tailCoarseToFine", default)


def _tail_candidate_k(c: int) -> int:
    """Bounded-candidate histogram width for the coarse-to-fine tail
    pass: ``-Dshifu.tree.tailCandidateK`` picks the top-K features (by
    the coarse tree's realized gains, coarse split features always
    included) and the exact tail verification scans only those K columns.
    0 (default) / K >= C = all features — the EXACT contract; K < C is
    the approximate bounded scan (the chosen split is exact-best WITHIN
    the candidate set)."""
    from ..config import environment
    k = environment.get_int("shifu.tree.tailCandidateK", 0)
    if k <= 0 or k >= c:
        return 0
    return k


def _c2f_feasible(settings: DTSettings, c: int, n_bins: int) -> bool:
    """Coarse-to-fine holds every level's left-child histograms at once
    ([depth, 2^(depth-1), K, B, S] f32 x3 live copies: resident head
    record, running accumulator, stale-tail evidence) — gate on
    ``shifu.tree.tailHistBudgetBytes`` (default 256 MiB) so deep/wide
    configs fall back to the exact per-level schedule instead of
    OOMing."""
    if settings.depth < 1 or settings.n_classes > 2:
        return False
    from ..config import environment
    budget = environment.get_int("shifu.tree.tailHistBudgetBytes", 1 << 28)
    k = _tail_candidate_k(c) or c
    width = 1 << max(settings.depth - 1, 0)
    return 3 * settings.depth * width * k * n_bins * 2 * 4 <= budget


@jax.jit  # shifu-lint: disable=recompile-hazard
def _pack_streamed_stacked(sf_b, lm_b, lv_b, fi_b, sums_b):
    """[TB, L] packer for a stacked tail batch — jitted so the
    partitioner reconciles whatever shardings the parts carry (an eager
    concatenate of mixed-sharding parts aborts XLA:CPU)."""
    tb = sf_b.shape[0]
    return jnp.concatenate([
        sf_b.astype(jnp.float32),
        jax.vmap(_pack_mask_bits)(lm_b),
        lv_b.reshape(tb, -1), fi_b, sums_b], axis=1)


def _stream_masks(idx: np.ndarray, n_valid: int, w_w: np.ndarray,
                  valid_rate: float, seed: int):
    """Hash-based train/valid weights for a window (stateless row split)."""
    from ..data.streaming import row_uniform
    vmask = row_uniform(seed, 11, idx) < valid_rate
    live = np.zeros(len(idx), np.float32)
    live[:n_valid] = 1.0
    w = np.asarray(w_w, np.float32) * live
    return (w * ~vmask).astype(np.float32), (w * vmask).astype(np.float32)


def _gbt_prepare(mesh, valid_rate: float, seed: int, n_bins: int,
                 y_transform=None, mask_fn=None, f_ref=None):
    """Window prepare hook for streamed GBT: hash train/valid masks once,
    arrays onto the device (mesh-sharded over the data axis).
    ``y_transform`` maps the raw window targets (one-vs-all binarization,
    reference per-class jobs ``TrainModelProcessor.java:684-714``);
    ``mask_fn(index, targets) -> (train_w, valid_w)`` overrides the plain
    valid-rate split (grid/bagging members supply their member's
    stateless bag/split, ``data.streaming.window_member_masks``).

    ``f_ref`` is a one-slot cell the trainer points at its host score
    cache: when set, the window's score slice ships as ``f_prep`` FROM
    THE PREP THREAD, so the tail path's per-window put overlaps device
    compute instead of serializing on the consumer (safe: a window's
    slice is only written by the consumer AFTER it consumed that window,
    and rows are disjoint across windows).  Resident windows ignore
    ``f_prep`` — their persistent device score cache lives under ``f``."""
    from ..data.streaming import PreparedWindow

    def prep(win):
        y_raw = np.asarray(win.arrays["y"], np.float32)
        if mask_fn is None:
            tw, vw = _stream_masks(win.index, win.n_valid, win.arrays["w"],
                                   valid_rate, seed)
        else:
            live = np.zeros(win.rows, np.float32)
            live[:win.n_valid] = 1.0
            w = np.asarray(win.arrays["w"], np.float32) * live
            t, v = mask_fn(win.index, y_raw)
            tw, vw = (w * t).astype(np.float32), (w * v).astype(np.float32)
        y = y_raw
        if y_transform is not None:
            y = np.asarray(y_transform(y), np.float32)
        dev = _put_row_floats(mesh, {"y": y, "tw": tw, "vw": vw})
        dev["bins"] = _put_bins(mesh, win.arrays["bins"], n_bins)
        fh = f_ref.get("f") if f_ref is not None else None
        if fh is not None:
            dev["f_prep"] = _window_f(fh, win, mesh)
        return PreparedWindow(win.start, win.n_valid, win.rows,
                              win.index, dev)
    return prep


@lru_cache(maxsize=None)
def _init_score_jit(loss: str):
    """Device GBT prior from [sum(w*y), sum(w)] sums — keeps the streamed
    warm pass fetch-free."""
    def f(sums):
        prior = sums[0] / jnp.maximum(sums[1], 1e-9)
        if loss == "log":
            p = jnp.clip(prior, 1e-6, 1 - 1e-6)
            return jnp.log(p / (1 - p))
        return prior
    return jax.jit(f)  # shifu-lint: disable=recompile-hazard


@lru_cache(maxsize=None)
def _bcast_rows(rows: int, mesh=None):
    """jit broadcasting a device scalar to a (sharded) row vector."""
    kw = {}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        kw["out_shardings"] = NamedSharding(mesh, P("data"))
    return jax.jit(lambda s: jnp.broadcast_to(s, (rows,)), **kw)  # shifu-lint: disable=recompile-hazard


def _progress_flusher(drain, history, progress, idx_off: int):
    """(flush, mark) for batched streamed progress: lines arrive in
    bursts of 8 (a per-tree fetch is a full link round-trip — the
    resident path's convention).  ``idx_off`` maps history positions to
    global tree indices (resume may restore trees without their history,
    e.g. a checkpoint whose .meta.json is missing).  ``mark`` advances
    the cursor after a caller emitted a line itself (per-tree sync
    paths)."""
    state = {"emitted": len(history)}

    def flush() -> None:
        drain()
        if progress:
            for j in range(state["emitted"], len(history)):
                progress(j + idx_off, history[j][0], history[j][1])
        state["emitted"] = len(history)

    def mark() -> None:
        state["emitted"] = len(history)
    return flush, mark


def train_gbt_streamed(stream, n_bins: int, cat_mask,
                       settings: DTSettings, progress=None,
                       init_trees: Optional[List[TreeArrays]] = None,
                       init_score: Optional[float] = None,
                       checkpoint_fn: Optional[Callable] = None,
                       start_history: Optional[List] = None,
                       mesh=None,
                       cache_budget: Optional[int] = None,
                       y_transform=None, mask_fn=None,
                       init_scores: Optional[np.ndarray] = None
                       ) -> ForestResult:
    """Out-of-core GBT over a ResidentCache: windows that fit the device
    budget are mesh-sharded HBM residents (re-sweeping them costs no IO);
    only the tail past the budget re-streams from disk per level.  The
    per-row score cache f (rows × 4B host) is the only global row state.

    When the dataset fits the budget a whole tree costs ZERO disk passes
    (one warm pass total); the round-2 depth+2-passes-per-tree design is
    gone.  (Reference: ``MemoryDiskFloatMLDataSet.java:54-99`` memory tier,
    ``DTWorker.java:763-884`` histogram merge.)"""
    from ..data.streaming import ResidentCache

    _require_divisible(stream, mesh)
    up = _use_pallas(mesh)
    n_rows = stream.num_rows
    total = n_tree_nodes(settings.depth)
    trees: List[TreeArrays] = list(init_trees or [])
    history: List[Tuple[float, float]] = list(start_history or [])
    stopper = GBTEarlyStopDecider()
    replay_stopped = False
    for _, va_prev in history:
        # see train_gbt: a restored forest that already early-stopped
        # must not grow past its truncation point
        if stopper.add(va_prev) and settings.early_stop:
            replay_stopped = True

    f_ref: Dict[str, Any] = {"f": None}   # prep-thread view of host scores
    bytes0 = stream.bytes_read
    cache = ResidentCache(stream,
                          _default_cache_budget() if cache_budget is None
                          else cache_budget,
                          _gbt_prepare(mesh, settings.valid_rate,
                                       settings.seed, n_bins, y_transform,
                                       mask_fn, f_ref),
                          pipeline_depth=_pipeline_depth(mesh))

    # warm pass: width probe + init-score sums in one sweep.  The sums
    # accumulate ON DEVICE (chained adds) and fetch once at the end — a
    # per-window float() fetch is a full device round-trip, and the warm
    # sweep was paying two per window
    c = None
    sums_d = None
    for it in cache.items():
        if c is None:
            c = int(it.arrays["bins"].shape[1])
        if init_score is None:
            s = jnp.stack([(it.arrays["tw"] * it.arrays["y"]).sum(),
                           it.arrays["tw"].sum()])
            sums_d = s if sums_d is None else sums_d + s
    if c is None:
        raise RuntimeError("streamed GBT: empty shard stream")
    init_d = None
    if init_score is None:
        if cache.tail is None and not trees:
            # fully-resident fresh run (the common fused path): keep the
            # prior ON DEVICE — the host float() here was a full link
            # round trip blocking the first tree (fetched lazily below
            # only for checkpoints / the final result)
            init_d = _init_score_jit(settings.loss)(sums_d)
        else:
            init_score = float(_init_score_jit(settings.loss)(sums_d))

    def init_host() -> float:
        """The prior as a host float — materialized at most once, off the
        tree-dispatch critical path."""
        nonlocal init_score
        if init_score is None:
            init_score = float(init_d)
        return init_score

    cat = jnp.asarray(cat_mask if cat_mask is not None else np.zeros(c, bool))
    hc = bool(np.asarray(cat).any())
    fi_parts: List[np.ndarray] = []    # per-tree split gains [C] (ride the
                                       # packed fetch; a mid-batch early
                                       # stop drops the tail's parts too)

    f = None if init_d is not None else np.full(n_rows, init_score,
                                                np.float32)
    f_ref["f"] = f
    if trees and init_scores is not None and len(init_scores) == n_rows:
        # checkpointed scores restore f byte-exact (see train_gbt: the
        # eager replay below is only f32-equivalent to the in-stream
        # update and can flip borderline splits)
        f = np.asarray(init_scores, np.float32).copy()
        f_ref["f"] = f
    else:
        for t in trees:  # continuous: replay stored trees over the cache
            sf, lm, lv = (jnp.asarray(t.split_feat),
                          jnp.asarray(t.left_mask),
                          jnp.asarray(t.leaf_value))
            for it in cache.items():
                pred = predict_tree(sf, lm, lv, it.arrays["bins"], t.depth)
                s, e = it.start, it.start + it.n_valid
                f[s:e] += settings.learning_rate * \
                    np.asarray(pred)[:it.n_valid]

    def window_f(it):
        """Resident windows keep their score slice ON DEVICE across trees
        and levels (zero fetches); only tail windows round-trip host f —
        and their slice was already put FROM THE PREP THREAD (``f_prep``,
        see :func:`_gbt_prepare`) so the transfer overlapped compute.
        A deferred device prior broadcasts on device (f is None only on
        the fully-resident fresh path, where no tail window exists)."""
        if it.resident:
            it.arrays.pop("f_prep", None)   # resumed warm pass: free the
            fw = it.arrays.get("f")         # prep-shipped slice, the
            if fw is None:                  # persistent cache wins
                fw = (_window_f(f, it, mesh) if f is not None
                      else _bcast_rows(it.rows, mesh)(init_d))
                it.arrays["f"] = fw
            return fw
        fp = it.arrays.pop("f_prep", None)
        return fp if fp is not None else _window_f(f, it, mesh)

    imp = "friedmanmse" if settings.impurity == "friedmanmse" else "variance"
    pending_fused: List[Any] = []

    def absorb_fused(flat_list) -> None:
        for packed in flat_list:
            tree, fi_h, sums = _unpack_streamed(packed, total, n_bins, c,
                                                settings.depth)
            fi_parts.append(fi_h.astype(np.float64))
            trees.append(tree)
            history.append((float(sums[0]) / max(float(sums[1]), 1e-9),
                            float(sums[2]) / max(float(sums[3]), 1e-9)))

    def drain_fused() -> None:
        if pending_fused:
            absorb_fused(_fetch(jnp.stack(pending_fused)))
            pending_fused.clear()

    # early stop reads the bulk-fetched error stream every
    # ``early_stop_check`` trees; a progress consumer's lines batch
    # through the shared flusher
    flush_progress, mark_progress = _progress_flusher(
        drain_fused, history, progress, len(trees) - len(history))
    es_checked = len(history)       # stopper already replayed these
    h0 = len(history)               # fi_parts align with history[h0:]

    # fully-resident: COALESCE the windows into one device-resident row
    # block once and run the RESIDENT per-tree round on it — the
    # per-(window, level) dispatch pattern cost ~(depth+2) x windows
    # kernel launches per tree (measured ~10x the resident path at bench
    # shapes), and the resident round carries every tree-kernel
    # optimization (histogram subtraction, leaf-sum bottom level, fused
    # predict).  Tail regimes keep the window loop below.
    mega = None
    if cache.warmed and cache.tail is None:
        items = list(cache.items())
        mega = {k: _concat_rows([it.arrays[k] for it in items])
                for k in ("bins", "y", "tw", "vw")}
        mega["f"] = _concat_rows([window_f(it) for it in items])

    # ------------------------------------------------------- disk tail
    # the dataset exceeds the resident budget: one disk pass must feed
    # everything.  The resident prefix coalesces into ONE device block
    # (per-window dispatch gone), and trees grow either coarse-to-fine
    # (speculate the structure on the resident prefix, verify every
    # level's exact histograms in ONE fused tail pass that also carries
    # the previous tree's score update — disk passes per tree drop from
    # depth+2 to ~1, repairs only where the speculation diverges) or, with
    # the knob off / an over-budget histogram state, by exact per-level
    # sweeps with subtraction + a leaf-sum bottom (the resident grow's
    # kernel savings, streamed).
    if mega is None and cache.tail is not None and not replay_stopped \
            and len(trees) < settings.n_trees:
        from ..data.streaming import PreparedWindow
        res_rows = cache.resident_rows
        rmega = None
        mega_it = None
        if cache.cached:
            items_r = list(cache.cached)
            rmega = {k: _concat_rows([it.arrays[k] for it in items_r])
                     for k in ("bins", "y", "tw", "vw")}
            rmega["f"] = _concat_rows([window_f(it) for it in items_r])
            for it in items_r:   # window buffers live on in the block
                it.arrays.clear()
            mega_it = PreparedWindow(0, res_rows, res_rows,
                                     np.arange(res_rows), rmega,
                                     resident=True)

        def sweep_items():
            if mega_it is not None:
                yield mega_it
            yield from cache.tail_items()

        def exact_levels(fa, sf, lm, lv, nodes_cnt, fi_add,
                         start_level: int, full_prev, capture=None):
            """Exact per-level sweeps for levels [start_level..depth-1]
            plus the leaf-sum bottom — the knob-off schedule AND the
            coarse-to-fine repair path (one implementation, they must
            never drift).  Levels with a parent histogram in hand build
            left children only and derive the right by subtraction.

            ``capture`` (optional dict) receives each LEFT-built level's
            tail-only left-child histogram (total minus the resident
            block's prefix sum) — exactly-routed along the FINAL
            structure, so the repair path can refresh the next tree's
            stale-tail evidence below the divergence point."""
            for level in range(start_level, settings.depth):
                n_nodes = 1 << level
                left = level > 0 and full_prev is not None
                width = n_nodes // 2 if left else n_nodes
                hist = jnp.zeros((width, c, n_bins, 2), jnp.float32)
                hist_res = None
                for it in sweep_items():
                    hist = _gbt_window_hist(
                        hist, it.arrays["bins"], it.arrays["y"],
                        it.arrays["tw"], window_f(it), sf, lm, width,
                        n_bins, level, settings.loss, up,
                        _hist_mesh(mesh), left)
                    if up:
                        # the pallas launch inside the program is opaque
                        # to XLA's cost analysis — record the analytic
                        # model (ops/hist_pallas) per window launch
                        obs.record_model_launch(
                            "pallas.hist",
                            rows=int(it.arrays["bins"].shape[0]),
                            n_feat=c, n_bins=n_bins, n_nodes=width)
                    if it.resident:
                        hist_res = hist
                if left:
                    if capture is not None:
                        capture[level] = hist - hist_res \
                            if hist_res is not None else hist
                    feat_prev = jax.lax.dynamic_slice_in_dim(
                        sf, width - 1, width)
                    hist = _derive_level(full_prev, hist, feat_prev,
                                         n_nodes)
                sf, lm, lv, nodes_cnt, fi_add = _tree_level_step(
                    hist, cat, fa, imp, settings.min_instances,
                    settings.min_gain, hc, level, settings.depth,
                    settings.max_leaves, sf, lm, lv, nodes_cnt, fi_add)
                full_prev = hist
            raw = jnp.zeros((2, 1 << settings.depth), jnp.float32)
            for it in sweep_items():
                raw = _gbt_window_leaf_raw(
                    raw, it.arrays["bins"], it.arrays["y"],
                    it.arrays["tw"], window_f(it), sf, lm,
                    settings.depth, settings.loss)
            return sf, lm, _set_bottom_leaves(lv, raw, settings.depth), \
                fi_add

        def update_sweep(sf, lm, lv, want_scores: bool):
            """Previous-tree score update + error sums over every window
            (resident block + tail); tail f slices write back DEFERRED so
            the fetches overlap the in-flight window programs."""
            sums_dev = jnp.zeros(4, jnp.float32)
            wb = []
            for it in sweep_items():
                f2, sums_dev = _gbt_window_update(
                    sums_dev, it.arrays["bins"], it.arrays["y"],
                    it.arrays["tw"], it.arrays["vw"], window_f(it),
                    sf, lm, lv, settings.learning_rate, settings.depth,
                    settings.loss)
                if it.resident:
                    it.arrays["f"] = f2
                else:
                    wb.append((it.start, it.n_valid, f2))
            for s, nv, f2 in wb:
                f[s:s + nv] = np.asarray(f2)[:nv]
            scores = None
            if want_scores:
                scores = tail_scores()
            return sums_dev, scores

        def tail_scores() -> np.ndarray:
            """Full per-row scores for a checkpoint: resident slice from
            the device block, tail rows from the host cache."""
            scores = np.empty(n_rows, np.float32)
            if rmega is not None:
                scores[:res_rows] = np.asarray(rmega["f"])[:res_rows]
            scores[res_rows:] = f[res_rows:n_rows]
            return scores

        use_c2f = (rmega is not None and _tail_coarse_to_fine()
                   and _c2f_feasible(settings, c, n_bins))
        cand_k = _tail_candidate_k(c) if use_c2f else 0
        lr_d = jnp.float32(settings.learning_rate)
        zero_tree = (jnp.zeros(total, jnp.int32),
                     jnp.zeros((total, n_bins), bool),
                     jnp.zeros(total, jnp.float32))
        prev = None                  # device arrays of the last built tree
        pend: List[Any] = []         # device-packed [sf, bits, lv, fi]
        drains = 0

        def drain_pend() -> None:
            nonlocal drains
            if not pend:
                return
            flat = _fetch(jnp.stack(pend))
            pend.clear()
            sizes = [total, _mask_nbytes(total, n_bins), total, c]
            for vec in flat:
                sf_h, lm_h, lv_h, fi_h = np.split(vec,
                                                  np.cumsum(sizes)[:-1])
                trees.append(TreeArrays(
                    split_feat=sf_h.astype(np.int32),
                    left_mask=_unpack_mask_bits(lm_h, total, n_bins),
                    leaf_value=lv_h.astype(np.float32),
                    depth=settings.depth))
                fi_parts.append(fi_h.astype(np.float64))
            drains += 1
            faults.fire("train", "superbatch", drains)

        built = len(trees)
        stopped = False
        f_behind = False             # last built tree's update pending?
        fell_back = False            # speculation gave up -> exact path
        if use_c2f:
            tail_extra = None        # prev pass's exact tail evidence
            valid_upto = jnp.int32(0)
            lowmis_run = 0           # consecutive near-root repairs
            while built < settings.n_trees:
                ti = built
                fa = jnp.asarray(_feat_subset(settings, c, ti))
                has_prev = prev is not None
                p_sf, p_lm, p_lv = prev if prev is not None else zero_tree
                (sf_c, lm_c, hl_res, raw_acc, f_res2, sums_d,
                 cand_idx) = _gbt_tail_head(
                        rmega["bins"], rmega["y"], rmega["tw"],
                        rmega["vw"], rmega["f"], p_sf, p_lm, p_lv, fa,
                        cat, lr_d, settings.min_instances,
                        settings.min_gain,
                        tail_extra if has_prev else None,
                        valid_upto, n_bins,
                        settings.depth, imp, settings.loss, up,
                        settings.max_leaves, hc, _hist_mesh(mesh),
                        has_prev, cand_k)
                rmega["f"] = f_res2
                hl_acc = hl_res
                wb = []
                for it in cache.tail_items():
                    hl_acc, raw_acc, sums_d, f2 = _gbt_tail_window_pass(
                        hl_acc, raw_acc, sums_d, it.arrays["bins"],
                        it.arrays["y"], it.arrays["tw"],
                        it.arrays["vw"], window_f(it), p_sf, p_lm, p_lv,
                        sf_c, lm_c, cand_idx, lr_d, n_bins,
                        settings.depth, settings.loss, up,
                        _hist_mesh(mesh), has_prev, cand_k > 0)
                    wb.append((it.start, it.n_valid, f2))
                sf_t, lm_t, lv_t, fi_lv, cnt_lv, mism_d, full_lv = \
                    _gbt_tail_select(
                        hl_acc, raw_acc, sf_c, lm_c, cand_idx, cat, fa,
                        settings.min_instances, settings.min_gain,
                        n_bins, settings.depth, imp, settings.max_leaves,
                        hc, cand_k > 0)
                tail_extra = _tail_extras(hl_acc, hl_res, cand_idx, c,
                                          cand_k > 0)
                for s, nv, f2 in wb:    # deferred: overlaps the select
                    f[s:s + nv] = np.asarray(f2)[:nv]
                small = _fetch(_pack_small(sums_d, mism_d))
                if has_prev:
                    tr_e = float(small[0]) / max(float(small[1]), 1e-9)
                    va_e = float(small[2]) / max(float(small[3]), 1e-9)
                    history.append((tr_e, va_e))
                    f_behind = False
                    if progress:
                        progress(ti - 1, tr_e, va_e)
                    if settings.early_stop and stopper.add(va_e):
                        # the stop decision lands one pass late; the
                        # in-flight tree ti is exactly the tree the
                        # per-tree loop would never have grown — drop it
                        obs.event("early_stop", trainer="gbt_streamed",
                                  tree=ti)
                        log.info("GBT early stop after %d trees "
                                 "(streamed tail)", ti)
                        drain_pend()
                        if checkpoint_fn and settings.checkpoint_every:
                            checkpoint_fn(trees, history, init_host())
                        stopped = True
                        break
                mis = int(small[4])
                valid_upto = jnp.int32(settings.depth)
                if mis < settings.depth:
                    # speculation diverged at `mis`: its own selection is
                    # exact (routed by confirmed levels), deeper
                    # histograms are mis-routed — repair them with exact
                    # per-level sweeps.  Seeding the repair's subtraction
                    # chain with the select pass's exact level-`mis` FULL
                    # histogram keeps the repair bit-identical to the
                    # pure exact schedule (a direct full rebuild would
                    # round differently than parent-minus-left); the
                    # repair's tail-only left sums refresh the stale
                    # evidence below the divergence so the NEXT tree
                    # speculates from full-depth, exactly-routed
                    # evidence.
                    # repair is the speculation MISS branch — rare
                    # or the schedule auto-falls-back entirely
                    obs.counter("train.tail_repairs").inc()  # shifu-lint: disable=telemetry-guard
                    obs.counter("train.tail_repair_levels").inc(  # shifu-lint: disable=telemetry-guard
                        settings.depth - mis)
                    fi_base = jnp.sum(fi_lv[:mis + 1], axis=0)
                    cap: Dict[int, Any] = {} if cand_k == 0 else None
                    sf_t, lm_t, lv_t, fi_tree = exact_levels(
                        fa, sf_t, lm_t, lv_t, cnt_lv[mis], fi_base,
                        mis + 1, full_lv[mis][:1 << mis]
                        if cand_k == 0 else None, capture=cap)
                    if cap:
                        for lvl, h in cap.items():
                            tail_extra = tail_extra.at[
                                lvl, :h.shape[0]].set(h)
                    elif cand_k > 0:
                        # bounded-candidate mode: deeper evidence stays
                        # routed by the abandoned speculation — invalid
                        valid_upto = jnp.int32(mis)
                else:
                    fi_tree = jnp.sum(fi_lv, axis=0)
                # adaptive surrender: with stale-tail evidence in play the
                # confirmed depth should climb tree over tree; a long run
                # of near-root repairs means this plane's split landscape
                # is speculation-hostile (e.g. label noise) and every c2f
                # tree costs exact + a wasted fused pass — finish the
                # forest on the exact schedule instead (same forest bits;
                # only the pass count changes)
                lowmis_run = lowmis_run + 1 \
                    if (has_prev and mis <= 1) else 0
                prev = (sf_t, lm_t, lv_t)
                pend.append(_pack_c2f(sf_t, lm_t, lv_t, fi_tree))
                built += 1
                f_behind = True
                if len(pend) >= 8:
                    drain_pend()
                if checkpoint_fn and settings.checkpoint_every and \
                        built > 1 and \
                        (built - 1) % settings.checkpoint_every == 0:
                    # super-batch drain boundary: commit the prefix whose
                    # scores are final (the freshly built tree's update
                    # lands fused into the NEXT tree's tail pass)
                    drain_pend()
                    checkpoint_fn(trees[:built - 1],
                                  history[:built - 1], init_host(),
                                  tail_scores())
                if lowmis_run >= 6 and built < settings.n_trees:
                    # fires at most once per train (exits c2f)
                    obs.counter("train.tail_c2f_fallbacks").inc()  # shifu-lint: disable=telemetry-guard
                    log.info("GBT tail: speculation repaired near the "
                             "root %d trees running — falling back to "
                             "the exact per-level schedule at tree %d",
                             lowmis_run, built)
                    fell_back = True
                    break
            if not stopped and f_behind and prev is not None:
                # trailing pass: the last tree's update + error sums
                sums_dev, _ = update_sweep(*prev, want_scores=False)
                sums_h = _fetch(sums_dev)
                tr_e = float(sums_h[0]) / max(float(sums_h[1]), 1e-9)
                va_e = float(sums_h[2]) / max(float(sums_h[3]), 1e-9)
                history.append((tr_e, va_e))
                if progress:
                    progress(built - 1, tr_e, va_e)
                f_behind = False
            drain_pend()
        if not use_c2f or fell_back:
            while built < settings.n_trees and not stopped:
                ti = built
                fa = jnp.asarray(_feat_subset(settings, c, ti))
                sf = jnp.full(total, -1, jnp.int32)
                lm = jnp.zeros((total, n_bins), bool)
                lv = jnp.zeros(total, jnp.float32)
                sf, lm, lv, fi_add = exact_levels(
                    fa, sf, lm, lv, jnp.int32(1),
                    jnp.zeros(c, jnp.float32), 0, None)
                ckpt_due = bool(
                    checkpoint_fn and settings.checkpoint_every and
                    (ti + 1) % settings.checkpoint_every == 0)
                sums_dev, scores = update_sweep(sf, lm, lv, ckpt_due)
                absorb_fused([_fetch(jnp.concatenate([
                    sf.astype(jnp.float32), _pack_mask_bits(lm),
                    lv, fi_add, sums_dev]))])
                built += 1
                tr_err, va_err = history[-1]
                if progress:
                    progress(ti, tr_err, va_err)
                mark_progress()
                if ckpt_due:
                    checkpoint_fn(trees, history, init_host(), scores)
                if settings.early_stop and stopper.add(va_err):
                    obs.event("early_stop", trainer="gbt_streamed",
                              tree=ti + 1)
                    log.info("GBT early stop after %d trees (streamed)",
                             ti + 1)
                    if checkpoint_fn and settings.checkpoint_every:
                        checkpoint_fn(trees, history, init_host())
                    stopped = True
        return ForestResult(
            trees=trees,
            spec_kwargs={"algorithm": "GBT", "loss": settings.loss,
                         "learning_rate": settings.learning_rate,
                         "init_score": init_host()},
            train_error=history[-1][0] if history else float("nan"),
            valid_error=history[-1][1] if history else float("nan"),
            feature_importance=(np.sum(fi_parts, axis=0) if fi_parts
                                else np.zeros(c)),
            trees_built=len(trees), history=history,
            disk_passes=cache.disk_passes,
            tail_sweeps=cache.tail_sweeps,
            bytes_read=stream.bytes_read - bytes0)

    start_ti = settings.n_trees if replay_stopped \
        else len(trees) + len(pending_fused)
    for ti in range(start_ti, settings.n_trees):
        fa = jnp.asarray(_feat_subset(settings, c, ti))
        if mega is not None:
            packed_d, mega["f"] = _gbt_round_streamed(
                mega["bins"], mega["y"], mega["tw"], mega["vw"], mega["f"],
                fa, cat, settings.learning_rate, settings.min_instances,
                settings.min_gain, n_bins, settings.depth, imp,
                settings.loss, up, settings.max_leaves, hc,
                _hist_mesh(mesh))
            pending_fused.append(packed_d)
            # early stop checks the bulk-fetched error stream every
            # ``early_stop_check`` trees (device-side accumulation in
            # between — no per-tree sync); a mid-batch trigger truncates
            # to the exact tree the per-tree decision would have kept
            if settings.early_stop and \
                    (len(pending_fused) >= settings.early_stop_check
                     or ti + 1 == settings.n_trees):
                drain_fused()
                triggered = None
                for j, (_, va_err) in enumerate(history[es_checked:]):
                    if stopper.add(va_err):
                        triggered = es_checked + j
                        break
                if triggered is not None:
                    kept = triggered + 1
                    del trees[kept + len(trees) - len(history):]
                    del fi_parts[kept - h0:]
                    del history[kept:]
                    obs.event("early_stop", trainer="gbt_streamed",
                              tree=len(trees))
                    log.info("GBT early stop after %d trees (streamed)",
                             len(trees))
                    if checkpoint_fn and settings.checkpoint_every:
                        # pin the truncated forest: a crash before the
                        # final model write resumes to this exact state
                        # (no scores — a stopped forest never grows)
                        checkpoint_fn(trees, history, init_host())
                    break
                es_checked = len(history)
                flush_progress()
            elif progress and len(pending_fused) >= 8:
                flush_progress()
            if checkpoint_fn and settings.checkpoint_every and \
                    (ti + 1) % min(settings.checkpoint_every, 8) == 0:
                # TreeBatch-boundary cadence (8 = the fused drain burst)
                flush_progress()
                checkpoint_fn(trees, history, init_host(),
                              np.asarray(mega["f"])[:n_rows])
    flush_progress()
    return ForestResult(
        trees=trees,
        spec_kwargs={"algorithm": "GBT", "loss": settings.loss,
                     "learning_rate": settings.learning_rate,
                     "init_score": init_host()},
        train_error=history[-1][0] if history else float("nan"),
        valid_error=history[-1][1] if history else float("nan"),
        feature_importance=(np.sum(fi_parts, axis=0) if fi_parts
                            else np.zeros(c)),
        trees_built=len(trees), history=history,
        disk_passes=cache.disk_passes,
        tail_sweeps=cache.tail_sweeps,
        bytes_read=stream.bytes_read - bytes0)


@lru_cache(maxsize=None)
def _concat_rows_jit(k: int):
    """jitted row-concat — eager concatenation of mesh-sharded window
    arrays aborts XLA:CPU (the known eager-reshard SIGABRT); under jit
    the partitioner inserts the reshard."""
    return jax.jit(lambda *xs: jnp.concatenate(xs, axis=0))  # shifu-lint: disable=recompile-hazard


def _concat_rows(xs):
    return xs[0] if len(xs) == 1 else _concat_rows_jit(len(xs))(*xs)


def _gbt_round_streamed_impl(bins, y, tw, vw, f, fa, cat, lr, mi, mg,
                             n_bins, depth, impurity, loss, use_pallas,
                             max_leaves, has_cat, mesh):
    return _pack_round_streamed(*_gbt_round_impl(
        bins, y, tw, vw, f, fa, cat, lr, mi, mg, n_bins, depth, impurity,
        loss, use_pallas, max_leaves, has_cat, mesh))


_gbt_round_streamed = obs.costed_jit(
    "gbt.round_streamed", _gbt_round_streamed_impl, lazy=True,
    static_argnames=("n_bins", "depth", "impurity", "loss", "use_pallas",
                     "max_leaves", "has_cat", "mesh"))


def _pack_round_streamed(sf, lm, lv, gfi, f2, tr, va):
    """Resident-round outputs in the STREAMED packed layout
    ([sf, mask-bits, lv, fi, sums4] — :func:`_unpack_streamed` divides
    sums pairwise, so unit denominators carry the ready-made errors)."""
    one = jnp.ones((), jnp.float32)
    return jnp.concatenate([
        sf.astype(jnp.float32), _pack_mask_bits(lm), lv, gfi,
        jnp.stack([tr, one, va, one])]), f2


@partial(obs.costed_jit, "rf.round_streamed", lazy=True,
         static_argnames=("n_bins", "depth", "impurity", "loss",
                          "poisson", "n_classes", "use_pallas",
                          "max_leaves", "has_cat", "mesh",
                          "stats_exact"))
def _rf_round_streamed(bins, y, w, idx_hi, idx_lo, khi, klo, thi, tlo,
                       oob_sum, oob_cnt, fa, cat, mi, mg, n_bins: int,
                       depth: int, impurity: str, loss: str,
                       poisson: bool, n_classes: int = 0,
                       use_pallas: bool = False, max_leaves: int = 0,
                       has_cat: bool = True, mesh=None,
                       stats_exact: bool = False):
    """Streamed-RF resident round: the per-tree hash bag replays ON
    DEVICE (``ops/hashing.py`` splitmix64, bit-identical to the host
    ``window_bag`` stream), then the shared RF round body runs and packs
    in the streamed layout."""
    from ..ops.hashing import hash_poisson_traced
    bag = hash_poisson_traced(idx_hi, idx_lo, khi, klo, thi, tlo) \
        if poisson else jnp.ones(w.shape[0], jnp.float32)
    sf, lm, lv, gfi, os2, oc2, tr, va = _rf_round_from_bag(
        bins, y, w, bag, oob_sum, oob_cnt, fa, cat, mi, mg, n_bins,
        depth, impurity, loss, n_classes, use_pallas, max_leaves,
        has_cat, mesh, stats_exact)
    one = jnp.ones((), jnp.float32)
    packed = jnp.concatenate([
        sf.astype(jnp.float32), _pack_mask_bits(lm), lv.reshape(-1), gfi,
        jnp.stack([tr, one, va, one])])
    return packed, os2, oc2


def _window_f(f: np.ndarray, win, mesh=None):
    """Slice a per-row cache (1D scores or 2D per-class votes) for a
    window, padding past the end; shard over the mesh data axis so it
    joins the window's arrays' layout."""
    s = win.start
    e = min(s + win.rows, len(f))
    out = np.zeros((win.rows,) + f.shape[1:], np.float32)
    out[:e - s] = f[s:e]
    return _shard_rows(out, mesh)


def _rf_prepare(mesh, n_bins: int, y_transform=None, mask_fn=None):
    """Window prepare hook for streamed RF: zero weights past n_valid once,
    arrays onto the device (mesh-sharded over the data axis).
    ``mask_fn(index, targets) -> (train_w, _)``: bagging/grid members
    multiply their member's stateless row sample into the weights (the
    out-of-bag vote still validates within the member's rows)."""
    from ..data.streaming import PreparedWindow

    def prep(win):
        w = np.asarray(win.arrays["w"], np.float32).copy()
        w[win.n_valid:] = 0.0
        y = np.asarray(win.arrays["y"], np.float32)
        if mask_fn is not None:
            w *= mask_fn(win.index, y)[0].astype(np.float32)
        if y_transform is not None:
            y = np.asarray(y_transform(y), np.float32)
        dev = _put_row_floats(mesh, {"y": y, "w": w})
        dev["bins"] = _put_bins(mesh, win.arrays["bins"], n_bins)
        return PreparedWindow(win.start, win.n_valid, win.rows,
                              win.index, dev)
    return prep


def _shard_rows(a: np.ndarray, mesh=None):
    """Place a per-window row array next to the window's (possibly
    mesh-sharded) arrays so jitted window steps see one layout."""
    if mesh is None:
        return jnp.asarray(a)
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    spec = P("data") if a.ndim == 1 else P("data", None)
    return jax.device_put(a, NamedSharding(mesh, spec))


def train_rf_streamed(stream, n_bins: int, cat_mask, settings: DTSettings,
                      progress=None,
                      checkpoint_fn: Optional[Callable] = None,
                      init_trees: Optional[List[TreeArrays]] = None,
                      start_history: Optional[List] = None,
                      mesh=None,
                      cache_budget: Optional[int] = None,
                      y_transform=None, mask_fn=None) -> ForestResult:
    """Out-of-core RF over a ResidentCache: hash-based Poisson bags per
    (tree, row) keep bagging stateless across sweeps; oob vote caches
    (2 host arrays, rows x 4B) carry validation across trees.  Windows
    under the device budget are mesh-sharded HBM residents (re-sweeping
    them costs no IO); only the tail re-streams from disk.  (Reference:
    ``DTWorker.java:763-884`` histogram merge, ``DTMaster.java:274-533``
    split pick, ``MemoryDiskFloatMLDataSet.java:54-99`` memory tier.)"""
    from ..data.streaming import ResidentCache, _hash_poisson, row_uniform

    _require_divisible(stream, mesh)
    up = _use_pallas(mesh)
    n_rows = stream.num_rows
    total = n_tree_nodes(settings.depth)
    trees: List[TreeArrays] = list(init_trees or [])
    history: List[Tuple[float, float]] = list(start_history or [])

    bytes0 = stream.bytes_read
    cache = ResidentCache(stream,
                          _default_cache_budget() if cache_budget is None
                          else cache_budget,
                          _rf_prepare(mesh, n_bins, y_transform, mask_fn),
                          pipeline_depth=_pipeline_depth(mesh))
    c = None
    for win in stream.windows():      # peek the first window for the width;
        c = int(win.arrays["bins"].shape[1])   # cache warms during useful
        break                                  # level-0 work, not here
    if c is None:
        raise RuntimeError("streamed RF: empty shard stream")
    cat = jnp.asarray(cat_mask if cat_mask is not None else np.zeros(c, bool))
    hc = bool(np.asarray(cat).any())
    K = settings.n_classes
    mc = K > 2          # NATIVE multiclass: per-class vote caches
    oob_sum = np.zeros((n_rows, K) if mc else n_rows, np.float32)
    oob_cnt = np.zeros(n_rows, np.float32)
    fi_dev = jnp.zeros(c, jnp.float32)     # device-accumulated split gains

    # per-(tree, window) bags are deterministic; memoized so the depth+2
    # sweeps of a tree hash/upload each window's bag once
    bag_cache: Dict[Tuple[int, int], Any] = {}

    def host_bag(ti: int, it) -> np.ndarray:
        """The per-(tree, window) stateless bag — the ONE place that knows
        the hash stream, shared by the per-tree and tail-batch paths so
        they stay bit-identical."""
        u = row_uniform(settings.seed, 5000 + ti, it.index)
        bag = _hash_poisson(settings.bagging_rate, u) \
            if settings.poisson_bagging else np.ones(it.rows, np.float32)
        bag[it.n_valid:] = 0.0
        return bag.astype(np.float32)

    def window_bag(ti: int, it):
        key = (ti, it.start)
        dev = bag_cache.get(key)
        if dev is None:
            dev = _shard_rows(host_bag(ti, it), mesh)
            if it.resident:      # tail bags would grow with the dataset
                bag_cache[key] = dev
        return dev

    def window_oob(it):
        """Resident windows keep oob vote state ON DEVICE across trees;
        tail windows round-trip the host arrays."""
        if it.resident:
            pair = it.arrays.get("oob")
            if pair is None:
                pair = (_window_f(oob_sum, it, mesh),
                        _window_f(oob_cnt, it, mesh))
                it.arrays["oob"] = pair
            return pair
        return (_window_f(oob_sum, it, mesh), _window_f(oob_cnt, it, mesh))

    def accumulate_oob(ti: int, sf, lm, lv, depth: int):
        """Device-side error sums; only tail windows fetch oob state."""
        sums_dev = jnp.zeros(4, jnp.float32)
        for it in cache.items():
            osw, ocw = window_oob(it)
            os2, oc2, sums_dev = _rf_window_update(
                sums_dev, it.arrays["bins"], it.arrays["y"],
                it.arrays["w"], window_bag(ti, it), osw, ocw, sf, lm, lv,
                depth, settings.loss, settings.n_classes)
            if it.resident:
                it.arrays["oob"] = (os2, oc2)
            else:
                s, e = it.start, it.start + it.n_valid
                oob_sum[s:e] = np.asarray(os2)[:it.n_valid]
                oob_cnt[s:e] = np.asarray(oc2)[:it.n_valid]
        return sums_dev

    # resumed/continuous: replay oob accumulation for stored trees
    for ti, t_old in enumerate(trees):
        bag_cache.clear()
        accumulate_oob(ti, jnp.asarray(t_old.split_feat),
                       jnp.asarray(t_old.left_mask),
                       jnp.asarray(t_old.leaf_value), t_old.depth)

    def absorb_rf(flat_list) -> None:
        nonlocal fi_dev
        for packed in flat_list:
            tree, fi_h, sums = _unpack_streamed(packed, total, n_bins, c,
                                                settings.depth,
                                                settings.n_classes)
            fi_dev = fi_dev + jnp.asarray(fi_h)
            trees.append(tree)
            va_err = float(sums[0]) / max(float(sums[1]), 1e-9) \
                if sums[1] > 0 else float("nan")
            history.append((float(sums[2]) / max(float(sums[3]), 1e-9),
                            va_err))

    pending_rf: List[Any] = []

    def drain_rf() -> None:
        if pending_rf:
            absorb_rf(_fetch(jnp.stack(pending_rf)))
            pending_rf.clear()

    flush_progress_rf, mark_progress_rf = _progress_flusher(
        drain_rf, history, progress, len(trees) - len(history))

    ti = len(trees) + len(pending_rf)
    mega = None                 # fully-resident: ONE coalesced row block
    thi_tlo = None              # device Poisson thresholds (tail batches)
    sb_drains = 0               # super-batch drains (faults site ordinal)
    while ti < settings.n_trees:
        bag_cache.clear()
        if mega is None and cache.warmed and cache.tail is None:
            # fully resident: coalesce windows once and run the resident
            # round per tree (see the GBT mega path).  Bags replay the
            # SAME host hash stream on device, BIT-identical
            # (ops/hashing.py) — resume replays over windows therefore
            # see exactly the bags these trees trained with; the
            # histogram arithmetic itself follows the resident kernel's
            # subtraction order (f32-equivalent, not byte-equal, to the
            # window sweep)
            from ..ops.hashing import split_index_u32, thresholds_u32
            items = list(cache.items())
            mega = {k: _concat_rows([it.arrays[k] for it in items])
                    for k in ("bins", "y", "w")}
            oobs = [window_oob(it) for it in items]
            mega["oob_sum"] = _concat_rows([o[0] for o in oobs])
            mega["oob_cnt"] = _concat_rows([o[1] for o in oobs])
            ih, il = split_index_u32(np.concatenate(
                [np.asarray(it.index, np.uint64) for it in items]))
            mega["idx_hi"] = _shard_rows(ih, mesh)
            mega["idx_lo"] = _shard_rows(il, mesh)
            thi, tlo = thresholds_u32(settings.bagging_rate)
            mega["thi"] = jnp.asarray(thi)
            mega["tlo"] = jnp.asarray(tlo)
        if mega is not None:
            from ..ops.hashing import row_key_u32
            khi, klo = row_key_u32(settings.seed, 5000 + ti)
            packed_d, mega["oob_sum"], mega["oob_cnt"] = _rf_round_streamed(
                mega["bins"], mega["y"], mega["w"], mega["idx_hi"],
                mega["idx_lo"], jnp.uint32(khi), jnp.uint32(klo),
                mega["thi"], mega["tlo"], mega["oob_sum"],
                mega["oob_cnt"], jnp.asarray(_feat_subset(settings, c, ti)),
                cat, settings.min_instances, settings.min_gain, n_bins,
                settings.depth, settings.impurity, settings.loss,
                settings.poisson_bagging, settings.n_classes, up,
                settings.max_leaves, hc, _hist_mesh(mesh),
                settings.stats_exact)
            pending_rf.append(packed_d)
            if progress and len(pending_rf) >= 8:
                flush_progress_rf()
            if checkpoint_fn and settings.checkpoint_every and \
                    (ti + 1) % min(settings.checkpoint_every, 8) == 0:
                # TreeBatch-boundary cadence (8 = the fetch burst)
                flush_progress_rf()
                checkpoint_fn(trees, history, None)
            ti += 1
            continue
        # disk-tail regime: grow a SUPER-BATCH of independent trees per
        # sweep — the reference's DTMaster grows ALL RF trees
        # simultaneously, one stats pass per level for the whole forest
        # (``DTMaster.java:91`` toDoQueue spans trees); per-tree sweeps
        # would re-stream the disk tail TreeNum times per level.  The
        # batch width is budget-derived (:func:`_tail_super_batch`, the
        # TailTreeBatch knob) so disk passes per tree scale as
        # (depth+2)/SB; bags hash ON DEVICE from two [W] uint32 index
        # halves per window (bit-identical to the host stream, and the
        # [SB, W] bag plane never rides the wire); levels > 0 accumulate
        # LEFT children only and derive right = parent - left, and the
        # bottom level is a leaf-sum dot instead of the deepest
        # histogram.  Bit-identical to the per-tree order: bags are
        # stateless per (tree, row) and oob votes chain through the
        # batch in tree order per window.
        from ..ops.hashing import row_key_u32, split_index_u32, \
            thresholds_u32
        n_stats = K if mc else 2
        SB = _tail_super_batch(settings, c, n_bins, n_stats)
        if thi_tlo is None:
            t_hi, t_lo = thresholds_u32(settings.bagging_rate)
            thi_tlo = (jnp.asarray(t_hi), jnp.asarray(t_lo))
        thi_d, tlo_d = thi_tlo

        def window_idx(it):
            """Device uint32 (hi, lo) halves of the window's global row
            indices — cached for resident windows, recomputed for tail
            re-streams (two [W] puts, ~TB x cheaper than bag planes)."""
            pair = it.arrays.get("idx32") if it.resident else None
            if pair is None:
                ih, il = split_index_u32(np.asarray(it.index, np.uint64))
                pair = (_shard_rows(ih, mesh), _shard_rows(il, mesh))
                if it.resident:
                    it.arrays["idx32"] = pair
            return pair

        TB = min(settings.n_trees - ti, SB)
        if checkpoint_fn and settings.checkpoint_every:
            nxt = ((ti // settings.checkpoint_every) + 1) * \
                settings.checkpoint_every
            TB = max(1, min(TB, nxt - ti))
        tis = list(range(ti, ti + TB))
        keys = [row_key_u32(settings.seed, 5000 + t) for t in tis]
        khi_b = jnp.asarray(np.asarray([k[0] for k in keys], np.uint32))
        klo_b = jnp.asarray(np.asarray([k[1] for k in keys], np.uint32))
        fa_b = jnp.asarray(np.stack(
            [np.asarray(_feat_subset(settings, c, t)) for t in tis]))
        sf_b = jnp.full((TB, total), -1, jnp.int32)
        lm_b = jnp.zeros((TB, total, n_bins), bool)
        lv_b = jnp.zeros((TB, total, K) if mc else (TB, total),
                         jnp.float32)
        cnt_b = jnp.ones(TB, jnp.int32)
        fi_b = jnp.zeros((TB, c), jnp.float32)
        hist_prev = None
        for level in range(settings.depth):
            n_nodes = 1 << level
            left = level > 0
            width = n_nodes // 2 if left else n_nodes
            hist_b = jnp.zeros((TB, width, c, n_bins, n_stats),
                               jnp.float32)
            for it in cache.items():
                ih_d, il_d = window_idx(it)
                hist_b = _rf_window_hist_batch(
                    hist_b, it.arrays["bins"], it.arrays["y"],
                    it.arrays["w"], ih_d, il_d, khi_b, klo_b, thi_d,
                    tlo_d, sf_b, lm_b, width, n_bins, level, up,
                    _hist_mesh(mesh), settings.n_classes,
                    settings.stats_exact, left,
                    settings.poisson_bagging)
                if up:
                    obs.record_model_launch(
                        "pallas.hist",
                        rows=int(it.arrays["bins"].shape[0]),
                        n_feat=c, n_bins=n_bins, n_nodes=width,
                        n_stats=n_stats, n_trees=TB)
            if left:
                feat_prev_b = jax.lax.dynamic_slice_in_dim(
                    sf_b, width - 1, width, axis=1)
                hist_b = _derive_level_batch(hist_prev, hist_b,
                                             feat_prev_b, n_nodes)
            sf_b, lm_b, lv_b, cnt_b, fi_b = _tree_level_step_batch(
                hist_b, cat, fa_b, settings.impurity,
                settings.min_instances, settings.min_gain, hc, level,
                settings.depth, settings.max_leaves, sf_b, lm_b, lv_b,
                cnt_b, fi_b, settings.n_classes)
            hist_prev = hist_b
        # bottom level: leaf-sum dots, one sweep
        raw_b = jnp.zeros((TB, n_stats, 1 << settings.depth),
                          jnp.float32)
        for it in cache.items():
            ih_d, il_d = window_idx(it)
            raw_b = _rf_window_leaf_batch(
                raw_b, it.arrays["bins"], it.arrays["y"],
                it.arrays["w"], ih_d, il_d, khi_b, klo_b, thi_d, tlo_d,
                sf_b, lm_b, settings.depth, settings.n_classes,
                settings.poisson_bagging)
        lv_b = _set_bottom_leaves_batch(lv_b, raw_b, settings.depth,
                                        settings.n_classes)
        # one more sweep: oob votes + error sums for the whole batch,
        # trees chained in order per window
        sums_b = jnp.zeros((TB, 4), jnp.float32)
        for it in cache.items():
            osw, ocw = window_oob(it)
            ih_d, il_d = window_idx(it)
            osw, ocw, sums_b = _rf_window_update_batch(
                sums_b, it.arrays["bins"], it.arrays["y"],
                it.arrays["w"], ih_d, il_d, khi_b, klo_b, thi_d, tlo_d,
                osw, ocw, sf_b, lm_b, lv_b, settings.depth,
                settings.loss, settings.n_classes,
                settings.poisson_bagging)
            if it.resident:
                it.arrays["oob"] = (osw, ocw)
            else:
                s, e = it.start, it.start + it.n_valid
                oob_sum[s:e] = np.asarray(osw)[:it.n_valid]
                oob_cnt[s:e] = np.asarray(ocw)[:it.n_valid]
        absorb_rf(_fetch(_pack_streamed_stacked(
            sf_b, lm_b, lv_b, fi_b, sums_b)))
        if progress:
            for j, t in enumerate(tis):
                tr_err, va_err = history[len(history) - TB + j]
                progress(t, tr_err, va_err)
        mark_progress_rf()
        ti += TB
        sb_drains += 1
        faults.fire("train", "superbatch", sb_drains)
        if checkpoint_fn and settings.checkpoint_every:
            # every super-batch drain is a commit boundary
            checkpoint_fn(trees, history, None)
    flush_progress_rf()
    spec_kwargs: Dict[str, Any] = {"algorithm": "RF"}
    if mc:
        spec_kwargs["extra"] = {"n_classes": K}
    return ForestResult(
        trees=trees, spec_kwargs=spec_kwargs,
        train_error=history[-1][0] if history else float("nan"),
        valid_error=history[-1][1] if history else float("nan"),
        feature_importance=np.asarray(fi_dev, np.float64),
        trees_built=len(trees), history=history,
        disk_passes=cache.disk_passes,
        tail_sweeps=cache.tail_sweeps,
        bytes_read=stream.bytes_read - bytes0)


# -------------------------------------------------------- pipeline driver
def _tree_member_masks(mc, n: int, bags: int, kfold: int, rf_like: bool,
                       targets, seed: int, distinct: bool = False):
    """(tw_m, vw_m) member weight matrices for bagged/fold tree members —
    RF-family members take the full bag as train weight (out-of-bag
    validates), GBT members keep the held-out split.

    ``distinct``: each bagging member draws its OWN validation split from
    its own seed (the reference's per-Guagua-job randomness) — without it,
    default-config GBT bags (sampleRate 1, no replacement, subset ALL)
    would be byte-identical forests.  Grid trials must NOT use it: trials
    share one split so the comparison isolates the hypers."""
    from .sampling import member_masks

    def one(b: int, nb: int, sd: int):
        return member_masks(
            n, nb, valid_rate=0.0 if rf_like else mc.train.validSetRate,
            kfold=kfold, sample_rate=mc.train.baggingSampleRate,
            replacement=mc.train.baggingWithReplacement,
            stratified=mc.train.stratifiedSample, targets=targets, seed=sd)

    if distinct and bags > 1 and not rf_like and not (kfold and kfold > 1):
        pairs = [one(b, 1, seed + b) for b in range(bags)]
        tw_m = np.concatenate([p[0] for p in pairs])
        vw_m = np.concatenate([p[1] for p in pairs])
    else:
        tw_m, vw_m = one(0, bags, seed)
    if rf_like and not (kfold and kfold > 1):
        tw_m = tw_m + vw_m
    return tw_m, vw_m


def _write_feature_importance(proc, col_nums, feature_names, fi_total):
    names = feature_names or [str(cn) for cn in col_nums]
    fi_named = sorted(((names[j], float(v)) for j, v in enumerate(fi_total)),
                      key=lambda kv: -kv[1])
    with open(os.path.join(proc.paths.tmp_dir, "feature_importance.json"),
              "w") as fjson:
        json.dump({k: v for k, v in fi_named}, fjson, indent=2)


def _tree_stream(shards, mesh, params=None):
    """A ShardStream with the tree trainers' window geometry (env knobs +
    data-axis rounding) — the ONE place that computes it (main streamed
    path and per-class OVA sweeps must agree).  ``params`` may carry a
    ``StreamPrefetch`` train-param override for the prefetch/pipeline
    depth (else ``SHIFU_TPU_PREFETCH`` / ``-Dshifu.stream.prefetch``)."""
    from ..data.streaming import ShardStream, stream_window_rows
    ncols = len(shards.schema.get("columnNums", [])) or 1
    window_rows = stream_window_rows(2 * ncols + 8, mesh.shape["data"],
                                     shards)
    prefetch = (params or {}).get("StreamPrefetch")
    return ShardStream(shards, ("bins", "y", "w"), window_rows,
                       prefetch=prefetch)


def _streamed_bag_mask_fn(mc, rf_like: bool, bags: int, seed: int,
                          member: int):
    """Streamed bagged member ``member``'s (train_w, valid_w) mask
    function — THE seed/row policy for out-of-core bags (single-class
    bagging and OVA x bagging must never drift): GBT bags draw their own
    validation split from their own seed (the in-RAM ``distinct=True``
    semantics — else default-config bags are identical forests); RF bags
    share masks and differ by the per-tree Poisson bag seed.  Stratified
    validation degrades to Bernoulli (needs a global pass) — callers warn
    once."""
    from ..data.streaming import mask_fn_from_settings
    if rf_like:
        mm = mask_fn_from_settings(
            bags, valid_rate=0.0,
            sample_rate=mc.train.baggingSampleRate,
            replacement=mc.train.baggingWithReplacement, seed=seed)
        row = member
    else:
        mm = mask_fn_from_settings(
            1, valid_rate=mc.train.validSetRate,
            sample_rate=mc.train.baggingSampleRate,
            replacement=mc.train.baggingWithReplacement,
            seed=seed + member)
        row = 0

    def mf(idx, tgt):
        t, v = mm(idx, tgt)
        return t[row], v[row]
    return mf


def _warn_streamed_stratified(mc) -> None:
    if mc.train.stratifiedSample:
        log.warning("streaming: stratified validation degrades to "
                    "Bernoulli split (needs a global pass)")


def _train_streamed_member(alg, shards, mesh, n_bins, cat_mask,
                           settings: DTSettings, mask_fn,
                           y_transform=None) -> ForestResult:
    """One sequential out-of-core member job (the reference's
    one-Guagua-job-per-bag/combo queue shape)."""
    stream = _tree_stream(shards, mesh)
    if alg == Algorithm.GBT:
        return train_gbt_streamed(stream, n_bins, cat_mask, settings,
                                  mesh=mesh, y_transform=y_transform,
                                  mask_fn=mask_fn)
    return train_rf_streamed(stream, n_bins, cat_mask, settings,
                             mesh=mesh, y_transform=y_transform,
                             mask_fn=mask_fn)


def _save_ova_bag_results(proc, results, alg, k: int, K: int,
                          settings: DTSettings, n_bins, col_nums,
                          feature_names, ext: str, pf) -> None:
    """Persist one OVA class's B bagged forests + progress trail (member
    ``b*K + k`` scores class k via its ``class_index`` extra)."""
    for b, res in enumerate(results):
        if alg != Algorithm.GBT:
            res.spec_kwargs["algorithm"] = \
                "RF" if alg != Algorithm.DT else "DT"
        res.spec_kwargs.setdefault("extra", {}).update(
            {"class_index": k, "n_classes": K})
        spec = tree_model.TreeModelSpec(
            n_trees=len(res.trees), depth=settings.depth,
            n_bins=n_bins, column_nums=list(col_nums),
            feature_names=feature_names, **res.spec_kwargs)
        tree_model.save_model(
            proc.paths.model_path(b * K + k, ext), spec, res.trees)
        for ti, (tr, va) in enumerate(res.history):
            pf.write(f"Class {k} Bag {b} Tree #{ti + 1} Train "
                     f"Error: {tr:.6f} Validation Error: "
                     f"{va:.6f}\n")
    pf.flush()
    log.info("train %s OVA class %d/%d: %d bagged forests, valid "
             "errs %s", alg.name, k + 1, K, len(results),
             [round(r.valid_error, 6) for r in results])


def _run_tree_ova_bagged(proc, shards, col_nums, cat_mask, n_bins,
                         settings: DTSettings, alg, K: int,
                         bags: int, streaming: bool = False) -> int:
    """OVA x bagging: B independent forests per class (reference runs one
    FULL bagging job per class, ``TrainModelProcessor.java:684-714``).
    Each class's B bags train as ONE vmapped multi-forest run (in-RAM) or
    as B sequential streamed jobs (``streaming=True``); model files
    follow the NN OVA convention (member ``b*K + k`` scores class k via
    its ``class_index`` extra — the scorer averages contributors per
    class, so file numbering is immaterial).  ``train -resume`` skips
    classes whose B models are all complete (per-class granularity; the
    un-bagged OVA path additionally restores mid-forest checkpoints)."""
    from ..parallel.mesh import device_mesh

    mc = proc.model_config
    mesh = device_mesh(n_ensemble=1)
    ext = alg.name.lower()
    os.makedirs(proc.paths.models_dir, exist_ok=True)
    if not settings.resume:
        for f in os.listdir(proc.paths.models_dir):
            if f.startswith("model"):
                os.remove(os.path.join(proc.paths.models_dir, f))
    if streaming:
        _warn_streamed_stratified(mc)
        bins = y = w = None
        n = 0
    else:
        data = shards.load_all()
        bins, y, w = data["bins"].astype(np.int32), data["y"], data["w"]
        n = len(y)
    rf_like = alg != Algorithm.GBT
    settings_list = [replace(settings, seed=settings.seed + b)
                     for b in range(bags)]
    fi_total = np.zeros(len(col_nums))
    feature_names = shards.schema.get("columnNames")

    def fi_path(k: int) -> str:
        return os.path.join(proc.paths.tmp_dir, f"fi_class{k}.npy")

    def class_complete(k: int) -> bool:
        for b in range(bags):
            p = proc.paths.model_path(b * K + k, ext)
            if not os.path.isfile(p):
                return False
            spec_k, _ = tree_model.load_model(p)
            if spec_k.n_trees < settings.n_trees:
                return False
        return True

    with open(proc.paths.progress_path,
              "a" if settings.resume else "w") as pf:
        for k in range(K):
            if settings.resume and class_complete(k):
                log.info("train %s OVA class %d/%d: all %d bags complete, "
                         "skipping", alg.name, k + 1, K, bags)
                continue
            if streaming:
                # out-of-core: K x B sequential streamed jobs (the
                # reference's per-class bagging job queue); the class
                # binarizes on device via y_transform, the bag is a
                # stateless hash of the global row index
                yt = (lambda yv, k=k:
                      (np.asarray(yv) == k).astype(np.float32))
                results = [
                    _train_streamed_member(
                        alg, shards, mesh, n_bins, cat_mask,
                        settings_list[b],
                        _streamed_bag_mask_fn(mc, rf_like, bags,
                                              settings.seed, b),
                        y_transform=yt)
                    for b in range(bags)]
                ioutil.atomic_save_npy(
                    fi_path(k), np.sum([r.feature_importance
                                        for r in results], axis=0))
                _save_ova_bag_results(proc, results, alg, k, K, settings,
                                      n_bins, col_nums, feature_names,
                                      ext, pf)
                continue
            yk = (np.asarray(y) == k).astype(np.float32)
            tw_m, vw_m = _tree_member_masks(mc, n, bags, -1, rf_like, yk,
                                            settings.seed, distinct=True)
            if settings.early_stop and alg == Algorithm.GBT:
                # early stop is a per-run decision loop; honor it
                # sequentially (train_gbt_bagged trains full forests)
                results = [train_gbt(bins, yk,
                                     w * (tw_m[b] + vw_m[b] > 0), n_bins,
                                     cat_mask, settings_list[b], mesh=mesh)
                           for b in range(bags)]
            elif alg == Algorithm.GBT:
                results = train_gbt_bagged(
                    bins, yk, tw_m * w[None, :], vw_m * w[None, :], n_bins,
                    cat_mask, settings_list, mesh=mesh)
            else:
                results = train_rf_bagged(
                    bins, yk, tw_m * w[None, :], n_bins, cat_mask,
                    settings_list, mesh=mesh)
            ioutil.atomic_save_npy(
                fi_path(k), np.sum([r.feature_importance
                                    for r in results], axis=0))
            _save_ova_bag_results(proc, results, alg, k, K, settings,
                                  n_bins, col_nums, feature_names, ext, pf)
    for k in range(K):      # FI sidecars survive resume-skipped classes
        if os.path.isfile(fi_path(k)):
            fi_total += np.load(fi_path(k))
    _write_feature_importance(proc, col_nums, feature_names, fi_total)
    return 0


def _run_tree_ova(proc, shards, col_nums, cat_mask, n_bins,
                  settings: DTSettings, alg, K: int,
                  streaming: bool = False) -> int:
    """One-vs-all tree multiclass: K binary forests, ``model{k}`` scores
    class k (reference ``TrainModelProcessor.java:684-714`` runs one
    bagging job per class; here each class is a sequential forest on the
    full mesh).  Streamed data trains each class out-of-core over its own
    ResidentCache sweep.  ``train -resume`` restarts at the first
    unfinished class, restoring a mid-forest checkpoint for the class
    that was interrupted (reference combo ``-resume`` semantics)."""
    from ..parallel.mesh import device_mesh
    mesh = device_mesh(n_ensemble=1)
    ext = alg.name.lower()
    os.makedirs(proc.paths.models_dir, exist_ok=True)
    if not settings.resume:
        for f in os.listdir(proc.paths.models_dir):
            if f.startswith("model"):
                os.remove(os.path.join(proc.paths.models_dir, f))
    bins = y = w = None
    if not streaming:
        data = shards.load_all()
        bins, y, w = data["bins"].astype(np.int32), data["y"], data["w"]

    # per-class FI sidecars: a resumed run skips finished classes but must
    # still report ALL classes' gains in feature_importance.json
    def fi_path(k: int) -> str:
        return os.path.join(proc.paths.tmp_dir, f"fi_class{k}.npy")

    with open(proc.paths.progress_path,
              "a" if settings.resume else "w") as pf:
        for k in range(K):
            model_path = proc.paths.model_path(k, ext)
            if settings.resume and os.path.isfile(model_path):
                spec_k, trees_k = tree_model.load_model(model_path)
                if spec_k.n_trees >= settings.n_trees:
                    log.info("train %s OVA class %d/%d: already complete "
                             "(%d trees), skipping", alg.name, k + 1, K,
                             spec_k.n_trees)
                    continue
            init_trees, init_score, start_history = (None, None, None)
            init_scores = None
            if settings.resume:
                ck = _forest_checkpoint_path(proc, f"_c{k}")
                if os.path.isfile(ck):
                    spec_c, init_trees = tree_model.load_model(ck)
                    init_score = spec_c.init_score
                    meta = {}
                    if os.path.isfile(ck + ".meta.json"):
                        with open(ck + ".meta.json") as f:
                            meta = json.load(f)
                    start_history = [tuple(h)
                                     for h in meta.get("history", [])]
                    try:               # byte-exact f restore (see
                        d = np.load(ck + ".scores.npz")  # _restore_or_…)
                        if int(d["trees_done"]) == len(init_trees):
                            init_scores = np.asarray(d["f"], np.float32)
                    except (OSError, ValueError, KeyError):
                        pass
                    log.info("OVA resume: class %d restarts from %d "
                             "checkpointed trees", k, len(init_trees))
            ckpt_fn = _forest_checkpoint_fn(proc, settings, alg, n_bins,
                                            col_nums, shards,
                                            suffix=f"_c{k}")

            def progress(ti, tr, va, k=k):
                pf.write(f"Class {k} Tree #{ti + 1} Train Error: {tr:.6f} "
                         f"Validation Error: {va:.6f}\n")
                pf.flush()

            if streaming:
                def yk_transform(yv, k=k):
                    return (np.asarray(yv) == k).astype(np.float32)
                if alg == Algorithm.GBT:
                    res = train_gbt_streamed(
                        _tree_stream(shards, mesh), n_bins, cat_mask,
                        settings, progress, init_trees=init_trees,
                        init_score=init_score, checkpoint_fn=ckpt_fn,
                        start_history=start_history, mesh=mesh,
                        y_transform=yk_transform,
                        init_scores=init_scores)
                else:
                    res = train_rf_streamed(
                        _tree_stream(shards, mesh), n_bins, cat_mask,
                        settings, progress, checkpoint_fn=ckpt_fn,
                        init_trees=init_trees,
                        start_history=start_history, mesh=mesh,
                        y_transform=yk_transform)
            else:
                yk = (np.asarray(y) == k).astype(np.float32)
                if alg == Algorithm.GBT:
                    res = train_gbt(bins, yk, w, n_bins, cat_mask, settings,
                                    progress, init_trees=init_trees,
                                    init_score=init_score,
                                    checkpoint_fn=ckpt_fn,
                                    start_history=start_history, mesh=mesh,
                                    init_scores=init_scores)
                else:
                    res = train_rf(bins, yk, w, n_bins, cat_mask, settings,
                                   progress, checkpoint_fn=ckpt_fn,
                                   init_trees=init_trees,
                                   start_history=start_history, mesh=mesh)
            if alg != Algorithm.GBT:
                res.spec_kwargs["algorithm"] = \
                    "RF" if alg != Algorithm.DT else "DT"
            res.spec_kwargs.setdefault("extra", {}).update(
                {"class_index": k, "n_classes": K})
            spec = tree_model.TreeModelSpec(
                n_trees=len(res.trees), depth=settings.depth, n_bins=n_bins,
                column_nums=list(col_nums),
                feature_names=shards.schema.get("columnNames"),
                **res.spec_kwargs)
            tree_model.save_model(model_path, spec, res.trees)
            ioutil.atomic_save_npy(fi_path(k),
                                   np.asarray(res.feature_importance))
            log.info("train %s OVA class %d/%d: %d trees, valid err %.6f",
                     alg.name, k + 1, K, res.trees_built, res.valid_error)
    fi_total = np.zeros(len(col_nums))
    for k in range(K):
        if os.path.isfile(fi_path(k)):
            fi_total += np.load(fi_path(k))
        else:                                         # pragma: no cover
            log.warning("OVA class %d has no stored feature importance "
                        "(pre-resume run?); totals omit it", k)
    _write_feature_importance(proc, col_nums,
                              shards.schema.get("columnNames"), fi_total)
    return 0


def _run_tree_multi(proc, shards, col_nums, cat_mask, n_bins, alg,
                    trials, is_gs: bool, kfold: int, bags: int) -> int:
    """Tree grid search / bagging / k-fold (reference
    ``TrainModelProcessor.java:768-945`` runs one Guagua job per
    bag/combo/fold; ``gs/GridSearch.java:62`` is algorithm-agnostic).

    Same-structure members train as ONE vmapped multi-forest executable
    (:func:`train_gbt_bagged` / :func:`train_rf_bagged`); structurally
    different grid trials run group by group.  Streamed data or early
    stop falls back to sequential full runs per member — the reference's
    own job-queue shape."""
    from ..parallel.mesh import device_mesh
    from ..train.grid_search import tree_stackable_groups

    mc = proc.model_config
    mesh = device_mesh(n_ensemble=1)
    streaming = proc._use_streaming(shards, shards.schema) \
        if hasattr(proc, "_use_streaming") else False
    if streaming and kfold and kfold > 1:
        log.warning("k-fold CV ignores streaming mode (the held-out fold "
                    "vote needs full-data passes); folds train in-RAM")
        streaming = False
    if streaming:
        bins = y = w = None
    else:
        data = shards.load_all()
        bins, y, w = data["bins"].astype(np.int32), data["y"], data["w"]
        n = len(y)

    base = settings_from_params(mc.train.params if not is_gs else trials[0],
                                mc.train, alg)
    base.stats_exact = not mc.dataSet.weightColumnName
    if is_gs:
        settings_list = [settings_from_params(t, mc.train, alg)
                         for t in trials]
        for s in settings_list:
            s.stats_exact = base.stats_exact
        member_trials = list(range(len(trials)))
    else:
        B = kfold if (kfold and kfold > 1) else bags
        settings_list = [replace(base, seed=base.seed + b)
                         for b in range(B)]
        member_trials = [None] * B

    ext = alg.name.lower()
    os.makedirs(proc.paths.models_dir, exist_ok=True)
    for f in os.listdir(proc.paths.models_dir):
        if f.startswith("model"):
            os.remove(os.path.join(proc.paths.models_dir, f))
    os.makedirs(proc.paths.tmp_dir, exist_ok=True)

    rf_like = alg != Algorithm.GBT
    if streaming:
        # out-of-core members: sequential full streamed runs — the
        # reference's own shape (one Guagua job per bag/combo over the
        # same HDFS data, SHIFU_TRAIN_BAGGING_INPARALLEL queue); each
        # member's bag/split is a stateless hash of the global row index
        from ..data.streaming import mask_fn_from_settings
        _warn_streamed_stratified(mc)
        B = len(settings_list)

        def member_mask(i: int):
            """Member i's (train_w, valid_w) window mask: grid trials
            share ONE split (isolate the hypers); bagging members follow
            the shared :func:`_streamed_bag_mask_fn` seed/row policy."""
            if not is_gs:
                return _streamed_bag_mask_fn(mc, rf_like, B, base.seed, i)
            mm = mask_fn_from_settings(
                1, valid_rate=0.0 if rf_like else mc.train.validSetRate,
                sample_rate=mc.train.baggingSampleRate,
                replacement=mc.train.baggingWithReplacement,
                seed=base.seed)

            def mf(idx, tgt):
                t, v = mm(idx, tgt)
                return t[0], v[0]
            return mf

        def run_members(idxs: List[int]) -> List[ForestResult]:
            return [_train_streamed_member(alg, shards, mesh, n_bins,
                                           cat_mask, settings_list[i],
                                           member_mask(i))
                    for i in idxs]
    else:
        def run_members(idxs: List[int]) -> List[ForestResult]:
            sl = [settings_list[i] for i in idxs]
            if base.early_stop and alg == Algorithm.GBT:
                # early stop is a per-run decision loop; honor it
                # sequentially
                return [train_gbt(bins, y, w * (tw_m[i] + vw_m[i] > 0),
                                  n_bins, cat_mask, sl[j], mesh=mesh)
                        for j, i in enumerate(idxs)]
            if alg == Algorithm.GBT:
                return train_gbt_bagged(bins, y, tw_m[idxs] * w[None, :],
                                        vw_m[idxs] * w[None, :], n_bins,
                                        cat_mask, sl, mesh=mesh)
            return train_rf_bagged(bins, y, tw_m[idxs] * w[None, :], n_bins,
                                   cat_mask, sl, mesh=mesh)

        # sampling masks: grid trials share ONE split (isolate the
        # hypers); bagging/k-fold members each get their bag/fold
        # (reference bagging sample rate / CV folds)
        if is_gs:
            tw1, vw1 = _tree_member_masks(mc, n, 1, -1, rf_like, y,
                                          base.seed)
            tw_m = np.repeat(tw1, len(trials), axis=0)
            vw_m = np.repeat(vw1, len(trials), axis=0)
        else:
            tw_m, vw_m = _tree_member_masks(mc, n, bags, kfold, rf_like, y,
                                            base.seed, distinct=True)

    results: List[Optional[ForestResult]] = [None] * len(settings_list)
    trees_c = obs.counter("train.trees")
    with open(proc.paths.progress_path, "w") as pf:  # shifu-lint: disable=atomic-write
        groups = tree_stackable_groups(trials) if is_gs \
            else [list(range(len(settings_list)))]
        for group in groups:
            for j, res in zip(group, run_members(group)):
                results[j] = res
                label = f"Trial [{j}]" if is_gs else f"Bag [{j}]"
                for ti, (tr, va) in enumerate(res.history):
                    pf.write(f"{label} Tree #{ti + 1} Train Error: "
                             f"{tr:.6f} Validation Error: {va:.6f}\n")
                pf.flush()
                trees_c.inc(res.trees_built)
                obs.event("forest_member", trainer=alg.name.lower(),
                          member=j, trees=res.trees_built,
                          valid_err=round(res.valid_error, 6))

    if rf_like and kfold and kfold > 1 and not is_gs:
        # RF k-fold: oob error is in-fold; the CV figure of merit is the
        # mean-vote error on the HELD-OUT fold (reference CV semantics)
        from ..ops.tree import predict_forest
        for i, res in enumerate(results):
            fold = vw_m[i] > 0
            vote = predict_forest(res.trees, bins[fold])
            yf, wf = y[fold], (w * vw_m[i])[fold]
            if base.loss == "log":
                p = np.clip(vote, 1e-9, 1 - 1e-9)
                per = -(yf * np.log(p) + (1 - yf) * np.log(1 - p))
            else:
                per = (yf - vote) ** 2
            res.valid_error = float((per * wf).sum() / max(wf.sum(), 1e-9))

    feature_names = shards.schema.get("columnNames")

    def save(res: ForestResult, member: int, s: DTSettings) -> None:
        kw = dict(res.spec_kwargs)
        if alg != Algorithm.GBT:
            kw["algorithm"] = "RF" if alg != Algorithm.DT else "DT"
        spec = tree_model.TreeModelSpec(
            n_trees=len(res.trees), depth=s.depth, n_bins=n_bins,
            column_nums=list(col_nums), feature_names=feature_names, **kw)
        tree_model.save_model(proc.paths.model_path(member, ext), spec,
                              res.trees)

    if is_gs:
        from ..train.grid_search import rank_and_report
        order = rank_and_report(proc.paths.tmp_dir,
                                [r.valid_error for r in results], trials)
        best = order[0]
        log.info("grid search: best trial #%d valid error %.6f params %s",
                 best, results[best].valid_error, trials[best])
        save(results[best], 0, settings_list[best])
    else:
        for i, res in enumerate(results):
            save(res, i, settings_list[i])
        log.info("saved %d bagged %s model(s); valid errors %s", len(results),
                 alg.name, [round(r.valid_error, 6) for r in results])
    _write_feature_importance(
        proc, col_nums, feature_names,
        np.sum([r.feature_importance for r in results], axis=0))
    return 0


def run_tree_training(proc) -> int:
    """Entry called by TrainProcessor for GBT/RF/DT."""
    mc = proc.model_config
    alg = mc.train.algorithm
    shards = proc._open_shards(proc.paths.clean_dir) \
        if hasattr(proc, "_open_shards") \
        else Shards.open(proc.paths.clean_dir)
    col_nums = shards.schema.get("columnNums", [])
    by_num = {c.columnNum: c for c in proc.column_configs}
    cat_mask = np.array([by_num[cn].is_categorical() if cn in by_num else False
                         for cn in col_nums])
    # bin-space width from ColumnConfig (num value bins + the missing bin) —
    # NOT from observed data, which may lack rare bins under sampling and
    # would make eval-time indices overflow the left_mask
    n_bins = max((by_num[cn].num_bins() + 1 for cn in col_nums if cn in by_num),
                 default=2)
    trials = proc._trials(dict(mc.train.params or {}))
    is_gs = len(trials) > 1
    kfold = mc.train.numKFold if mc.train.isCrossValidation else -1
    bags = 1 if is_gs else max(1, mc.train.baggingNum)
    multi = is_gs or bags > 1 or (kfold and kfold > 1)
    # trials[0] == params when no grid axes; raw params may hold lists
    settings = settings_from_params(trials[0], mc.train, alg)
    settings.resume = bool(proc.params.get("resume"))
    settings.checkpoint_dir = proc.paths.checkpoint_dir
    # no weight column -> RF stat channels are small-integer-exact in bf16
    # (streamed windows can't inspect the data up front; resident paths
    # also auto-detect from the weights themselves)
    settings.stats_exact = not mc.dataSet.weightColumnName

    K = len(mc.dataSet.posTags) if mc.is_multi_class() else 0
    if K > 2 and multi:
        from ..config.model_config import MultipleClassification
        ova = mc.train.multiClassifyMethod == \
            MultipleClassification.ONEVSALL or alg == Algorithm.GBT
        if ova and bags > 1 and not is_gs and not (kfold and kfold > 1):
            streaming = proc._use_streaming(shards, shards.schema) \
                if hasattr(proc, "_use_streaming") else False
            return _run_tree_ova_bagged(proc, shards, col_nums, cat_mask,
                                        n_bins, settings, alg, K, bags,
                                        streaming=streaming)
        from ..config.validator import ValidationError
        what = "grid search / k-fold" if (is_gs or (kfold and kfold > 1)) \
            else "bagging with NATIVE multi-class"
        raise ValidationError(
            [f"{what} is not supported with multi-class tree training — "
             "train trials/folds individually, or use ONEVSALL (OVA "
             "bagging is supported)"])
    if multi:
        return _run_tree_multi(proc, shards, col_nums, cat_mask, n_bins,
                               alg, trials, is_gs, kfold, bags)
    streaming = proc._use_streaming(shards, shards.schema) \
        if hasattr(proc, "_use_streaming") else False
    if K > 2:
        from ..config.model_config import MultipleClassification
        # GBT has no NATIVE multiclass mode (reference restricts NATIVE to
        # NN/RF, ``TrainModelProcessor.java:347-349``)
        if mc.train.multiClassifyMethod == MultipleClassification.ONEVSALL \
                or alg == Algorithm.GBT:
            return _run_tree_ova(proc, shards, col_nums, cat_mask, n_bins,
                                 settings, alg, K, streaming=streaming)
        settings.n_classes = K
        settings.loss = "squared"          # errors are misclassification
        if settings.impurity not in ("entropy", "gini"):
            settings.impurity = "entropy"

    ckpt_fn = _forest_checkpoint_fn(proc, settings, alg, n_bins, col_nums,
                                    shards)

    progress_path = proc.paths.progress_path
    with open(progress_path, "w") as pf:  # shifu-lint: disable=atomic-write
        def progress(ti, tr, va):
            line = (f"Tree #{ti + 1} Train Error: {tr:.6f} "
                    f"Validation Error: {va:.6f}")
            pf.write(line + "\n")
            pf.flush()
            obs.counter("train.trees").inc()
            obs.event("tree", trainer=alg.name.lower(), tree=ti + 1,
                      train_err=round(tr, 6), valid_err=round(va, 6))
            faults.fire("train", "tree", ti + 1)
            if (ti + 1) % 5 == 0 or ti == 0:
                log.info(line)

        init_trees, init_score, start_history, init_scores = \
            _restore_or_continuous(proc, alg, settings)
        refresh_extra = int(proc.params.get("refresh_extra") or 0)
        if refresh_extra and init_trees:
            # refresh warm-start: the budget is N MORE trees APPENDED
            # past the restored forest (a plain resume keeps TreeNum);
            # on the new data window the restored scores replay unless
            # the byte-exact sidecar still covers the exact same rows
            settings.n_trees = len(init_trees) + refresh_extra
            # an early-stop that tripped on the OLD stream must not veto
            # appending trees for the new window: don't replay it
            start_history = None
            log.info("refresh warm-start: %d restored trees + %d new "
                     "(target %d)", len(init_trees), refresh_extra,
                     settings.n_trees)
        if init_scores is not None and len(init_scores) != shards.num_rows:
            # the sidecar pinned f for a DIFFERENT plane (data-window
            # cursor sliced it, or new rows landed) — fall back to
            # replaying the restored trees over the current rows
            log.info("checkpoint scores cover %d rows, plane has %d — "
                     "replaying restored trees instead",
                     len(init_scores), shards.num_rows)
            init_scores = None
        from ..parallel.mesh import device_mesh
        mesh = device_mesh(n_ensemble=1)   # trees are sequential: all devices
        if streaming:                      # on the data axis
            stream = _tree_stream(shards, mesh, dict(mc.train.params or {}))
            log.info("train %s STREAMED: %d rows, window %d rows, mesh %s",
                     alg.name, stream.num_rows, stream.window_rows,
                     dict(mesh.shape))
            if alg == Algorithm.GBT:
                res = train_gbt_streamed(stream, n_bins, cat_mask, settings,
                                         progress, init_trees=init_trees,
                                         init_score=init_score,
                                         checkpoint_fn=ckpt_fn,
                                         start_history=start_history,
                                         mesh=mesh,
                                         init_scores=init_scores)
            else:
                res = train_rf_streamed(stream, n_bins, cat_mask, settings,
                                        progress, checkpoint_fn=ckpt_fn,
                                        init_trees=init_trees,
                                        start_history=start_history,
                                        mesh=mesh)
        else:
            data = shards.load_all()
            bins, y, w = data["bins"].astype(np.int32), data["y"], data["w"]
            log.info("train %s: %d rows x %d features, %d bins, %d trees "
                     "depth %d", alg.name, *bins.shape, n_bins,
                     settings.n_trees, settings.depth)
            if alg == Algorithm.GBT:
                res = train_gbt(bins, y, w, n_bins, cat_mask, settings,
                                progress, init_trees=init_trees,
                                init_score=init_score, checkpoint_fn=ckpt_fn,
                                start_history=start_history, mesh=mesh,
                                init_scores=init_scores)
            else:
                res = train_rf(bins, y, w, n_bins, cat_mask, settings,
                               progress, checkpoint_fn=ckpt_fn,
                               init_trees=init_trees,
                               start_history=start_history, mesh=mesh)
        if alg != Algorithm.GBT:
            res.spec_kwargs["algorithm"] = "RF" if alg != Algorithm.DT else "DT"

    spec = tree_model.TreeModelSpec(
        n_trees=len(res.trees), depth=settings.depth, n_bins=n_bins,
        column_nums=list(col_nums),
        feature_names=shards.schema.get("columnNames"),
        **res.spec_kwargs)
    os.makedirs(proc.paths.models_dir, exist_ok=True)
    for f in os.listdir(proc.paths.models_dir):
        if f.startswith("model"):
            os.remove(os.path.join(proc.paths.models_dir, f))
    path = proc.paths.model_path(0, alg.name.lower())
    tree_model.save_model(path, spec, res.trees)

    fi_named = sorted(
        ((shards.schema.get("columnNames", [str(cn) for cn in col_nums])[j],
          float(v)) for j, v in enumerate(res.feature_importance)),
        key=lambda kv: -kv[1])
    with open(os.path.join(proc.paths.tmp_dir, "feature_importance.json"),
              "w") as fjson:
        json.dump({k: v for k, v in fi_named}, fjson, indent=2)
    obs.gauge("train.valid_err").set(res.valid_error)
    obs.gauge("train.trees_built").set(res.trees_built)
    log.info("train %s done: %d trees, train err %.6f valid err %.6f; "
             "top features %s", alg.name, res.trees_built, res.train_error,
             res.valid_error, [n for n, _ in fi_named[:5]])
    return 0


def _forest_checkpoint_path(proc, suffix: str = "") -> str:
    return os.path.join(proc.paths.checkpoint_dir,
                        f"forest_ckpt{suffix}.npz")


def _forest_checkpoint_fn(proc, settings: DTSettings, alg, n_bins, col_nums,
                          shards, suffix: str = ""):
    """Mid-forest checkpoint (reference ``DTMaster.doCheckPoint`` every
    checkpointInterval iterations): partial forest + history persist; a
    killed run resumes from the last saved tree.  ``suffix`` separates
    per-class OVA checkpoints (``forest_ckpt_c{k}.npz``).  ``scores``
    (GBT per-row f) rides a sidecar so resume restores f BYTE-exact
    instead of replaying trees (replay is only f32-equivalent)."""
    def save(trees, history, init_score, scores=None):
        from ..ioutil import atomic_savez, atomic_write_json
        os.makedirs(proc.paths.checkpoint_dir, exist_ok=True)
        spec = tree_model.TreeModelSpec(
            n_trees=len(trees), depth=settings.depth, n_bins=n_bins,
            column_nums=list(col_nums),
            feature_names=shards.schema.get("columnNames"),
            algorithm=alg.name, loss=settings.loss,
            learning_rate=settings.learning_rate,
            init_score=init_score if init_score is not None else 0.0)
        path = _forest_checkpoint_path(proc, suffix)
        tmp = path + ".tmp"
        tree_model.save_model(tmp, spec, trees)
        os.replace(tmp, path)
        spath = path + ".scores.npz"
        if scores is not None:
            atomic_savez(spath, f=np.asarray(scores, np.float32),
                         trees_done=np.asarray(len(trees), np.int64))
        else:
            try:           # never pair stale scores with a newer forest
                os.remove(spath)
            except OSError:
                pass
        atomic_write_json(path + ".meta.json",
                          {"trees_done": len(trees), "history": history,
                           "seed": settings.seed}, indent=0)
        log.info("forest checkpoint: %d trees", len(trees))
    return save


def _restore_or_continuous(proc, alg, settings: DTSettings):
    """Resume order: explicit ``train -resume`` from the mid-forest
    checkpoint, else continuous training from the final saved model.
    Returns (trees, init_score, history, scores) — ``scores`` is the
    checkpointed per-row f (None for continuous / legacy checkpoints;
    the trainers then fall back to tree replay)."""
    if settings.resume:
        path = _forest_checkpoint_path(proc)
        if os.path.isfile(path):
            spec, trees = tree_model.load_model(path)
            meta = {}
            if os.path.isfile(path + ".meta.json"):
                with open(path + ".meta.json") as f:
                    meta = json.load(f)
            history = [tuple(h) for h in meta.get("history", [])]
            scores = None
            try:
                d = np.load(path + ".scores.npz")
                if int(d["trees_done"]) == len(trees):
                    scores = np.asarray(d["f"], np.float32)
            except (OSError, ValueError, KeyError):
                pass
            log.info("resume: restored %d trees from forest checkpoint"
                     "%s", len(trees),
                     " (+ per-row scores)" if scores is not None else "")
            return trees, spec.init_score, history, scores
    init_trees, init_score = _continuous_trees(proc, alg, settings)
    return init_trees, init_score, None, None


def _continuous_trees(proc, alg, settings: DTSettings
                      ) -> Tuple[Optional[List[TreeArrays]], Optional[float]]:
    """GBT continuous training appends trees to the existing forest —
    guarded like reference ``checkContinuousTraining``: the saved forest's
    shrinkage/loss must match or resuming would mis-score the old trees."""
    if not proc.model_config.train.isContinuous or alg != Algorithm.GBT:
        return None, None
    path = proc.paths.model_path(0, alg.name.lower())
    if not os.path.isfile(path):
        return None, None
    spec, trees = tree_model.load_model(path)
    if spec.loss != settings.loss or \
            abs(spec.learning_rate - settings.learning_rate) > 1e-12:
        log.warning("continuous GBT: saved forest used loss=%s lr=%s but "
                    "params now say loss=%s lr=%s — training fresh",
                    spec.loss, spec.learning_rate, settings.loss,
                    settings.learning_rate)
        return None, None
    log.info("continuous GBT: resuming from %d existing trees", len(trees))
    return trees, spec.init_score
