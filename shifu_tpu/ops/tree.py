"""Decision-tree kernels: histogram build + split-gain scan + batched predict.

Reference mapping (``core/dtrain/dt/``):
- per-(node,feature,bin) stats accumulation (``DTWorker.java:763-884``, the
  thread-parallel ``impurity.featureUpdate`` hot loop at ``:844-854``) →
  one ``segment_sum`` scatter-add per feature over the whole row shard, all
  features vmapped;
- ``Impurity.computeImpurity`` split scan (``dt/Impurity.java:38-734``:
  Variance:106, FriedmanMSE:255, Entropy:368, Gini:553) → vectorized prefix
  sums over the bin axis for every (node, feature) at once;
- categorical splits sort bins by response rate then scan prefixes
  (``Impurity.java:33`` comment) → per-(node,feature) ``argsort`` + gather;
- trees are complete binary arrays with positional ids (``dt/Node.java``
  ``indexToLevel`` layout): ``split_feat[node]``, per-bin ``left_mask`` —
  one uniform representation for numeric (bin <= k) and categorical
  (bin-subset) splits (``dt/Split.java`` numeric threshold / SimpleBitSet).

Everything is binned (int bins from the cleaned data plane), so a split is
always "bin ∈ left set" — scoring never touches raw floats.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

EPS = 1e-12


# ------------------------------------------------- per-row select lowering
# cap on the [N, n_nodes] one-hot operand width: past this the select
# form's memory (O(N * nodes) f32, materialized for the matmul) outgrows
# its speed win and the gather form takes over (deep trees: MaxDepth can
# go to 20 per config meta — 2^20-wide one-hots would OOM any HBM)
ONEHOT_MAX_NODES = 512


@lru_cache(maxsize=None)
def _onehot_traversal() -> bool:
    """Row-level tree traversal lowering.  XLA serializes per-row gathers
    (``x[idx]`` with a [N]-shaped ``idx``) on TPU — measured ~21 ns/row,
    which put 64% of resident-GBT tree time into ``take_along_axis`` — so
    on TPU the traversal selects through one-hot matmuls/reductions instead
    (MXU/VPU, ~7x at bench shapes).  CPU keeps native gathers (they are
    fast there and the tests run on the virtual CPU mesh).
    ``SHIFU_TREE_ONEHOT=1/0`` overrides; tests pin both paths.  Resolved
    ONCE per process (cached): traced programs bake the lowering in, so a
    mid-process env flip could not reach already-jitted shapes anyway —
    set it before the first traversal."""
    env = os.environ.get("SHIFU_TREE_ONEHOT", "auto")
    if env in ("0", "off"):
        return False
    if env in ("1", "force"):
        return True
    return jax.default_backend() == "tpu"


def _use_onehot(n_nodes: int) -> bool:
    return _onehot_traversal() and n_nodes <= ONEHOT_MAX_NODES


def _sel_exact(oh, table):
    """``table[idx]`` as a one-hot matmul (``oh`` = one_hot(idx)).  Exact:
    the one-hot operand is 0/1 and every output element sums exactly one
    term; HIGHEST precision keeps selected f32 values bit-identical to a
    gather."""
    return jnp.matmul(oh, table.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _row_bin_of(bins, feat):
    """``bins[i, feat[i]]`` without a gather: one-hot mask + reduce over
    the (small) feature axis — fused elementwise on the VPU, exact for
    integer bin ids."""
    featoh = jax.nn.one_hot(jnp.maximum(feat, 0), bins.shape[1],
                            dtype=jnp.float32)
    return jnp.round((featoh * bins.astype(jnp.float32)).sum(1)) \
        .astype(jnp.int32)


def _goes_left(lmask, oh, row_bin):
    """``lmask[node[i], row_bin[i]]`` without a gather: select the node's
    bin-mask row by matmul (0/1 operands, exact at any precision), then
    mask-reduce over bins."""
    lrow = jnp.matmul(oh, lmask.astype(jnp.float32))      # [N, B]
    binoh = jax.nn.one_hot(row_bin, lmask.shape[1], dtype=jnp.float32)
    return (lrow * binoh).sum(1) > 0.5


def _level_select(bins, node, feat, lmask):
    """One traversal level's selects for already-clamped node ids [N]
    (callers mask frozen rows themselves): returns (node_feat [N],
    goes_left [N]).  The single place both lowerings live — `_descend`
    (training descent) and `traverse_nodes` (predict/encode) must never
    drift."""
    if _use_onehot(feat.shape[0]):
        # ONE [N, K] one-hot shared by the feature-id and mask-row selects
        oh = jax.nn.one_hot(node, feat.shape[0], dtype=jnp.float32)
        node_feat = jnp.round(_sel_exact(oh, feat)).astype(jnp.int32)
        row_bin = _row_bin_of(bins, node_feat)
        return node_feat, _goes_left(lmask, oh, row_bin)
    node_feat = feat[node]
    row_bin = jnp.take_along_axis(
        bins, jnp.maximum(node_feat, 0)[:, None],
        axis=1)[:, 0].astype(jnp.int32)    # bins may ride the narrow wire
    return node_feat, lmask[node, row_bin]


@dataclass
class TreeArrays:
    """Complete binary tree, node i's children at 2i+1 / 2i+2."""
    split_feat: np.ndarray   # [nodes] int32, -1 = leaf
    left_mask: np.ndarray    # [nodes, n_bins] bool: bin goes left
    leaf_value: np.ndarray   # [nodes] float32
    depth: int

    @property
    def n_nodes(self) -> int:
        return len(self.split_feat)


def n_tree_nodes(depth: int) -> int:
    return (1 << (depth + 1)) - 1


# ------------------------------------------------------------- histograms
@partial(jax.jit, static_argnames=("n_nodes", "n_bins", "use_pallas",
                                   "mesh", "stats_exact"))
def build_histograms(bins, node_idx, stats, n_nodes: int, n_bins: int,
                     use_pallas: bool = False, mesh=None,
                     stats_exact: bool = False):
    """Per-row stats into (node, feature, bin) cells.

    bins: [N, C] any integer dtype — the trainers keep bins in the compact
    uint8/uint16 wire format all the way into HBM (4x the resident-cache
    capacity of int32); the widen to int32 happens here, in-graph, where
    XLA fuses it into the first consumer.  node_idx: [N] int32 level-local
    (-1 = inactive); stats: [N, S] float32 (S stat channels: [w, w*y] for
    binary/regression trees; per-class weight counts for multiclass).
    Returns [n_nodes, C, n_bins, S].

    Two lowerings: ``use_pallas=True`` → MXU one-hot-matmul kernel
    (:mod:`shifu_tpu.ops.hist_pallas`, ~50x on a TPU chip), shard_mapped
    over the mesh's data axis + psum when ``mesh`` spans devices; default
    → ``segment_sum`` scatter-add (CPU tests, or kernel disabled), which
    GSPMD partitions over the data axis on its own.

    ``stats_exact=True`` asserts every stats value is bf16-exact (small
    integer bag counts x 0/1 targets — RF without a weight column): the
    kernel skips its f32-recovery dots, ~1.6x at bench shapes.
    """
    bins = bins.astype(jnp.int32)      # no-op for int32 inputs
    if use_pallas:
        from .hist_pallas import (build_histograms_pallas,
                                  build_histograms_sharded, target_platform)
        # forced-on CPU meshes/tests take interpret mode; dispatch follows
        # where the op runs, not the host's default backend
        interpret = target_platform(mesh) != "tpu"
        if mesh is not None and mesh.size > 1:
            return build_histograms_sharded(bins, node_idx, stats, n_nodes,
                                            n_bins, mesh, interpret,
                                            stats_exact)
        return build_histograms_pallas(bins, node_idx, stats, n_nodes,
                                       n_bins, interpret, stats_exact)
    return _hist_scatter(bins, node_idx, stats, n_nodes, n_bins)


def _hist_scatter(bins, node_idx, stats, n_nodes: int, n_bins: int):
    """segment_sum lowering of the histogram build — the CPU/test path and
    the batched fallback's per-tree body (one implementation, so batched
    and sequential scatter results are bit-identical)."""
    active = node_idx >= 0
    seg_base = jnp.where(active, node_idx, 0) * n_bins
    masked = stats * active[:, None].astype(stats.dtype)

    def per_feature(bcol):
        idx = seg_base + bcol
        return jax.ops.segment_sum(masked, idx, num_segments=n_nodes * n_bins)

    out = jax.vmap(per_feature, in_axes=1)(bins)        # [C, nodes*bins, S]
    c = bins.shape[1]
    return out.reshape(c, n_nodes, n_bins, -1).transpose(1, 0, 2, 3)


# -------------------------------------------------- analytic cost model
# the scatter lowering's hand model, the CPU-side sibling of
# ``hist_pallas.hist_kernel_cost`` (registered under ``tree.scatter_hist``
# with obs.costs): segment_sum does one add per (row, feature, stat
# channel) plus the index arithmetic; output written once
def scatter_hist_cost(rows: int, n_feat: int, n_bins: int, n_nodes: int,
                      n_stats: int = 2, n_trees: int = 1) -> dict:
    flops = float(rows) * n_feat * (n_stats + 2) * n_trees
    read = 4.0 * rows * n_feat + 4.0 * rows * n_stats * n_trees
    write = 4.0 * n_trees * n_nodes * n_feat * n_bins * n_stats
    return {"flops": flops, "bytes_accessed": read + write}


def _register_cost_models() -> None:
    from ..obs import costs
    costs.register_cost_model("tree.scatter_hist", scatter_hist_cost)


_register_cost_models()


@partial(jax.jit, static_argnames=("n_nodes", "n_bins", "use_pallas",
                                   "mesh", "stats_exact"))
def build_histograms_batch(bins, node_idx_b, stats_b, n_nodes: int,
                           n_bins: int, use_pallas: bool = False, mesh=None,
                           stats_exact: bool = False):
    """Tree-batched :func:`build_histograms`: B independent trees' level
    histograms in ONE device program / ONE kernel launch.

    bins: [N, C] shared rows (narrow wire dtypes widen here, in-graph);
    node_idx_b: [TB, N] per-tree level-local positions (-1 = inactive);
    stats_b: [TB, N, S] per-tree channels.  Returns
    [TB, n_nodes, C, n_bins, S].

    The MXU lowering shares the bins one-hot across the tree batch
    (:func:`shifu_tpu.ops.hist_pallas.build_histograms_pallas_batch`) —
    one launch instead of TB, with each tree's slice bit-identical to its
    sequential build; the scatter fallback vmaps the shared per-tree body.
    """
    bins = bins.astype(jnp.int32)
    if use_pallas:
        from .hist_pallas import (build_histograms_batch_sharded,
                                  build_histograms_pallas_batch,
                                  target_platform)
        interpret = target_platform(mesh) != "tpu"
        if mesh is not None and mesh.size > 1:
            return build_histograms_batch_sharded(
                bins, node_idx_b, stats_b, n_nodes, n_bins, mesh, interpret,
                stats_exact)
        return build_histograms_pallas_batch(bins, node_idx_b, stats_b,
                                             n_nodes, n_bins, interpret,
                                             stats_exact)
    return jax.vmap(
        lambda ni, st: _hist_scatter(bins, ni, st, n_nodes, n_bins))(
        node_idx_b, stats_b)


# ------------------------------------------------------------- split scan
def _impurity_score(w, wy, kind: str):
    """Per-partition purity score; gain = score_L + score_R - score_P.
    variance uses sum^2/weight (equivalent to SSE reduction — the sum of
    squares cancels out of the gain, so histograms carry only (w, wy));
    entropy/gini use binary class counts (pos = wy, neg = w - wy)."""
    if kind == "variance":
        return wy * wy / jnp.maximum(w, EPS)
    pos = jnp.clip(wy, 0.0, None)
    neg = jnp.clip(w - wy, 0.0, None)
    tot = jnp.maximum(pos + neg, EPS)
    p = pos / tot
    if kind == "entropy":
        h = -(jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, EPS)), 0.0)
              + jnp.where(1 - p > 0, (1 - p) * jnp.log2(jnp.maximum(1 - p, EPS)),
                          0.0))
        return -tot * h
    if kind == "gini":
        return -tot * 2.0 * p * (1 - p)
    raise ValueError(f"unknown impurity {kind!r}")


def _class_score(cnt, kind: str):
    """Multi-class purity score from per-class weight counts ``cnt``
    [..., K]; gain = score_L + score_R - score_P (reference multiclass
    Entropy/Gini, ``dt/Impurity.java:368,553``)."""
    tot = jnp.maximum(cnt.sum(-1), EPS)
    p = jnp.clip(cnt, 0.0, None) / tot[..., None]
    if kind == "entropy":
        h = -(jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, EPS)), 0.0)).sum(-1)
        return -tot * h
    if kind == "gini":
        return -tot * (1.0 - (p * p).sum(-1))
    raise ValueError(f"multi-class impurity must be entropy/gini, "
                     f"got {kind!r}")


@partial(jax.jit, static_argnames=("impurity", "n_classes", "has_cat"))
def best_splits(hist, cat_mask, feat_active, impurity: str = "variance",
                min_instances: float = 1.0, min_gain: float = 0.0,
                n_classes: int = 0, has_cat: bool = True):
    """Best split per node from the level histogram.

    hist: [nodes, C, B, 2] (w, wy) — or, when ``n_classes > 2``,
    [nodes, C, B, K] per-class weight counts (multiclass NATIVE mode).
    cat_mask: [C] bool (categorical → bins sorted by response before the
    prefix scan); feat_active: [C] bool (feature sub-sampling, reference
    featureSubsetStrategy).

    Returns (gain [nodes], feat [nodes], left_mask [nodes, B],
             leaf_value [nodes] — or [nodes, K] class distributions when
             multiclass — and node_w [nodes]).
    """
    multiclass = n_classes > 2
    if multiclass:
        cls = hist                                         # [nodes, C, B, K]
        w = cls.sum(-1)
        if has_cat:
            # scalar "response" for categorical ordering: mean class index
            # (equals pos rate for K=2).  Only the categorical sort reads
            # it — ``has_cat=False`` (static) drops the [nodes, C, B, K]
            # reduction entirely (the active impurity never touches wy)
            kidx = jnp.arange(n_classes, dtype=hist.dtype)
            wy = (cls * kidx).sum(-1)
        else:
            wy = w          # placeholder, compiled out (w_o path unused)
    else:
        w, wy = hist[..., 0], hist[..., 1]
    n_nodes, c, b = w.shape

    # ---- per-(node,feat) bin order: natural for numeric, response-sorted
    # for categorical (empty bins pushed last so prefixes skip them).
    # The argsort/gather machinery only matters for categorical features:
    # ``has_cat=False`` (static, trainers know their cat_mask host-side)
    # compiles it out entirely; otherwise a runtime lax.cond still skips
    # the sort when the mask is dynamically empty
    if has_cat:
        nat_order = jnp.broadcast_to(jnp.arange(b), (n_nodes, c, b))

        def _mixed_order():
            rate = wy / jnp.maximum(w, EPS)
            sort_key = jnp.where(w > 0, -rate, jnp.inf)
            cat_order = jnp.argsort(sort_key, axis=-1)    # [nodes, C, B]
            return jnp.where(cat_mask[None, :, None], cat_order, nat_order)

        order = jax.lax.cond(jnp.any(cat_mask), _mixed_order,
                             lambda: nat_order)
        w_o = jnp.take_along_axis(w, order, axis=-1)
        wy_o = jnp.take_along_axis(wy, order, axis=-1)
    else:
        w_o, wy_o = w, wy

    cw = jnp.cumsum(w_o, axis=-1)
    cwy = jnp.cumsum(wy_o, axis=-1)
    tw, twy = cw[..., -1:], cwy[..., -1:]

    if multiclass:
        cls_o = jnp.take_along_axis(cls, order[..., None], axis=2) \
            if has_cat else cls
        ccls = jnp.cumsum(cls_o, axis=2)                  # [nodes, C, B, K]
        tcls = ccls[:, :, -1:, :]
        score_l = _class_score(ccls, impurity)
        score_r = _class_score(tcls - ccls, impurity)
        score_p = _class_score(tcls, impurity)
        gain = score_l + score_r - score_p                 # [nodes, C, B]
    elif impurity == "friedmanmse":
        # Friedman's improvement (reference ``dt/Impurity.java:313-315``):
        # (w_r*s_l - w_l*s_r)^2 / (w_l*w_r*(w_l+w_r))
        wl, wr = cw, tw - cw
        diff = wr * cwy - wl * (twy - cwy)
        gain = diff * diff / jnp.maximum(wl * wr * (wl + wr), EPS)
    else:
        score_l = _impurity_score(cw, cwy, impurity)
        score_r = _impurity_score(tw - cw, twy - cwy, impurity)
        score_p = _impurity_score(tw, twy, impurity)
        gain = score_l + score_r - score_p                 # [nodes, C, B]

    valid = (cw >= min_instances) & (tw - cw >= min_instances)
    valid = valid & feat_active[None, :, None]
    valid = valid.at[..., -1].set(False)                   # full prefix = no split
    gain = jnp.where(valid, gain, -jnp.inf)

    best_k = jnp.argmax(gain, axis=-1)                     # [nodes, C]
    best_gain_f = jnp.take_along_axis(gain, best_k[..., None], axis=-1)[..., 0]
    best_feat = jnp.argmax(best_gain_f, axis=-1)           # [nodes]
    node_gain = jnp.take_along_axis(best_gain_f, best_feat[:, None],
                                    axis=-1)[:, 0]

    # ---- build left_mask for the winning (feat, k): order[:k+1] goes left
    k_sel = jnp.take_along_axis(best_k, best_feat[:, None], axis=-1)  # [nodes,1]
    if has_cat:
        order_sel = jnp.take_along_axis(
            order, best_feat[:, None, None], axis=1)[:, 0]  # [nodes, B]
        ranks = jnp.argsort(order_sel, axis=-1)             # bin -> position
        left_mask = ranks <= k_sel
    else:   # natural order: position == bin index
        left_mask = jnp.arange(b)[None, :] <= k_sel

    node_w = tw[..., 0, 0]
    if multiclass:
        node_cls = tcls[:, 0, 0, :]                       # [nodes, K]
        leaf_value = node_cls / jnp.maximum(node_w, EPS)[:, None]
    else:
        leaf_value = twy[..., 0, 0] / jnp.maximum(node_w, EPS)
    ok = jnp.isfinite(node_gain) & (node_gain > min_gain)
    feat = jnp.where(ok, best_feat, -1)
    return node_gain, feat.astype(jnp.int32), left_mask & ok[:, None], \
        leaf_value, node_w


def cap_splits_by_leaves(gain, feat, lmask, nodes_cnt, max_leaves: int):
    """Leaf-wise node budget (reference ``DTMaster.java:543-560``
    ``splitNodeForLeafWisedTree``: a split is refused once the tree's node
    count would exceed MaxLeaves; each split adds two nodes).  TPU-shaped
    as best-first-within-level: candidate splits rank by gain and consume
    the remaining budget in that order, the rest freeze to leaves — same
    budget arithmetic, static shapes, no host queue.

    Returns (feat, lmask, new nodes_cnt); ``nodes_cnt`` is a traced int32
    scalar starting at 1 (the root)."""
    cand = feat >= 0
    key = jnp.where(cand, -gain, jnp.inf)
    rank = jnp.argsort(jnp.argsort(key))
    # reference arithmetic: a split is allowed while nodeNum + 1 <=
    # maxLeaves BEFORE its two children land, so for even MaxLeaves the
    # final count may reach maxLeaves + 1 (one more split than a strict
    # <= maxLeaves cap); rank r's split sees nodes_cnt + 2r nodes
    budget = jnp.maximum((max_leaves - nodes_cnt + 1) // 2, 0)
    allow = cand & (rank < budget)
    return (jnp.where(allow, feat, -1), lmask & allow[:, None],
            nodes_cnt + 2 * allow.sum().astype(nodes_cnt.dtype))


# ------------------------------------------------------------------ grow
def _descend(bins, node_idx, feat, lmask):
    """One level of worker tree traversal: rows whose node split move to a
    child's level-local index; rows at leaves freeze at -1 (frozen rows
    select node 0's values through the clamp, masked by ``active``)."""
    node_feat, goes_left = _level_select(
        bins, jnp.maximum(node_idx, 0), feat, lmask)
    active = (node_idx >= 0) & (node_feat >= 0)
    return jnp.where(active, 2 * node_idx + jnp.where(goes_left, 0, 1), -1)


@partial(jax.jit, static_argnames=("n_bins", "depth", "impurity",
                                   "n_classes", "use_pallas", "max_leaves",
                                   "has_cat", "mesh", "stats_exact",
                                   "record_hists"))
def grow_tree_jit(bins, stats, cat, fa, n_bins: int, depth: int,
                  impurity: str, min_instances: float, min_gain: float,
                  n_classes: int = 0, use_pallas: bool = False,
                  max_leaves: int = 0, has_cat: bool = True, mesh=None,
                  stats_exact: bool = False, record_hists: bool = False,
                  tail_extra=None, prev_sf=None, prev_lm=None,
                  valid_upto=None):
    """Whole-tree level-wise growth as ONE jitted program — zero host syncs
    per level (reference ``DTMaster.java:543-600`` level mode; the round-1
    build synced feat/lmask/leaf to host every level).

    Returns (split_feat [total], left_mask [total, B], leaf_value [total],
    gain_fi [C]) device arrays; per-level arrays concatenate into the
    positional complete-binary-tree layout because level l starts at node
    2^l - 1.  ``gain_fi`` accumulates realized split gains per feature
    (gain-weighted FI, reference ``GainInfo`` aggregation).

    ``record_hists=True`` additionally returns (hist_left [depth,
    2^(depth-1), C, B, S], leaf_raw [S, 2^depth]): the per-level LEFT-child
    histograms (level 0 = the full root histogram) and the bottom level's
    raw stat sums, in exactly the accumulator layout
    :func:`build_path_histograms` emits — a coarse-to-fine tail grow on
    the resident prefix keeps its own histograms as the resident
    contribution to the exact totals instead of recomputing them.

    ``tail_extra`` ([depth, 2^(depth-1), C, B, S], optional — with
    ``prev_sf``/``prev_lm`` [total]/[total, B] and ``valid_upto`` traced
    int32) is STALE TAIL EVIDENCE for the split DECISIONS only: the
    previous coarse-to-fine pass's exact tail-only per-level left-child
    histograms (level 0 slot = the full tail root).  Level l's decision
    histogram becomes resident + tail_extra-derived WHEN the evidence is
    routing-compatible: l <= valid_upto (the previous pass confirmed its
    speculation through level l, so its accumulators are exactly routed
    there) AND this tree's structure above l bit-matches the previous
    tree's (checked level-by-level in-graph — GBT trees on smooth
    objectives repeat their upper structure, so the gate stays open deep
    and the speculated thresholds pin to near-full-data optima instead
    of the resident prefix's).  The evidence NEVER enters the recorded
    histograms or the subtraction chain — it only steers speculation;
    exactness is enforced downstream by the verify/repair pass.
    """
    n, c = bins.shape
    feats, lmasks, leaves = [], [], []
    gain_fi = jnp.zeros(c, jnp.float32)
    node_idx = jnp.zeros(n, jnp.int32)       # level-local position, -1 done
    leaf_glob = jnp.zeros(n, jnp.int32)      # global node id where row rests
    nodes_cnt = jnp.int32(1)                 # leaf-wise budget state
    half = max(1 << max(depth - 1, 0), 1)    # record slot width per level
    rec_left: list = []
    leaf_raw = None
    hist_prev = None
    feat_prev = None
    stale = tail_extra is not None
    prefix_ok = jnp.bool_(True)              # structure matches prev tree
    tail_full = None                         # prev level's full tail hist
    for level in range(depth + 1):
        n_nodes = 1 << level
        if level == depth:
            # the bottom level never splits — best_splits' gain/feat/lmask
            # would be discarded, so the full [K, C, B, S] histogram (the
            # deepest, most expensive kernel call of the tree) is pure
            # waste.  Leaf values need only per-node stat sums: one
            # [S, N] x [N, K] dot (HIGHEST precision keeps f32-exact
            # counts; frozen rows mask to no column).
            leaf_raw = _level_leaf_raw(stats, node_idx, n_nodes)
            leaves.append(leaf_values_from_raw(leaf_raw, n_classes))
            feats.append(jnp.full(n_nodes, -1, jnp.int32))
            lmasks.append(jnp.zeros((n_nodes, n_bins), bool))
            break
        if level == 0:
            hist = build_histograms(bins, node_idx, stats, n_nodes, n_bins,
                                    use_pallas, mesh, stats_exact)
            if record_hists:
                rec_left.append(_pad_nodes(hist, half))
            if stale:
                tail_full = tail_extra[0, :1]     # tail root, routing-free
                hist_decide = hist + tail_full
            else:
                hist_decide = hist
        else:
            # histogram SUBTRACTION (the LightGBM trick the reference's
            # level-wise DTMaster never had): build only the LEFT-child
            # histograms — half the one-hot node width, so half the MXU
            # work — and derive each right child as parent - left.  A
            # frozen (unsplit) parent contributes neither child: its left
            # rows map to no node (idx -1) and its right half is masked
            # to zero instead of inheriting the parent's histogram.
            hl = build_histograms(
                bins, _left_child_index(node_idx), stats, n_nodes // 2,
                n_bins, use_pallas, mesh, stats_exact)
            if record_hists:
                rec_left.append(_pad_nodes(hl, half))
            split_ok = feat_prev >= 0
            hr = jnp.where(split_ok[:, None, None, None],
                           hist_prev - hl, 0.0)
            hist = jnp.stack([hl, hr], axis=1) \
                .reshape(n_nodes, c, hl.shape[2], hl.shape[3])
            if stale:
                # derive the tail's full level hist the same way (the
                # evidence chain routes along the PREVIOUS tree, so its
                # subtraction uses prev_sf's split mask), then gate: the
                # prev pass must have confirmed through this level AND
                # this tree's prefix must still match the prev tree's
                t_hl = tail_extra[level][:n_nodes // 2]
                p_feat = jax.lax.dynamic_slice_in_dim(
                    prev_sf, n_nodes // 2 - 1, n_nodes // 2)
                t_hr = jnp.where((p_feat >= 0)[:, None, None, None],
                                 tail_full - t_hl, 0.0)
                tail_full = jnp.stack([t_hl, t_hr], axis=1) \
                    .reshape(n_nodes, c, hl.shape[2], hl.shape[3])
                gate = (jnp.int32(level) <= valid_upto) & prefix_ok
                hist_decide = jnp.where(gate, hist + tail_full, hist)
            else:
                hist_decide = hist
        gain, feat, lmask, leaf, node_w = best_splits(
            hist_decide, cat, fa, impurity, min_instances, min_gain,
            n_classes, has_cat)
        if max_leaves > 0:
            feat, lmask, nodes_cnt = cap_splits_by_leaves(
                gain, feat, lmask, nodes_cnt, max_leaves)
        if stale:
            p_feat = jax.lax.dynamic_slice_in_dim(prev_sf, n_nodes - 1,
                                                  n_nodes)
            p_lm = jax.lax.dynamic_slice_in_dim(prev_lm, n_nodes - 1,
                                                n_nodes, axis=0)
            prefix_ok = prefix_ok & jnp.all(feat == p_feat) & \
                jnp.all(lmask == p_lm)
        feats.append(feat)
        lmasks.append(lmask)
        leaves.append(leaf)
        gain_fi = gain_fi + jax.ops.segment_sum(
            jnp.where(feat >= 0, jnp.maximum(gain, 0.0), 0.0).astype(jnp.float32),
            jnp.maximum(feat, 0), num_segments=c)
        hist_prev, feat_prev = hist, feat
        node_idx = _descend(bins, node_idx, feat, lmask)
        # rows that just descended rest at their child's GLOBAL id; frozen
        # rows keep the node they stopped at — after the loop this is the
        # terminal node per row (predict = leaf_value[leaf_glob], no
        # re-walk; see traverse_nodes for the standalone path)
        leaf_glob = jnp.where(node_idx >= 0,
                              ((1 << (level + 1)) - 1) + node_idx,
                              leaf_glob)
    out = (jnp.concatenate(feats), jnp.concatenate(lmasks, axis=0),
           jnp.concatenate(leaves), gain_fi, leaf_glob)
    if record_hists:
        return out + (jnp.stack(rec_left), leaf_raw)
    return out


def _pad_nodes(hist, width: int):
    """Zero-pad a level histogram's node axis to ``width`` so every level
    shares one accumulator slot shape."""
    k = hist.shape[0]
    if k >= width:
        return hist
    return jnp.concatenate(
        [hist, jnp.zeros((width - k,) + hist.shape[1:], hist.dtype)])


@partial(jax.jit, static_argnames=("depth", "n_bins", "use_pallas", "mesh",
                                   "stats_exact"))
def build_path_histograms(bins, stats, split_feat, left_mask, depth: int,
                          n_bins: int, use_pallas: bool = False, mesh=None,
                          stats_exact: bool = False, hist_bins=None):
    """EVERY level's histograms along a FIXED tree structure in one pass
    over the rows — the coarse-to-fine disk-tail schedule's core op.

    The per-level tail re-stream exists because level l's node routing
    depends on level l-1's chosen splits.  Given a *speculated* structure
    (``split_feat``/``left_mask`` from the resident prefix), the routing
    of every level is known up front, so ONE pass over a window computes
    all of them: per level the LEFT-child histogram only (level 0 = the
    full root histogram; right children derive as parent - left at
    selection time, the same subtraction :func:`grow_tree_jit` uses) plus
    the bottom level's raw leaf stat sums.

    Returns (hist_left [depth, 2^(depth-1), C, B, S] — level l occupying
    the first ``max(2^(l-1), 1)`` node slots, rest zero — and leaf_raw
    [S, 2^depth]).  Layout matches ``grow_tree_jit(record_hists=True)``
    exactly so resident and tail contributions add cell-for-cell.

    ``hist_bins`` (optional [N, K]) narrows the HISTOGRAM build to a
    candidate feature subset while routing still walks the full ``bins``
    — the bounded-candidate scan of the coarse-to-fine tail.
    """
    assert depth >= 1
    n, c = bins.shape
    half = max(1 << (depth - 1), 1)
    node_idx = jnp.zeros(n, jnp.int32)
    idx_levels = [node_idx]                    # level 0: full root
    for level in range(1, depth + 1):
        base = (1 << (level - 1)) - 1
        feat = jax.lax.dynamic_slice_in_dim(split_feat, base,
                                            1 << (level - 1))
        lmask = jax.lax.dynamic_slice_in_dim(left_mask, base,
                                             1 << (level - 1), axis=0)
        node_idx = _descend(bins, node_idx, feat, lmask)
        if level < depth:
            idx_levels.append(_left_child_index(node_idx))
    idx_b = jnp.stack(idx_levels)              # [depth, N]
    stats_b = jnp.broadcast_to(stats[None], (depth,) + stats.shape)
    hb = bins if hist_bins is None else hist_bins
    hist_left = build_histograms_batch(hb, idx_b, stats_b, half, n_bins,
                                       use_pallas, mesh, stats_exact)
    leaf_raw = _level_leaf_raw(stats, node_idx, 1 << depth)
    return hist_left, leaf_raw


@partial(jax.jit, static_argnames=("n_bins", "depth", "impurity",
                                   "n_classes", "use_pallas", "max_leaves",
                                   "has_cat", "mesh", "stats_exact"))
def grow_forest_jit(bins, stats_b, cat, fa_b, n_bins: int, depth: int,
                    impurity: str, min_instances: float, min_gain: float,
                    n_classes: int = 0, use_pallas: bool = False,
                    max_leaves: int = 0, has_cat: bool = True, mesh=None,
                    stats_exact: bool = False):
    """TB independent same-structure trees grown level-wise as ONE jitted
    program — the tree-batched :func:`grow_tree_jit` (reference
    ``DTMaster.java:91``: the toDoQueue spans ALL RF trees of a round, one
    stats pass per level for the whole forest).

    stats_b: [TB, N, S] per-tree stat channels (RF bags differ per tree);
    fa_b: [TB, C] per-tree feature subsets; ``bins``/``cat`` are shared.
    Each level's TB histograms build in ONE kernel launch
    (:func:`build_histograms_batch` — the bins one-hot amortizes across
    the batch, and shallow levels' skinny [K, nblk] node operands stack
    into full MXU tiles).  Histogram subtraction, the leaf-sum bottom
    level and the leaf-wise budget all apply per tree exactly as in
    :func:`grow_tree_jit`; every per-tree result is bit-identical to a
    sequential grow (the batched==sequential parity guard pins it).

    Returns ([TB, total] split_feat, [TB, total, B] left_mask,
    [TB, total] (or [TB, total, K]) leaf_value, [TB, C] gain_fi,
    [TB, N] leaf_glob).
    """
    n, c = bins.shape
    tb = stats_b.shape[0]
    feats, lmasks, leaves = [], [], []
    gain_fi = jnp.zeros((tb, c), jnp.float32)
    node_idx = jnp.zeros((tb, n), jnp.int32)
    leaf_glob = jnp.zeros((tb, n), jnp.int32)
    nodes_cnt = jnp.ones(tb, jnp.int32)
    hist_prev = None
    feat_prev = None
    for level in range(depth + 1):
        n_nodes = 1 << level
        if level == depth:
            leaves.append(jax.vmap(
                lambda st, ni: _level_leaf_sums(st, ni, n_nodes,
                                                n_classes))(
                stats_b, node_idx))
            feats.append(jnp.full((tb, n_nodes), -1, jnp.int32))
            lmasks.append(jnp.zeros((tb, n_nodes, n_bins), bool))
            break
        if level == 0:
            hist = build_histograms_batch(bins, node_idx, stats_b, n_nodes,
                                          n_bins, use_pallas, mesh,
                                          stats_exact)
        else:
            hl = build_histograms_batch(
                bins, jax.vmap(_left_child_index)(node_idx), stats_b,
                n_nodes // 2, n_bins, use_pallas, mesh, stats_exact)
            split_ok = feat_prev >= 0                      # [TB, K/2]
            hr = jnp.where(split_ok[:, :, None, None, None],
                           hist_prev - hl, 0.0)
            hist = jnp.stack([hl, hr], axis=2) \
                .reshape(tb, n_nodes, c, hl.shape[3], hl.shape[4])
        gain, feat, lmask, leaf, _ = jax.vmap(
            lambda h, f: best_splits(h, cat, f, impurity, min_instances,
                                     min_gain, n_classes, has_cat))(
            hist, fa_b)
        if max_leaves > 0:
            feat, lmask, nodes_cnt = jax.vmap(
                lambda g, f, lm, nc: cap_splits_by_leaves(g, f, lm, nc,
                                                          max_leaves))(
                gain, feat, lmask, nodes_cnt)
        feats.append(feat)
        lmasks.append(lmask)
        leaves.append(leaf)
        gain_fi = gain_fi + jax.vmap(
            lambda g, f: jax.ops.segment_sum(
                jnp.where(f >= 0, jnp.maximum(g, 0.0),
                          0.0).astype(jnp.float32),
                jnp.maximum(f, 0), num_segments=c))(gain, feat)
        hist_prev, feat_prev = hist, feat
        node_idx = jax.vmap(
            lambda ni, f, lm: _descend(bins, ni, f, lm))(node_idx, feat,
                                                         lmask)
        leaf_glob = jnp.where(node_idx >= 0,
                              ((1 << (level + 1)) - 1) + node_idx,
                              leaf_glob)
    return (jnp.concatenate(feats, axis=1),
            jnp.concatenate(lmasks, axis=1),
            jnp.concatenate(leaves, axis=1), gain_fi, leaf_glob)


def _level_leaf_raw(stats, node_idx, n_nodes: int):
    """Per-node RAW stat sums [S, K] at one level (frozen rows contribute
    nothing) — the accumulable form of :func:`_level_leaf_sums`: streamed
    sweeps sum these across windows and divide once at the end, so the
    bottom level of an out-of-core tree costs a [S, N] x [N, K] dot per
    window instead of the full [K, C, B, S] histogram."""
    oh = jax.nn.one_hot(node_idx, n_nodes, dtype=jnp.float32)  # -1 -> 0s
    return jax.lax.dot_general(stats, oh, (((0,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


def leaf_values_from_raw(sums, n_classes: int = 0):
    """``[S, K]`` raw stat sums -> leaf values ([K] ``wy/w`` or [K, S]
    class distributions) — the ONE place the ratio lives (resident grow,
    streamed bottom sweeps and the coarse-to-fine tail must agree)."""
    if n_classes > 2:
        w = sums.sum(axis=0)                               # [K]
        return (sums / jnp.maximum(w, EPS)[None, :]).T     # [K, S]
    return sums[1] / jnp.maximum(sums[0], EPS)


def _level_leaf_sums(stats, node_idx, n_nodes: int, n_classes: int = 0):
    """Per-node leaf values from stat sums alone: [K] ``wy/w`` (binary /
    regression) or [K, n_classes] class distributions (multiclass)."""
    return leaf_values_from_raw(_level_leaf_raw(stats, node_idx, n_nodes),
                                n_classes)


def _left_child_index(node_idx):
    """Level-local LEFT-child selector for histogram subtraction: a row in
    left child ``2p`` maps to parent slot ``p``; right-child and frozen
    rows map to -1 (contribute to no one-hot node)."""
    return jnp.where((node_idx >= 0) & (node_idx % 2 == 0),
                     node_idx // 2, -1)


def grow_tree(bins, targets, weights, n_bins: int, depth: int,
              impurity: str = "variance", min_instances: float = 1.0,
              min_gain: float = 0.0, cat_mask: Optional[np.ndarray] = None,
              feat_active: Optional[np.ndarray] = None) -> TreeArrays:
    """Host-facing wrapper over :func:`grow_tree_jit`."""
    n, c = bins.shape
    bins = jnp.asarray(bins, jnp.int32)
    t = jnp.asarray(targets, jnp.float32)
    wt = jnp.asarray(weights, jnp.float32)
    stats = jnp.stack([wt, wt * t], axis=1)
    cat = jnp.zeros(c, bool) if cat_mask is None else jnp.asarray(cat_mask)
    fa = jnp.ones(c, bool) if feat_active is None else jnp.asarray(feat_active)
    split_feat, left_mask, leaf_value, _, _ = grow_tree_jit(
        bins, stats, cat, fa, n_bins, depth, impurity,
        float(min_instances), float(min_gain))
    return TreeArrays(split_feat=np.asarray(split_feat),
                      left_mask=np.asarray(left_mask),
                      leaf_value=np.asarray(leaf_value), depth=depth)


@partial(jax.jit, static_argnames=("level",))
def node_index_at_level(split_feat, left_mask, bins, level: int):
    """Level-local node index of every row in a PARTIAL tree (levels above
    ``level`` already decided); -1 where an ancestor froze.  The streaming
    trainers re-derive window row positions from the tree instead of keeping
    a per-row index resident (rows don't fit)."""
    n = bins.shape[0]
    node_idx = jnp.zeros(n, jnp.int32)
    for l in range(level):
        base = (1 << l) - 1
        feat = jax.lax.dynamic_slice_in_dim(split_feat, base, 1 << l)
        lmask = jax.lax.dynamic_slice_in_dim(left_mask, base, 1 << l, axis=0)
        node_idx = _descend(bins, node_idx, feat, lmask)
    return node_idx


# ---------------------------------------------------------------- predict
def traverse_nodes(split_feat, left_mask, bins, depth: int):
    """Terminal global node id per row after ``depth`` descents (shared by
    predict and the `encode` step's leaf indexing).

    The one-hot lowering works LEVEL-LOCALLY (selects against the 2^l
    nodes of level l, not all 2^(depth+1)-1 nodes) so the [N, K] one-hot
    width — and with it the :data:`ONEHOT_MAX_NODES` fast-path bound —
    grows with the widest level, keeping MXU selects through the
    reference's common depth range."""
    n = bins.shape[0]
    node = jnp.zeros(n, jnp.int32)           # global node ids, never -1
    for level in range(depth):
        k = 1 << level
        if _use_onehot(k):
            base = k - 1
            feat_l = jax.lax.dynamic_slice_in_dim(split_feat, base, k)
            lm_l = jax.lax.dynamic_slice_in_dim(left_mask, base, k, axis=0)
            loc = node - base                # frozen rows: loc < 0
            in_level = loc >= 0
            feat, goes_left = _level_select(
                bins, jnp.clip(loc, 0, k - 1), feat_l, lm_l)
            is_split = in_level & (feat >= 0)
        else:
            feat, goes_left = _level_select(bins, node, split_feat,
                                            left_mask)
            is_split = feat >= 0
        child = jnp.where(goes_left, 2 * node + 1, 2 * node + 2)
        node = jnp.where(is_split, child, node)
    return node


@partial(jax.jit, static_argnames=("depth",))
def predict_tree(split_feat, left_mask, leaf_value, bins, depth: int):
    """Batched traversal: one descent per level over all rows."""
    node = traverse_nodes(split_feat, left_mask, bins, depth)
    if _use_onehot(split_feat.shape[0]):
        oh = jax.nn.one_hot(node, split_feat.shape[0], dtype=jnp.float32)
        return _sel_exact(oh, leaf_value)    # [N] or [N, K] (multiclass)
    return leaf_value[node]


def stack_forest(trees) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stack same-depth trees into [T, ...] arrays for one vmapped predict."""
    return (jnp.stack([jnp.asarray(t.split_feat) for t in trees]),
            jnp.stack([jnp.asarray(t.left_mask) for t in trees]),
            jnp.stack([jnp.asarray(t.leaf_value) for t in trees]))


@partial(jax.jit, static_argnames=("depth",))
def predict_forest_stacked(split_feats, left_masks, leaf_values, bins,
                           depth: int):
    """[T, N] predictions of a stacked forest in one compiled call — the
    per-tree Python loop (round-1 ``predict_tree`` per tree per model)
    becomes a single vmap."""
    return jax.vmap(predict_tree, in_axes=(0, 0, 0, None, None))(
        split_feats, left_masks, leaf_values, bins, depth)


def predict_forest(trees, bins, weights=None) -> np.ndarray:
    """Weighted-average forest prediction (RF mean vote / GBT partial sums
    are built by the caller).  Trees stack per depth group (continuous runs
    may append trees of a different depth).  Multiclass forests (2D
    ``leaf_value`` class distributions) average to [n, K]."""
    bins = jnp.asarray(bins)
    if not jnp.issubdtype(bins.dtype, jnp.integer):
        bins = bins.astype(jnp.int32)
    # integer bins keep their wire dtype (uint8 since PR 2): the gather
    # traversal consumes the narrow plane directly — the widen here cost
    # 4x the bytes of scoring's dominant operand
    k = trees[0].leaf_value.shape[1] if trees[0].leaf_value.ndim == 2 else 0
    shape = (len(trees), bins.shape[0], k) if k \
        else (len(trees), bins.shape[0])
    preds = np.empty(shape, np.float32)
    by_depth: dict = {}
    for i, t in enumerate(trees):
        by_depth.setdefault(t.depth, []).append(i)
    for depth, idxs in by_depth.items():
        sf, lm, lv = stack_forest([trees[i] for i in idxs])
        preds[idxs] = np.asarray(
            predict_forest_stacked(sf, lm, lv, bins, depth))
    if weights is None:
        return preds.mean(axis=0)
    w = np.asarray(weights).reshape((-1,) + (1,) * (preds.ndim - 1))
    return (preds * w).sum(axis=0)
