"""Plain reference for gradient-boosted regression trees on binned rows.

NumPy, float64, no code shared with ``shifu_tpu/``.  Written from the rules
of the reference system's ``DTWorker``/``DTMaster`` that SURVEY.md cites:

- a level's statistics are per (node, column, bin) sums of the row weight
  and of weight x residual (``bincount``);
- *variance* impurity: a partition's score is ``sum^2 / weight``; a split's
  gain is ``score(left) + score(right) - score(parent)``;
- numeric columns split on a prefix of the natural bin order (the missing
  bin is a column's last value bin + 1, so it goes right); categorical
  columns on a prefix of the bins sorted by mean response, empty bins last;
- a side lighter than ``MinInstancesPerNode`` is no candidate; a best gain
  not above ``MinInfoGain`` makes a leaf; a node's value is its mean
  residual; squared loss: residual = y - f, f = prior + rate x sum(leaves).

Trees are complete binary arrays: node i's children are 2i+1 / 2i+2,
``split_feat`` -1 marks a leaf, ``left_mask[node, bin]`` says bin goes left.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def node_histogram(bins: np.ndarray, resid: np.ndarray, n_bins: int) -> Tuple[np.ndarray, np.ndarray]:
    """(w, s) [C, B] float64 for the rows given."""
    n, c = bins.shape
    flat = (bins.astype(np.int64) + np.arange(c, dtype=np.int64)[None, :] * n_bins).ravel()
    w = np.bincount(flat, minlength=c * n_bins).astype(np.float64)
    s = np.bincount(flat, weights=np.repeat(resid.astype(np.float64), c), minlength=c * n_bins)
    return w.reshape(c, n_bins), s.reshape(c, n_bins)


def _score(s, w):
    return np.where(w > 0, s * s / np.maximum(w, 1e-300), 0.0)


def candidates(w: np.ndarray, s: np.ndarray, cat_mask: np.ndarray,
               min_instances: float) -> Dict[str, np.ndarray]:
    """Every prefix candidate of one node.  Returns per (column, position):
    gain (-inf where not valid), the bin order used, and the sums a band
    needs.  Position k sends ``order[:k+1]`` left."""
    c, b = w.shape
    order = np.broadcast_to(np.arange(b), (c, b)).copy()
    rate = np.where(w > 0, s / np.maximum(w, 1e-300), 0.0)
    key = np.where(w > 0, -rate, np.inf)
    cat_order = np.argsort(key, axis=1, kind="stable")
    order[cat_mask] = cat_order[cat_mask]
    w_o = np.take_along_axis(w, order, 1)
    s_o = np.take_along_axis(s, order, 1)
    wl, sl = np.cumsum(w_o, 1), np.cumsum(s_o, 1)
    tw, ts = wl[:, -1:], sl[:, -1:]
    wr, sr = tw - wl, ts - sl
    gain = _score(sl, wl) + _score(sr, wr) - _score(ts, tw)
    valid = (wl >= min_instances) & (wr >= min_instances)
    valid[:, -1] = False
    return {"gain": np.where(valid, gain, -np.inf), "order": order,
            "abs_sums": np.abs(sl) + np.abs(sr) + np.abs(ts), "w_o": w_o}


def split_gain(w: np.ndarray, s: np.ndarray, feat: int, mask: np.ndarray,
               min_instances: float) -> Tuple[float, float]:
    """Gain of an arbitrary split (column ``feat``, bins in ``mask`` go
    left) and its |sums| (for the band); -inf if a side is too light."""
    wl, sl = w[feat][mask].sum(), s[feat][mask].sum()
    tw, ts = w[feat].sum(), s[feat].sum()
    wr, sr = tw - wl, ts - sl
    if wl < min_instances or wr < min_instances:
        return -np.inf, 0.0
    g = float(_score(sl, wl) + _score(sr, wr) - _score(ts, tw))
    return g, float(abs(sl) + abs(sr) + abs(ts))


def best_and_runner_up(cand: Dict[str, np.ndarray]) -> Tuple[float, int, np.ndarray, float, float]:
    """(best gain, its column, its left mask, the best gain among candidates
    that part the node's rows *differently*, the best's |sums|).  Two
    positions of one column that differ only by empty bins part the rows
    alike, so they are one candidate."""
    gain, order, w_o = cand["gain"], cand["order"], cand["w_o"]
    c, b = gain.shape
    flat = int(np.argmax(gain))
    f, k = divmod(flat, b)
    best = float(gain[f, k])
    mask = np.zeros(b, bool)
    if not np.isfinite(best):
        return best, -1, mask, -np.inf, 0.0
    mask[order[f, :k + 1]] = True
    others = gain.copy()
    # same column, same rows on the left: positions k' with no row between
    nonempty = np.cumsum(w_o[f] > 0)
    others[f, nonempty == nonempty[k]] = -np.inf
    return best, f, mask, float(others.max()), float(cand["abs_sums"][f, k])


def walk(split_feat: np.ndarray, left_mask: np.ndarray, bins: np.ndarray, depth: int) -> np.ndarray:
    """Node each row ends in (it stops at a leaf)."""
    n = len(bins)
    node = np.zeros(n, np.int64)
    rows = np.arange(n)
    for _ in range(depth):
        f = split_feat[node]
        inner = f >= 0
        b = bins[rows, np.maximum(f, 0)]
        left = left_mask[node, b]
        node = np.where(inner, 2 * node + np.where(left, 1, 2), node)
    return node


def forest_score(trees, bins: np.ndarray, prior: float, rate: float) -> np.ndarray:
    """f = prior + rate x sum of the leaves a row falls in (float64).
    ``trees``: objects with split_feat, left_mask, leaf_value, depth."""
    f = np.full(len(bins), float(prior))
    for t in trees:
        node = walk(np.asarray(t.split_feat), np.asarray(t.left_mask), bins, int(t.depth))
        f += rate * np.asarray(t.leaf_value, np.float64)[node]
    return f


def check_first_tree(tree, bins: np.ndarray, y: np.ndarray, cat_mask: np.ndarray, n_bins: int,
                     min_instances: float, min_gain: float, rel_band: float) -> Dict[str, float]:
    """Hold the program's first tree to the rules above, node by node, on
    the rows its own splits send there.

    ``rel_band`` is the relative error granted to a histogram cell's sum
    (rows carry |residual| <= 1, counts are exact).  A gain is a difference
    of ``sum^2/weight`` terms, so its band is ``2 x rel_band x (|s_left| +
    |s_right| + |s_parent|)``.  At a node the reference's best split beats
    every differently-parting candidate by more than the band (*decisive*),
    the program's column and left rows must equal the reference's.  At any
    other node the program's split must be worth the best gain to within
    the band (its *regret*).  Nothing depends on which side of a near-tie a
    seed falls.  Returns counts and the closest cases.
    """
    prior = float(y.mean())
    resid = (y - prior).astype(np.float64)
    sf = np.asarray(tree.split_feat)
    lm = np.asarray(tree.left_mask)
    lv = np.asarray(tree.leaf_value, np.float64)
    out = {"internal": 0, "decisive": 0, "mismatch": 0, "worst_regret_over_band": 0.0,
           "worst_leaf_err": 0.0, "leaf_disagree": 0, "nodes": 0, "prior": prior,
           "internal_by_depth": [0] * int(tree.depth), "decisive_by_depth": [0] * int(tree.depth)}
    todo = [(0, np.arange(len(y)), None)]
    while todo:
        node, rows, hist = todo.pop()
        if len(rows) == 0:
            continue
        depth = int(np.log2(node + 1))
        out["nodes"] += 1
        r = resid[rows]
        out["worst_leaf_err"] = max(out["worst_leaf_err"], abs(lv[node] - r.mean()))
        f_p = int(sf[node])
        at_floor = 2 * node + 2 >= len(sf)
        if at_floor:
            continue
        w, s = hist if hist is not None else node_histogram(bins[rows], r, n_bins)
        cand = candidates(w, s, cat_mask, min_instances)
        best, f_r, mask_r, second, abs_sums = best_and_runner_up(cand)
        band = 2.0 * rel_band * max(abs_sums, 1e-300)
        ref_leaf = not np.isfinite(best) or best <= min_gain
        if f_p < 0:
            # the program made a leaf: right unless a split was clearly worth it
            if not ref_leaf and best - min_gain > band:
                out["leaf_disagree"] += 1
            continue
        out["internal"] += 1
        out["internal_by_depth"][depth] += 1
        g_p, abs_p = split_gain(w, s, f_p, lm[node], min_instances)
        band = max(band, 2.0 * rel_band * abs_p)
        regret = best - g_p if np.isfinite(g_p) else np.inf
        out["worst_regret_over_band"] = max(out["worst_regret_over_band"], regret / band)
        if np.isfinite(best) and best - second > band:
            out["decisive"] += 1
            out["decisive_by_depth"][depth] += 1
            seen = w[f_p] > 0
            if f_p != f_r or not np.array_equal(lm[node][seen], mask_r[seen]):
                out["mismatch"] += 1
        go_left = lm[node][bins[rows, f_p]]
        left, right = rows[go_left], rows[~go_left]
        # the lighter child's statistics by bincount, the other's by subtraction
        h_left = h_right = None
        if 2 * (2 * node + 1) + 2 < len(sf) and len(left) and len(right):
            if len(left) <= len(right):
                h_left = node_histogram(bins[left], resid[left], n_bins)
                h_right = (w - h_left[0], s - h_left[1])
            else:
                h_right = node_histogram(bins[right], resid[right], n_bins)
                h_left = (w - h_right[0], s - h_right[1])
        todo.append((2 * node + 1, left, h_left))
        todo.append((2 * node + 2, right, h_right))
    return out
