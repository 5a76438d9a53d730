"""Operations and bytes a kernel's call needs, from its shapes, and the
chip's peaks.  Copied from ``shifu_tpu/ops/hist_pallas.hist_kernel_cost`` and
``shifu_tpu/ops/tree_quant.quant_traverse_cost`` (sound; kept here so that no
later PR can move the yardstick — the originals are listed in PERF.md for a
later PR to delete or keep in step)."""

from __future__ import annotations

import json
import os

_TAB_ROWS = 16          # node-table rows of the traversal kernel's select dot


def hist_kernel_cost(rows: int, n_feat: int, n_bins: int, n_nodes: int,
                     n_stats: int = 2, n_trees: int = 1) -> dict:
    """One histogram-kernel launch.  Dominant term: per (feature, stat
    channel) a [K, N] x [N, B] dot (node one-hot x bin one-hot), 2*K*N*B
    operations, plus the one-hot constructions (~N*B + N*K compares).
    Bytes: bins read once (int32 in VMEM), stats per tree, the
    [K, C, B, S] output written once."""
    dot = 2.0 * rows * n_nodes * n_bins * n_stats * n_feat * n_trees
    onehot = float(rows) * (n_bins + n_nodes) * n_feat * n_trees
    read = 4.0 * rows * n_feat + 4.0 * rows * n_stats * n_trees
    write = 4.0 * n_trees * n_nodes * n_feat * n_bins * n_stats
    return {"flops": dot + onehot, "bytes_accessed": read + write}


def quant_traverse_cost(rows: int, n_feat: int, n_bins: int, n_nodes: int, depth: int,
                        n_trees: int = 1) -> dict:
    """One traversal-kernel launch: per (tree, level) the node one-hot, the
    node-table dot, the feature one-hot + bin select, the mask dot and the
    bin membership reduce, plus the terminal leaf select.  Bytes: the uint8
    bins plane read once, per-tree tables and masks once, [T, N] f32 out."""
    sel = (1.0 + 2.0 * _TAB_ROWS) * n_nodes
    level = sel + 3.0 * n_feat + 2.0 * n_nodes * n_bins + 3.0 * n_bins
    flops = float(rows) * n_trees * (depth * level + sel)
    read = 1.0 * rows * n_feat + n_trees * (4.0 * n_nodes + 1.0 * n_nodes * n_bins + 4.0 * n_nodes)
    write = 4.0 * n_trees * rows
    return {"flops": flops, "bytes_accessed": read + write}


def mlp_train_flops(macs_per_row: float, rows: float) -> float:
    """Forward + backward of a dense MLP: 2 operations a MAC forward, twice
    that backward (input and weight gradients): 6 x MACs x rows."""
    return 6.0 * macs_per_row * rows


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)["peaks"]
    kind = device_kind.lower()
    for key, row in table.items():
        if key in kind:
            return row
    raise KeyError(f"device_kind {device_kind!r} is not in benchmark/peaks.json")


def min_seconds(cost: dict, peaks: dict) -> tuple:
    """(least time the chip could take, which bound): the larger of
    operations / peak FLOP/s and bytes / peak bytes/s."""
    t_f = cost["flops"] / peaks["flops_per_s"]
    t_b = cost["bytes_accessed"] / peaks["bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
