"""Quantized tree-traversal scoring: forests walked directly on uint8
bin planes, f32 only at the leaf-value accumulate.

Bins have been uint8 on the wire since PR 2 (the spill cache re-emits
the compact dtype) and stay uint8 in HBM for the trainers — yet every
SCORING traversal widened them to int32 at entry
(``IndependentTreeModel.compute``, ``ops.tree.predict_forest``), so the
serving plane's dominant operand cost 4x the bytes it carried.  This
module keeps the whole walk narrow:

- routing state is integer end-to-end: feature-index gather (uint8 bins,
  int32 node ids), bin-subset membership test (uint8 left-mask planes),
  child-index arithmetic — bit-identical to the f32/one-hot traversal in
  :mod:`shifu_tpu.ops.tree` by construction (every decision is an exact
  integer select; the one-hot form was itself exact);
- f32 appears exactly once, at the terminal leaf-value gather.

Two lowerings, dispatched like the histogram kernel
(:mod:`shifu_tpu.ops.hist_pallas`):

- a Pallas TPU kernel (``SHIFU_TREE_QUANT`` / property
  ``shifu.tree.quantKernel``): grid (row-blocks x trees), the bins block
  loaded into VMEM ONCE per row block and revisited across the whole
  forest — where the XLA lowering re-streams the [N, C] plane per
  (tree, level), the kernel pays the HBM read once.  Selects are
  single-pass bf16 one-hot matmuls in which every output sums exactly
  one non-zero term (f32 table values ride as three bf16-exact pieces),
  so the kernel lowers through the MXU without gathers and stays
  bit-identical to the walk.  Mosaic compiles it on a TPU backend;
  tests drive it in interpret mode on CPU.
- a jnp gather walk (CPU, kernel off, and the shapes
  :func:`quant_lowering` names) that IS the narrow twin of
  ``ops.tree.traverse_nodes``'s gather branch — same routing, uint8
  operands.

The kernel is opaque to XLA's cost analysis, so an analytic model
registers under ``pallas.tree_traverse`` (the ``hist_kernel_cost``
pattern) and the serving plane records one model launch per scored
bucket — serving MFU rows stay honest.
"""

from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

LANE = 128


# ------------------------------------------------------------------ knobs
def _quant_knob() -> str:
    """``SHIFU_TREE_QUANT`` env, falling back to the documented
    ``-Dshifu.tree.quantKernel`` property (the docs promised the
    property form long before it was wired — the knob-registry lint
    caught the gap)."""
    env = os.environ.get("SHIFU_TREE_QUANT")
    if env is not None:
        return env
    from ..config import environment
    return environment.get_property("shifu.tree.quantKernel", "auto")


@lru_cache(maxsize=None)
def quant_scoring() -> bool:
    """Use the quantized (uint8-narrow) scoring path at all.  Default ON —
    routing is bit-identical to the classic traversal on every backend;
    ``SHIFU_TREE_QUANT=0`` pins the old path (tests pin both)."""
    return _quant_knob() not in ("0", "off")


@lru_cache(maxsize=None)
def quant_kernel() -> bool:
    """Lower the traversal through the Pallas kernel (a TPU backend; the
    jnp walk serves CPU and kernel-off).  ``SHIFU_TREE_QUANT=force``
    pins the kernel on (interpret mode off-TPU — tests); ``=0/off``
    disables with the whole quant path."""
    env = _quant_knob()
    if env in ("0", "off"):
        return False
    if env == "force":
        return True
    return jax.default_backend() == "tpu"


def bins_fit_uint8(n_bins: int) -> bool:
    """Whether a forest's bin ids ride uint8 (ids in [0, n_bins))."""
    return n_bins <= 256


def ensemble_bins_dtype(models: Sequence) -> np.dtype:
    """The narrowest dtype an ensemble's bins input can ride: uint8 when
    every bin-consuming model's id space fits a byte (tree forests with
    n_bins <= 256 — the PR 2 wire contract — and WDL categorical
    cardinalities <= 256), else int32.  Scoring batches then carry 1/4
    the bin bytes across H2D and HBM."""
    for m in models:
        name = type(m).__name__
        if name == "IndependentTreeModel":
            if m.spec.n_bins > 256:
                return np.dtype(np.int32)
        elif getattr(m, "max_bin_id", None) is not None:
            if m.max_bin_id > 255:           # a model that states its own id space
                return np.dtype(np.int32)
        elif getattr(m, "input_kind", "norm") == "both":
            cards = getattr(m.spec, "cat_cardinalities", None) or []
            if cards and max(cards) > 256:
                return np.dtype(np.int32)
    return np.dtype(np.uint8)


# ------------------------------------------------------------ forest prep
def stack_forest_quant(trees) -> Tuple[jnp.ndarray, jnp.ndarray,
                                       jnp.ndarray]:
    """Same-depth trees stacked in the quantized layout: split_feat
    [T, K] int32, left-mask planes [T, K, B] uint8 (1 = bin goes left),
    leaf values [T, K] (or [T, K, S] multiclass) f32."""
    sf = jnp.stack([jnp.asarray(t.split_feat, jnp.int32) for t in trees])
    lm = jnp.stack([jnp.asarray(np.asarray(t.left_mask, np.uint8))
                    for t in trees])
    lv = jnp.stack([jnp.asarray(t.leaf_value, jnp.float32) for t in trees])
    return sf, lm, lv


# ------------------------------------------------------- fallback (jnp)
def traverse_quant(split_feat, left_u8, bins, depth: int):
    """Terminal global node id per row — the narrow gather walk.  bins
    [N, C] any integer dtype (uint8 stays uint8: the gather consumes it
    directly, no widen of the plane); split_feat [K] int32; left_u8
    [K, B] uint8.  Routing is the gather branch of
    ``ops.tree.traverse_nodes`` verbatim, so node ids — and therefore
    scores — are bit-identical to the classic path."""
    n = bins.shape[0]
    node = jnp.zeros(n, jnp.int32)
    for _ in range(depth):
        feat = split_feat[node]
        row_bin = jnp.take_along_axis(
            bins, jnp.maximum(feat, 0)[:, None], axis=1)[:, 0] \
            .astype(jnp.int32)
        goes_left = left_u8[node, row_bin] > 0
        child = jnp.where(goes_left, 2 * node + 1, 2 * node + 2)
        node = jnp.where(feat >= 0, child, node)
    return node


@partial(jax.jit, static_argnames=("depth",))
def _predict_quant_ref(split_feats, left_u8s, leaf_values, bins,
                       depth: int):
    """[T, N] (or [T, N, S]) fallback forest predict: vmapped narrow
    walks, one f32 leaf gather at the end."""
    def one(sf, lm, lv):
        return lv[traverse_quant(sf, lm, bins, depth)]
    return jax.vmap(one)(split_feats, left_u8s, leaf_values)


# --------------------------------------------------------- pallas kernel
# forests past this node count keep the jnp walk: the kernel's per-level
# select is a one-hot over the WHOLE node axis ([K_pad, nblk] in VMEM),
# the same bound ``ops.tree.ONEHOT_MAX_NODES`` puts on the XLA one-hot
# traversal (MaxDepth goes to 20 in config meta)
KERNEL_MAX_NODES = 512
# node-table rows (bf16): split feature hi/mid/lo, leaf value hi/mid/lo,
# zero-padded to one bf16 sublane tile
_TAB_ROWS = 16


def _bf16_split3(a):
    """(hi, mid, lo) bf16-exact f32 pieces with hi + mid + lo == a.  A
    one-hot select of each piece is a single-pass bf16 MXU dot with
    exactly one non-zero term, so the selected f32 value is reassembled
    bit-for-bit."""
    from .hist_pallas import _bf16_trunc
    hi = _bf16_trunc(a)
    mid = _bf16_trunc(a - hi)
    return hi, mid, a - hi - mid


def _traverse_kernel(bins_ref, tab_ref, lmt_ref, out_ref, *, depth: int,
                     nblk: int):
    """One (row block, tree) cell: walk ``depth`` levels with one-hot
    selects over the tree's node axis (0/1 operands — exact), then the
    leaf-value select.

    bins_ref [C_pad, nblk] int32 (features on sublanes, rows on lanes —
    the block is fetched from HBM once per row block and revisited
    across the tree sweep); tab_ref [1, 16, K_pad] bf16 node table (see
    ``_TAB_ROWS``); lmt_ref [1, B_pad, K_pad] bf16 0/1 left masks, bins
    on sublanes.  Every level runs the same shapes, so the walk is one
    ``fori_loop`` body whatever the depth."""
    bins = bins_ref[...]                                 # [C_pad, nblk]
    tab = tab_ref[0]                                     # [16, K_pad]
    lmt = lmt_ref[0]                                     # [B_pad, K_pad]
    c_pad, k_pad, b_pad = bins.shape[0], tab.shape[1], lmt.shape[0]
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (k_pad, nblk), 0)
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (c_pad, nblk), 0)
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (b_pad, nblk), 0)
    mm = (((1,), (0,)), ((), ()))                        # plain matmul

    def select(node):
        """(node one-hot [K_pad, nblk] bf16, table rows at each row's
        node [16, nblk] f32)."""
        oh = (k_iota == node).astype(jnp.bfloat16)
        return oh, jax.lax.dot_general(
            tab, oh, mm, preferred_element_type=jnp.float32)

    def level(_, node):
        oh, sel = select(node)
        feat = (sel[0:1] + sel[1:2] + sel[2:3]).astype(jnp.int32)
        # row's bin at that feature: one-hot over the feature sublanes
        rb = jnp.where(c_iota == feat, bins, 0).sum(axis=0, keepdims=True)
        # the node's left-mask row, then bin membership — [B_pad, nblk]
        # oriented so every reduction runs over sublanes (no transposes)
        lrow = jax.lax.dot_general(
            lmt, oh, mm, preferred_element_type=jnp.float32)
        goes_left = jnp.where(b_iota == rb, lrow, 0.0) \
            .sum(axis=0, keepdims=True) > 0.5            # [1, nblk]
        child = 2 * node + jnp.where(goes_left, 1, 2)
        return jnp.where(feat >= 0, child, node)         # leaves freeze

    node = jax.lax.fori_loop(0, depth, level,
                             jnp.zeros((1, nblk), jnp.int32))
    _, sel = select(node)
    out_ref[0] = sel[3:4] + sel[4:5] + sel[5:6]          # [1, nblk]


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@partial(jax.jit, static_argnames=("depth", "interpret"))
def _predict_quant_pallas(split_feats, left_u8s, leaf_values, bins,
                          depth: int, interpret: bool = False):
    """Kernel launch wrapper: pads/transposes operands to tile shapes
    (bins widen to int32 per VMEM block, the ``hist_pallas`` convention —
    uint8 in HBM, int32 only block-local) and trims the output.  Per-tree
    operands carry a unit middle axis so a one-tree block's last two
    dims are whole array dims (Mosaic's block-shape rule)."""
    from jax.experimental import pallas as pl

    t, k = split_feats.shape
    n, c = bins.shape
    b = left_u8s.shape[2]
    nblk = LANE if n <= LANE else 4 * LANE
    n_pad = _pad_to(n, nblk)
    c_pad = _pad_to(c, 8)
    k_pad = _pad_to(k, LANE)
    b_pad = _pad_to(b, 16)                               # bf16 sublanes
    binst = jnp.zeros((c_pad, n_pad), jnp.int32) \
        .at[:c, :n].set(bins.astype(jnp.int32).T)
    # split ids pad with -1 (leaf): pad nodes route nowhere
    sf = jnp.full((t, k_pad), -1.0, jnp.float32) \
        .at[:, :k].set(split_feats.astype(jnp.float32))
    lv = jnp.zeros((t, k_pad), jnp.float32).at[:, :k].set(leaf_values)
    rows = _bf16_split3(sf) + _bf16_split3(lv)
    tab = jnp.zeros((t, _TAB_ROWS, k_pad), jnp.bfloat16) \
        .at[:, :len(rows), :].set(
            jnp.stack(rows, axis=1).astype(jnp.bfloat16))
    lmt = jnp.zeros((t, b_pad, k_pad), jnp.bfloat16) \
        .at[:, :b, :k].set(left_u8s.astype(jnp.bfloat16)
                           .transpose(0, 2, 1))
    grid = (n_pad // nblk, t)
    out = pl.pallas_call(
        partial(_traverse_kernel, depth=depth, nblk=nblk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((c_pad, nblk), lambda r, ti: (0, r)),
            pl.BlockSpec((1, _TAB_ROWS, k_pad), lambda r, ti: (ti, 0, 0)),
            pl.BlockSpec((1, b_pad, k_pad), lambda r, ti: (ti, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, nblk), lambda r, ti: (ti, 0, r)),
        out_shape=jax.ShapeDtypeStruct((t, 1, n_pad), jnp.float32),
        interpret=interpret,
    )(binst, tab, lmt)
    return out[:, 0, :n]


# ------------------------------------------------------------- dispatch
def quant_lowering(bins, n_nodes: int, leaf_ndim: int = 2) -> str:
    """The lowering :func:`predict_forest_quant` picks for these operands,
    and why: ``"pallas"`` or ``"walk:<reason>"``.  The kernel serves
    scalar-leaf forests of at most ``KERNEL_MAX_NODES`` nodes on
    single-device bins; a pallas_call is not partitionable, so
    mesh-sharded bins take the jnp walk (which GSPMD partitions like any
    other traversal), and multiclass leaf distributions ([T, K, S]) are
    not scalar-leaf shaped."""
    if not quant_kernel():
        return "walk:kernel-off"
    if leaf_ndim != 2:
        return "walk:multiclass-leaves"
    if n_nodes > KERNEL_MAX_NODES:
        return "walk:nodes>%d" % KERNEL_MAX_NODES
    sh = getattr(bins, "sharding", None)      # None for numpy and tracers
    if sh is not None and len(sh.device_set) > 1:
        return "walk:mesh-sharded-bins"
    return "pallas"


def predict_forest_quant(split_feats, left_u8s, leaf_values, bins,
                         depth: int, use_kernel=None, interpret=None):
    """[T, N] forest predictions over the narrow plane, lowered as
    :func:`quant_lowering` says (``use_kernel`` overrides it).  Mosaic
    compiles the kernel on a TPU backend; anywhere else a forced kernel
    runs in interpret mode (tests, the CPU rehearsal of the chip smoke)."""
    if use_kernel is None:
        use_kernel = quant_lowering(bins, split_feats.shape[1],
                                    leaf_values.ndim) == "pallas"
    if use_kernel and leaf_values.ndim == 2:
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return _predict_quant_pallas(split_feats, left_u8s, leaf_values,
                                     bins, depth, interpret)
    return _predict_quant_ref(split_feats, left_u8s, leaf_values, bins,
                              depth)


# -------------------------------------------------- analytic cost model
def quant_traverse_cost(rows: int, n_feat: int, n_bins: int,
                        n_nodes: int, depth: int,
                        n_trees: int = 1) -> dict:
    """FLOPs / bytes of one traversal-kernel launch.

    Per (tree, level), over the whole K-node axis: the node one-hot
    (K*N), the node-table dot (2*16*K*N), the feature one-hot + bin
    select (~3*C*N), the mask dot (2*K*B*N) and the bin membership
    reduce (~3*B*N); plus the terminal leaf select (one more one-hot and
    table dot).  Bytes: the uint8 bins plane read ONCE (the kernel's
    point — the XLA lowering reads it per tree), per-tree node tables
    and masks once, [T, N] f32 out written once."""
    sel = (1.0 + 2.0 * _TAB_ROWS) * n_nodes
    level = sel + 3.0 * n_feat + 2.0 * n_nodes * n_bins + 3.0 * n_bins
    flops = float(rows) * n_trees * (depth * level + sel)
    read = 1.0 * rows * n_feat \
        + n_trees * (4.0 * n_nodes + 1.0 * n_nodes * n_bins
                     + 4.0 * n_nodes)
    write = 4.0 * n_trees * rows
    return {"flops": flops, "bytes_accessed": read + write}


def _register_cost_model() -> None:
    from ..obs import costs
    costs.register_cost_model("pallas.tree_traverse", quant_traverse_cost)


_register_cost_model()
