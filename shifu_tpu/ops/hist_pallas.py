"""Pallas TPU kernel for the decision-tree histogram build — the hot op.

The reference accumulates per-(node, feature, bin) stats with a
thread-parallel scalar loop (``DTWorker.java:763-884``, the
``impurity.featureUpdate`` hot loop at ``:844-854``).  The XLA port of that
idea (``jax.ops.segment_sum``) lowers to scatter-add, which the TPU
serializes — measured ~0.8 s per tree at 131k rows x 64 features on a v5e
chip, dwarfing every other part of tree growth.

TPU-first formulation: a histogram is a matmul against one-hot encodings,

    out[k*S+s, c*B+b] = sum_n  [node(n)==k] * stats(n,s) * [bins(n,c)==b]

so the MXU can do the accumulation — *if* the one-hot operands never
materialize in HBM (a [N, C*B] one-hot would be GBs).  This kernel builds
both one-hots on the fly in VMEM per (feature, row-block) grid cell and
feeds them straight to ``dot_general``:

    grid (C, R):   rows blocked over R, one feature per grid column
      oneh_T  [B_pad, nblk] = (bin_iota == bins_T[c, block])     (VPU)
      node1h  [K, nblk]     = (node_iota == node_T[block])       (VPU)
      per s:  out[c, s] += (node1h * stats_T[s]) @ oneh_T.T      (MXU)

Everything is static-shaped; rows past N pad with node=-1 (matches no
one-hot row, contributes zero).  S generalizes to per-class stat channels
for multiclass forests.  Measured ~50x over the scatter path at bench
shapes (131k x 64 x 64 bins, K=64).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128

def _bf16_trunc(a):
    """f32 ``a`` truncated to its bf16-exact leading bits, still f32.
    Must NOT be written as a convert round-trip (f32(bf16(a))): XLA's
    allow-excess-precision simplification — explicitly enabled on this
    TPU toolchain — folds ``a - f32(bf16(a))`` to zero, silently
    degrading a hi/lo split to plain bf16.  Masking the low mantissa
    bits via bitcast is opaque to the simplifier."""
    return jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(a, jnp.uint32)
        & jnp.uint32(0xFFFF0000), jnp.float32)


def _bf16_split(a):
    """bf16 (hi, lo) halves of an f32 operand — two native-rate MXU
    passes recover ~f32 accuracy (residual ~eps_bf16^2)."""
    hi_f = _bf16_trunc(a)
    return hi_f.astype(jnp.bfloat16), (a - hi_f).astype(jnp.bfloat16)


def _hist_kernel(bins_ref, node_ref, stats_ref, out_ref, *, n_stats: int,
                 n_nodes: int, b_pad: int, nblk: int, cblk: int,
                 pair: bool = False, exact: bool = False):
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    nview = node_ref[0:1, :]
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (n_nodes, nblk), 0)
    node1h = (k_iota == nview).astype(jnp.float32)        # [K, nblk]
    # f32 accuracy at bf16 speed (see _bf16_split): stats channels feed
    # split gains, and the reference accumulates in double
    # (``DTWorker.java:850-852``) — plain bf16 rounding shifted chosen
    # thresholds measurably (2.5% cell error at bench shapes), the hi/lo
    # split does not.  ``exact=True`` (every stats value bf16-exact —
    # integer bag counts x 0/1 targets, the RF-without-weight-column
    # case) skips the split and the recovery dot entirely.
    #
    # Stat-channel PAIRS pack along the sublane axis ([2K, nblk] left
    # operands, K <= K_MAX = 64): one dot drives a full 128-row MXU tile
    # where per-channel dots drove two half-empty ones.
    a_hi, a_lo = [], []                  # per channel-GROUP operands
    groups = []                          # (s0, n_in_group)
    s = 0
    while s < n_stats:
        g = 2 if s + 1 < n_stats else 1
        a = jnp.concatenate(
            [node1h * stats_ref[s + j:s + j + 1, :] for j in range(g)],
            axis=0)                       # [g*K, nblk] f32
        if exact:
            a_hi.append(a.astype(jnp.bfloat16))
            a_lo.append(None)
        else:
            hi_b, lo_b = _bf16_split(a)
            a_hi.append(hi_b)
            a_lo.append(lo_b)
        groups.append((s, g))
        s += g
    dims = (((1,), (1,)), ((), ()))
    half = LANE // 2

    def accumulate(oneh, store):
        """One (or two) dots per channel group; ``store(gi, s, acc_s)``
        writes channel s's [K, LANE] slice."""
        for gi, (s0, g) in enumerate(groups):
            acc = jax.lax.dot_general(
                a_hi[gi], oneh, dims,
                preferred_element_type=jnp.float32)       # [g*K, LANE]
            if a_lo[gi] is not None:
                acc += jax.lax.dot_general(
                    a_lo[gi], oneh, dims,
                    preferred_element_type=jnp.float32)
            for j in range(g):
                store(s0 + j, acc[j * n_nodes:(j + 1) * n_nodes, :])

    if pair:
        # n_bins <= 64: pack TWO features per 128-lane tile (lanes 0-63 =
        # feature cf's bins, 64-127 = feature cf+1's) — halves the dots
        b_iota = jax.lax.broadcasted_iota(jnp.int32, (LANE, nblk), 0)
        lo_half = b_iota < half
        lane_val = jnp.where(lo_half, b_iota, b_iota - half)
        for cf in range(0, cblk, 2):
            bview_a = bins_ref[cf:cf + 1, :]              # [1, nblk]
            bview_b = bins_ref[cf + 1:cf + 2, :]
            oneh = (lane_val == jnp.where(lo_half, bview_a, bview_b)) \
                .astype(jnp.bfloat16)                     # [LANE, nblk]

            def store_pair(s, acc_s, cf=cf):
                out_ref[cf, s, :, :] += acc_s[:, :half]
                out_ref[cf + 1, s, :, :] += acc_s[:, half:]
            accumulate(oneh, store_pair)
        return
    for cf in range(cblk):
        bview = bins_ref[cf:cf + 1, :]                    # [1, nblk]
        for bt in range(b_pad // LANE):
            b_iota = jax.lax.broadcasted_iota(
                jnp.int32, (LANE, nblk), 0) + bt * LANE
            oneh = (b_iota == bview).astype(jnp.bfloat16)  # [LANE, nblk]

            def store_flat(s, acc_s, cf=cf, bt=bt):
                out_ref[cf, s, :, bt * LANE:(bt + 1) * LANE] += acc_s
            accumulate(oneh, store_flat)


def _hist_kernel_batch(bins_ref, node_ref, stats_ref, out_ref, *,
                       n_stats: int, n_trees: int, n_nodes: int, b_pad: int,
                       nblk: int, cblk: int, pair: bool = False,
                       exact: bool = False):
    """Multi-TREE histogram grid: TB independent trees' level histograms in
    ONE kernel launch.

    Same one-hot-matmul formulation as :func:`_hist_kernel`, with a
    tree-batch axis: each tree t has its own level-local ``node_ref[t]``
    row positions and its own ``stats_ref[t*S:(t+1)*S]`` channels (RF bags
    differ per tree), while the bins one-hot — the dominant VPU work at
    shallow levels — is built ONCE per (feature, row-block) grid cell and
    shared by every tree's dots.  The per-tree dot sequence (row blocks in
    grid order, channel pairs packed on the sublane axis, the bf16 hi/lo
    split) is IDENTICAL to the single-tree kernel's, so each tree's
    histogram is bit-identical to what ``_hist_kernel`` would produce —
    the batched==sequential parity guard pins this.

    Replaces TB sequential launches in the forest inner loop
    (``DTWorker.java:763-884`` runs the same per-tree loop thread-parallel;
    ``DTMaster.java:91`` grows all RF trees of a round simultaneously).
    """
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    k_iota = jax.lax.broadcasted_iota(jnp.int32, (n_nodes, nblk), 0)
    a_hi, a_lo = [], []                  # per (tree, channel-group) operands
    groups = []                          # (tree, s0, n_in_group)
    for t in range(n_trees):
        node1h = (k_iota == node_ref[t:t + 1, :]).astype(jnp.float32)
        s = 0
        while s < n_stats:
            g = 2 if s + 1 < n_stats else 1
            a = jnp.concatenate(
                [node1h * stats_ref[t * n_stats + s + j:
                                    t * n_stats + s + j + 1, :]
                 for j in range(g)], axis=0)          # [g*K, nblk] f32
            if exact:
                a_hi.append(a.astype(jnp.bfloat16))
                a_lo.append(None)
            else:
                hi_b, lo_b = _bf16_split(a)
                a_hi.append(hi_b)
                a_lo.append(lo_b)
            groups.append((t, s, g))
            s += g
    dims = (((1,), (1,)), ((), ()))
    half = LANE // 2

    def accumulate(oneh, store):
        """One (or two) dots per (tree, channel group); ``store(t, s,
        acc_s)`` writes tree t / channel s's [K, LANE] slice."""
        for gi, (t, s0, g) in enumerate(groups):
            acc = jax.lax.dot_general(
                a_hi[gi], oneh, dims,
                preferred_element_type=jnp.float32)       # [g*K, LANE]
            if a_lo[gi] is not None:
                acc += jax.lax.dot_general(
                    a_lo[gi], oneh, dims,
                    preferred_element_type=jnp.float32)
            for j in range(g):
                store(t, s0 + j, acc[j * n_nodes:(j + 1) * n_nodes, :])

    if pair:
        b_iota = jax.lax.broadcasted_iota(jnp.int32, (LANE, nblk), 0)
        lo_half = b_iota < half
        lane_val = jnp.where(lo_half, b_iota, b_iota - half)
        for cf in range(0, cblk, 2):
            bview_a = bins_ref[cf:cf + 1, :]              # [1, nblk]
            bview_b = bins_ref[cf + 1:cf + 2, :]
            oneh = (lane_val == jnp.where(lo_half, bview_a, bview_b)) \
                .astype(jnp.bfloat16)                     # [LANE, nblk]

            def store_pair(t, s, acc_s, cf=cf):
                out_ref[cf, t, s, :, :] += acc_s[:, :half]
                out_ref[cf + 1, t, s, :, :] += acc_s[:, half:]
            accumulate(oneh, store_pair)
        return
    for cf in range(cblk):
        bview = bins_ref[cf:cf + 1, :]                    # [1, nblk]
        for bt in range(b_pad // LANE):
            b_iota = jax.lax.broadcasted_iota(
                jnp.int32, (LANE, nblk), 0) + bt * LANE
            oneh = (b_iota == bview).astype(jnp.bfloat16)  # [LANE, nblk]

            def store_flat(t, s, acc_s, cf=cf, bt=bt):
                out_ref[cf, t, s, :, bt * LANE:(bt + 1) * LANE] += acc_s
            accumulate(oneh, store_flat)


K_MAX = 64   # per-call node cap: the [C_pad, S, K, B_pad] output must sit
             # under the ~16 MB VMEM scoped-allocation limit


# -------------------------------------------------- analytic cost model
# A pallas_call is an opaque custom call to XLA's cost analysis — the
# flops/bytes the obs cost plane would read off ``lowered.
# cost_analysis()`` come back zero.  This hand model of the one-hot MXU
# formulation registers with obs.costs under ``pallas.hist`` so the
# utilization report still attributes the kernel's work (the streamed
# trainers record one model launch per window when the kernel path is
# on).
def hist_kernel_cost(rows: int, n_feat: int, n_bins: int, n_nodes: int,
                     n_stats: int = 2, n_trees: int = 1) -> dict:
    """FLOPs / bytes of one histogram-kernel launch.

    Dominant term: per (feature, stat channel) the kernel feeds the MXU
    a [K, N] x [N, B] dot (node one-hot x bin one-hot) — 2*K*N*B MACs —
    plus the VPU one-hot constructions (~N*B + N*K compares).  Bytes:
    bins read once per launch (int32 in VMEM after the in-graph widen),
    stats per tree, and the [K, C, B, S] output written once.
    """
    dot = 2.0 * rows * n_nodes * n_bins * n_stats * n_feat * n_trees
    onehot = float(rows) * (n_bins + n_nodes) * n_feat * n_trees
    read = 4.0 * rows * n_feat + 4.0 * rows * n_stats * n_trees
    write = 4.0 * n_trees * n_nodes * n_feat * n_bins * n_stats
    return {"flops": dot + onehot, "bytes_accessed": read + write}


def _register_cost_model() -> None:
    from ..obs import costs
    costs.register_cost_model("pallas.hist", hist_kernel_cost)


_register_cost_model()


@partial(jax.jit, static_argnames=("n_nodes", "n_bins", "interpret",
                                   "exact"))
def build_histograms_pallas(bins, node_idx, stats, n_nodes: int,
                            n_bins: int, interpret: bool = False,
                            exact: bool = False):
    """Drop-in for :func:`shifu_tpu.ops.tree.build_histograms` on TPU.

    bins: [N, C] int32; node_idx: [N] int32 (-1 = inactive);
    stats: [N, S] float32.  Returns [n_nodes, C, n_bins, S] float32.
    ``exact=True`` asserts every stats value is exactly representable in
    bfloat16 (small-integer bag counts x 0/1 indicators): the f32-recovery
    dot is skipped (see ``_hist_kernel``).

    Deep levels decompose into K_MAX-node partitions: shifting
    ``node_idx`` by the partition base makes out-of-range rows match no
    one-hot row, so each call accumulates exactly its node range.
    """
    bins = bins.astype(jnp.int32)   # narrow-wire (uint8/uint16) bins widen
    if n_nodes > K_MAX:             # here; Mosaic sees the one int32 layout
        parts = []
        for k0 in range(0, n_nodes, K_MAX):
            parts.append(build_histograms_pallas(
                bins, node_idx - k0, stats, min(K_MAX, n_nodes - k0),
                n_bins, interpret, exact))
        return jnp.concatenate(parts, axis=0)
    n, c = bins.shape
    s = stats.shape[1]
    pair = n_bins <= LANE // 2       # two features share one 128-lane tile
    b_pad = LANE // 2 if pair else ((n_bins + LANE - 1) // LANE) * LANE
    cblk = 8                 # Mosaic wants >=8 sublanes per bins block
    c_pad = ((c + cblk - 1) // cblk) * cblk
    # row-block: large enough to keep the MXU busy, small enough that the
    # [K, nblk] + [B_pad, nblk] VMEM operands stay comfortably resident;
    # shallow levels (tiny K) take wider blocks — they are grid-step
    # bound, not VMEM bound (K is already <= K_MAX here)
    # wider row blocks when the one-hot node operand is small (histogram
    # subtraction keeps K <= 32 through depth 6): fewer grid steps, same
    # VMEM envelope (~10 MB at 16384)
    nblk = int(os.environ.get("SHIFU_HIST_NBLK", 0)) or \
        (16384 if n_nodes <= 16 else 8192 if n_nodes <= 32 else 2048)
    n_pad = ((n + nblk - 1) // nblk) * nblk

    bins_t = jnp.pad(bins, ((0, n_pad - n), (0, c_pad - c))).T  # [C_pad, N_pad]
    node_t = jnp.pad(node_idx, (0, n_pad - n),
                     constant_values=-1)[None, :]            # [1, N_pad]
    stats_t = jnp.pad(stats, ((0, n_pad - n), (0, 0))).T    # [S, N_pad]

    grid = (c_pad // cblk, n_pad // nblk)
    out = pl.pallas_call(
        partial(_hist_kernel, n_stats=s, n_nodes=n_nodes, b_pad=b_pad,
                nblk=nblk, cblk=cblk, pair=pair, exact=exact),
        grid=grid,
        in_specs=[
            pl.BlockSpec((cblk, nblk), lambda ci, r: (ci, r)),
            pl.BlockSpec((1, nblk), lambda ci, r: (0, r)),
            pl.BlockSpec((s, nblk), lambda ci, r: (0, r)),
        ],
        out_specs=pl.BlockSpec((cblk, s, n_nodes, b_pad),
                               lambda ci, r: (ci, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c_pad, s, n_nodes, b_pad),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bins_t, node_t, stats_t)
    # [C_pad, S, K, B_pad] -> [K, C, B, S]
    return out[:c, :, :, :n_bins].transpose(2, 0, 3, 1)


def _batch_vmem_bytes(tb: int, s: int, n_nodes: int, b_pad: int,
                      nblk: int, cblk: int, exact: bool) -> int:
    """Rough VMEM footprint of one batched grid cell: output block +
    per-(tree, group) dot operands (the hi/lo split doubles them) +
    double-buffered input blocks."""
    out = cblk * tb * s * n_nodes * b_pad * 4
    n_groups = (s + 1) // 2
    opnd = tb * n_groups * min(2, s) * n_nodes * nblk * 2
    if not exact:
        opnd *= 2
    inputs = 2 * nblk * (cblk * 4 + tb * 4 + tb * s * 4)
    return out + opnd + inputs


_BATCH_VMEM_BUDGET = 10 << 20     # leave headroom under the ~16 MB scope


@partial(jax.jit, static_argnames=("n_nodes", "n_bins", "interpret",
                                   "exact"))
def build_histograms_pallas_batch(bins, node_idx_b, stats_b, n_nodes: int,
                                  n_bins: int, interpret: bool = False,
                                  exact: bool = False):
    """Batched drop-in for :func:`build_histograms_pallas` over a leading
    TREE axis: B independent trees' level histograms in ONE launch.

    bins: [N, C] shared row matrix; node_idx_b: [TB, N] per-tree level-local
    positions (-1 = inactive); stats_b: [TB, N, S] per-tree stat channels.
    Returns [TB, n_nodes, C, n_bins, S] float32.

    Every per-tree parameter that shapes the accumulation order (nblk row
    blocking, K_MAX node partitioning, channel pairing, bf16 hi/lo split)
    matches the single-tree kernel exactly, so each tree's slice is
    BIT-identical to a sequential :func:`build_histograms_pallas` call —
    only the dispatch count changes (1 launch instead of TB, with the bins
    one-hot built once per grid cell instead of TB times).  Tree batches
    that would overflow the VMEM scope split transparently.
    """
    bins = bins.astype(jnp.int32)
    tb, n = node_idx_b.shape
    s = stats_b.shape[2]
    if n_nodes > K_MAX:             # deep levels: same node partitioning
        parts = []                  # as the single-tree path
        for k0 in range(0, n_nodes, K_MAX):
            parts.append(build_histograms_pallas_batch(
                bins, node_idx_b - k0, stats_b, min(K_MAX, n_nodes - k0),
                n_bins, interpret, exact))
        return jnp.concatenate(parts, axis=1)
    c = bins.shape[1]
    pair = n_bins <= LANE // 2
    b_pad = LANE // 2 if pair else ((n_bins + LANE - 1) // LANE) * LANE
    cblk = 8
    c_pad = ((c + cblk - 1) // cblk) * cblk
    # nblk MUST be the single-tree formula for the given node count — the
    # row-block accumulation order is what makes batched == sequential
    # bit-identical
    nblk = int(os.environ.get("SHIFU_HIST_NBLK", 0)) or \
        (16384 if n_nodes <= 16 else 8192 if n_nodes <= 32 else 2048)
    while tb > 1 and _batch_vmem_bytes(tb, s, n_nodes, b_pad, nblk, cblk,
                                       exact) > _BATCH_VMEM_BUDGET:
        # split the tree batch, not the row block: nblk is pinned by the
        # bit-identity contract above
        half_tb = tb // 2
        return jnp.concatenate([
            build_histograms_pallas_batch(
                bins, node_idx_b[:half_tb], stats_b[:half_tb], n_nodes,
                n_bins, interpret, exact),
            build_histograms_pallas_batch(
                bins, node_idx_b[half_tb:], stats_b[half_tb:], n_nodes,
                n_bins, interpret, exact)], axis=0)
    n_pad = ((n + nblk - 1) // nblk) * nblk

    bins_t = jnp.pad(bins, ((0, n_pad - n), (0, c_pad - c))).T  # [C_pad, N_pad]
    node_t = jnp.pad(node_idx_b, ((0, 0), (0, n_pad - n)),
                     constant_values=-1)                  # [TB, N_pad]
    stats_t = jnp.pad(stats_b, ((0, 0), (0, n_pad - n), (0, 0))) \
        .transpose(0, 2, 1).reshape(tb * s, n_pad)        # [TB*S, N_pad]

    grid = (c_pad // cblk, n_pad // nblk)
    out = pl.pallas_call(
        partial(_hist_kernel_batch, n_stats=s, n_trees=tb, n_nodes=n_nodes,
                b_pad=b_pad, nblk=nblk, cblk=cblk, pair=pair, exact=exact),
        grid=grid,
        in_specs=[
            pl.BlockSpec((cblk, nblk), lambda ci, r: (ci, r)),
            pl.BlockSpec((tb, nblk), lambda ci, r: (0, r)),
            pl.BlockSpec((tb * s, nblk), lambda ci, r: (0, r)),
        ],
        out_specs=pl.BlockSpec((cblk, tb, s, n_nodes, b_pad),
                               lambda ci, r: (ci, 0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c_pad, tb, s, n_nodes, b_pad),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bins_t, node_t, stats_t)
    # [C_pad, TB, S, K, B_pad] -> [TB, K, C, B, S]
    return out[:c, :, :, :, :n_bins].transpose(1, 3, 0, 4, 2)


def build_histograms_batch_sharded(bins, node_idx_b, stats_b, n_nodes: int,
                                   n_bins: int, mesh,
                                   interpret: bool = False,
                                   exact: bool = False):
    """Mesh lowering of the batched kernel (see
    :func:`build_histograms_sharded`): rows shard over ``data``, the tree
    axis replicates, one psum merges the per-device tree-batch grids."""
    from jax.sharding import PartitionSpec as P

    def local(b, ni, st):
        h = build_histograms_pallas_batch(b, ni, st, n_nodes, n_bins,
                                          interpret, exact)
        return jax.lax.psum(h, "data")

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("data", None), P(None, "data"), P(None, "data", None)),
        out_specs=P(), check_vma=False)(bins, node_idx_b, stats_b)


def build_histograms_sharded(bins, node_idx, stats, n_nodes: int,
                             n_bins: int, mesh, interpret: bool = False,
                             exact: bool = False):
    """Mesh lowering of the kernel: ``shard_map`` over the ``data`` axis.

    A ``pallas_call`` is opaque to the GSPMD partitioner, so under a
    multi-device mesh the kernel must be placed per-shard explicitly: each
    device builds the histogram of its local rows (the ``DTWorker`` side),
    then a ``psum`` over the data axis merges them on ICI (the
    ``DTMaster.java:274-533`` aggregation).  Inputs must already be sharded
    row-wise over ``data`` (the trainers' `_device_put_rows` layout); axes
    the specs don't mention (``ensemble``) stay replicated.

    ``check_vma=False``: the replication checker can't see through the
    kernel, but the output IS replicated — inputs are replicated over
    every non-data axis and the psum makes it data-invariant.
    """
    from jax.sharding import PartitionSpec as P

    def local(b, ni, st):
        h = build_histograms_pallas(b, ni, st, n_nodes, n_bins, interpret,
                                    exact)
        return jax.lax.psum(h, "data")

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("data", None), P("data"), P("data", None)),
        out_specs=P(), check_vma=False)(bins, node_idx, stats)


def target_platform(mesh=None) -> str:
    """The platform the histogram will actually run on: the mesh's devices
    when one is given (a CPU mesh on a TPU-backed host must NOT get the
    Mosaic lowering), the default backend otherwise."""
    if mesh is not None:
        return mesh.devices.flat[0].platform
    return jax.default_backend()


def pallas_available(mesh=None) -> bool:
    """Histogram kernel dispatch gate: runs on a real TPU and not disabled.
    ``SHIFU_HIST_PALLAS=force`` enables it on any platform (tests exercise
    the kernel + shard_map wiring in interpret mode on the CPU mesh)."""
    env = os.environ.get("SHIFU_HIST_PALLAS", "1")
    if env == "0":
        return False
    if env == "force":
        return True
    return target_platform(mesh) == "tpu"


# ---------------------------------------------------- wide-B stats kernel
def _stats_hist_kernel(idx_ref, stats_ref, out_ref, *, n_stats: int,
                      hi_n: int, nblk: int, cblk: int,
                      exact: tuple):
    """Fine-histogram build for the STATS plane (wide bucket axis).

    The tree kernel's one-hot trick is linear in the bucket count (one
    128-lane compare tile per 128 buckets), which is fine at B<=256 but
    hopeless at the stats plane's 4096 fine buckets.  Wide histograms
    factor instead: bucket id = hi*64 + lo, and

        out[c, s, hi, lo] = sum_n [hi(n)==hi] * stats(n,s) * [lo(n)==lo]

    is a ``dot_general`` per (column, stat-pair) — B-independent MXU work
    (the reference accumulates the same cells one row at a time in
    ``UpdateBinningInfoMapper.java:71``'s combiner).  Invalid cells
    arrive as idx -1: the arithmetic shift keeps hi == -1, which matches
    no one-hot row.

    Two MXU economies over the naive per-channel hi/lo-split loop
    (measured 5.5x at bench shapes together):

    * channel pairs pack along the sublane axis — rows 0-63 of the
      [128, nblk] left operand carry channel s's hi-one-hot, rows 64-127
      channel s+1's, so one dot feeds the whole 128-row MXU tile instead
      of two half-empty ones;
    * ``exact[s]`` marks channels whose values are bf16-exact (0/1
      indicators — the pos/neg count channels): the product
      one-hot * stats is then exactly representable and the f32-recovery
      lo dot (see :func:`_bf16_split`) is skipped entirely.  Weighted
      channels keep the split (weights are arbitrary f32 and feed
      KS/IV/WOE).
    """
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (LANE // 2, nblk), 0)
    pack_iota = jax.lax.broadcasted_iota(jnp.int32, (LANE, nblk), 0) % (LANE // 2)
    dims = (((1,), (1,)), ((), ()))
    for cf in range(cblk):
        col = idx_ref[cf:cf + 1, :]                       # [1, nblk] int32
        hi = col >> 6                                     # -1 stays -1
        lo = col & 63
        lo1h = (lane_iota == lo).astype(jnp.bfloat16)     # [64, nblk]
        s = 0
        while s < n_stats:
            if s + 1 < n_stats:
                # packed pair: [128, nblk] left operand, one (or two) dots
                hi2 = (pack_iota == jnp.broadcast_to(hi, (LANE, nblk))) \
                    .astype(jnp.float32)
                st = jnp.concatenate([
                    jnp.broadcast_to(stats_ref[s:s + 1, :],
                                     (LANE // 2, nblk)),
                    jnp.broadcast_to(stats_ref[s + 1:s + 2, :],
                                     (LANE // 2, nblk))], axis=0)
                a = hi2 * st                              # [128, nblk] f32
                if exact[s] and exact[s + 1]:
                    acc = jax.lax.dot_general(
                        a.astype(jnp.bfloat16), lo1h, dims,
                        preferred_element_type=jnp.float32)  # [128, 64]
                else:
                    hi_b, lo_b = _bf16_split(a)
                    acc = jax.lax.dot_general(
                        hi_b, lo1h, dims,
                        preferred_element_type=jnp.float32)
                    acc += jax.lax.dot_general(
                        lo_b, lo1h, dims,
                        preferred_element_type=jnp.float32)
                out_ref[cf, s, :, :] += acc[:hi_n, :]
                out_ref[cf, s + 1, :, :] += \
                    acc[LANE // 2:LANE // 2 + hi_n, :]
                s += 2
                continue
            hi1h = (lane_iota == hi).astype(jnp.float32)  # [64, nblk]
            a = hi1h * stats_ref[s:s + 1, :]              # [64, nblk] f32
            if exact[s]:
                acc = jax.lax.dot_general(
                    a.astype(jnp.bfloat16), lo1h, dims,
                    preferred_element_type=jnp.float32)   # [64, 64]
            else:
                hi_b, lo_b = _bf16_split(a)
                acc = jax.lax.dot_general(
                    hi_b, lo1h, dims,
                    preferred_element_type=jnp.float32)
                acc += jax.lax.dot_general(
                    lo_b, lo1h, dims,
                    preferred_element_type=jnp.float32)
            out_ref[cf, s, :, :] += acc[:hi_n, :]
            s += 1


@partial(jax.jit, static_argnames=("num_buckets", "interpret", "exact"))
def stats_histograms_pallas(idx, stats, num_buckets: int,
                            interpret: bool = False,
                            exact: tuple = None):
    """[C, num_buckets, S] fine-histogram from per-cell bucket ids.

    idx: [N, C] int32, -1 = invalid cell (missing value — contributes
    nowhere); stats: [N, S] float32 per-row channels (pos/neg indicators,
    weighted variants).  ``num_buckets`` must be a multiple of 64 and at
    most 4096 (the stats plane's fine-sketch width).  ``exact[s]`` marks
    channels whose values are exactly representable in bfloat16 (0/1
    indicators) — those skip the f32-recovery second dot.
    """
    assert num_buckets % 64 == 0 and num_buckets <= 4096, num_buckets
    if exact is None:
        exact = (False,) * stats.shape[1]
    n, c = idx.shape
    s = stats.shape[1]
    hi_n = num_buckets // 64
    cblk = 8
    c_pad = ((c + cblk - 1) // cblk) * cblk
    nblk = 2048
    n_pad = ((n + nblk - 1) // nblk) * nblk
    idx_t = jnp.pad(idx, ((0, n_pad - n), (0, c_pad - c)),
                    constant_values=-1).T                 # [C_pad, N_pad]
    stats_t = jnp.pad(stats, ((0, n_pad - n), (0, 0))).T  # [S, N_pad]
    grid = (c_pad // cblk, n_pad // nblk)
    out = pl.pallas_call(
        partial(_stats_hist_kernel, n_stats=s, hi_n=hi_n, nblk=nblk,
                cblk=cblk, exact=tuple(exact)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((cblk, nblk), lambda ci, r: (ci, r)),
            pl.BlockSpec((s, nblk), lambda ci, r: (0, r)),
        ],
        out_specs=pl.BlockSpec((cblk, s, hi_n, 64),
                               lambda ci, r: (ci, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c_pad, s, hi_n, 64), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(idx_t, stats_t)
    # [C_pad, S, HI, 64] -> [C, HI*64, S]
    return out[:c].reshape(c, s, hi_n * 64).transpose(0, 2, 1)


def stats_histograms_sharded(idx, stats, num_buckets: int, mesh,
                             interpret: bool = False, exact: tuple = None):
    """Mesh lowering of the stats fine-histogram: ``shard_map`` over the
    ``data`` axis (see :func:`build_histograms_sharded` — the pallas_call
    is opaque to GSPMD, so each device sketches its local rows and a
    ``psum`` merges on ICI; the reference's up-to-999 stats reducers,
    ``MapReducerStatsWorker.java:111-139``).  Rows must already be sharded
    over ``data`` and divide the axis (the accumulator pads)."""
    from jax.sharding import PartitionSpec as P

    def local(i, st):
        h = stats_histograms_pallas(i, st, num_buckets, interpret, exact)
        return jax.lax.psum(h, "data")

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P("data", None), P("data", None)),
        out_specs=P(), check_vma=False)(idx, stats)
