"""MXU histogram kernel (ops/hist_pallas.py) vs the scatter-add reference.

The kernel runs in interpret mode here (tests are CPU); on a TPU backend
the same program lowers through Mosaic.  Matching the segment_sum path at
f32 tolerance is the contract that lets the trainers dispatch freely
(reference hot loop: ``DTWorker.java:844-854``).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from shifu_tpu.ops.hist_pallas import build_histograms_pallas
from shifu_tpu.ops.tree import build_histograms


@pytest.mark.parametrize(
    "n,c,b,k,s",
    [
        (1000, 7, 10, 4, 3),      # typical stats shapes, K under one level
        (4096, 16, 64, 1, 3),     # root level
        (5000, 3, 130, 8, 5),     # bins past one lane tile; 5 stat channels
        (2048, 4, 64, 128, 3),    # deep level: K_MAX partitioning path
        (333, 9, 7, 2, 4),        # ragged everything (padding paths)
    ],
)
def test_pallas_matches_segment_sum(n, c, b, k, s):
    rng = np.random.default_rng(42)
    bins = jnp.asarray(rng.integers(0, b, (n, c)), jnp.int32)
    node = jnp.asarray(rng.integers(-1, k, n), jnp.int32)  # -1 = inactive
    stats = jnp.asarray(rng.normal(size=(n, s)), jnp.float32)
    ref = np.asarray(build_histograms(bins, node, stats, k, b))
    out = np.asarray(build_histograms_pallas(bins, node, stats, k, b,
                                             interpret=True))
    assert out.shape == (k, c, b, s)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-5)


@pytest.mark.parametrize("n,c,b,k,s", [(2000, 6, 32, 8, 2),
                                       (1500, 5, 64, 64, 3)])
def test_pallas_exact_channels_bit_match(n, c, b, k, s):
    """``exact=True`` (small-integer stats — RF bag counts x 0/1 targets)
    must BIT-match the split path: skipping the f32-recovery dot is only
    legal because the products are exactly representable in bf16."""
    rng = np.random.default_rng(3)
    bins = jnp.asarray(rng.integers(0, b, (n, c)), jnp.int32)
    node = jnp.asarray(rng.integers(-1, k, n), jnp.int32)
    bag = rng.poisson(1.0, n).astype(np.float32)          # integer counts
    y = (rng.random(n) < 0.4).astype(np.float32)
    cols = [bag, bag * y, (bag > 0).astype(np.float32)]
    stats = jnp.asarray(np.stack(cols[:s], axis=1))
    a = np.asarray(build_histograms_pallas(bins, node, stats, k, b,
                                           interpret=True))
    e = np.asarray(build_histograms_pallas(bins, node, stats, k, b,
                                           interpret=True, exact=True))
    np.testing.assert_array_equal(a, e)
    ref = np.asarray(build_histograms(bins, node, stats, k, b))
    np.testing.assert_allclose(e, ref, atol=2e-4, rtol=2e-5)


def test_sharded_kernel_matches_segment_sum():
    """shard_map'd kernel over the mesh data axis + psum == scatter path
    (the DTWorker→DTMaster merge on ICI, VERDICT r3 item 1)."""
    import jax
    from shifu_tpu.ops.hist_pallas import build_histograms_sharded
    from shifu_tpu.parallel.mesh import device_mesh

    n, c, b, k = 1024, 6, 16, 8
    rng = np.random.default_rng(7)
    bins = jnp.asarray(rng.integers(0, b, (n, c)), jnp.int32)
    node = jnp.asarray(rng.integers(-1, k, n), jnp.int32)
    stats = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    mesh = device_mesh(2, devices=jax.devices("cpu")[:8])  # ensemble axis too
    ref = np.asarray(build_histograms(bins, node, stats, k, b))
    out = np.asarray(build_histograms_sharded(bins, node, stats, k, b,
                                              mesh, interpret=True))
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-5)


def test_gbt_mesh_equivalence_with_kernel(monkeypatch):
    """Forced kernel (interpret on CPU): an 8-device mesh GBT with the
    shard_map'd kernel builds the same trees as the scatter path — the
    north-star config (GBT on a multi-chip mesh) keeps the MXU path."""
    import jax
    from shifu_tpu.parallel.mesh import device_mesh
    from shifu_tpu.train.dt_trainer import DTSettings, train_gbt

    rng = np.random.default_rng(3)
    n, c, n_bins = 640, 6, 8
    bins = rng.integers(0, n_bins - 1, size=(n, c)).astype(np.int32)
    logit = (bins[:, 0] - 3) * 0.8 + (bins[:, 1] == 2) * 1.5 - 0.5
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    w = np.ones(n, np.float32)
    settings = DTSettings(n_trees=3, depth=3, loss="log", seed=0)
    mesh8 = device_mesh(1, devices=jax.devices("cpu")[:8])
    r_scatter = train_gbt(bins, y, w, n_bins, None, settings, mesh=mesh8)
    monkeypatch.setenv("SHIFU_HIST_PALLAS", "force")
    r_kernel = train_gbt(bins, y, w, n_bins, None, settings, mesh=mesh8)
    for t1, t8 in zip(r_scatter.trees, r_kernel.trees):
        np.testing.assert_array_equal(t1.split_feat, t8.split_feat)
        np.testing.assert_array_equal(t1.left_mask, t8.left_mask)
        np.testing.assert_allclose(t1.leaf_value, t8.leaf_value,
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(r_scatter.valid_error, r_kernel.valid_error,
                               rtol=1e-4)


def test_pallas_weighted_counts_exact():
    """Integer weights accumulate exactly (counting semantics)."""
    rng = np.random.default_rng(0)
    n, c, b, k = 2500, 5, 16, 8
    bins = jnp.asarray(rng.integers(0, b, (n, c)), jnp.int32)
    node = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    stats = jnp.asarray(rng.integers(0, 5, (n, 2)), jnp.float32)
    out = np.asarray(build_histograms_pallas(bins, node, stats, k, b,
                                             interpret=True))
    gt = np.zeros((k, c, b, 2))
    bins_h, node_h, stats_h = map(np.asarray, (bins, node, stats))
    for i in range(n):
        for j in range(c):
            gt[node_h[i], j, bins_h[i, j]] += stats_h[i]
    np.testing.assert_array_equal(out, gt)


def test_stats_histogram_kernel_matches_scatter():
    """The two-level (hi*64+lo) one-hot MXU stats histogram must agree
    with the scatter lowering: counts exactly, weighted channels within
    the bf16 hi/lo-split residual (~eps_bf16^2 per product)."""
    import jax.numpy as jnp

    from shifu_tpu.ops.binning import _histogram_kernel

    rng = np.random.default_rng(0)
    R, C, B = 3000, 10, 256
    x = (rng.normal(size=(R, C)) * 10).astype(np.float32)
    valid = rng.random((R, C)) > 0.07          # per-CELL missing values
    t = (rng.random(R) < 0.3).astype(np.float32)
    w = rng.uniform(0.5, 2.0, R).astype(np.float32)
    lo = x.min(0) - 1e-3
    hi = x.max(0) + 1e-3
    args = (jnp.asarray(x), jnp.asarray(valid), jnp.asarray(t),
            jnp.asarray(w), jnp.asarray(lo), jnp.asarray(hi), B)
    a = np.asarray(_histogram_kernel(*args, use_pallas=False))
    b = np.asarray(_histogram_kernel(*args, use_pallas=True))
    np.testing.assert_array_equal(a[..., :2], b[..., :2])   # counts exact
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    # totals: every valid cell lands in exactly one bucket
    np.testing.assert_allclose(b[..., 0].sum(1) + b[..., 1].sum(1),
                               valid.sum(0), rtol=0, atol=0)


def test_gbt_mesh_equivalence_with_onehot_traversal(monkeypatch):
    """The one-hot traversal lowering under the GSPMD-partitioned mesh
    (the real multi-chip configuration pairs it with the shard_map'd
    kernel) builds the same trees as the gather lowering."""
    import jax

    from shifu_tpu.ops import tree as ot
    from shifu_tpu.parallel.mesh import device_mesh
    from shifu_tpu.train.dt_trainer import DTSettings, train_gbt

    rng = np.random.default_rng(4)
    n, c, n_bins = 640, 6, 8
    bins = rng.integers(0, n_bins - 1, size=(n, c)).astype(np.int32)
    logit = (bins[:, 0] - 3) * 0.8 + (bins[:, 1] == 2) * 1.5 - 0.5
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    w = np.ones(n, np.float32)
    settings = DTSettings(n_trees=3, depth=3, loss="log", seed=0)
    mesh8 = device_mesh(1, devices=jax.devices("cpu")[:8])
    r_gather = train_gbt(bins, y, w, n_bins, None, settings, mesh=mesh8)
    monkeypatch.setenv("SHIFU_TREE_ONEHOT", "1")
    ot._onehot_traversal.cache_clear()
    # the lowering choice is resolved at TRACE time and the env var is
    # not in the jit cache key — without clearing the trace caches the
    # second run would reuse the gather executable (vacuous test)
    jax.clear_caches()
    assert ot._use_onehot(8)
    try:
        r_onehot = train_gbt(bins, y, w, n_bins, None, settings,
                             mesh=mesh8)
    finally:
        monkeypatch.setenv("SHIFU_TREE_ONEHOT", "auto")
        ot._onehot_traversal.cache_clear()
        jax.clear_caches()
    for t1, t8 in zip(r_gather.trees, r_onehot.trees):
        np.testing.assert_array_equal(t1.split_feat, t8.split_feat)
        np.testing.assert_array_equal(t1.left_mask, t8.left_mask)
        np.testing.assert_allclose(t1.leaf_value, t8.leaf_value,
                                   rtol=1e-6, atol=1e-7)


# ------------------------------------------------- TPU lowering, no chip
def _lowers_for_tpu(fn, *avals) -> str:
    """StableHLO text of ``fn`` exported for the TPU platform — runs the
    Pallas->Mosaic lowering (block-shape rules, op types, MLIR
    verification) on this CPU host; what Mosaic then does with the
    kernel (VMEM, layouts) only ``chip_smoke.py`` can tell.  Lowered in
    x32, the chip's configuration (Mosaic has no 64-bit types)."""
    import jax
    from jax import export
    with jax.enable_x64(False):
        return export.export(jax.jit(fn),
                             platforms=["tpu"])(*avals).mlir_module()


# chip_smoke.py's shapes: 131,072 rows x 66 columns; 65 bins (maxNumBin 64
# + the missing bin: flat 128-lane tiles) and 64 (paired-lane tiles); the
# level widths histogram subtraction leaves at MaxDepth 7, K_MAX split
@pytest.mark.parametrize("n_bins", [65, 64])
@pytest.mark.parametrize("k", [1, 8, 32, 128])
def test_tree_hist_kernels_lower_for_tpu(k, n_bins):
    import jax
    from functools import partial

    from shifu_tpu.ops.hist_pallas import (build_histograms_pallas,
                                           build_histograms_pallas_batch)
    n, c, tb = 131072, 66, 8
    S = jax.ShapeDtypeStruct
    text = _lowers_for_tpu(
        partial(build_histograms_pallas, n_nodes=k, n_bins=n_bins),
        S((n, c), jnp.uint8), S((n,), jnp.int32), S((n, 2), jnp.float32))
    assert "tpu_custom_call" in text
    text = _lowers_for_tpu(
        partial(build_histograms_pallas_batch, n_nodes=k, n_bins=n_bins),
        S((n, c), jnp.uint8), S((tb, n), jnp.int32),
        S((tb, n, 2), jnp.float32))
    assert "tpu_custom_call" in text


def test_stats_hist_kernel_lowers_for_tpu():
    import jax
    from functools import partial

    from shifu_tpu.ops.hist_pallas import stats_histograms_pallas
    S = jax.ShapeDtypeStruct
    for n, c, s, exact in ((131072, 64, 4, (True, True, False, False)),
                           (131072, 64, 2, (True, True)),
                           (262144, 256, 4, None)):
        text = _lowers_for_tpu(
            partial(stats_histograms_pallas, num_buckets=4096, exact=exact),
            S((n, c), jnp.int32), S((n, s), jnp.float32))
        assert "tpu_custom_call" in text
