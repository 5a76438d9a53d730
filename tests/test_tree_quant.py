"""Quantized (uint8-narrow) tree-traversal scoring — bit-parity and
fallback contracts (``shifu_tpu/ops/tree_quant.py``).

The quant path's one promise is BIT-IDENTITY with the classic traversal:
routing decisions are integer selects on both paths, f32 appears only at
the leaf gather, so any divergence is a bug, never tolerance.  Suites
cover the jnp fallback (the CPU production path), the Pallas kernel in
interpret mode, GBT/RF/mixed ensembles through the serve scorer
(including padded buckets), and the clean-CPU-fallback smoke the CI
tier-1 sweep rides.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from shifu_tpu.models.nn import IndependentNNModel, NNModelSpec, init_params
from shifu_tpu.models.tree import IndependentTreeModel, TreeModelSpec
from shifu_tpu.ops import tree_quant as tq
from shifu_tpu.ops.tree import (grow_tree, predict_forest_stacked,
                                stack_forest)
from shifu_tpu.serve.scorer import AOTScorer, serve_recompile_count

pytestmark = pytest.mark.perf


def _forest(rng, n=600, c=9, n_bins=32, depth=4, n_trees=4,
            weighted=True):
    bins = rng.integers(0, n_bins, size=(n, c)).astype(np.uint8)
    trees = []
    for _ in range(n_trees):
        y = (rng.random(n) < 0.35).astype(np.float32)
        w = (rng.random(n) + 0.5).astype(np.float32) if weighted \
            else np.ones(n, np.float32)
        trees.append(grow_tree(bins.astype(np.int32), y, w, n_bins, depth))
    return bins, trees


def _classic(trees, bins, depth):
    return np.asarray(predict_forest_stacked(
        *stack_forest(trees), jnp.asarray(bins, jnp.int32), depth))


@pytest.mark.parametrize("n_bins,depth", [(32, 4), (64, 6), (256, 3)])
def test_fallback_bit_identical(rng, n_bins, depth):
    bins, trees = _forest(rng, n_bins=n_bins, depth=depth)
    got = np.asarray(tq.predict_forest_quant(
        *tq.stack_forest_quant(trees), jnp.asarray(bins), depth,
        use_kernel=False))
    assert np.array_equal(_classic(trees, bins, depth), got)


@pytest.mark.parametrize("n_bins,depth", [(20, 4), (64, 6)])
def test_pallas_kernel_bit_identical_interpret(rng, n_bins, depth):
    """The TPU kernel, driven in interpret mode on CPU: same one-hot
    select math, bit-identical scores."""
    bins, trees = _forest(rng, n=333, n_bins=n_bins, depth=depth)
    got = np.asarray(tq.predict_forest_quant(
        *tq.stack_forest_quant(trees), jnp.asarray(bins), depth,
        use_kernel=True, interpret=True))
    assert np.array_equal(_classic(trees, bins, depth), got)


def test_kernel_handles_row_padding_blocks(rng):
    """Row counts straddling the kernel's lane blocking (1, 127, 128,
    129) — pad rows must never leak into real rows' scores."""
    bins, trees = _forest(rng, n=300, n_bins=16, depth=3)
    full = np.asarray(tq.predict_forest_quant(
        *tq.stack_forest_quant(trees), jnp.asarray(bins), 3,
        use_kernel=True, interpret=True))
    for n in (1, 127, 128, 129):
        part = np.asarray(tq.predict_forest_quant(
            *tq.stack_forest_quant(trees), jnp.asarray(bins[:n]), 3,
            use_kernel=True, interpret=True))
        assert np.array_equal(part, full[:, :n])


def test_independent_tree_model_quant_scores(rng):
    """``IndependentTreeModel.compute`` (the eval plane's tree column)
    rides the quant path by default and must match the classic link
    math bit-for-bit for GBT and RF."""
    bins, trees = _forest(rng, n_bins=32, depth=4)
    for algorithm in ("GBT", "RF"):
        spec = TreeModelSpec(algorithm=algorithm, n_trees=len(trees),
                             depth=4, n_bins=32, loss="log",
                             learning_rate=0.1, init_score=-0.3)
        m = IndependentTreeModel(spec, trees)
        got = m.compute(bins.astype(np.int32))
        preds = _classic(trees, bins, 4)
        if algorithm == "GBT":
            f = spec.init_score + spec.learning_rate * preds.sum(axis=0)
            want = (1.0 / (1.0 + np.exp(-f)))[:, None].astype(np.float32)
        else:
            want = preds.mean(axis=0)[:, None].astype(np.float32)
        # the same host numpy link expressions on bit-equal traversal
        # outputs: byte-equal results
        assert np.array_equal(want, got)


def test_mixed_ensemble_serve_bucket_parity(rng, monkeypatch):
    """The AOT serving graph over a MIXED ensemble (NN + GBT + RF) on
    padded buckets: the SAME ensemble graph built with the classic
    (widened int32) traversal must emit bit-identical raw scores —
    every column, every bucket, including a partial batch that pads."""
    bins, trees = _forest(rng, n=200, n_bins=32, depth=4, n_trees=3)
    gbt = IndependentTreeModel(
        TreeModelSpec(algorithm="GBT", n_trees=3, depth=4, n_bins=32,
                      loss="log", learning_rate=0.1, init_score=-0.2),
        trees)
    rf = IndependentTreeModel(
        TreeModelSpec(algorithm="RF", n_trees=3, depth=4, n_bins=32),
        trees)
    nn_spec = NNModelSpec(input_dim=4, hidden_nodes=[4],
                          activations=["relu"])
    nn = IndependentNNModel(nn_spec,
                            init_params(jax.random.PRNGKey(0), nn_spec))

    def build(name):
        s = AOTScorer([nn, gbt, rf], buckets=(8, 64), name=name)
        s.warm()
        return s

    quant = build("serve.score.tqtest")
    assert quant.bins_dtype == np.dtype(np.uint8)
    monkeypatch.setattr(tq, "quant_scoring", lambda: False)
    classic = build("serve.score.tqtest.classic")
    assert classic.bins_dtype == np.dtype(np.int32)

    x = rng.normal(size=(13, quant.n_features)).astype(np.float32)
    b = bins[:13, :quant.n_bins_cols]
    raw_q = quant.score_batch(x, b)          # pads 13 -> 64
    raw_c = classic.score_batch(x, b.astype(np.int32))
    assert raw_q.shape == (13, 3)
    assert np.array_equal(raw_c, raw_q)
    full = bins[:64, :quant.n_bins_cols]
    xf = rng.normal(size=(64, quant.n_features)).astype(np.float32)
    assert np.array_equal(classic.score_batch(xf, full.astype(np.int32)),
                          quant.score_batch(xf, full))
    assert serve_recompile_count("serve.score.tqtest") == 0


def test_cpu_backend_clean_fallback_smoke(rng):
    """Tier-1 smoke (CI runs JAX_PLATFORMS=cpu): the default dispatch on
    a CPU backend must pick the fallback — no Pallas crash — and hold
    parity.  Guards the exact regression where a TPU-only kernel leaks
    into the CPU path."""
    assert jax.default_backend() == "cpu"
    assert tq.quant_scoring() is True
    assert tq.quant_kernel() is False        # auto resolves off-TPU
    bins, trees = _forest(rng, n=150, n_bins=16, depth=3)
    got = np.asarray(tq.predict_forest_quant(
        *tq.stack_forest_quant(trees), jnp.asarray(bins), 3))
    assert np.array_equal(_classic(trees, bins, 3), got)


def test_multiclass_leaves_take_fallback(rng):
    """2D (class-distribution) leaf values dispatch to the fallback even
    when the kernel is requested — and stay bit-identical."""
    bins, trees = _forest(rng, n=120, n_bins=16, depth=3, n_trees=2)
    k = 3
    wide = []
    for t in trees:
        lv = np.stack([np.asarray(t.leaf_value)] * k, axis=1)
        wide.append(type(t)(split_feat=t.split_feat,
                            left_mask=t.left_mask, leaf_value=lv,
                            depth=t.depth))
    got = np.asarray(tq.predict_forest_quant(
        *tq.stack_forest_quant(wide), jnp.asarray(bins), 3,
        use_kernel=True, interpret=True))
    want = _classic(wide, bins, 3)
    assert got.shape == want.shape and np.array_equal(want, got)


def test_ensemble_bins_dtype_rules():
    class FakeTree:
        def __init__(self, n_bins):
            self.spec = TreeModelSpec(algorithm="GBT", n_trees=0,
                                      depth=1, n_bins=n_bins)
    FakeTree.__name__ = "IndependentTreeModel"

    class FakeWDL:
        input_kind = "both"

        def __init__(self, cards):
            class S:
                cat_cardinalities = cards
            self.spec = S()
    assert tq.ensemble_bins_dtype([FakeTree(256)]) == np.dtype(np.uint8)
    assert tq.ensemble_bins_dtype([FakeTree(257)]) == np.dtype(np.int32)
    assert tq.ensemble_bins_dtype([FakeWDL([256, 8])]) == np.dtype(np.uint8)
    assert tq.ensemble_bins_dtype([FakeWDL([300])]) == np.dtype(np.int32)


def test_cost_model_registered():
    from shifu_tpu.obs import costs
    fn = costs.cost_models().get("pallas.tree_traverse")
    assert fn is not None
    est = fn(rows=512, n_feat=32, n_bins=64, n_nodes=127, depth=6,
             n_trees=50)
    assert est["flops"] > 0 and est["bytes_accessed"] > 0
    # bins plane billed ONCE (uint8), not per tree — the kernel's point
    est1 = fn(rows=512, n_feat=32, n_bins=64, n_nodes=127, depth=6,
              n_trees=1)
    assert est["bytes_accessed"] - est1["bytes_accessed"] < \
        50 * 512 * 32          # grows with trees' arrays, not the plane


# serve buckets (1, 8, 64, 512) and the eval plane of chip_smoke.py's GBT
# (16 trees, MaxDepth 7 = 255 nodes, 65 bins, 66 columns)
@pytest.mark.parametrize("n", [1, 8, 64, 512, 131072])
def test_traversal_kernel_lowers_for_tpu(n):
    """The kernel exported for the TPU platform on this CPU host: runs
    the Pallas->Mosaic lowering, which is where illegal block shapes and
    op types are refused (a [T, K] operand blocked (1, K); a float
    iota) — what Mosaic then does with the kernel only the chip shows."""
    from functools import partial

    from jax import export
    t, depth, b, c = 16, 7, 65, 66
    k = (1 << (depth + 1)) - 1
    S = jax.ShapeDtypeStruct
    with jax.enable_x64(False):     # the chip's configuration
        exp = export.export(
            jax.jit(partial(tq._predict_quant_pallas, depth=depth)),
            platforms=["tpu"])(
            S((t, k), jnp.int32), S((t, k, b), jnp.uint8),
            S((t, k), jnp.float32), S((n, c), jnp.uint8))
    assert "tpu_custom_call" in exp.mlir_module()
