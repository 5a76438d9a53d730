"""Each plain reference against the program at toy size, in this process, on
the CPU — and what the tolerances can and cannot tell apart."""

import numpy as np
import pytest

from benchmark.reference import gbt, mlp


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(7)
    n, c, n_bins = 6000, 10, 17
    bins = rng.integers(0, n_bins, (n, c)).astype(np.uint8)
    cat = np.zeros(c, bool)
    cat[-3:] = True
    effect = rng.normal(0, 1.0, n_bins)
    logit = 0.25 * (bins[:, 0].astype(float) - 8) - 0.2 * (bins[:, 3].astype(float) - 8) \
        + effect[bins[:, -1]] - 2.0
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return bins, y, cat, n_bins


def test_first_tree_of_the_program_passes_the_reference(rows):
    from shifu_tpu.ops.tree import grow_tree, predict_tree
    bins, y, cat, n_bins = rows
    resid = y - y.mean()
    tree = grow_tree(bins, resid, np.ones(len(y), np.float32), n_bins, 4, "variance",
                     min_instances=5, min_gain=0.0, cat_mask=cat)
    res = gbt.check_first_tree(tree, bins, y.astype(np.float64), cat, n_bins, 5.0, 0.0, 2e-5)
    assert res["internal"] >= 7 and res["mismatch"] == 0 and res["leaf_disagree"] == 0
    assert res["decisive"] * 2 >= res["internal"]
    assert res["worst_regret_over_band"] <= 1.0 and res["worst_leaf_err"] < 2e-5
    # the walk agrees with the program's own prediction of the same tree
    node = gbt.walk(tree.split_feat, tree.left_mask, bins, tree.depth)
    want = np.asarray(predict_tree(tree.split_feat, tree.left_mask, tree.leaf_value,
                                   bins.astype(np.int32), tree.depth))
    assert np.allclose(tree.leaf_value[node], want, atol=1e-7)
    f = gbt.forest_score([tree, tree], bins, 0.1, 0.05)
    assert np.allclose(f, 0.1 + 0.1 * tree.leaf_value[node], atol=1e-9)


def test_a_wrong_split_or_leaf_is_refused(rows):
    from shifu_tpu.ops.tree import grow_tree
    bins, y, cat, n_bins = rows
    tree = grow_tree(bins, y - y.mean(), np.ones(len(y), np.float32), n_bins, 3, "variance",
                     min_instances=5, cat_mask=cat)
    bad = type(tree)(split_feat=tree.split_feat.copy(), left_mask=tree.left_mask.copy(),
                     leaf_value=tree.leaf_value.copy(), depth=tree.depth)
    bad.split_feat[0] = (bad.split_feat[0] + 1) % 7          # another column at the root
    res = gbt.check_first_tree(bad, bins, y.astype(np.float64), cat, n_bins, 5.0, 0.0, 2e-5)
    assert res["worst_regret_over_band"] > 1.0 and res["mismatch"] >= 1
    bad.split_feat[0] = tree.split_feat[0]
    bad.leaf_value[1] += 1e-3
    res = gbt.check_first_tree(bad, bins, y.astype(np.float64), cat, n_bins, 5.0, 0.0, 2e-5)
    assert res["worst_leaf_err"] > 2e-5


def test_runner_up_ignores_positions_that_part_the_rows_alike():
    w = np.array([[4.0, 0.0, 0.0, 6.0], [5.0, 1.0, 2.0, 2.0]])
    s = np.array([[4.0, 0.0, 0.0, -6.0], [1.0, 0.0, -1.0, 0.0]])
    cand = gbt.candidates(w, s, np.zeros(2, bool), 1.0)
    best, f, mask, second, _ = gbt.best_and_runner_up(cand)
    assert f == 0 and mask.tolist() == [True, False, False, False]
    assert cand["gain"][0, 0] == cand["gain"][0, 1] == cand["gain"][0, 2] == best
    assert second < best                       # column 0's empty-bin twins are no runner-up


@pytest.fixture(scope="module")
def net():
    rng = np.random.default_rng(3)
    dims = [40, 64, 32, 1]
    weights = [(rng.uniform(-1, 1, (a, b)).astype(np.float32) * (6 / (a + b)) ** 0.5,
                rng.normal(0, 0.1, b).astype(np.float32)) for a, b in zip(dims[:-1], dims[1:])]
    x = np.clip(rng.standard_normal((512, 40)), -4, 4).astype(np.float32)
    return weights, x


def test_forward_matches_the_programs(net):
    import jax.numpy as jnp
    from shifu_tpu.models import nn
    weights, x = net
    spec = nn.NNModelSpec(input_dim=40, hidden_nodes=[64, 32], activations=["relu", "relu"],
                          output_dim=1, output_activation="sigmoid", loss="log")
    params = [{"w": jnp.asarray(w), "b": jnp.asarray(b)} for w, b in weights]
    got = np.asarray(nn.forward(params, spec, jnp.asarray(x)))[:, 0]
    want, sigma = mlp.forward64(weights, x)
    assert np.abs(got - want).max() < 1e-5                    # f32 on the CPU
    assert (sigma > 0).all()


def test_what_the_forward_tolerance_tells_apart(net):
    """bf16 operands with f32 accumulation (the TPU's default) sit inside
    10 sigma; twice the rounding (7-bit mantissas) does not.  Activations
    *kept* in bf16 are rounded again at the next dot anyway and cannot be
    told from the default — written in PERF.md."""
    weights, x = net
    want, sigma = mlp.forward64(weights, x)

    def cut(v, bits):
        u = np.asarray(v, np.float32).view(np.uint32)
        drop = 23 - bits
        u = (u + (1 << (drop - 1))) & ~np.uint32((1 << drop) - 1)
        return u.view(np.float32).astype(np.float64)

    def fwd(bits):
        a = x.astype(np.float64)
        for w, b in weights:
            z = cut(a, bits) @ cut(w, bits) + b
            a = np.maximum(z, 0.0)
        return 1 / (1 + np.exp(-z[:, 0]))

    assert (np.abs(fwd(7) - want) / sigma).max() < 5.0        # bf16: 7 explicit mantissa bits
    assert (np.abs(fwd(4) - want) / sigma).max() > 10.0       # eight times the rounding: refused
    assert (np.abs(mlp.forward_bf16_everywhere(weights, x) - want) / sigma).max() < 10.0


def test_adam_reference_learns():
    rng = np.random.default_rng(5)
    x = rng.standard_normal((2048, 12)).astype(np.float32)
    y = (rng.random(2048) < 1 / (1 + np.exp(-(1.5 * x[:, 0] - x[:, 1])))).astype(np.float32)
    runs = mlp.train_adam(x, y, [16, 8], epochs=6, batch=256, rate=0.01, valid_rate=0.2,
                          runs=3, seed=2 ** 32 - 1)
    assert runs["curve"].shape == (3, 6) and (runs["last"] < runs["first"]).all()
    assert runs["last"].std() > 0                             # own init, split and shuffles
    assert mlp.log_loss(np.full(4, 0.5), np.array([0, 1, 0, 1])) == pytest.approx(np.log(2))
