"""Guards the driver's entry points (`__graft_entry__`) and multi-device
numerics — the round-1 headline failure was exactly this file not existing.

Runs on the conftest-forced 8-device virtual CPU mesh."""

import numpy as np
import pytest

import jax


def _entry_module():
    import __graft_entry__
    return __graft_entry__


def test_entry_compiles_and_runs():
    fn, args = _entry_module().entry()
    out = jax.jit(fn)(*args)
    assert np.isfinite(np.asarray(out)).all()


def test_dryrun_multichip_8():
    # the exact call the driver makes
    _entry_module().dryrun_multichip(8)


def test_dryrun_hermetic():
    """Every buffer the dryrun creates must live on the platform of the
    devices it was given — ``jax.devices()`` taken as given, which under
    the conftest env is the 8 virtual CPU devices."""
    mod = _entry_module()
    devices = mod._pick_devices(8)
    assert list(devices) == list(jax.devices()[:8])
    platform = devices[0].platform
    before_refs = list(jax.live_arrays())   # hold refs: pin ids against reuse
    before = {id(a) for a in before_refs}
    mod.dryrun_multichip(8)
    leaked = [a for a in jax.live_arrays()
              if id(a) not in before and a.devices()
              and any(d.platform != platform for d in a.devices())]
    del before_refs
    assert not leaked


def test_dryrun_fails_on_broken_or_short_backend(monkeypatch):
    """A backend that cannot be asked, or is too small, is an error — the
    dryrun never goes looking for another backend to pass on."""
    mod = _entry_module()
    with pytest.raises(RuntimeError, match="need 4096 devices"):
        mod._pick_devices(4096)

    def poisoned(*args, **kwargs):
        raise RuntimeError("FAILED_PRECONDITION: libtpu version mismatch")

    monkeypatch.setattr(jax, "devices", poisoned)
    with pytest.raises(RuntimeError, match="FAILED_PRECONDITION"):
        mod.dryrun_multichip(8)


def test_device_mesh_shape():
    from shifu_tpu.parallel.mesh import device_mesh
    devs = jax.devices("cpu")
    assert len(devs) >= 8, "conftest must force an 8-device CPU platform"
    mesh = device_mesh(n_ensemble=2, devices=devs[:8])
    assert mesh.shape["ensemble"] == 2
    assert mesh.shape["data"] == 4


@pytest.mark.parametrize("bags", [1, 2])
def test_one_vs_eight_device_equivalence(bags):
    """Training on a 1-device mesh and an 8-device mesh must agree: the mesh
    only changes WHERE the rows live, never the math (GSPMD inserts the
    psum; full-batch + no dropout makes the run deterministic)."""
    from shifu_tpu.models import nn as nn_model
    from shifu_tpu.parallel.mesh import device_mesh
    from shifu_tpu.train.nn_trainer import TrainSettings, train_ensemble
    from shifu_tpu.train.sampling import member_masks

    rng = np.random.default_rng(3)
    n, d = 96, 6
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    train_w, valid_w = member_masks(n, bags, valid_rate=0.25, sample_rate=1.0,
                                    replacement=False, targets=y, seed=0)
    spec = nn_model.NNModelSpec(input_dim=d, hidden_nodes=[8],
                                activations=["tanh"], loss="log")
    settings = TrainSettings(optimizer="ADAM", learning_rate=0.05,
                             epochs=5, seed=0)
    devs = jax.devices("cpu")
    res1 = train_ensemble(x, y, train_w, valid_w, spec, settings,
                          mesh=device_mesh(n_ensemble=bags, devices=devs[:1]))
    res8 = train_ensemble(x, y, train_w, valid_w, spec, settings,
                          mesh=device_mesh(n_ensemble=bags, devices=devs[:8]))
    np.testing.assert_allclose(res1.valid_errors, res8.valid_errors,
                               rtol=1e-4, atol=1e-6)
    for p1, p8 in zip(res1.params, res8.params):
        flat1 = jax.tree_util.tree_leaves(p1)
        flat8 = jax.tree_util.tree_leaves(p8)
        for a, b in zip(flat1, flat8):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


def test_stats_accumulator_mesh_equivalence():
    """NumericAccumulator on a 1-device vs 8-device mesh: the data-axis
    sharding must only change WHERE rows live (reference stats fan-out,
    ``MapReducerStatsWorker.java:111-139``).  Counts are integer-exact
    either way; weighted sums may differ by reduction order only."""
    from shifu_tpu.config.model_config import BinningMethod
    from shifu_tpu.ops.binning import NumericAccumulator
    from shifu_tpu.parallel.mesh import device_mesh

    rng = np.random.default_rng(11)
    n, c = 997, 5                       # deliberately NOT divisible by 8
    x = rng.normal(size=(n, c)).astype(np.float32) * [1, 10, 100, 1, 1]
    valid = rng.random((n, c)) > 0.07
    target = (rng.random(n) < 0.3).astype(np.float32)
    weight = rng.uniform(0.5, 2.0, n).astype(np.float32)
    devs = jax.devices("cpu")

    def run(mesh):
        acc = NumericAccumulator(n_cols=c, num_buckets=256, mesh=mesh)
        for s, e in ((0, 400), (400, n)):    # two uneven chunks
            acc.update_moments(x[s:e], valid[s:e])
        acc.finalize_range()
        for s, e in ((0, 400), (400, n)):
            acc.update_histogram(x[s:e], valid[s:e], target[s:e],
                                 weight[s:e])
        return acc, acc.finalize_sketch(BinningMethod.EqualTotal, 8)

    acc1, (b1, a1, p1, d1) = run(None)
    acc8, (b8, a8, p8, d8) = run(device_mesh(devices=devs[:8]))
    assert acc1.total_rows == acc8.total_rows == n
    np.testing.assert_array_equal(acc1.missing, acc8.missing)
    np.testing.assert_allclose(acc1.moments["mean"], acc8.moments["mean"],
                               rtol=1e-5)
    for i in range(c):
        np.testing.assert_array_equal(b1[i], b8[i])          # boundaries
        np.testing.assert_array_equal(a1[i][:, :2], a8[i][:, :2])  # counts
        np.testing.assert_allclose(a1[i][:, 2:], a8[i][:, 2:], rtol=1e-5)
    np.testing.assert_array_equal(d1, d8)
    np.testing.assert_allclose(p1, p8, rtol=1e-6)


def test_scorer_mesh_equivalence(tmp_path):
    """Scorer with a data-sharded mesh scores identically to the
    single-device layout (reference cluster eval,
    ``EvalModelProcessor.java:424-436``)."""
    from shifu_tpu.eval.scorer import Scorer
    from shifu_tpu.models import nn as nn_model
    from shifu_tpu.models.nn import IndependentNNModel
    from shifu_tpu.parallel.mesh import device_mesh

    rng = np.random.default_rng(5)
    d = 6
    spec = nn_model.NNModelSpec(input_dim=d, hidden_nodes=[8],
                                activations=["tanh"])
    models = [IndependentNNModel(
        spec, nn_model.init_params(jax.random.PRNGKey(i), spec))
        for i in range(3)]
    x = rng.normal(size=(997, d)).astype(np.float32)   # not divisible by 8
    devs = jax.devices("cpu")
    r1 = Scorer(models).score(x)
    r8 = Scorer(models, mesh=device_mesh(devices=devs[:8])).score(x)
    np.testing.assert_allclose(r1.scores, r8.scores, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(r1.mean, r8.mean, rtol=1e-5, atol=1e-5)
