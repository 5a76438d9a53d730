"""The resident NN trainer puts its plane on the device ONCE: the rows are
padded on the host to their final multiple (the minibatch when ``MiniBatchs``
is set, else the mesh's data extent) before the only ``device_put``, and
nothing of the plane ever comes back to the host.

The parent of this change padded to the data extent, uploaded, gathered the
whole plane back, padded to the batch multiple and uploaded again; both
orders append the same zeros, so the trained parameters are pinned bit for
bit: against a run on the same data padded by hand, and against
``tests/golden/nn_plane_once.npz``, written by that parent
(``python tests/test_nn_plane_once.py --regen`` on a commit to be trusted;
the bits are XLA:CPU's on this rig's x64 suite settings).
"""

import collections
import os
import sys

import numpy as np
import pytest

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "nn_plane_once.npz")
BAGS, DIM, BATCH, EPOCHS = 2, 5, 64, 3
EVEN, RAGGED = 256, 250                  # 4 x 64, and 6 rows short of it
CASES = [(n, ova) for n in (EVEN, RAGGED) for ova in (False, True)]


def _case(n, ova):
    """One seeded job: x, y, the member weights and (one-vs-all) the
    per-member targets, with its spec and settings."""
    from shifu_tpu.models import nn as nn_model
    from shifu_tpu.train.nn_trainer import TrainSettings
    from shifu_tpu.train.sampling import member_masks
    rng = np.random.default_rng(7)
    x = rng.normal(size=(n, DIM)).astype(np.float32)
    y = (x[:, 0] - x[:, 1] + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    tw, vw = member_masks(n, BAGS, valid_rate=0.25, sample_rate=1.0,
                          replacement=False, targets=y, seed=0)
    ym = np.stack([y, 1.0 - y]).astype(np.float32) if ova else None
    spec = nn_model.NNModelSpec(input_dim=DIM, hidden_nodes=[4],
                                activations=["tanh"], loss="log")
    settings = TrainSettings(optimizer="ADAM", learning_rate=0.05,
                             epochs=EPOCHS, batch_size=BATCH, seed=3)
    return x, y, tw, vw, ym, spec, settings


def _mesh(n_devices):
    import jax
    from shifu_tpu.parallel.mesh import device_mesh
    return device_mesh(n_ensemble=BAGS,
                       devices=jax.devices("cpu")[:n_devices])


def _train(n, ova, n_devices=1, pad_to=None):
    """The job's result; ``pad_to`` appends zero rows of zero weight by
    hand first, so that the trainer finds nothing to pad."""
    from shifu_tpu.train.nn_trainer import _pad_all, train_ensemble
    x, y, tw, vw, ym, spec, settings = _case(n, ova)
    if pad_to:
        x, y, tw, vw, *rest = _pad_all(x, y, tw, vw, pad_to, ym)
        ym = rest[0] if rest else None
    return train_ensemble(x, y, tw, vw, spec, settings, mesh=_mesh(n_devices),
                          y_members=ym)


def _flat(res):
    import jax
    return np.concatenate([np.ravel(leaf) for p in res.params
                           for leaf in jax.tree_util.tree_leaves(p)])


def _padded(n):
    return n + -n % BATCH


def _key(n, ova):
    return f"n{n}_{'ova' if ova else 'plain'}"


# ------------------------------------------- (a) the same plane, bit for bit
@pytest.mark.parametrize("n,ova", CASES)
def test_params_equal_hand_padded_run_and_parent_golden(n, ova):
    got = _flat(_train(n, ova))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, _flat(_train(n, ova, pad_to=BATCH)))
    with np.load(GOLDEN) as golden:
        np.testing.assert_array_equal(got, golden[_key(n, ova)])


# --------------------------------------- (b) the plane never comes down
@pytest.mark.parametrize("n", [EVEN, RAGGED])
def test_no_gather_before_the_epoch_loop_nor_of_the_plane(n, monkeypatch):
    from shifu_tpu import obs
    from shifu_tpu.train import nn_trainer
    obs.reset_for_tests()
    obs.set_enabled(True)               # live_spans() says where a call fell
    calls = []
    real = nn_trainer._gather_np

    def recorder(a):
        calls.append((int(np.size(a)),
                      [s["name"] for s in obs.live_spans()]))
        return real(a)
    monkeypatch.setattr(nn_trainer, "_gather_np", recorder)
    try:
        _train(n, ova=True)
    finally:
        obs.reset_for_tests()
    assert len(calls) >= EPOCHS         # the error vector, once an epoch
    for size, open_spans in calls:
        assert "nn.epoch" in open_spans, (size, open_spans)
        assert size <= 2 * BAGS, size


# ------------------------------------------ (c) and goes up exactly once
@pytest.mark.parametrize("n,ova", CASES)
def test_each_plane_array_is_put_once(n, ova, monkeypatch):
    import jax
    n_padded = _padded(n)
    puts = collections.Counter()
    real = jax.device_put

    def recorder(v, *args, **kwargs):
        shape = getattr(v, "shape", ())
        if n_padded in shape or n in shape:
            puts[tuple(shape)] += 1
        return real(v, *args, **kwargs)
    monkeypatch.setattr(jax, "device_put", recorder)
    _train(n, ova)
    assert puts == {(n_padded, DIM): 1, (n_padded,): 1,
                    (BAGS, n_padded): 3 if ova else 2}


# ------------------------------- (d) a data axis of 2 and a ragged row count
@pytest.mark.parametrize("ova", [False, True])
def test_ragged_rows_on_2x2_mesh_match_one_device(ova):
    import jax
    one, four = _train(RAGGED, ova), _train(RAGGED, ova, n_devices=4)
    assert _mesh(4).shape == {"ensemble": 2, "data": 2}
    assert four.epochs_run == one.epochs_run == EPOCHS
    np.testing.assert_allclose(four.valid_errors, one.valid_errors,
                               rtol=1e-4, atol=1e-6)
    for p1, p4 in zip(one.params, four.params):
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p4)):
            np.testing.assert_allclose(b, a, rtol=1e-3, atol=1e-5)


# ------------------------------------- what nn.h2d says the mechanism did
@pytest.mark.parametrize("n", [EVEN, RAGGED])
def test_h2d_span_carries_padded_bytes_and_pad_rows(n):
    from shifu_tpu import obs
    obs.reset_for_tests()
    obs.set_enabled(True)
    try:
        _train(n, ova=True)
        spans = [r for r in obs.pending_records() if r["kind"] == "span"]
    finally:
        obs.reset_for_tests()
    names = [s["name"] for s in spans]
    assert names.count("nn.h2d") == 1 and "nn.repad" not in names
    (h2d,) = [s for s in spans if s["name"] == "nn.h2d"]
    n_padded = _padded(n)
    assert h2d["attrs"]["pad_rows"] == n_padded - n
    # x and y in f32; train_w, valid_w and y_members [BAGS, rows] in f32
    assert h2d["attrs"]["bytes"] == 4 * n_padded * (DIM + 1 + 3 * BAGS)


if __name__ == "__main__":
    if sys.argv[1:] != ["--regen"]:
        sys.exit("usage: python tests/test_nn_plane_once.py --regen")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import conftest                                    # noqa: F401  (x64, cpu)
    np.savez(GOLDEN, **{_key(n, ova): _flat(_train(n, ova))
                        for n, ova in CASES})
    print("wrote", GOLDEN)
