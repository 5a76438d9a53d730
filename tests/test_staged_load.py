"""The resident NN job's plane goes shard file -> staging piece -> device:
``Shards.load_all(on_device={"x": layout})`` hands back ``x`` as a device
array in the trainer's layout, built from small host pieces that are reused,
and no host array of the plane's size is made.  The reference is the host
path — ``load_all()`` and one ``device_put`` — and the result must equal it
bit for bit, whichever way each member was read; the CRC, the quarantine
rule and the fault hook guard every shard as before, and a ``train`` run
writes the same model either way."""

import json
import os
import threading
import zipfile

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from shifu_tpu import faults, obs
from shifu_tpu.config import environment
from shifu_tpu.data import shards as shards_mod
from shifu_tpu.data import staging
from shifu_tpu.data.shards import Shards
from shifu_tpu.parallel.mesh import device_mesh

WIDTH = 5
ROW_BYTES = 4 * WIDTH


@pytest.fixture(autouse=True)
def _clean():
    environment.reset_for_tests()
    faults.reset_for_tests()
    obs.reset_for_tests()
    obs.set_enabled(True)
    yield
    environment.reset_for_tests()
    faults.reset_for_tests()
    obs.reset_for_tests()


def _part(rng, rows):
    return {"x": rng.standard_normal((rows, WIDTH)).astype(np.float32),
            "y": rng.integers(0, 2, rows).astype(np.float32),
            "w": rng.random(rows).astype(np.float32)}


def _shard_set(tmp_path, sizes, compressed=()):
    d = tmp_path / "shards"
    d.mkdir()
    rng = np.random.default_rng(11)
    for i, rows in enumerate(sizes):
        save = np.savez_compressed if i in compressed else np.savez
        save(d / f"part-{i:05d}.npz", **_part(rng, rows))
    with open(d / "schema.json", "w") as f:
        json.dump({"numShards": len(sizes), "numRows": sum(sizes),
                   "shardRows": list(sizes)}, f)
    return str(d)


def _wire_set(tmp_path, sizes):
    from shifu_tpu.data.spill import SpillWriter, wire_dir
    d = tmp_path / "wire"
    d.mkdir()
    keys = ["x", "y", "w"]
    rng = np.random.default_rng(3)
    wr = SpillWriter(wire_dir(str(d), keys), keys, "sig", 1 << 30)
    assert all(wr.append(_part(rng, n)) for n in sizes) and wr.finish()
    with open(d / "schema.json", "w") as f:
        json.dump({"wire": True, "wireKeys": keys, "wireSignature": "sig",
                   "shardRows": sizes, "numRows": sum(sizes)}, f)
    return str(d)


def _layout(multiple=1, n_devices=1, bags=1):
    mesh = device_mesh(n_ensemble=bags,
                       devices=jax.devices("cpu")[:n_devices])
    return staging.RowLayout(NamedSharding(mesh, P("data", None)), multiple)


def _load_attrs():
    (sp,) = [r for r in obs.pending_records()
             if r["kind"] == "span" and r["name"] == "data.load"]
    return sp["attrs"]


def _assert_staged_equals_host(d, layout):
    """``x`` through staging against the host path's plane; the other keys
    stay host arrays, equal too.  Returns the load's span attrs."""
    want = Shards.open(d).load_all()
    obs.reset_for_tests()
    obs.set_enabled(True)
    got = Shards.open(d).load_all({"x": layout})
    assert list(got) == list(want)
    for k in ("y", "w"):
        assert isinstance(got[k], np.ndarray)
        np.testing.assert_array_equal(got[k], want[k])
    x, rows = got["x"], len(want["y"])
    assert isinstance(x, jax.Array) and x.dtype == want["x"].dtype
    assert x.sharding == layout.sharding
    assert x.shape == (rows + -rows % layout.multiple, WIDTH)
    padded = np.concatenate(
        [want["x"], np.zeros((x.shape[0] - rows, WIDTH), np.float32)])
    np.testing.assert_array_equal(
        np.asarray(x), np.asarray(jax.device_put(padded, layout.sharding)))
    attrs = _load_attrs()
    assert attrs["staged_bytes"] == want["x"].nbytes
    assert attrs["bytes"] == x.nbytes + want["y"].nbytes + want["w"].nbytes
    return attrs


# ------------------------------- (a) the same plane, bit for bit, on the device
@pytest.mark.parametrize("case, sizes, piece_rows, multiple, compressed", [
    ("many-pieces-a-shard", [640, 640, 640], 64, 1, ()),
    ("shards-of-unequal-rows", [300, 77, 512, 1], 64, 1, ()),
    ("piece-does-not-divide-a-shard", [100, 100, 100], 64, 1, ()),
    ("piece-larger-than-a-shard", [40, 40], 4096, 1, ()),
    ("piece-cut-to-the-lane-multiple", [700, 700], 300, 1, ()),
    ("ragged-rows-padded-tail-is-zero", [128, 122], 64, 64, ()),
    ("a-deflated-member", [90, 90, 90], 64, 1, (1,)),
    ("an-empty-shard", [48, 0, 48], 64, 1, ()),
])
def test_staged_plane_equals_device_put_of_the_host_plane(
        tmp_path, monkeypatch, case, sizes, piece_rows, multiple, compressed):
    monkeypatch.setattr(staging, "PIECE_BYTES", piece_rows * ROW_BYTES)
    d = _shard_set(tmp_path, sizes, compressed)
    attrs = _assert_staged_equals_host(d, _layout(multiple))
    step = piece_rows - piece_rows % 128 if piece_rows > 128 else piece_rows
    assert attrs["pieces"] == sum(-(-n // step) for n in sizes)
    assert attrs["direct"] == len(sizes) - len(compressed)


def test_staged_plane_of_a_wire_plane(tmp_path, monkeypatch):
    monkeypatch.setattr(staging, "PIECE_BYTES", 32 * ROW_BYTES)
    sizes = [50, 50, 23]
    attrs = _assert_staged_equals_host(_wire_set(tmp_path, sizes),
                                       _layout(8))
    assert attrs["pieces"] == 2 + 2 + 1


# --------------------------------------------------- (c) staging is reused
@pytest.mark.parametrize("rows", [256, 4096])
def test_staging_does_not_grow_with_the_plane(tmp_path, monkeypatch, rows):
    monkeypatch.setattr(staging, "PIECE_BYTES", 64 * ROW_BYTES)
    d = _shard_set(tmp_path, [rows] * 3)
    x = Shards.open(d).load_all({"x": _layout()})["x"]
    attrs = _load_attrs()
    # two pieces a fill thread, whatever the rows
    assert attrs["staging_bytes"] == 2 * attrs["threads"] * 64 * ROW_BYTES
    assert attrs["staged_bytes"] == x.nbytes == 3 * rows * ROW_BYTES
    assert attrs["pieces"] == 3 * rows // 64


def test_host_load_stages_nothing(tmp_path):
    Shards.open(_shard_set(tmp_path, [32, 32])).load_all()
    attrs = _load_attrs()
    assert attrs["staged_bytes"] == attrs["staging_bytes"] == \
        attrs["pieces"] == 0


def test_waits_for_the_device_are_children_of_the_read(tmp_path, monkeypatch):
    monkeypatch.setattr(staging, "PIECE_BYTES", 16 * ROW_BYTES)
    Shards.open(_shard_set(tmp_path, [256, 256])).load_all({"x": _layout()})
    spans = [r for r in obs.pending_records() if r["kind"] == "span"]
    (read,) = [s for s in spans if s["name"] == "data.read"]
    puts = [s for s in spans if s["name"] == "data.put"]
    assert puts and all(s["parent"] == read["id"] for s in puts)
    assert all(s["tid"].startswith("shard-fill") for s in puts)


# ------------------------------------- (d) the guards of a shard still hold
@pytest.mark.parametrize("threshold", [0.0, 0.5])
def test_flipped_byte_is_refused_after_pieces_were_placed(
        tmp_path, monkeypatch, threshold):
    monkeypatch.setattr(staging, "PIECE_BYTES", 64 * ROW_BYTES)
    sizes = [512, 512, 512, 512]
    d = _shard_set(tmp_path, sizes)
    bad = os.path.join(d, "part-00001.npz")
    m = shards_mod._plan_npz(bad).members["x"]
    at = m.start + m.head + 6000        # inside the second piece and later
    with open(bad, "r+b") as f:
        f.seek(at)
        byte = f.read(1)
        f.seek(at)
        f.write(bytes([byte[0] ^ 0x10]))
    if not threshold:
        with pytest.raises(zipfile.BadZipFile, match="CRC"):
            Shards.open(d).load_all({"x": _layout()})
        return
    environment.set_property("shifu.data.badThreshold", str(threshold))
    want = Shards.open(d).load_all()
    assert len(want["y"]) == 3 * 512
    assert obs.get_registry().counter("data.quarantined_shards").value == 1
    got = Shards.open(d).load_all({"x": _layout(64)})
    assert obs.get_registry().counter("data.quarantined_shards").value == 2
    # the rare branch: rows were on the device when the CRC refused the
    # shard, so the plane comes back as the host path's would
    assert all(isinstance(a, np.ndarray) and a.flags.writeable
               for a in got.values())
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


def test_torn_shard_is_quarantined_before_anything_is_placed(
        tmp_path, monkeypatch):
    monkeypatch.setattr(staging, "PIECE_BYTES", 16 * ROW_BYTES)
    d = _shard_set(tmp_path, [24, 24, 24, 24, 11])
    bad = os.path.join(d, "part-00002.npz")
    with open(bad, "r+b") as f:
        f.truncate(os.path.getsize(bad) // 2)
    with pytest.raises(zipfile.BadZipFile):
        Shards.open(d).load_all({"x": _layout()})
    environment.set_property("shifu.data.badThreshold", "0.5")
    _assert_staged_equals_host(d, _layout(8))
    assert obs.get_registry().counter("data.quarantined_shards").value == 1


def test_fault_hook_sees_each_shard_once_an_attempt(tmp_path, monkeypatch):
    monkeypatch.setattr(staging, "PIECE_BYTES", 4 * ROW_BYTES)
    sizes = [10] * 11
    d = _shard_set(tmp_path, sizes)
    want = Shards.open(d).load_all()["x"]
    seen, lock = [], threading.Lock()

    def fire(site, point, value, path=None):
        with lock:
            seen.append((site, point, value, os.path.basename(path)))
    monkeypatch.setattr(faults, "fire", fire)
    del seen[:]
    x = Shards.open(d).load_all({"x": _layout()})["x"]
    np.testing.assert_array_equal(np.asarray(x), want)
    assert sorted(seen) == [("shards", "shard", i, f"part-{i:05d}.npz")
                            for i in range(len(sizes))]
    # a transient error half way: the shard's pieces are placed again
    monkeypatch.undo()
    monkeypatch.setattr(staging, "PIECE_BYTES", 4 * ROW_BYTES)
    environment.set_property("shifu.io.retryBaseMs", "1")
    environment.set_property("shifu.faults", "shards:shard=6:ioerror")
    faults.reset_for_tests()
    x = Shards.open(d).load_all({"x": _layout()})["x"]
    np.testing.assert_array_equal(np.asarray(x), want)
    assert obs.get_registry().counter("ingest.retries").value == 1


def test_fill_threads_place_disjoint_rows(tmp_path, monkeypatch):
    """More fills in flight than cores, a short switch interval: every
    shard's rows land in their own range of the device plane."""
    import sys
    monkeypatch.setattr(staging, "PIECE_BYTES", 32 * ROW_BYTES)
    d = _shard_set(tmp_path, [257] * 24)
    want = Shards.open(d).load_all()["x"]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for _ in range(3):
            x = Shards.open(d).load_all({"x": _layout()})["x"]
            np.testing.assert_array_equal(np.asarray(x), want)
    finally:
        sys.setswitchinterval(old)


# ------------------------------ (e) a data axis > 1: rows go to their owners
@pytest.mark.parametrize("n_devices, bags, multiple", [
    (4, 1, 4), (8, 2, 64), (8, 1, 8)])
def test_rows_go_to_the_devices_that_own_them(tmp_path, monkeypatch,
                                              n_devices, bags, multiple):
    monkeypatch.setattr(staging, "PIECE_BYTES", 48 * ROW_BYTES)
    layout = _layout(multiple, n_devices, bags)
    assert layout.sharding.mesh.shape["data"] == n_devices // bags > 1
    d = _shard_set(tmp_path, [100, 37, 100, 13])
    _assert_staged_equals_host(d, layout)
    x = Shards.open(d).load_all({"x": layout})["x"]
    assert len(x.addressable_shards) == n_devices
    want = Shards.open(d).load_all()["x"]
    for shard in x.addressable_shards:
        lo, hi, _ = shard.index[0].indices(x.shape[0])
        mine = np.zeros((hi - lo, WIDTH), np.float32)
        have = want[lo:hi]
        mine[:len(have)] = have
        np.testing.assert_array_equal(np.asarray(shard.data), mine)


@pytest.mark.parametrize("n_devices", [1, 4])
def test_trainer_takes_a_staged_x_and_trains_the_same_model(
        tmp_path, monkeypatch, n_devices):
    """``train_ensemble`` on x as the loader built it against x as a NumPy
    array, same mesh: the same parameters, bit for bit; ``nn.h2d`` then
    sends the small arrays only."""
    from shifu_tpu.models import nn as nn_model
    from shifu_tpu.train.nn_trainer import (TrainSettings, plane_layout,
                                            train_ensemble)
    from shifu_tpu.train.sampling import member_masks
    monkeypatch.setattr(staging, "PIECE_BYTES", 32 * ROW_BYTES)
    bags, batch, sizes = 2, 64, [100, 100, 50]      # 250 rows: ragged
    d = _shard_set(tmp_path, sizes)
    host = Shards.open(d).load_all()
    n = len(host["y"])
    tw, vw = member_masks(n, bags, valid_rate=0.25, sample_rate=1.0,
                          replacement=False, targets=host["y"], seed=0)
    spec = nn_model.NNModelSpec(input_dim=WIDTH, hidden_nodes=[4],
                                activations=["tanh"], loss="log")
    settings = TrainSettings(optimizer="ADAM", learning_rate=0.05,
                             epochs=3, batch_size=batch, seed=3)
    mesh = device_mesh(n_ensemble=bags,
                       devices=jax.devices("cpu")[:n_devices])
    _, bs, layout = plane_layout(settings, bags, mesh)
    assert (bs, layout.multiple) == (batch, batch)
    staged = Shards.open(d).load_all({"x": layout})["x"]
    assert staged.shape[0] == 256

    def train(x):
        obs.reset_for_tests()
        obs.set_enabled(True)
        res = train_ensemble(x, host["y"], tw, vw, spec, settings, mesh=mesh)
        (h2d,) = [r for r in obs.pending_records()
                  if r["kind"] == "span" and r["name"] == "nn.h2d"]
        return res, h2d["attrs"]
    (a, ha), (b, hb) = train(host["x"]), train(staged)
    assert ha["pad_rows"] == hb["pad_rows"] == 6
    assert ha["bytes"] == 4 * 256 * (WIDTH + 1 + 2 * bags)
    assert hb["bytes"] == 4 * 256 * (1 + 2 * bags)
    for pa, pb in zip(a.params, b.params):
        for la, lb in zip(jax.tree_util.tree_leaves(pa),
                          jax.tree_util.tree_leaves(pb)):
            np.testing.assert_array_equal(la, lb)
    np.testing.assert_array_equal(a.valid_errors, b.valid_errors)
    # an x laid out for another job (another multiple) takes the host path
    other = Shards.open(d).load_all({"x": layout._replace(multiple=4)})["x"]
    (c, hc) = train(other)
    assert hc["bytes"] == ha["bytes"]
    np.testing.assert_array_equal(c.valid_errors, a.valid_errors)


# ----------------------------- (b) a `train` run writes the same model bytes
def _train_cli(mdir, *flags):
    from shifu_tpu.cli import main
    assert main(["-Dshifu.train.streaming=off", "--dir", mdir, "train",
                 *flags]) == 0
    out = {}
    for name in sorted(os.listdir(os.path.join(mdir, "models"))):
        with open(os.path.join(mdir, "models", name), "rb") as f:
            out[name] = f.read()
    with open(os.path.join(mdir, "tmp", "train.progress")) as f:
        return out, f.read()


@pytest.mark.parametrize("case, algorithm, bags, flags, staged", [
    ("nn", "NN", 1, (), True),
    ("lr", "LR", 1, (), True),
    ("nn-bag-of-2", "NN", 2, (), True),
    ("nn-shuffle", "NN", 1, ("-shuffle",), False),
])
def test_train_cli_writes_the_same_model_as_with_a_numpy_x(
        prepared_set, monkeypatch, case, algorithm, bags, flags, staged):
    from shifu_tpu.config import ModelConfig
    mc_path = os.path.join(prepared_set, "ModelConfig.json")
    mc = ModelConfig.load(mc_path)
    mc.train.algorithm = algorithm
    mc.train.baggingNum = bags
    mc.train.numTrainEpochs = 4
    mc.train.params = {"Propagation": "ADAM", "LearningRate": 0.01,
                       "MiniBatchs": 512}
    if algorithm == "NN":
        mc.train.params.update(NumHiddenNodes=[8], ActivationFunc=["relu"])
    mc.save(mc_path)
    monkeypatch.setattr(staging, "PIECE_BYTES", 1 << 14)
    kinds = []
    real = Shards.load_all

    def spy(self, on_device=None, host_only=False):
        out = real(self, None if host_only else on_device)
        kinds.append(type(out["x"]))
        return out
    monkeypatch.setattr(Shards, "load_all", spy)
    got = _train_cli(prepared_set, *flags)
    assert issubclass(kinds[-1], jax.Array if staged else np.ndarray), kinds
    monkeypatch.setattr(
        Shards, "load_all",
        lambda self, on_device=None: spy(self, on_device, host_only=True))
    want = _train_cli(prepared_set, *flags)
    assert issubclass(kinds[-1], np.ndarray)
    assert got[0] and got[0].keys() == want[0].keys()
    assert len(got[0]) == bags
    assert got == want
