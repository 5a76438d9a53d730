"""``Shards.load_all``: one preallocated plane filled from the shard files
on several threads.  The reference is what the loader did before —
``np.concatenate`` of ``np.load`` of every shard — and the plane must equal
it bit for bit, whichever way each member was read; the CRC, the
quarantine rule, the fault hook and the retry ladder still guard every
shard."""

import json
import os
import threading
import zipfile

import numpy as np
import pytest

from shifu_tpu import faults, obs
from shifu_tpu.config import environment
from shifu_tpu.data import shards as shards_mod
from shifu_tpu.data.shards import Shards

pytestmark = pytest.mark.faults


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


def _part(rng, rows, kind):
    if kind == "bins":
        return {"bins": rng.integers(0, 65, (rows, 7)).astype(np.uint8),
                "y": rng.integers(0, 2, rows).astype(np.float32),
                "w": np.ones(rows, np.float32)}
    return {"x": rng.standard_normal((rows, 5)).astype(np.float32),
            "y": rng.integers(0, 2, rows).astype(np.float32),
            "w": rng.random(rows).astype(np.float32)}


def _shard_set(tmp_path, sizes, kind="x", compressed=(), schema_rows=True):
    d = tmp_path / "shards"
    d.mkdir()
    rng = np.random.default_rng(7)
    for i, rows in enumerate(sizes):
        save = np.savez_compressed if i in compressed else np.savez
        save(d / f"part-{i:05d}.npz", **_part(rng, rows, kind))
    schema = {"numShards": len(sizes), "numRows": sum(sizes)}
    if schema_rows:
        schema["shardRows"] = list(sizes)
    with open(d / "schema.json", "w") as f:
        json.dump(schema, f)
    return str(d)


def _reference(d, skip=()):
    """What ``load_all`` returned before this loader existed."""
    files = sorted(f for f in os.listdir(d) if f.endswith(".npz"))
    parts = [dict(np.load(os.path.join(d, f)))
             for i, f in enumerate(files) if i not in skip]
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def _assert_same(got, want):
    assert list(got) == list(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        assert got[k].shape == want[k].shape, k
        assert np.array_equal(got[k], want[k]), k


def _load_span():
    (sp,) = [r for r in obs.pending_records()
             if r["kind"] == "span" and r["name"] == "data.load"]
    return sp["attrs"]


def _member_data_offset(path, key):
    """File offset of the first array byte of a stored member."""
    m = shards_mod._plan_npz(path).members[key]
    return m.start + m.head


# ------------------------------------------------- equal to the old loader
@pytest.mark.parametrize("case, sizes, kind, compressed", [
    ("stored-f32-uneven-last", [64, 64, 64, 17], "x", ()),        # (a)
    ("one-byte-bins", [48, 48, 31], "bins", ()),                   # (b)
    ("one-deflated-shard", [40, 40, 40, 40], "x", (2,)),          # (c)
    ("single-shard", [33], "x", ()),                              # (e)
    ("more-shards-than-threads", [9] * 19 + [4], "x", ()),        # (e)
    ("an-empty-shard", [16, 0, 16], "x", ()),
])
def test_load_all_equals_concatenated_np_load(tmp_path, case, sizes, kind,
                                              compressed):
    d = _shard_set(tmp_path, sizes, kind, compressed)
    got = Shards.open(d).load_all()
    want = _reference(d)
    _assert_same(got, want)
    assert all(a.flags.c_contiguous and a.flags.writeable
               for a in got.values())
    attrs = _load_span()
    assert attrs["shards"] == len(sizes)
    assert attrs["direct"] == len(sizes) - len(compressed)
    assert attrs["threads"] == shards_mod._fill_width(len(sizes)) \
        <= min(len(sizes), 8)
    assert attrs["bytes"] == sum(a.nbytes for a in want.values())


def test_load_all_sizes_from_headers_without_schema_rows(tmp_path):
    d = _shard_set(tmp_path, [20, 20, 5], schema_rows=False)
    _assert_same(Shards.open(d).load_all(), _reference(d))


def test_load_all_wire_plane(tmp_path):                            # (d)
    from shifu_tpu.data.spill import SpillWriter, wire_dir
    d = tmp_path / "wire"
    d.mkdir()
    keys, sizes = ["bins", "y", "w"], [50, 50, 23]
    rng = np.random.default_rng(3)
    parts = [_part(rng, n, "bins") for n in sizes]
    wr = SpillWriter(wire_dir(str(d), keys), keys, "sig", 1 << 30)
    assert all(wr.append(p) for p in parts) and wr.finish()
    with open(d / "schema.json", "w") as f:
        json.dump({"wire": True, "wireKeys": keys, "wireSignature": "sig",
                   "shardRows": sizes, "numRows": sum(sizes)}, f)
    sh = Shards.open(str(d))
    want = {k: np.concatenate([p[k] for p in sh.iter_shards()])
            for k in keys}
    got = sh.load_all()
    _assert_same(got, want)
    _assert_same(got, {k: np.concatenate([p[k] for p in parts])
                       for k in keys})
    # the plane owns its bytes: nothing of it is a window on the files
    assert not any(isinstance(a, np.memmap) or isinstance(a.base, np.memmap)
                   for a in got.values())
    # the refresh cursor's tail view loads the tail
    tail = sh.from_row(60).load_all()
    _assert_same(tail, {k: v[50:] for k, v in want.items()})


# ------------------------------------------------------ the CRC still guards
@pytest.mark.parametrize("threshold", [0.0, 0.5])                  # (f)
def test_flipped_byte_in_a_stored_member_is_refused(tmp_path, threshold):
    sizes = [512, 512, 512, 512]
    d = _shard_set(tmp_path, sizes)
    want = _reference(d, skip={1})
    bad = os.path.join(d, "part-00001.npz")
    at = _member_data_offset(bad, "x") + 6000
    with open(bad, "r+b") as f:
        f.seek(at)
        byte = f.read(1)
        f.seek(at)
        f.write(bytes([byte[0] ^ 0x10]))
    # the flip is inside the array's bytes, so the directory, the headers
    # and every length still agree: only the CRC can tell
    assert shards_mod._plan_npz(bad).rows == 512
    if threshold:
        environment.set_property("shifu.data.badThreshold", str(threshold))
        _assert_same(Shards.open(d).load_all(), want)
        assert obs.get_registry().counter(
            "data.quarantined_shards").value == 1
    else:
        with pytest.raises(zipfile.BadZipFile, match="CRC"):
            Shards.open(d).load_all()


# ------------------------------------------------------------- quarantine
@pytest.mark.parametrize("torn", [2, 4], ids=["middle", "last"])   # (g)
@pytest.mark.parametrize("schema_rows", [True, False])
def test_torn_shard_is_quarantined_like_before(tmp_path, torn, schema_rows):
    d = _shard_set(tmp_path, [24, 24, 24, 24, 11], schema_rows=schema_rows)
    want = _reference(d, skip={torn})
    bad = os.path.join(d, f"part-{torn:05d}.npz")
    with open(bad, "r+b") as f:
        f.truncate(os.path.getsize(bad) // 2)
    with pytest.raises(zipfile.BadZipFile):
        Shards.open(d).load_all()
    environment.set_property("shifu.data.badThreshold", "0.5")
    _assert_same(Shards.open(d).load_all(), want)
    assert obs.get_registry().counter("data.quarantined_shards").value == 1
    # and the streaming reader skips the same shard
    parts = list(Shards.open(d).iter_shards())
    _assert_same({k: np.concatenate([p[k] for p in parts])
                  for k in parts[0]}, want)


def test_quarantine_over_the_threshold_is_a_coded_error(tmp_path):
    from shifu_tpu.config.errors import ShifuError
    d = _shard_set(tmp_path, [8, 8, 8, 8])
    for i in (0, 3):
        bad = os.path.join(d, f"part-{i:05d}.npz")
        with open(bad, "r+b") as f:
            f.truncate(os.path.getsize(bad) // 2)
    environment.set_property("shifu.data.badThreshold", "0.25")
    with pytest.raises(ShifuError, match="badThreshold"):
        Shards.open(d).load_all()


# ------------------------------------------- fault hook and the retry ladder
def test_fault_hook_sees_every_shard_once_and_oserror_is_retried(  # (h)
        tmp_path, monkeypatch):
    sizes = [10] * 11
    d = _shard_set(tmp_path, sizes)
    want = _reference(d)
    seen, lock = [], threading.Lock()

    def fire(site, point, value, path=None):
        with lock:
            seen.append((site, point, value, os.path.basename(path)))
    monkeypatch.setattr(faults, "fire", fire)
    _assert_same(Shards.open(d).load_all(), want)
    assert sorted(seen) == [("shards", "shard", i, f"part-{i:05d}.npz")
                            for i in range(len(sizes))]

    monkeypatch.undo()
    environment.set_property("shifu.io.retryBaseMs", "1")
    environment.set_property("shifu.faults", "shards:shard=6:ioerror")
    faults.reset_for_tests()
    _assert_same(Shards.open(d).load_all(), want)
    assert obs.get_registry().counter("ingest.retries").value == 1


# ----------------------------------------------------- a set that disagrees
@pytest.mark.parametrize("threshold", ["0", "0.5"])                # (i)
def test_shard_of_another_width_names_the_file(tmp_path, threshold):
    d = _shard_set(tmp_path, [12, 12, 12])
    rng = np.random.default_rng(1)
    part = _part(rng, 12, "x")
    part["x"] = rng.standard_normal((12, 6)).astype(np.float32)
    np.savez(os.path.join(d, "part-00001.npz"), **part)
    environment.set_property("shifu.data.badThreshold", threshold)
    with pytest.raises(ValueError, match="part-00001.npz"):
        Shards.open(d).load_all()


def test_fill_threads_write_disjoint_slices(tmp_path):
    """More fills in flight than cores, a short switch interval: every
    shard's rows land in its own slice and nowhere else."""
    import sys
    sizes = [257] * 40
    d = _shard_set(tmp_path, sizes)
    want = _reference(d)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for _ in range(5):
            _assert_same(Shards.open(d).load_all(), want)
    finally:
        sys.setswitchinterval(old)
