"""Out-of-core ingest overhaul: mmap spill cache, pipelined prepared
windows, compact uint8 wire format, prefetch knobs, ingest telemetry."""

import json
import os

import numpy as np
import pytest


def _write_shards(d, n, c=6, n_bins=8, shard_rows=300, seed=3):
    from shifu_tpu.data.shards import Shards
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, n_bins - 1, size=(n, c)).astype(np.int16)
    y = (rng.random(n) < 0.4).astype(np.float32)
    w = np.ones(n, np.float32)
    os.makedirs(d, exist_ok=True)
    shard = 0
    for s in range(0, n, shard_rows):
        e = min(s + shard_rows, n)
        np.savez(os.path.join(d, f"part-{shard:05d}.npz"),
                 bins=bins[s:e], y=y[s:e], w=w[s:e])
        shard += 1
    with open(os.path.join(d, "schema.json"), "w") as f:
        json.dump({"columnNums": list(range(c)), "numShards": shard,
                   "numRows": n}, f)
    return Shards.open(d), bins, y, w


def _collect(stream, **kw):
    return [(win.start, win.n_valid, win.src,
             {k: np.asarray(a).copy() for k, a in win.arrays.items()})
            for win in stream.windows(**kw)]


def test_spill_second_epoch_identical_and_mmap_backed(tmp_path):
    """Epoch 2 must serve from the committed spill (manifest on disk) and
    reproduce epoch 1's windows exactly — values, srcs, row ids."""
    from shifu_tpu.data.streaming import ShardStream
    shards, bins, y, w = _write_shards(str(tmp_path / "s"), 1000)
    stream = ShardStream(shards, ("bins", "y", "w"), window_rows=96)
    cold = _collect(stream)
    man = os.path.join(str(tmp_path / "s"), ".spill_cache",
                       "spill-bins-y-w", "manifest.json")
    assert os.path.isfile(man)
    with open(man) as f:
        m = json.load(f)
    assert m["rows"] == 1000
    # integer bins narrowed to the compact wire dtype in the spill
    assert np.dtype(m["dtypes"]["bins"]) == np.uint8
    warm = _collect(stream)
    assert len(cold) == len(warm)
    for (s1, v1, src1, a1), (s2, v2, src2, a2) in zip(cold, warm):
        assert (s1, v1, src1) == (s2, v2, src2)
        for k in a1:
            np.testing.assert_array_equal(a1[k], a2[k])
    assert warm[0][3]["bins"].dtype == np.uint8       # zero-cast wire


def test_spill_midshard_resume_equivalence(tmp_path):
    """windows(start_shard, shard_offset, start_row) must be identical
    from the spill fast path and the cold npz path — the ResidentCache
    tail must not care which layout serves it."""
    from shifu_tpu.data.streaming import ShardStream
    d = str(tmp_path / "s")
    shards, *_ = _write_shards(d, 1100, shard_rows=250)
    spilled = ShardStream(shards, ("bins", "y", "w"), window_rows=128)
    list(spilled.windows())                           # build the spill
    cold = ShardStream(shards, ("bins", "y", "w"), window_rows=128,
                       spill=False)
    for kw in ({"start_shard": 2, "shard_offset": 37, "start_row": 537},
               {"start_shard": 1, "shard_offset": 0, "start_row": 250},
               {"start_shard": 4, "shard_offset": 99, "start_row": 1099}):
        a = _collect(spilled, **kw)
        b = _collect(cold, **kw)
        assert len(a) == len(b) and len(a) > 0 or kw["start_row"] == 1099
        for (s1, v1, src1, w1), (s2, v2, src2, w2) in zip(a, b):
            assert (s1, v1, src1) == (s2, v2, src2)
            for k in w1:
                np.testing.assert_array_equal(w1[k], w2[k])


def test_spill_stale_source_invalidates(tmp_path):
    """Rewriting a shard (re-norm) must invalidate the spill: the next
    epoch re-reads npz and rebuilds rather than serving stale bytes."""
    from shifu_tpu.data.shards import Shards
    from shifu_tpu.data.streaming import ShardStream
    d = str(tmp_path / "s")
    shards, *_ = _write_shards(d, 500, shard_rows=250)
    list(ShardStream(shards, ("y",), window_rows=100).windows())
    # rewrite shard 1 with different values (and size/mtime)
    part = dict(np.load(os.path.join(d, "part-00001.npz")))
    part["y"] = part["y"] + 7.0
    np.savez(os.path.join(d, "part-00001.npz"), **part)
    stream2 = ShardStream(Shards.open(d), ("y",), window_rows=100)
    got = np.concatenate([w.arrays["y"][:w.n_valid]
                          for w in stream2.windows()])
    assert (got[250:] >= 7.0).all()                   # fresh bytes, not stale


def test_spill_budget_abort_streams_npz_and_marks(tmp_path):
    """A stream larger than the spill budget must abort the write once
    (marker manifest), keep emitting correct windows, and not retry."""
    from shifu_tpu.config import environment
    from shifu_tpu.data.streaming import ShardStream
    d = str(tmp_path / "s")
    shards, bins, y, w = _write_shards(d, 800, shard_rows=200)
    environment.set_property("shifu.stream.spillBudgetBytes", "1024")
    try:
        stream = ShardStream(shards, ("bins", "y", "w"), window_rows=128)
        a = _collect(stream)
        man = os.path.join(d, ".spill_cache", "spill-bins-y-w",
                           "manifest.json")
        with open(man) as f:
            assert "budget" in json.load(f)["aborted"]
        b = _collect(stream)                          # still correct, npz
        for (s1, v1, src1, w1), (s2, v2, src2, w2) in zip(a, b):
            assert (s1, v1, src1) == (s2, v2, src2)
            for k in w1:
                np.testing.assert_array_equal(w1[k], w2[k])
        got = np.concatenate([t[3]["bins"][:t[1]] for t in b])
        np.testing.assert_array_equal(got, bins)
    finally:
        environment.set_property("shifu.stream.spillBudgetBytes", "")


def test_num_rows_without_decoding(tmp_path):
    """Shards.num_rows reads schema shardRows / the sidecar manifest /
    npy headers — never a full npz decode; the sidecar persists."""
    from shifu_tpu.data.shards import ROWS_SIDECAR, Shards
    d = str(tmp_path / "s")
    shards, *_ = _write_shards(d, 1100, shard_rows=250)
    assert shards.num_rows == 1100
    assert shards.shard_rows == [250, 250, 250, 250, 100]
    assert os.path.isfile(os.path.join(d, ROWS_SIDECAR))
    # a fresh handle hits the sidecar (counts survive the process)
    assert Shards.open(d).num_rows == 1100
    # schema shardRows wins when present (norm writes it)
    sch = dict(shards.schema)
    sch["shardRows"] = [250, 250, 250, 250, 100]
    with open(os.path.join(d, "schema.json"), "w") as f:
        json.dump(sch, f)
    s2 = Shards.open(d)
    os.remove(os.path.join(d, ROWS_SIDECAR))
    assert s2.num_rows == 1100
    assert not os.path.isfile(os.path.join(d, ROWS_SIDECAR))  # no scan ran


def test_prefetch_depth_knobs(monkeypatch):
    from shifu_tpu.config import environment
    from shifu_tpu.data.streaming import stream_prefetch_depth
    assert stream_prefetch_depth() == 2                # default
    assert stream_prefetch_depth(5) == 5               # explicit override
    environment.set_property("shifu.stream.prefetch", "7")
    try:
        assert stream_prefetch_depth() == 7
        monkeypatch.setenv("SHIFU_TPU_PREFETCH", "3")  # env beats property
        assert stream_prefetch_depth() == 3
    finally:
        environment.set_property("shifu.stream.prefetch", "")


def test_prepared_pipelined_matches_inline(tmp_path):
    """prepared() with a background thread (depth>0) must yield the same
    sequence as inline prep, and carry src for tail bookkeeping."""
    from shifu_tpu.data.streaming import PreparedWindow, ShardStream
    shards, *_ = _write_shards(str(tmp_path / "s"), 900, shard_rows=200)

    def prep(win):
        return PreparedWindow(win.start, win.n_valid, win.rows, win.index,
                              {k: np.asarray(a, np.float64).sum()
                               for k, a in win.arrays.items()})

    stream = ShardStream(shards, ("bins", "y", "w"), window_rows=128)
    inline = list(stream.prepared(prep, depth=0))
    piped = list(stream.prepared(prep, depth=3))
    assert len(inline) == len(piped) > 0
    for a, b in zip(inline, piped):
        assert (a.start, a.n_valid, a.src) == (b.start, b.n_valid, b.src)
        assert a.src is not None
        assert a.arrays == b.arrays


def test_resident_cache_disk_passes_guard(tmp_path):
    """Regression guard: under budget the whole forest costs ONE disk
    pass; a forced tail costs exactly 1 + sweeps."""
    from shifu_tpu.data.streaming import PreparedWindow, ResidentCache, \
        ShardStream
    shards, *_ = _write_shards(str(tmp_path / "s"), 1024, shard_rows=256)

    def prep(win):
        return PreparedWindow(win.start, win.n_valid, win.rows, win.index,
                              {k: np.asarray(a) for k, a in
                               win.arrays.items()})

    stream = ShardStream(shards, ("bins", "y", "w"), window_rows=256)
    cache = ResidentCache(stream, 1 << 30, prep)
    for _ in range(4):                       # warm + 3 re-sweeps
        n = sum(1 for _ in cache.items())
        assert n == 4
    assert cache.disk_passes == 1
    assert cache.tail is None and cache.resident_rows == 1024

    tail_cache = ResidentCache(stream, 2 * 256 * (6 + 8) + 64, prep)
    for _ in range(4):
        assert sum(1 for _ in tail_cache.items()) == 4
    assert tail_cache.tail is not None
    assert tail_cache.disk_passes == 4       # warm + one per re-sweep


def test_streamed_gbt_trainer_one_disk_pass_and_spill(tmp_path):
    """Trainer-level guard under the new layout: fully-resident streamed
    GBT stays at disk_passes == 1 per forest AND leaves a committed
    spill behind for the next forest."""
    from shifu_tpu.data.streaming import ShardStream
    from shifu_tpu.train.dt_trainer import DTSettings, train_gbt_streamed
    d = str(tmp_path / "s")
    shards, bins, y, w = _write_shards(d, 1024, shard_rows=256)
    stream = ShardStream(shards, ("bins", "y", "w"), window_rows=256)
    res = train_gbt_streamed(stream, 8, None,
                             DTSettings(n_trees=4, depth=3, loss="log",
                                        seed=0), cache_budget=1 << 30)
    assert res.trees_built == 4
    assert res.disk_passes == 1
    assert os.path.isfile(os.path.join(d, ".spill_cache", "spill-bins-y-w",
                                       "manifest.json"))


def test_put_bins_uint8_wire_roundtrip():
    from shifu_tpu.train.dt_trainer import _put_bins, _wire_bins_dtype
    assert _wire_bins_dtype(256) == np.uint8
    assert _wire_bins_dtype(257) == np.uint16
    bins = np.array([[0, 5], [250, 3]], np.int32)
    d = _put_bins(None, bins, 256)
    assert d.dtype == np.uint8                 # narrow all the way into HBM
    np.testing.assert_array_equal(np.asarray(d), bins)
    d8 = _put_bins(None, bins.astype(np.uint8), 256)   # zero-cast path
    assert d8.dtype == np.uint8
    with pytest.raises(ValueError):
        _put_bins(None, np.array([[300]], np.int32), 256)


def test_uint8_bins_build_identical_trees(tmp_path):
    """Bins shipped/resident as uint8 must grow bit-identical trees to an
    int32 run (the widen happens in-graph)."""
    from shifu_tpu.train.dt_trainer import DTSettings, train_gbt
    rng = np.random.default_rng(0)
    bins = rng.integers(0, 7, size=(600, 5)).astype(np.int32)
    y = (rng.random(600) < 0.4).astype(np.float32)
    w = np.ones(600, np.float32)
    s = DTSettings(n_trees=3, depth=3, loss="log", seed=1)
    a = train_gbt(bins, y, w, 8, None, s)
    b = train_gbt(bins.astype(np.uint8), y, w, 8, None, s)
    for ta, tb in zip(a.trees, b.trees):
        np.testing.assert_array_equal(ta.split_feat, tb.split_feat)
        np.testing.assert_array_equal(ta.left_mask, tb.left_mask)
        np.testing.assert_allclose(ta.leaf_value, tb.leaf_value,
                                   rtol=1e-6, atol=1e-7)


def test_ingest_telemetry_counters(tmp_path):
    """With telemetry on, the ingest plane reports bytes/windows/stall and
    ResidentCache disk passes through the obs registry."""
    from shifu_tpu import obs
    from shifu_tpu.data.streaming import ShardStream
    from shifu_tpu.train.dt_trainer import DTSettings, train_gbt_streamed
    shards, *_ = _write_shards(str(tmp_path / "s"), 512, shard_rows=256)
    obs.reset_for_tests()
    obs.set_enabled(True)
    try:
        stream = ShardStream(shards, ("bins", "y", "w"), window_rows=256)
        train_gbt_streamed(stream, 8, None,
                           DTSettings(n_trees=2, depth=2, loss="log"),
                           cache_budget=1 << 30)
        names = {m["name"]: m for m in obs.snapshot()}
        assert names["ingest.bytes_read"]["value"] > 0
        assert names["ingest.windows_emitted"]["value"] >= 2
        assert names["ingest.disk_passes"]["value"] == 1
        assert "ingest.h2d_wait_seconds" in names
    finally:
        obs.reset_for_tests()


def test_report_renders_ingest_stall_fraction(tmp_path):
    from shifu_tpu import obs
    from shifu_tpu.obs.report import render_telemetry
    obs.reset_for_tests()
    obs.set_enabled(True)
    try:
        with obs.span("train", kind="step"):
            obs.counter("ingest.h2d_wait_seconds").inc(0.25)
        obs.flush(os.path.join(str(tmp_path), "telemetry", "trace.jsonl"),
                  step="train")
        text = render_telemetry(str(tmp_path))
        assert "ingest stall fraction" in text
    finally:
        obs.reset_for_tests()


def test_tail_super_batch_disk_pass_telemetry_guard(tmp_path, monkeypatch):
    """Round-9 regression guard, telemetry-backed: under the super-batch
    tail schedule, passes per tree must stay within the acceptance bound
    (RF: ceil(depth/SB)+1; GBT exact: depth+2) — any future change that
    silently reintroduces per-(depth x tree) re-streams fails here."""
    import math

    from shifu_tpu import obs
    from shifu_tpu.data.streaming import ShardStream
    from shifu_tpu.train.dt_trainer import (DTSettings, train_gbt_streamed,
                                            train_rf_streamed)
    shards, *_ = _write_shards(str(tmp_path / "s"), 1024, shard_rows=300)
    budget = 2 * 256 * (6 * 1 + 3 * 4) + 64     # ~2 of 4 windows resident
    n_trees, depth = 6, 3
    obs.reset_for_tests()
    obs.set_enabled(True)
    try:
        res = train_rf_streamed(
            ShardStream(shards, ("bins", "y", "w"), window_rows=256),
            8, None,
            DTSettings(n_trees=n_trees, depth=depth, impurity="entropy",
                       loss="squared", seed=2),
            cache_budget=budget)
        names = {m["name"]: m for m in obs.snapshot(reset=True)}
        sweeps = names["train.tail_sweeps"]["value"]
        assert sweeps == res.tail_sweeps > 0
        assert sweeps / n_trees <= math.ceil(depth / n_trees) + 1
        assert names["ingest.disk_passes"]["value"] == 1 + sweeps

        monkeypatch.setenv("SHIFU_TREE_TAIL_C2F", "0")
        res_g = train_gbt_streamed(
            ShardStream(shards, ("bins", "y", "w"), window_rows=256),
            8, None, DTSettings(n_trees=2, depth=depth, loss="log"),
            cache_budget=budget)
        names = {m["name"]: m for m in obs.snapshot()}
        assert names["train.tail_sweeps"]["value"] == res_g.tail_sweeps \
            == 2 * (depth + 2)
    finally:
        obs.reset_for_tests()


def test_report_renders_tail_sweep_line(tmp_path):
    """The v4 tail-plane line: sweep count, disk passes and speculation
    repairs surface in `analysis --telemetry`."""
    from shifu_tpu import obs
    from shifu_tpu.obs.report import render_telemetry
    obs.reset_for_tests()
    obs.set_enabled(True)
    try:
        with obs.span("train", kind="step"):
            obs.counter("train.tail_sweeps").inc(12)
            obs.counter("ingest.disk_passes").inc(13)
            obs.counter("train.tail_repairs").inc(2)
            obs.counter("train.tail_repair_levels").inc(5)
            obs.counter("ingest.h2d_wait_seconds").inc(0.1)
        obs.flush(os.path.join(str(tmp_path), "telemetry", "trace.jsonl"),
                  step="train")
        text = render_telemetry(str(tmp_path))
        assert "tail sweeps: 12" in text
        assert "13 disk passes" in text
        assert "2 speculation repairs over 5 levels" in text
        assert "ingest stall fraction" in text
    finally:
        obs.reset_for_tests()
