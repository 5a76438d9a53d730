"""The program's spans on the profiler's clock: a live span is also a
``shifu:`` annotation in a ``jax.profiler`` session's ``.xplane.pb``, the
off path builds nothing, and the in-RAM NN train job is spanned from the
shard load to the epoch's fetch."""

import glob
import json
import os

import pytest

import jax
import jax.numpy as jnp

from shifu_tpu import obs
from shifu_tpu.obs import manifest, tracer

pytestmark = pytest.mark.obs

SETUP_SPANS = ("setup.config", "setup.probe", "setup.columns",
               "setup.journal", "setup.precheck")
TRAIN_JOB_SPANS = (
    "data.load", "data.alloc", "data.read", "data.put", "train.split",
    "nn.init", "nn.h2d", "nn.epoch", "nn.epoch.dispatch",
    "nn.epoch.fetch", "nn.epoch.best_copy", "nn.epoch.progress",
    "nn.epoch.checkpoint", "xla.build") + SETUP_SPANS + (
    "tower.save.clear", "tower.save.fetch", "tower.save.write",
    "tower.save.commit")
# went with the code they timed: the per-shard decode and the concatenate
# (PR 26), the plane's trip down and second upload (PR 28)
RETIRED_SPANS = ("data.shard_decode", "data.concat", "nn.repad")


@pytest.fixture
def telemetry():
    obs.reset_for_tests()
    obs.set_enabled(True)
    yield obs
    obs.reset_for_tests()


@pytest.fixture
def telemetry_off():
    obs.reset_for_tests()
    obs.set_enabled(False)
    yield obs
    obs.reset_for_tests()


def _profiled(out_dir, body):
    """Run ``body`` inside a profiler session (python tracer off, as the
    benchmark sets it); the program's annotations of the written
    ``.xplane.pb`` as ``{name: [(start_ns, end_ns, stats)]}`` plus the
    planes they were found on."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(out_dir), "**", "*.xplane.pb"),
                        recursive=True)
    found, planes = {}, set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracer.ANNOTATION_PREFIX):
                    planes.add(plane.name)
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return found, planes


# ------------------------------------------------------------ (a) one clock
def test_span_lies_in_the_profilers_trace(telemetry, tmp_path):
    ids = {}

    def body():
        with obs.span("TRAIN", kind="step") as root:
            with obs.span("data.load") as sp:
                jnp.ones(8).block_until_ready()
                sp.set(bytes=12345678901, source="npz")
            ids.update(root=root.id, child=sp.id)

    found, planes = _profiled(tmp_path, body)
    assert planes == {"/host:CPU"}
    (root,) = found["shifu:TRAIN"]
    (child,) = found["shifu:data.load"]
    assert root[2] == {"id": ids["root"]}          # no parent, no strings
    assert child[2] == {"id": ids["child"], "parent": ids["root"],
                        "bytes": 12345678901}
    assert root[0] <= child[0] <= child[1] <= root[1]
    # the JSONL record is unchanged by the annotation
    recs = {r["name"]: r for r in obs.pending_records()
            if r["kind"] == "span"}
    assert recs["data.load"]["parent"] == recs["TRAIN"]["id"] == ids["root"]
    assert recs["data.load"]["attrs"] == {"bytes": 12345678901,
                                          "source": "npz"}


def test_xla_build_is_a_marker_that_carries_its_seconds(telemetry,
                                                        tmp_path):
    obs.ensure_compile_listener()

    def program(a):
        for _ in range(300):            # a trace well over the floor
            a = a * 1.01 + 1.0
        return a

    ones = jnp.ones(7)                  # its own build stays outside

    def body():
        with obs.span("nn.epoch.dispatch"):
            jax.jit(program)(ones).block_until_ready()

    found, _ = _profiled(tmp_path, body)
    (parent,) = found["shifu:nn.epoch.dispatch"]
    # an inner trace (jnp's own wrappers) that a loaded machine stretches
    # over the floor is a build of its own: only ``program``'s are judged
    builds = [b for b in found["shifu:xla.build"]
              if b[2]["program"] == "program"]
    assert sorted(b[2]["stage"] for b in builds) == [
        "compile", "lower", "trace"]
    for start, end, stats in builds:
        assert stats["parent"] == parent[2]["id"]
        assert stats["secs"] > 0
        # the rebuilt interval [end - secs, end] lies inside its parent
        assert parent[0] <= end - stats["secs"] * 1e9 and end <= parent[1]
    recs = [r for r in obs.pending_records() if r["name"] == "xla.build"
            and r["attrs"]["program"] == "program"]
    assert sorted(r["id"] for r in recs) == \
        sorted(b[2]["id"] for b in builds)
    assert all(r["dur_s"] > 0 and r["parent"] == parent[2]["id"]
               for r in recs)


# ------------------------------------------------------------ (b) off path
def test_telemetry_off_writes_no_annotation(telemetry_off, tmp_path):
    obs.ensure_compile_listener()

    def body():
        assert obs.span("TRAIN") is obs.span("data.load")   # the null span
        with obs.span("TRAIN") as sp:
            sp.set(bytes=1)
            jax.jit(lambda a: a - 2.0)(jnp.ones(5)).block_until_ready()

    found, _ = _profiled(tmp_path, body)
    assert found == {}
    assert obs.pending_records() == []


# ------------------------------------------------------ (c) the train job
def _span_tree(mdir):
    with open(os.path.join(mdir, "telemetry", "trace.jsonl")) as f:
        spans = [r for r in map(json.loads, f) if r["kind"] == "span"]
    by_id = {s["id"]: s for s in spans}

    def path(s):
        out = [s["name"]]
        while s["parent"] is not None:
            s = by_id[s["parent"]]
            out.append(s["name"])
        return " < ".join(out)
    return spans, path


def test_train_job_is_spanned_from_shard_to_epoch(telemetry, prepared_set):
    from shifu_tpu.cli import main
    from shifu_tpu.config import ModelConfig
    from shifu_tpu.data.shards import Shards

    epochs, batch = 3, 512
    mc_path = os.path.join(prepared_set, "ModelConfig.json")
    mc = ModelConfig.load(mc_path)
    mc.train.algorithm = "NN"
    mc.train.numTrainEpochs = epochs
    mc.train.params = {"NumHiddenNodes": [8], "ActivationFunc": ["relu"],
                       "Propagation": "ADAM", "LearningRate": 0.01,
                       "MiniBatchs": batch}
    mc.save(mc_path)
    obs.set_enabled(None)               # the flag is what turns it on
    assert main(["-Dshifu.train.streaming=off", "--dir", prepared_set,
                 "train", "--telemetry"]) == 0

    spans, path = _span_tree(prepared_set)
    paths = [path(s) for s in spans]
    under_train = " < train < process < TRAIN"
    # what the job pays before its step body, by name: setup's five
    # children, one each, in order, and nothing of setup outside them
    (setup,) = [s for s in spans if s["name"] == "setup"]
    kids = sorted((s for s in spans if s["parent"] == setup["id"]),
                  key=lambda s: s["ts"])
    assert tuple(s["name"] for s in kids) == SETUP_SPANS
    assert all(path(s) == s["name"] + " < setup < TRAIN" for s in kids)
    assert sum(s["dur_s"] for s in kids) <= setup["dur_s"]
    by_name = {s["name"]: s["attrs"] for s in kids}
    cc_path = os.path.join(prepared_set, "ColumnConfig.json")
    columns = by_name["setup.columns"]
    assert isinstance(columns.pop("plans_built"), int)
    with open(cc_path) as f:
        assert columns == {
            "columns": len(json.load(f)), "bytes": os.path.getsize(cc_path)}
    with open(os.path.join(prepared_set, "tmp", "journal",
                           "NORMALIZE.json")) as f:
        assert by_name["setup.precheck"] == {
            "shards": len(json.load(f)["items"])} and \
            by_name["setup.precheck"]["shards"] > 0
    shards = Shards.open(os.path.join(prepared_set, "tmp", "NormalizedData"))
    (load,) = [s for s in spans if s["name"] == "data.load"]
    (alloc,) = [s for s in spans if s["name"] == "data.alloc"]
    (read,) = [s for s in spans if s["name"] == "data.read"]
    assert path(load) == "data.load < load_data < process < TRAIN"
    assert alloc["parent"] == read["parent"] == load["id"]
    assert alloc["ts"] <= read["ts"]
    plane = shards.load_all()
    assert load["attrs"]["shards"] == load["attrs"]["direct"] == \
        shards.n_shards
    assert 1 <= load["attrs"]["threads"] <= shards.n_shards
    rows = len(plane["y"])
    pad = -rows % batch
    assert pad > 0                      # this set's row count is ragged
    # x went file -> staging piece -> device, its zero rows with it; the
    # fill threads' waits for the device hang under the read
    assert read["attrs"]["bytes"] == sum(a.nbytes for a in plane.values())
    assert load["attrs"]["bytes"] == read["attrs"]["bytes"] + \
        pad * plane["x"][0].nbytes
    assert load["attrs"]["staged_bytes"] == plane["x"].nbytes
    assert 0 < load["attrs"]["staging_bytes"]
    assert load["attrs"]["pieces"] >= shards.n_shards
    puts = [s for s in spans if s["name"] == "data.put"]
    assert puts and all(s["parent"] == read["id"] for s in puts)

    for name in ("train.split", "nn.init", "nn.h2d"):
        assert paths.count(name + (" < process < TRAIN"
                                   if name == "train.split"
                                   else under_train)) == 1, name
    assert not {s["name"] for s in spans} & set(RETIRED_SPANS)
    # x is on the device when the trainer gets it: nn.h2d sends y and one
    # member's train and validation weights, all f32, padded on the host
    (h2d,) = [s for s in spans if s["name"] == "nn.h2d"]
    assert h2d["attrs"]["pad_rows"] == pad
    assert h2d["attrs"]["bytes"] == 4 * (rows + pad) * 3

    ep = [s for s in spans if s["name"] == "nn.epoch"]
    assert [s["attrs"]["epoch"] for s in ep] == list(range(epochs))
    assert all(path(s) == "nn.epoch" + under_train for s in ep)
    for child in ("nn.epoch.dispatch", "nn.epoch.fetch",
                  "nn.epoch.progress"):
        got = [s for s in spans if s["name"] == child]
        assert [s["parent"] for s in got] == [s["id"] for s in ep], child
    # epoch 0 improves on +inf: its best-params copy is spanned
    assert any(s["name"] == "nn.epoch.best_copy" and s["parent"] == ep[0]["id"]
               for s in spans)
    # the step and validation programs are built inside epoch 0's dispatch
    d0 = next(s for s in spans if s["name"] == "nn.epoch.dispatch")
    built = {s["attrs"]["program"] for s in spans
             if s["name"] == "xla.build" and s["parent"] == d0["id"]}
    assert {"epoch_steps", "eval_errors"} <= built

    # a second job in the process converts the columns on the plans the
    # first one left: it builds none
    assert main(["-Dshifu.train.streaming=off", "--dir", prepared_set,
                 "train", "--telemetry"]) == 0
    spans, _ = _span_tree(prepared_set)
    last = [s for s in spans if s["name"] == "setup.columns"][-1]
    assert last["attrs"]["plans_built"] == 0


# ------------------------------------------------------- (d) the manifest
@pytest.mark.parametrize("name", TRAIN_JOB_SPANS)
def test_new_span_is_declared(name):
    assert manifest.is_declared_span(name), name
    assert name in manifest.SPANS and manifest.SPANS[name].strip()


@pytest.mark.parametrize("name", RETIRED_SPANS)
def test_retired_span_is_not_declared(name):
    assert not manifest.is_declared_span(name), name
