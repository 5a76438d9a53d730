"""A cell, a configuration, a traffic mix and a layer metric dropped in as new
files are found by name: no file that exists is edited."""

import json
import os
import shutil

import pytest

from benchmark import readers, run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def copy(tmp_path, monkeypatch):
    for d in ("configs", "workloads", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(HERE, d), tmp_path / d)
    (tmp_path / "readers").mkdir()
    monkeypatch.setattr(run, "HERE", str(tmp_path))
    monkeypatch.setattr(readers, "__path__", list(readers.__path__) + [str(tmp_path / "readers")])
    return tmp_path


def test_new_files_are_found(copy):
    cfg = json.loads((copy / "configs" / "nn-fraud.json").read_text())
    cfg["name"] = "wdl-criteo"
    (copy / "configs" / "wdl-criteo.json").write_text(json.dumps(cfg))
    (copy / "traffic" / "retrain-tail.json").write_text(json.dumps(
        {"name": "retrain-tail", "kind": "closed_loop_jobs", "rows": 4194304,
         "iterations_per_job": 2}))
    (copy / "workloads" / "wdl-train-tail.json").write_text(json.dumps(
        {"name": "wdl-train-tail", "config": "wdl-criteo", "traffic": "retrain-tail",
         "chips": 1, "driver": "train_nn", "rehearse": {"traffic": {"rows": 64}}}))
    (copy / "layer_metrics" / "tail_sweeps.json").write_text(json.dumps(
        {"name": "tail_sweeps", "layer": "tree training", "unit": "count/job", "better": "lower",
         "source": "program_counter", "moves": "train_rate", "workloads": ["wdl-train-tail"],
         "reader": "doubled", "args": {"name": "train.tail_sweeps"}}))
    (copy / "readers" / "doubled.py").write_text(
        "def read(summary, ctx, name):\n    v = ctx.counters.get(name)\n"
        "    return None if v is None else 2 * v\n")

    cell = run.load_cell("wdl-train-tail")
    assert cell["config_doc"]["name"] == "wdl-criteo" and cell["traffic_doc"]["rows"] == 4194304
    assert run.load_cell("wdl-train-tail", rehearse=True)["traffic_doc"]["rows"] == 64
    docs = run.layer_metrics_for("wdl-train-tail")
    assert [d["name"] for d in docs] == ["tail_sweeps"]
    ctx = run.Ctx(cell, seed=1, seconds=1.0, trace=True, rehearse=True)
    assert readers.read_metric(docs[0], None, ctx) is None          # nothing to read: left out
    ctx.counters["train.tail_sweeps"] = 9
    assert readers.read_metric(docs[0], None, ctx) == 18.0
    # the cells that were there are untouched by the additions
    assert "tail_sweeps" not in [d["name"] for d in run.layer_metrics_for("gbt-train")]


def test_every_committed_cell_loads():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"]
        assert os.path.isfile(os.path.join(HERE, "drivers", cell["driver"] + ".py"))
        assert run.layer_metrics_for(w["name"])
