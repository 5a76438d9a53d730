"""The metrics that read what PR 35 named — ``setup``, ``tower.save``'s
children, the step's new scopes — are data for readers the benchmark had:
each file loads, names a reader that exists, lists cells of ``BENCHMARK.json``,
has its ``per_layer`` entry there, and reads the number laid out by hand in
synthetic spans (``test_spans.py``'s) and a synthetic step (``test_tower_cell.py``'s)."""

import importlib
import json
import os

import pytest

from benchmark import run
from benchmark.readers import read_metric
from benchmark.trace import Summary
from test_spans import Ctx as SpanCtx
from test_spans import span

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOWER_CELLS = ["sdar-train", "nemotron-train", "trinity-train"]
NEW = {                                 # metric -> (its cells, what the synthetic job reads)
    "job_setup_s": (["nn-train"] + TOWER_CELLS, 0.050),
    "save_fetch_s": (TOWER_CELLS, 0.030),
    "save_write_s": (TOWER_CELLS, 0.044),
    "step_scoped_share": (TOWER_CELLS, 75.0),
    "embed_time_share": (TOWER_CELLS, 10.0),
    "trunk_rest_time_share": (TOWER_CELLS, 5.0),
}
# what each driver hands the step's readers as ``params``: ``share`` needs ``cfg`` alone
DRIVER_PARAMS = [{"cfg": {}, "rows": 16}, {"tower": "nemotron_h", "cfg": {}}, {"tower": "afmoe", "cfg": {}}]


def _job_spans():
    """One job of 1000 ms: ``setup`` 0-50 with its five children, ``tower.save``
    900-980 with its four."""
    return [span("TRAIN", 0, 1000, 1), span("setup", 0, 50, 2, 1),
            span("setup.config", 0, 2, 3, 2), span("setup.probe", 2, 3, 4, 2),
            span("setup.columns", 3, 47, 5, 2, columns=432, bytes=2600000),
            span("setup.journal", 47, 48, 6, 2), span("setup.precheck", 48, 50, 7, 2, shards=12),
            span("process", 50, 990, 8, 1), span("save_models", 900, 980, 9, 8),
            span("tower.save", 900, 980, 10, 9, bytes=44),
            span("tower.save.clear", 900, 905, 11, 10, bytes=44),
            span("tower.save.fetch", 905, 935, 12, 10, bytes=40),
            span("tower.save.write", 935, 979, 13, 10, bytes=44),
            span("tower.save.commit", 979, 980, 14, 10)]


def _step_summary():
    """Two steps of 1000 ns: attention 400, a ``ragged-dot`` kernel 200, the
    embedding 100, the trunk's rest 50, a copy no scope names 100, gaps 150."""
    dev = "/device:TPU:0"
    ev = [(dev, "XLA Modules", "jit_tower_step(1)", t0, 1000.0) for t0 in (0.0, 2000.0)]
    for t0 in (0.0, 2000.0):
        ev += [(dev, "XLA Ops", "%while.1 = (s32[]) while(...)", t0, 1000.0),
               (dev, "XLA Ops", "%fusion.1 = f32[8] fusion(...)", t0, 400.0),
               (dev, "XLA Ops", "%ragged-dot-none.2 = f32[8] custom-call(...)", t0 + 400.0, 200.0),
               (dev, "XLA Ops", "%fusion.7 = f32[8] fusion(...)", t0 + 600.0, 100.0),
               (dev, "XLA Ops", "%fusion.9 = f32[8] fusion(...)", t0 + 700.0, 50.0),
               (dev, "XLA Ops", "%copy.3 = f32[8] copy(...)", t0 + 800.0, 100.0)]
    return Summary(ev)


class _StepCtx(SpanCtx):
    device_kind = "TPU v5 lite"

    def __init__(self, params, table):
        super().__init__(_job_spans())
        self.counters = {"params": params, "op_scopes": table}


TABLE = {"tower/attn": ["fusion.1"], "tower/embed": ["fusion.7"], "tower/trunk": ["fusion.9"],
         "tower/input": [], "tower/acc": []}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_is_data_for_a_reader_that_was_there(name):
    cells, expected = NEW[name]
    doc = run.load_json("layer_metrics", name + ".json")
    assert doc["name"] == name and doc["workloads"] == cells and doc["moves"] == "train_rate"
    assert callable(importlib.import_module("benchmark.readers." + doc["reader"]).read)
    bench = run.load_bench()
    assert set(cells) <= {w["name"] for w in bench["workloads"]}
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {k: doc[k] for k in ("name", "unit", "better", "source", "layer", "moves", "workloads")}
    assert bench["per_layer"].index(entry) >= 61                 # appended: nothing before it moved
    for cell in cells:
        assert name in [d["name"] for d in run.layer_metrics_for(cell)]
    for params in DRIVER_PARAMS:
        assert read_metric(doc, _step_summary(), _StepCtx(params, TABLE)) == pytest.approx(expected)
    # a program without these spans and scopes (the parent): the new names read nothing and
    # do not raise; the two that read older names read the older number
    ctx = _StepCtx(DRIVER_PARAMS[0], {"tower/attn": ["fusion.1"]})
    ctx.program_spans = [s for s in _job_spans() if "." not in s.name or s.name == "tower.save"]
    older = {"job_setup_s": 0.050, "step_scoped_share": 60.0}.get(name)
    got = read_metric(doc, _step_summary(), ctx)
    assert got == (None if older is None else pytest.approx(older))
    # nothing traced: a span is read all the same, a share of the step is not
    assert read_metric(doc, None, _StepCtx({}, None)) == \
        (pytest.approx(expected) if doc["reader"] == "span_seconds" else None)


def test_the_cells_metric_counts_after_pr35():
    """``test_tower_cell`` / ``test_nemotron_cell`` / ``test_trinity_cell`` end on the counts
    before these six files (14 / 17 / 20): files this PR may not edit."""
    assert {c: len(run.layer_metrics_for(c)) for c in ["nn-train"] + TOWER_CELLS} == \
        {"nn-train": 11, "sdar-train": 20, "nemotron-train": 23, "trinity-train": 26}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(json.load(f)["per_layer"]) == 67
