"""The ``sdar-train`` cell: its configuration against the model-configs
catalog's keys, its costs against the mask and the program's own parameter
count, its step reader on made-up events, its ``--rehearse`` at toy widths,
taken from the cell's ``rehearse`` block by the driver, and its controls —
the reference one precision lower and the planted faults — put through the
cell's limits by the harness: each comes out as not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import costs_tower, run
from benchmark.readers import read_metric
from benchmark.reference import sdar_moe as ref
from benchmark.trace import Summary

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def cell():
    return run.load_cell("sdar-train")


def _cfg(cell):
    doc = cell["config_doc"]
    return {**doc, "expert_parallel_size": doc["deployment"]["expert_parallel_size"]}


def test_configuration_keeps_every_published_width(cell):
    doc = cell["config_doc"]
    assert (doc["hidden_size"], doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["head_dim"], doc["moe_intermediate_size"], doc["num_experts_per_tok"]) == \
        (2048, 32, 4, 128, 768, 8)
    assert doc["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                              "max_position_embeddings"]
    assert doc["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936, "max_position_embeddings": 32768}
    assert doc["num_experts"] * doc["deployment"]["expert_parallel_size"] == 128
    assert len(doc["source"]) <= 200 and set(doc["assumed"]) >= {"block_length", "tokenisation"}
    worst = 383 * (doc["stats"]["maxNumBin"] + 1) + 49 * 65 + 4
    assert worst <= doc["vocab_size"]


def test_costs_count_the_mask_and_the_parameters(cell):
    cfg = _cfg(cell)
    for s, b in ((436, 4), (12, 4), (9, 3)):
        assert costs_tower.allowed_pairs(s, b) == int(ref.block_mask(s, b).sum())
    assert costs_tower.n_params(cfg) == 456_346_624
    step = costs_tower.step_model_flops(cfg, 16, 436, 4, 13952.0)
    assert 9.5e12 < step < 11e12                      # about 0.64 TFLOP a row
    assert costs_tower.opt_cost(cfg)["bytes_accessed"] == 28.0 * 456_346_624


class _Ctx:
    device_kind = "TPU v5 lite"

    def __init__(self, counters):
        self.counters = counters

    def say(self, msg):
        pass


def test_step_reader_sums_device_time_by_scope(cell):
    dev = "/device:TPU:0"
    ev = [(dev, "XLA Modules", "jit_tower_step(1)", 0.0, 1000.0),
          (dev, "XLA Modules", "jit_tower_step(1)", 2000.0, 1000.0),
          (dev, "XLA Modules", "jit_tower_valid_step(2)", 4000.0, 500.0)]
    for t0 in (0.0, 2000.0):
        ev += [(dev, "XLA Ops", "%while.1 = (s32[]) while(...)", t0, 1000.0),
               (dev, "XLA Ops", "%fusion.1 = f32[8] fusion(...)", t0, 400.0),
               (dev, "XLA Ops", "%ragged-dot-none.2 = f32[8] custom-call(...)", t0 + 400.0, 200.0),
               (dev, "XLA Ops", "%fusion.7 = f32[8] fusion(...)", t0 + 600.0, 100.0)]
    ev.append((dev, "XLA Ops", "%fusion.1 = f32[8] fusion(...)", 4000.0, 300.0))   # the other program's
    summary = Summary(ev)
    params = {"cfg": _cfg(cell), "rows": 16, "seq": 436, "block": 4, "pairs_per_layer": 13952.0}
    ctx = _Ctx({"params": params,
                "op_scopes": {"tower/attn": ["fusion.1"], "tower/moe/experts": ["fusion.7"]}})
    docs = {d["name"]: d for d in run.layer_metrics_for("sdar-train")}
    assert read_metric(docs["attn_time_share"], summary, ctx) == pytest.approx(40.0)
    assert read_metric(docs["moe_time_share"], summary, ctx) == pytest.approx(30.0)   # with ^ragged-dot
    assert read_metric(docs["opt_time_share"], summary, ctx) is None
    assert read_metric(docs["head_time_share"], summary, ctx) is None
    mfu = read_metric(docs["tower_step_mfu"], summary, ctx)
    assert mfu == pytest.approx(100 * costs_tower.step_model_flops(**params) / 197e12 / 1e-6)
    assert read_metric(docs["moe_experts_roofline"], summary, ctx) > 0
    # a program that records no scopes, or no counters: nothing to read
    assert read_metric(docs["attn_time_share"], summary, _Ctx({"params": params})) is None
    assert read_metric(docs["tower_step_mfu"], summary, _Ctx({})) is None
    assert read_metric(docs["moe_load_max_over_mean"], None, _Ctx({})) is None
    assert len(docs) == 14 and all(d["moves"] == "train_rate" for d in docs.values())


def _rehearse(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_ENABLE_X64", None)
    return subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", "sdar-train",
                           "--rehearse", *args], cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=900)


def test_controls_come_out_not_correct():
    """``--check-seeds``: the sound program passes every limit, and each
    control, judged by the same functions on a context of its own, is
    refused (a control that passed would fail the seed).  The reference one
    precision lower is refused by the parameters' change and by nothing
    else: the other distances do not tell bf16 storage from f32."""
    out = _rehearse("--check-seeds", "2147483659", "--full-jobs", "1")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    controls = {}
    for line in out.stdout.splitlines():
        if "] CONTROL " in line:
            name, _, rest = line.split("] CONTROL ", 1)[1].partition(": ")
            verdict, _, readings = rest.partition("; reading of limit: ")
            controls[name] = (verdict, json.loads(readings))
    assert set(controls) == {"lower_precision", "dropped_pairs", "half_batch", "state_unchanged",
                             "unchanged_job"}
    assert all(verdict == "not correct" for verdict, _ in controls.values())
    refused = lambda name: sorted(k for k, v in controls[name][1].items() if v.endswith("REFUSED"))
    assert refused("lower_precision") == ["step.update_vs_reference"]
    assert "step.gradient_vs_reference" in refused("half_batch")
    assert "step.loss_vs_reference" in refused("half_batch")
    assert {"forward.p90_vs_reference", "forward.p99_vs_reference",
            "step.gradient_vs_reference"} <= set(refused("dropped_pairs"))
    assert {"step.gradient_vs_reference", "step.second_moment_vs_reference",
            "step.update_vs_reference"} <= set(refused("state_unchanged"))
    assert refused("unchanged_job") == ["learn.train_loss_falls"]
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["failed"] == 0 and summary["closest_margin"]["step.update_vs_reference"] > 1


def test_rehearse_at_toy_widths():
    """The whole run on the CPU: set-up, the one-step check, a job, eval, a window."""
    out = _rehearse("--seed", "2147483659", "--seconds", "1")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert line["metrics"]["train_rate"]["value"] > 0 and line["metrics"]["setup_s"]["value"] > 0
