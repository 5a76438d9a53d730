"""The ``trinity-train`` cell: its configuration against the model-configs
catalog's keys, its costs against the program's own parameter count and the
issue's arithmetic, its step reader on made-up events, its ``--rehearse`` at
toy widths, and its controls — the reference one precision lower and the
planted faults — put through the cell's limits by the harness: each comes out
as not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import costs_afmoe, run
from benchmark.drivers.train_afmoe import tower_params
from benchmark.readers import read_metric
from benchmark.trace import Summary

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size",
           "max_position_embeddings"]
CONTROLS = {"lower_precision", "dropped_pairs", "half_batch", "state_unchanged", "every_layer_full",
            "window_on_full_layer", "rotary_on_full_layer", "gate_left_out", "post_norms_left_out",
            "shared_expert_left_out", "rows_not_packed", "bias_never_moved", "unchanged_job"}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell("trinity-train")


def test_configuration_keeps_every_published_width(cell):
    doc = cell["config_doc"]
    assert (doc["hidden_size"], doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["head_dim"], doc["sliding_window"], doc["intermediate_size"],
            doc["moe_intermediate_size"], doc["num_experts_per_tok"], doc["route_scale"],
            doc["rope_theta"], doc["rms_norm_eps"], doc["load_balance_coeff"]) == \
        (2048, 32, 4, 128, 2048, 6144, 1024, 8, 2.826, 10000, 1e-5, 0.001)
    assert doc["reduced"] == REDUCED and list(doc["published"]) == REDUCED
    dep = doc["deployment"]
    assert doc["num_experts"] * dep["expert_parallel_size"] == doc["published"]["num_experts"] == 128
    assert doc["vocab_size"] * dep["vocabulary_parallel_size"] == doc["published"]["vocab_size"]
    # one leading dense layer, then one whole period of the published pattern (layers 4-7)
    assert doc["layer_types"][1:] == doc["published"]["layer_types"][4:8]
    assert doc["layer_types"][0] == doc["published"]["layer_types"][1]
    assert len(doc["layer_types"]) == doc["num_hidden_layers"] == 5 and doc["num_dense_layers"] == 1
    assert doc["num_experts"] >= 8 and doc["vocab_size"] * 8 >= 200192          # the floors
    assert len(doc["source"]) <= 200 and set(doc["assumed"]) >= {
        "q/k norm", "output gate", "norms", "rotary", "embedding scale", "selection bias", "init",
        "packing", "loss"}
    worst = 383 * (doc["stats"]["maxNumBin"] + 1) + 49 * 65 + 4
    assert worst == 17360 <= doc["vocab_size"]
    params, traffic = doc["train"]["params"], cell["traffic_doc"]
    assert (params["MiniBatchs"], params["RowsPerSequence"], traffic["rows"],
            traffic["iterations_per_job"]) == (18, 18, 540, 2)
    assert 18 * 433 == 7794 and -(-7794 // 512) * 512 == doc["max_position_embeddings"] == 8192


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog beside the model-configs guide")
def test_configuration_holds_the_catalogs_keys(cell):
    doc = cell["config_doc"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == doc["source"])
    changed = sorted(k for k, v in row["config"].items() if doc.get(k, "missing") != v)
    assert changed == sorted(REDUCED)
    assert doc["published"] == {k: row["config"][k] for k in REDUCED}


def test_costs_count_the_programs_parameters_and_the_issues_operations(cell):
    cfg = tower_params(cell["config_doc"])
    assert costs_afmoe.n_params(cfg) == 705_474_304 == 65_020_160 + 4 * 134_488_448 + 102_500_352
    assert costs_afmoe.allowed_pairs(8192, 2048) == 14_681_088 == sum(min(i + 1, 2048) for i in range(8192))
    assert costs_afmoe.allowed_pairs(8192) == 33_558_528
    pairs = 8192 * 8 / 8                                # 512 a held expert, 16 held
    step = costs_afmoe.step_model_flops(cfg, 1, 8192, pairs)
    assert 18.0e12 < step < 18.3e12 and abs(step / 3 - 6.04e12) < 0.02e12
    kernels = 4 * costs_afmoe.window_attn_cost(cfg, 1, 8192)["flops"] + \
        costs_afmoe.full_attn_cost(cfg, 1, 8192)["flops"]
    assert 0.24 < kernels / step < 0.26
    attn = sum(costs_afmoe.layer_flops(i, cfg, 1, 8192, 0) for i in range(5)) - \
        3 * 2 * 8192 * (3 * 2048 * 6144 + 4 * (2048 * 128 + 3 * 2048 * 1024))
    assert 0.61 < attn / step < 0.63
    assert 0.06 < 4 * costs_afmoe.experts_cost(cfg, pairs)["flops"] / step < 0.08
    assert costs_afmoe.full_attn_cost(cfg, 1, 8192)["flops"] / \
        costs_afmoe.window_attn_cost(cfg, 1, 8192)["flops"] == pytest.approx(2.2858, abs=1e-3)
    assert costs_afmoe.opt_cost(cfg)["bytes_accessed"] == 28.0 * 705_474_304


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
        ev += [(dev, "XLA Ops", "%blocked_attention_fwd.1 = f32[8] custom-call(...)", t0, 300.0),
               (dev, "XLA Ops", "%blocked_attention_dkv.2 = f32[8] custom-call(...)", t0 + 300.0, 100.0),
               (dev, "XLA Ops", "%ragged-dot-none.2 = f32[8] custom-call(...)", t0 + 400.0, 200.0),
               (dev, "XLA Ops", "%fusion.7 = f32[8] fusion(...)", t0 + 600.0, 100.0),
               (dev, "XLA Ops", "%fusion.9 = f32[8] fusion(...)", t0 + 700.0, 50.0)]
    summary = Summary(ev)
    params = {"tower": "afmoe", "cfg": tower_params(cell["config_doc"]), "seq": 8192,
              "sequences": 1, "pairs_per_layer": 8192.0}
    ctx = _Ctx({"params": params, "tower.attn_key_blocks": 416.0, "tower.attn_key_blocks_dense": 680.0,
                "tower.pad_positions": 398.0, "tower.sequence_positions": 8192.0,
                "op_scopes": {"tower/attn/window": ["blocked_attention_fwd.1"],
                              "tower/attn/full": ["blocked_attention_dkv.2"],
                              "tower/moe/experts": ["fusion.7"], "tower/head": ["fusion.9"]}})
    docs = {d["name"]: d for d in run.layer_metrics_for("trinity-train")}
    assert read_metric(docs["window_attn_time_share"], summary, ctx) == pytest.approx(30.0)
    assert read_metric(docs["full_attn_time_share"], summary, ctx) == pytest.approx(10.0)
    assert read_metric(docs["trinity_moe_time_share"], summary, ctx) == pytest.approx(30.0)  # with ^ragged-dot
    assert read_metric(docs["trinity_head_time_share"], summary, ctx) == pytest.approx(5.0)
    assert read_metric(docs["trinity_opt_time_share"], summary, ctx) is None
    mfu = read_metric(docs["trinity_step_mfu"], summary, ctx)
    flops = costs_afmoe.step_model_flops(params["cfg"], 1, 8192, 8192.0)
    assert mfu == pytest.approx(100 * flops / 197e12 / 1e-6)
    window = read_metric(docs["window_attn_roofline"], summary, ctx)
    least = 4 * costs_afmoe.window_attn_cost(params["cfg"], 1, 8192)["flops"] / 197e12
    assert window == pytest.approx(100 * least / 300e-9)
    assert read_metric(docs["full_attn_roofline"], summary, ctx) > 0
    assert read_metric(docs["trinity_experts_roofline"], summary, ctx) > 0
    assert read_metric(docs["trinity_attn_blocks_share"], summary, ctx) == pytest.approx(416 / 680)
    assert read_metric(docs["trinity_pad_share"], summary, ctx) == pytest.approx(100 * 398 / 8192)
    # another tower's counters, no scopes, or no counters: nothing to read
    other = {**params, "tower": "nemotron_h"}
    assert read_metric(docs["trinity_step_mfu"], summary, _Ctx({"params": other})) is None
    assert read_metric(docs["window_attn_time_share"], summary, _Ctx({"params": params})) is None
    assert read_metric(docs["trinity_step_mfu"], summary, _Ctx({})) is None
    assert read_metric(docs["trinity_attn_blocks_share"], None, _Ctx({})) is None
    assert len(docs) == 20 and all(d["moves"] == "train_rate" for d in docs.values())
    assert not set(docs) & {d["name"] for d in run.layer_metrics_for("nemotron-train")}


def _rehearse(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_ENABLE_X64", None)
    return subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", "trinity-train",
                           "--rehearse", *args], cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=1200)


def test_controls_come_out_not_correct():
    """``--check-seeds``: the sound program passes every limit, and each
    control, judged by the same functions on a context of its own, is refused."""
    out = _rehearse("--check-seeds", "2147483659", "--full-jobs", "1")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    controls = {}
    for line in out.stdout.splitlines():
        if "] CONTROL " in line:
            name, _, rest = line.split("] CONTROL ", 1)[1].partition(": ")
            verdict, _, readings = rest.partition("; reading of limit: ")
            controls[name] = (verdict, json.loads(readings))
    assert set(controls) == CONTROLS
    assert all(verdict == "not correct" for verdict, _ in controls.values())
    refused = lambda name: sorted(k for k, v in controls[name][1].items() if v.endswith("REFUSED"))
    assert "step.update_vs_reference" in refused("lower_precision")
    for name in ("every_layer_full", "window_on_full_layer", "rotary_on_full_layer", "gate_left_out",
                 "post_norms_left_out", "shared_expert_left_out", "rows_not_packed", "half_batch"):
        assert "step.gradient_vs_reference" in refused(name), name
    assert {"step.gradient_vs_reference", "step.second_moment_vs_reference",
            "step.update_vs_reference"} <= set(refused("state_unchanged"))
    assert refused("bias_never_moved") == [] and refused("unchanged_job") == ["learn.train_loss_falls"]
    assert "CHECK FAILED step.bias_vs_reference" not in out.stdout.split("CONTROL")[0]
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["failed"] == 0 and summary["closest_margin"]["step.update_vs_reference"] > 1


def test_rehearse_at_toy_widths():
    """The whole run on the CPU: set-up, the one-step check, a job, eval, a window."""
    out = _rehearse("--seed", "2147483659", "--seconds", "1")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert line["metrics"]["train_rate"]["value"] > 0 and line["metrics"]["setup_s"]["value"] > 0
