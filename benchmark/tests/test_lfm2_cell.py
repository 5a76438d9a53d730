"""The ``lfm2-train`` cell: its configuration against the architecture
catalog's keys, its costs against the program's own parameter count and a
count by hand, its step reader on made-up events, its ``--rehearse`` at
toy widths, and its controls — the reference one precision lower and the
planted faults, the convolution's taps reversed among them — put through the
cell's limits by the harness: each comes out as not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import costs_lfm2, run
from benchmark.drivers.train_afmoe import tower_params
from benchmark.readers import read_metric
from benchmark.trace import Summary

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = os.environ.get("ARCHITECTURE_CATALOG", "")  # architectures.jsonl, one published config a line
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size",
           "max_position_embeddings"]
CONTROLS = {"lower_precision", "dropped_pairs", "taps_reversed", "rows_not_packed", "half_batch",
            "bias_never_moved", "state_unchanged", "unchanged_job"}
METRICS = {"lfm2_step_mfu", "conv_mix_roofline", "lfm2_attn_roofline", "lfm2_experts_roofline",
           "conv_mix_time_share", "conv_proj_time_share", "lfm2_attn_time_share",
           "lfm2_dense_mlp_time_share", "lfm2_moe_time_share", "lfm2_moe_load_max_over_mean"}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell("lfm2-train")


def test_configuration_keeps_every_published_width(cell):
    doc = cell["config_doc"]
    assert (doc["hidden_size"], doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["intermediate_size"], doc["moe_intermediate_size"], doc["num_experts_per_tok"],
            doc["conv_L_cache"], doc["rope_parameters"]["rope_theta"], doc["norm_eps"],
            doc["routed_scaling_factor"], doc["conv_bias"], doc["use_expert_bias"]) == \
        (2048, 32, 8, 11776, 1536, 4, 3, 1000000, 1e-5, 1, False, True)
    assert doc["reduced"] == REDUCED and list(doc["published"]) == REDUCED
    dep = doc["deployment"]
    assert doc["num_experts"] * dep["expert_parallel_size"] == doc["published"]["num_experts"] == 64
    assert doc["vocab_size"] * dep["vocabulary_parallel_size"] == doc["published"]["vocab_size"]
    # one leading dense layer (a conv layer, as published layers 1 and 2), then one whole period
    pub = doc["published"]["layer_types"]
    assert doc["layer_types"][0] == pub[1] == "conv" and doc["layer_types"][1:] == pub[2:6]
    assert (pub.count("conv"), pub.count("full_attention")) == (30, 10)
    assert len(doc["layer_types"]) == doc["num_hidden_layers"] == 5 and doc["num_dense_layers"] == 1
    assert doc["num_experts"] >= 8 and doc["vocab_size"] * 8 >= 65536            # the floors
    assert len(doc["source"]) <= 200 and set(doc["assumed"]) >= {
        "head_dim", "head", "short convolution", "router", "selection bias", "init", "packing", "loss"}
    worst = 383 * (doc["stats"]["maxNumBin"] + 1) + 49 * 65 + 4
    assert worst == 7402 <= doc["vocab_size"]
    params, traffic = doc["train"]["params"], cell["traffic_doc"]
    assert (params["MiniBatchs"], params["RowsPerSequence"], traffic["rows"],
            traffic["iterations_per_job"]) == (18, 18, 540, 2)
    assert 18 * 433 == 7794 and -(-7794 // 512) * 512 == doc["max_position_embeddings"] == 8192


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no architecture catalog given")
def test_configuration_holds_the_catalogs_keys(cell):
    doc = cell["config_doc"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == doc["source"])
    changed = sorted(k for k, v in row["config"].items() if doc.get(k, "missing") != v)
    assert changed == sorted(REDUCED)
    assert doc["published"] == {k: row["config"][k] for k in REDUCED}


def test_costs_count_the_programs_parameters_and_the_operations_by_hand(cell):
    cfg = tower_params(cell["config_doc"])
    assert costs_lfm2.n_params(cfg) == 486_062_464 == 89_139_200 + 86_118_592 + 3 * 92_416_064 + 33_556_480
    assert costs_lfm2.head_dim(cfg) == 64
    # the count by hand: 405.8 M operations a position forward, 19.95 TFLOP a step of two sequences
    pairs = 16384 * 4 / 8                               # 8,192 pairs over the 8 held experts
    two = costs_lfm2.step_model_flops(cfg, 2, 8192, pairs)
    assert abs(two / 3 / 16384 - 405.8e6) < 0.1e6 and abs(two - 19.95e12) < 0.01e12
    one = costs_lfm2.step_model_flops(cfg, 1, 8192, pairs / 2)
    assert one == pytest.approx(two / 2, rel=1e-3)      # the cell's step: one sequence
    layers = [costs_lfm2.layer_flops(i, cfg, 2, 8192, pairs) for i in range(5)]
    positions, d = 16384, 2048
    conv_proj = 4 * 3 * 2 * 4 * d * d * positions
    dense = 3 * 2 * 3 * d * 11776 * positions
    kernel = costs_lfm2.attn_cost(cfg, 2, 8192)["flops"]
    experts = 4 * costs_lfm2.experts_cost(cfg, pairs)["flops"]
    for part, share in ((conv_proj, 0.33), (dense, 0.36), (kernel, 0.08), (experts, 0.09)):
        assert abs(part / two - share) < 0.01, (part / two, share)
    assert sum(layers) < two and layers[1] > layers[2]  # the kernel makes the attention layer the heavier
    # the mix is bandwidth-bound: forward 4, backward 7 f32 channels x D a position
    mix = costs_lfm2.conv_mix_cost(cfg, 2, 8192)
    assert mix["bytes_accessed"] == 4 * 11 * 2048 * 16384 and mix["flops"] < 1e-2 * two


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
        ev += [(dev, "XLA Ops", "%blocked_attention_fwd.1 = f32[8] custom-call(...)", t0, 100.0),
               (dev, "XLA Ops", "%fusion.3 = f32[8] fusion(...)", t0 + 100.0, 50.0),
               (dev, "XLA Ops", "%fusion.4 = f32[8] fusion(...)", t0 + 150.0, 300.0),
               (dev, "XLA Ops", "%ragged-dot-none.2 = f32[8] custom-call(...)", t0 + 450.0, 200.0),
               (dev, "XLA Ops", "%fusion.7 = f32[8] fusion(...)", t0 + 650.0, 100.0),
               (dev, "XLA Ops", "%fusion.9 = f32[8] fusion(...)", t0 + 750.0, 200.0)]
    summary = Summary(ev)
    params = {"tower": "lfm2_moe", "cfg": tower_params(cell["config_doc"]), "seq": 8192,
              "sequences": 1, "pairs_per_layer": 4096.0}
    ctx = _Ctx({"params": params, "tower.moe_pairs_max_expert": 30.0, "tower.moe_pairs_mean_expert": 10.0,
                "op_scopes": {"tower/attn/full": ["blocked_attention_fwd.1"], "tower/attn/proj": ["fusion.3"],
                              "tower/conv/proj": ["fusion.4"], "tower/moe/experts": ["fusion.7"],
                              "tower/conv/mix": ["fusion.9"]}})
    docs = {d["name"]: d for d in run.layer_metrics_for("lfm2-train")}
    assert set(docs) == METRICS
    assert all(d["moves"] == "train_rate" and d["workloads"] == ["lfm2-train"] for d in docs.values())
    assert read_metric(docs["lfm2_attn_time_share"], summary, ctx) == pytest.approx(15.0)
    assert read_metric(docs["conv_proj_time_share"], summary, ctx) == pytest.approx(30.0)
    assert read_metric(docs["conv_mix_time_share"], summary, ctx) == pytest.approx(20.0)
    assert read_metric(docs["lfm2_moe_time_share"], summary, ctx) == pytest.approx(30.0)  # with ^ragged-dot
    assert read_metric(docs["lfm2_dense_mlp_time_share"], summary, ctx) is None
    mfu = read_metric(docs["lfm2_step_mfu"], summary, ctx)
    flops = costs_lfm2.step_model_flops(params["cfg"], 1, 8192, 4096.0)
    assert mfu == pytest.approx(100 * flops / 197e12 / 1e-6)
    attn = read_metric(docs["lfm2_attn_roofline"], summary, ctx)
    assert attn == pytest.approx(100 * costs_lfm2.attn_cost(params["cfg"], 1, 8192)["flops"] / 197e12 / 100e-9)
    mix = read_metric(docs["conv_mix_roofline"], summary, ctx)
    assert mix == pytest.approx(100 * 4 * costs_lfm2.conv_mix_cost(params["cfg"], 1, 8192)["bytes_accessed"]
                                / 819e9 / 200e-9)
    assert read_metric(docs["lfm2_experts_roofline"], summary, ctx) > 0
    assert read_metric(docs["lfm2_moe_load_max_over_mean"], summary, ctx) == pytest.approx(3.0)
    # another tower's counters, no scopes, or no counters: nothing to read
    other = {**params, "tower": "afmoe"}
    assert read_metric(docs["lfm2_step_mfu"], summary, _Ctx({"params": other})) is None
    assert read_metric(docs["conv_mix_time_share"], summary, _Ctx({"params": params})) is None
    assert read_metric(docs["lfm2_step_mfu"], summary, _Ctx({})) is None
    assert read_metric(docs["lfm2_moe_load_max_over_mean"], None, _Ctx({})) is None
    assert not set(docs) & {d["name"] for d in run.layer_metrics_for("trinity-train")}


def _rehearse(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_ENABLE_X64", None)
    return subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", "lfm2-train",
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
    for name in ("taps_reversed", "rows_not_packed", "half_batch", "dropped_pairs"):
        assert "step.gradient_vs_reference" in refused(name), name
    assert "forward.p90_vs_reference" in refused("taps_reversed")
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
