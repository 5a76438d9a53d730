"""The ``moonlight-train`` cell: its configuration against the architecture
catalog's keys, its costs against the program's own parameter count and a
count by hand, its step reader on made-up events (the nine metrics read
numbers), its ``--rehearse`` at toy widths, and its controls — the reference
one precision lower and the planted faults, latent attention's scale and norm
and the balance loss among them — put through the cell's limits by the
harness: each comes out as not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import costs_mla, run
from benchmark.drivers.train_afmoe import tower_params
from benchmark.readers import read_metric
from benchmark.trace import Summary

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = os.environ.get("ARCHITECTURE_CATALOG", "")  # architectures.jsonl, one published config a line
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
CONTROLS = {"lower_precision", "dropped_pairs", "scale_of_nope_alone", "latent_norm_left_out",
            "balance_loss_left_out", "rows_not_packed", "half_batch", "bias_never_moved",
            "state_unchanged", "unchanged_job"}
METRICS = {"moonlight_step_mfu", "mla_attn_roofline", "moonlight_experts_roofline",
           "mla_latent_time_share", "mla_proj_time_share", "mla_core_time_share",
           "moonlight_moe_time_share", "moonlight_shared_expert_time_share",
           "moonlight_moe_load_max_over_mean"}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell("moonlight-train")


def test_configuration_keeps_every_published_width(cell):
    doc = cell["config_doc"]
    assert (doc["hidden_size"], doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["qk_nope_head_dim"], doc["qk_rope_head_dim"], doc["v_head_dim"], doc["kv_lora_rank"],
            doc["q_lora_rank"], doc["intermediate_size"], doc["moe_intermediate_size"],
            doc["n_shared_experts"], doc["num_experts_per_tok"], doc["routed_scaling_factor"],
            doc["rope_theta"], doc["rms_norm_eps"], doc["first_k_dense_replace"], doc["seq_aux"]) == \
        (2048, 16, 16, 128, 64, 128, 512, None, 11264, 1408, 2, 6, 2.446, 50000, 1e-5, 1, True)
    assert doc["reduced"] == REDUCED and list(doc["published"]) == REDUCED
    assert doc["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 163840,
                                "max_position_embeddings": 8192}
    dep = doc["deployment"]
    assert doc["n_routed_experts"] * dep["expert_parallel_size"] == 64
    assert doc["vocab_size"] * dep["vocabulary_parallel_size"] == 163840
    # one dense layer, then four MoE layers: the period is one layer, four is the floor after it
    assert doc["num_hidden_layers"] == 5 and doc["moe_layer_freq"] == 1
    assert doc["n_routed_experts"] >= 8 and doc["vocab_size"] * 8 >= 163840             # the floors
    assert "568,484,608" in dep["state"] and "768 pairs" in dep["expert_load"]
    assert len(doc["source"]) <= 200 and set(doc["assumed"]) >= {
        "rotary", "latent attention", "balance loss", "router", "selection bias", "init", "packing",
        "loss", "tokenisation", "stats.maxNumBin"}
    worst = 383 * (doc["stats"]["maxNumBin"] + 1) + 49 * 65 + 4
    assert worst == 17360 <= doc["vocab_size"]
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
    assert set(changed) <= set(REDUCED) and "max_position_embeddings" not in changed   # as published
    assert doc["published"] == {k: row["config"][k] for k in REDUCED}


def test_costs_count_the_programs_parameters_and_the_operations_by_hand(cell):
    cfg = tower_params(cell["config_doc"])
    attn = 6_291_456 + 1_179_648 + 512 + 2_097_152 + 4_194_304
    assert attn == 13_763_072
    dense, moe = attn + 4096 + 69_206_016, attn + 4096 + 131_072 + 64 + 17_301_504 + 8 * 8_650_752
    assert (dense, moe) == (82_973_184, 100_405_824)
    assert costs_mla.n_params(cfg) == 568_484_608 == dense + 4 * moe + 83_888_128
    # the count by hand: a forward pass of one 8,192-position sequence is 6.23 TFLOP, 18.70 a step
    pairs = 8192 * 6 / 8                                # 6,144 pairs over the 8 held experts at uniform routing
    step = costs_mla.step_model_flops(cfg, 1, 8192, pairs)
    assert abs(step / 3 - 6.234e12) < 0.001e12 and abs(step - 18.70e12) < 0.01e12
    kernel = costs_mla.attn_cost(cfg, 1, 8192)["flops"]
    assert kernel == 3 * 2 * (192 + 128) * 16 * (8192 * 8193 // 2)
    positions, d = 8192, 2048
    proj = 5 * 3 * 2 * (attn - 512) * positions        # the latent's norm weights multiply nothing
    dense_mlp = 3 * 2 * 3 * d * 11264 * positions
    experts = 4 * (costs_mla.experts_cost(cfg, pairs)["flops"] + 3 * 2 * (3 * d * 2816 + d * 64) * positions)
    head = 3 * 2 * d * 20480 * positions
    for part, share in ((5 * kernel + proj, 0.456), (5 * kernel, 0.276), (experts, 0.251),
                        (dense_mlp, 0.182), (head, 0.110)):
        assert abs(part / step - share) < 0.001, (part / step, share)
    assert abs(5 * kernel + proj + experts + dense_mlp + head - step) < 1e-6 * step
    layers = [costs_mla.layer_flops(i, cfg, 1, 8192, pairs) for i in range(5)]
    assert layers[0] > layers[1] == layers[4]           # the dense layer outweighs a MoE layer


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
               (dev, "XLA Ops", "%fusion.3 = f32[8] fusion(...)", t0 + 300.0, 100.0),
               (dev, "XLA Ops", "%fusion.4 = f32[8] fusion(...)", t0 + 400.0, 150.0),
               (dev, "XLA Ops", "%ragged-dot-none.2 = f32[8] custom-call(...)", t0 + 550.0, 100.0),
               (dev, "XLA Ops", "%fusion.7 = f32[8] fusion(...)", t0 + 650.0, 50.0),
               (dev, "XLA Ops", "%fusion.9 = f32[8] fusion(...)", t0 + 700.0, 80.0)]
    summary = Summary(ev)
    params = {"tower": "deepseek_v3", "cfg": tower_params(cell["config_doc"]), "seq": 8192,
              "sequences": 1, "pairs_per_layer": 6144.0}
    ctx = _Ctx({"params": params, "tower.moe_pairs_max_expert": 30.0, "tower.moe_pairs_mean_expert": 12.0,
                "op_scopes": {"tower/attn/full": ["blocked_attention_fwd.1"], "tower/attn/proj": ["fusion.3"],
                              "tower/attn/latent": ["fusion.4"], "tower/moe/experts": ["fusion.7"],
                              "tower/moe/shared": ["fusion.9"]}})
    docs = {d["name"]: d for d in run.layer_metrics_for("moonlight-train")}
    assert set(docs) == METRICS
    assert all(d["moves"] == "train_rate" and d["workloads"] == ["moonlight-train"] for d in docs.values())
    assert read_metric(docs["mla_core_time_share"], summary, ctx) == pytest.approx(30.0)
    assert read_metric(docs["mla_proj_time_share"], summary, ctx) == pytest.approx(10.0)
    assert read_metric(docs["mla_latent_time_share"], summary, ctx) == pytest.approx(15.0)
    assert read_metric(docs["moonlight_moe_time_share"], summary, ctx) == pytest.approx(15.0)   # with ^ragged-dot
    assert read_metric(docs["moonlight_shared_expert_time_share"], summary, ctx) == pytest.approx(8.0)
    mfu = read_metric(docs["moonlight_step_mfu"], summary, ctx)
    flops = costs_mla.step_model_flops(params["cfg"], 1, 8192, 6144.0)
    assert mfu == pytest.approx(100 * flops / 197e12 / 1e-6)
    attn = read_metric(docs["mla_attn_roofline"], summary, ctx)
    assert attn == pytest.approx(100 * 5 * costs_mla.attn_cost(params["cfg"], 1, 8192)["flops"] / 197e12 / 300e-9)
    experts = read_metric(docs["moonlight_experts_roofline"], summary, ctx)
    least = max(4 * costs_mla.experts_cost(params["cfg"], 6144.0)["flops"] / 197e12,
                4 * costs_mla.experts_cost(params["cfg"], 6144.0)["bytes_accessed"] / 819e9)
    assert experts == pytest.approx(100 * least / 150e-9)
    assert read_metric(docs["moonlight_moe_load_max_over_mean"], summary, ctx) == pytest.approx(2.5)
    # another tower's counters, no scopes, or no counters: nothing to read
    other = {**params, "tower": "lfm2_moe"}
    assert read_metric(docs["moonlight_step_mfu"], summary, _Ctx({"params": other})) is None
    assert read_metric(docs["mla_core_time_share"], summary, _Ctx({"params": params})) is None
    assert read_metric(docs["moonlight_step_mfu"], summary, _Ctx({})) is None
    assert read_metric(docs["moonlight_moe_load_max_over_mean"], None, _Ctx({})) is None
    assert not set(docs) & {d["name"] for d in run.layer_metrics_for("lfm2-train")}


def _rehearse(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_ENABLE_X64", None)
    return subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", "moonlight-train",
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
    assert refused("balance_loss_left_out") == ["step.balance_vs_reference"]
    for name in ("scale_of_nope_alone", "latent_norm_left_out", "rows_not_packed", "half_batch"):
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
