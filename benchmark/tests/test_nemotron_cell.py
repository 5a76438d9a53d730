"""The ``nemotron-train`` cell: its configuration against the model-configs
catalog's keys, its costs against the program's own parameter count, its step
reader on made-up events, its ``--rehearse`` at toy widths, and its controls —
the reference one precision lower and the planted faults — put through the
cell's limits by the harness: each comes out as not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import costs_nemotron, run
from benchmark.drivers.train_nemotron import tower_params
from benchmark.readers import read_metric
from benchmark.trace import Summary

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "num_attention_heads",
           "num_key_value_heads", "mamba_num_heads", "n_groups", "n_routed_experts", "vocab_size",
           "max_position_embeddings"]


@pytest.fixture(scope="module")
def cell():
    return run.load_cell("nemotron-train")


def test_configuration_keeps_every_published_width(cell):
    doc = cell["config_doc"]
    assert (doc["hidden_size"], doc["head_dim"], doc["mamba_head_dim"], doc["ssm_state_size"],
            doc["conv_kernel"], doc["chunk_size"], doc["expand"], doc["moe_latent_size"],
            doc["moe_intermediate_size"], doc["moe_shared_expert_intermediate_size"],
            doc["num_experts_per_tok"], doc["routed_scaling_factor"]) == \
        (4096, 128, 64, 128, 4, 128, 2, 1024, 2688, 5376, 22, 5)
    assert doc["reduced"] == REDUCED and list(doc["published"]) == REDUCED
    dep = doc["deployment"]
    assert doc["mamba_num_heads"] * dep["tensor_parallel_size"] == doc["published"]["mamba_num_heads"]
    assert doc["n_groups"] * dep["tensor_parallel_size"] == doc["published"]["n_groups"]
    assert doc["num_attention_heads"] * dep["tensor_parallel_size"] == 32
    assert doc["n_routed_experts"] * dep["expert_parallel_size"] == 512
    assert doc["vocab_size"] * dep["vocabulary_parallel_size"] == 131072
    assert doc["expand"] * doc["hidden_size"] == \
        doc["mamba_num_heads"] * doc["mamba_head_dim"] * dep["tensor_parallel_size"]
    # one whole period of the published pattern: layers 26-36
    assert doc["published"]["hybrid_override_pattern"][26:37] == doc["hybrid_override_pattern"]
    assert len(doc["hybrid_override_pattern"]) == doc["num_hidden_layers"] == 11
    assert doc["n_routed_experts"] >= 8 and doc["vocab_size"] * 8 >= 131072       # the floors
    assert len(doc["source"]) <= 200 and set(doc["assumed"]) >= {"no rotary", "MTP", "init", "loss"}
    worst = 383 * (doc["stats"]["maxNumBin"] + 1) + 49 * 65 + 4
    assert worst == 15828 <= doc["vocab_size"]


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
    assert costs_nemotron.n_params(cfg) == 838_249_968
    pairs = 8 * 432 * 22 / 64
    step = costs_nemotron.step_model_flops(cfg, 8, 433, pairs)
    assert 11.5e12 < step < 12.8e12                    # ~ 587 M multiply-adds a position forward
    moe = 5 * costs_nemotron.layer_flops("E", cfg, 8, 432, pairs)
    ssm = 5 * costs_nemotron.layer_flops("M", cfg, 8, 432, pairs)
    attn = costs_nemotron.layer_flops("*", cfg, 8, 432, pairs)
    assert 0.40 < moe / step < 0.55 and 0.10 < ssm / step < 0.14 and attn / step < 0.03
    assert costs_nemotron.opt_cost(cfg)["bytes_accessed"] == 28.0 * 838_249_968
    assert costs_nemotron.experts_cost(cfg, 2 * pairs)["flops"] == \
        2 * costs_nemotron.experts_cost(cfg, pairs)["flops"]


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
        ev += [(dev, "XLA Ops", "%fusion.1 = f32[8] fusion(...)", t0, 400.0),
               (dev, "XLA Ops", "%ragged-dot-none.2 = f32[8] custom-call(...)", t0 + 400.0, 200.0),
               (dev, "XLA Ops", "%fusion.7 = f32[8] fusion(...)", t0 + 600.0, 100.0),
               (dev, "XLA Ops", "%fusion.9 = f32[8] fusion(...)", t0 + 700.0, 50.0)]
    summary = Summary(ev)
    params = {"tower": "nemotron_h", "cfg": tower_params(cell["config_doc"]), "rows": 8,
              "seq": 433, "pairs_per_layer": 1188.0}
    ctx = _Ctx({"params": params,
                "op_scopes": {"tower/ssm/scan": ["fusion.1"], "tower/moe/experts": ["fusion.7"],
                              "tower/mtp": ["fusion.9"]}})
    docs = {d["name"]: d for d in run.layer_metrics_for("nemotron-train")}
    assert read_metric(docs["ssm_time_share"], summary, ctx) == pytest.approx(40.0)
    assert read_metric(docs["latent_moe_time_share"], summary, ctx) == pytest.approx(30.0)  # with ^ragged-dot
    assert read_metric(docs["mtp_time_share"], summary, ctx) == pytest.approx(5.0)
    assert read_metric(docs["nemotron_opt_time_share"], summary, ctx) is None
    mfu = read_metric(docs["nemotron_step_mfu"], summary, ctx)
    flops = costs_nemotron.step_model_flops(params["cfg"], 8, 433, 1188.0)
    assert mfu == pytest.approx(100 * flops / 197e12 / 1e-6)
    assert read_metric(docs["ssm_scan_roofline"], summary, ctx) > 0
    assert read_metric(docs["latent_experts_roofline"], summary, ctx) > 0
    assert read_metric(docs["nemotron_attn_roofline"], summary, ctx) is None
    # another tower's counters, no scopes, or no counters: nothing to read
    other = {**params, "tower": "sdar_moe"}
    assert read_metric(docs["nemotron_step_mfu"], summary, _Ctx({"params": other})) is None
    assert read_metric(docs["ssm_time_share"], summary, _Ctx({"params": params})) is None
    assert read_metric(docs["nemotron_step_mfu"], summary, _Ctx({})) is None
    assert read_metric(docs["nemotron_moe_load_max_over_mean"], None, _Ctx({})) is None
    assert len(docs) == 17 and all(d["moves"] == "train_rate" for d in docs.values())
    assert not set(docs) & {d["name"] for d in run.layer_metrics_for("sdar-train")}


def _rehearse(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_ENABLE_X64", None)
    return subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", "nemotron-train",
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
    assert set(controls) == {"lower_precision", "dropped_pairs", "state_reset_every_chunk",
                             "softmax_router", "mtp_left_out", "half_batch", "state_unchanged",
                             "unchanged_job"}
    assert all(verdict == "not correct" for verdict, _ in controls.values())
    refused = lambda name: sorted(k for k, v in controls[name][1].items() if v.endswith("REFUSED"))
    assert "step.update_vs_reference" in refused("lower_precision")
    assert "step.loss_vs_reference" in refused("mtp_left_out")
    assert "step.gradient_vs_reference" in refused("state_reset_every_chunk")
    assert "step.gradient_vs_reference" in refused("softmax_router")
    assert "step.gradient_vs_reference" in refused("half_batch")
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
