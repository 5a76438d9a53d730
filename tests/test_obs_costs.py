"""Device cost-attribution plane suite (obs/costs + obs/utilization):
costed_jit capture (flops/bytes/memory, compile/launch counts), the
shape-churn recompile sentinel (counter + warn-once — the acceptance
test), lazy module-scope wrapping, analytic Pallas models, cost records
in the flush/trace, the utilization/roofline report (incl. the
deterministic-render golden), padding-waste accounting, timeline cost
annotation + torn-trace hardening, and `monitor --once --json`."""

import json
import logging
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from shifu_tpu import obs
from shifu_tpu.obs import costs as costs_mod
from shifu_tpu.obs import monitor as monitor_mod
from shifu_tpu.obs import timeline as timeline_mod
from shifu_tpu.obs import utilization as util_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.obs        # `pytest -m obs` collects this suite


@pytest.fixture
def telemetry():
    obs.reset_for_tests()
    obs.set_enabled(True)
    yield obs
    obs.reset_for_tests()


def _metric(name):
    return next((m for m in obs.snapshot() if m["name"] == name), None)


# ------------------------------------------------------------ costed_jit
def test_costed_jit_captures_costs_memory_and_launches(telemetry):
    def f(x, y, n=None):
        return (x @ y).sum() + n

    cj = obs.costed_jit("test.mm", f, static_argnames=("n",))
    assert isinstance(cj, costs_mod.CostedJit)
    v = float(cj(jnp.ones((8, 8)), jnp.ones((8, 8)), n=3))
    assert v == pytest.approx(8 * 8 * 8 + 3)
    float(cj(jnp.ones((8, 8)), jnp.ones((8, 8)), n=3))   # warm launch
    (rec,) = obs.cost_snapshot()
    assert rec["kind"] == "cost" and rec["name"] == "test.mm"
    assert rec["compiles"] == 1 and rec["launches"] == 2
    assert rec["flops"] and rec["flops"] > 0
    assert rec["bytes_accessed"] and rec["bytes_accessed"] > 0
    assert rec["memory"]["args"] > 0 and not rec["analytic"]
    assert "[8,8]" in rec["signature"]
    assert _metric("xla.launches")["value"] == 2
    assert _metric("xla.recompiles") is None      # one signature only


def test_costed_jit_static_values_key_executables(telemetry):
    """Distinct STATIC values are distinct executables (and count as a
    recompile under one name — statics churn like shapes churn)."""
    def f(x, k=2):
        return (x * k).sum()

    cj = obs.costed_jit("test.static", f, static_argnames=("k",))
    assert float(cj(jnp.ones(4), k=2)) == 8.0
    assert float(cj(jnp.ones(4), k=3)) == 12.0
    recs = obs.cost_snapshot()
    assert len(recs) == 2
    assert _metric("xla.recompiles")["value"] == 1


def test_recompile_sentinel_counter_and_warn_once(telemetry, caplog):
    """ACCEPTANCE: two distinct input shapes through ONE costed_jit name
    increment ``xla.recompiles`` and fire the warn-once log EXACTLY
    once (a third shape counts silently)."""
    def f(x):
        return (x * 2.0).sum()

    cj = obs.costed_jit("test.churn", f)
    with caplog.at_level(logging.WARNING, logger="shifu_tpu.obs.costs"):
        float(cj(jnp.ones((4,))))
        float(cj(jnp.ones((8,))))                # recompile 1 -> warns
        float(cj(jnp.ones((16,))))               # recompile 2 -> silent
    assert _metric("xla.recompiles")["value"] == 2
    warned = [r for r in caplog.records
              if "recompiled for a new input signature" in r.message]
    assert len(warned) == 1
    assert "test.churn" in warned[0].message
    # three executables, one launch each, all under the one name
    recs = obs.cost_snapshot()
    assert [r["name"] for r in recs] == ["test.churn"] * 3
    assert all(r["launches"] == 1 for r in recs)


def test_costed_jit_lazy_enables_after_wrap(telemetry):
    """The module-scope form: wrapped while telemetry is OFF (import
    time), it must still attribute once telemetry turns on — and go
    quiet again when it turns off."""
    obs.set_enabled(False)

    def f(x):
        return x.sum()

    lz = costs_mod.costed_jit("test.lazylate", f, lazy=True)
    assert isinstance(lz, costs_mod.CostedJit)
    float(lz(jnp.ones(4)))
    assert obs.cost_snapshot() == []
    obs.set_enabled(True)
    float(lz(jnp.ones(4)))
    (rec,) = obs.cost_snapshot(reset=True)
    assert rec["name"] == "test.lazylate" and rec["launches"] == 1
    obs.set_enabled(False)
    float(lz(jnp.ones(4)))
    assert obs.cost_snapshot() == []


def test_costed_jit_tracer_args_fall_through(telemetry):
    """Called from inside another trace (tracer args), the wrapper must
    fall through to the plain jitted path — correct value, no bogus
    cost entry."""
    inner = obs.costed_jit("test.inner", lambda x: x * 2.0)

    @jax.jit
    def outer(x):
        return inner(x).sum()

    assert float(outer(jnp.ones(4))) == 8.0
    assert all(r["name"] != "test.inner" for r in obs.cost_snapshot())


def test_costed_jit_results_match_plain_jit(telemetry, rng):
    """AOT dispatch is an implementation detail: outputs must equal the
    plain jitted fn's, including committed/sharded-style numpy inputs."""
    def f(x, w):
        return jnp.tanh(x @ w).sum(axis=1)

    x = rng.normal(size=(32, 8)).astype(np.float32)
    w = rng.normal(size=(8, 8)).astype(np.float32)
    cj = obs.costed_jit("test.parity", f)
    np.testing.assert_allclose(np.asarray(cj(x, w)),
                               np.asarray(jax.jit(f)(x, w)), rtol=1e-6)


def test_record_executable_direct_hook(telemetry):
    """The lower-level API: code holding a (lowered, compiled) pair
    registers it; the signature derives from the lowering."""
    def f(x):
        return x * 3.0

    lowered = jax.jit(f).lower(jnp.ones((4, 4)))
    obs.record_executable("test.direct", lowered, lowered.compile())
    (rec,) = obs.cost_snapshot()
    assert rec["name"] == "test.direct" and rec["compiles"] == 1
    assert "[4,4]" in rec["signature"]


# ------------------------------------------------------- analytic models
def test_pallas_and_scatter_cost_models_registered(telemetry):
    import shifu_tpu.ops.hist_pallas  # noqa: F401  (registers pallas.hist)
    import shifu_tpu.ops.tree         # noqa: F401  (registers scatter)
    models = costs_mod.cost_models()
    assert "pallas.hist" in models and "tree.scatter_hist" in models
    est = models["pallas.hist"](rows=1024, n_feat=64, n_bins=64,
                                n_nodes=8, n_stats=2, n_trees=1)
    # dominant term: 2*N*K*B*S*C MACs
    assert est["flops"] >= 2.0 * 1024 * 8 * 64 * 2 * 64
    assert est["bytes_accessed"] > 0


def test_record_model_launch_accumulates(telemetry):
    import shifu_tpu.ops.hist_pallas  # noqa: F401
    for _ in range(3):
        obs.record_model_launch("pallas.hist", rows=512, n_feat=8,
                                n_bins=16, n_nodes=4)
    (rec,) = obs.cost_snapshot()
    assert rec["name"] == "pallas.hist" and rec["analytic"]
    assert rec["launches"] == 3 and rec["flops"] > 0
    assert "rows=512" in rec["signature"]
    # unknown model: silent no-op, never a crash
    obs.record_model_launch("pallas.nope", rows=1)


# ------------------------------------------------ flush / trace plumbing
def test_flush_emits_cost_records_and_backend_meta(telemetry, tmp_path):
    cj = obs.costed_jit("test.flushme", lambda x: x.sum())
    with obs.span("TRAIN", kind="step"):
        float(cj(jnp.ones(16)))
    trace = str(tmp_path / "telemetry" / "trace.jsonl")
    assert obs.flush(trace, step="TRAIN")
    lines = [json.loads(line) for line in open(trace)]
    assert lines[0]["schema_version"] == obs.SCHEMA_VERSION == 15
    assert lines[0]["backend"]["platform"]      # peak-table resolver key
    costs = [ln for ln in lines if ln["kind"] == "cost"]
    assert len(costs) == 1 and costs[0]["name"] == "test.flushme"
    from shifu_tpu.obs.report import load_blocks
    (block,) = load_blocks(trace)
    assert block["costs"] == costs
    # flush drained the cost accumulation: a second flush adds none
    assert obs.flush(trace, step="EMPTY")
    lines2 = [json.loads(line) for line in open(trace)]
    assert sum(1 for ln in lines2 if ln["kind"] == "cost") == 1
    # ...but a warm relaunch re-emits the entry with launches=1
    float(cj(jnp.ones(16)))
    assert obs.flush(trace, step="WARM")
    lines3 = [json.loads(line) for line in open(trace)]
    warm = [ln for ln in lines3 if ln["kind"] == "cost"][-1]
    assert warm["launches"] == 1 and warm["compiles"] == 0


# -------------------------------------------------------- compile listener
def test_compile_listener_counts_time_spent_not_time_saved(telemetry):
    """``xla.compile_time_s`` sums jax's trace/lower/backend-compile
    stages; the persistent cache's ``compile_time_saved_sec`` event (time
    NOT spent, emitted on every cache hit) must not land in it — it once
    did, so a warm-cache run reported its cold compile time."""
    import jax.monitoring
    obs.ensure_compile_listener()
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/compile_time_saved_sec", 100.0)
    assert _metric("xla.compile_time_s") is None
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 0.25)
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.5)
    assert _metric("xla.compile_time_s")["value"] == pytest.approx(0.75)
    assert _metric("xla.compile_count")["value"] == 1


# ------------------------------------------------------------ peak table
def test_resolve_peaks_table_and_env_override(monkeypatch):
    monkeypatch.delenv("SHIFU_TPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("SHIFU_TPU_PEAK_BW", raising=False)
    f, b, label = costs_mod.resolve_peaks({"platform": "tpu",
                                           "device_kind": "TPU v4"})
    assert (f, b) == (275e12, 1228e9) and label == "tpu v4"
    f, b, label = costs_mod.resolve_peaks({"platform": "tpu",
                                           "device_kind": "TPU v5 lite"})
    assert (f, b) == (197e12, 819e9) and label == "tpu v5 lite"
    # a device_kind the table does not know has NO peak — neither the
    # platform name nor an unknown/unknown stamp matches a row
    for backend in ({"platform": "cpu", "device_kind": "cpu"},
                    {"platform": "tpu", "device_kind": "TPU v99"},
                    {"platform": "unknown", "device_kind": "unknown"}):
        assert costs_mod.resolve_peaks(backend) == \
            (None, None, costs_mod.PEAK_UNKNOWN)
    monkeypatch.setenv("SHIFU_TPU_PEAK_FLOPS", "2e12")
    monkeypatch.setenv("SHIFU_TPU_PEAK_BW", "3e11")
    f, b, label = costs_mod.resolve_peaks({"platform": "cpu",
                                           "device_kind": "cpu"})
    assert (f, b) == (2e12, 3e11)
    assert "SHIFU_TPU_PEAK_FLOPS" in label


def test_verdict_roofline_split():
    # machine balance = 1e11/5e10 = 2 FLOPs/byte
    assert util_mod.verdict_for(4e6, 1e6, 1e11, 5e10) == "compute-bound"
    assert util_mod.verdict_for(1e6, 4e6, 1e11, 5e10) == "bandwidth-bound"
    assert util_mod.verdict_for(0, 0, 1e11, 5e10) == "no-cost-data"
    assert util_mod.verdict_for(4e6, 1e6, None, None) == \
        costs_mod.PEAK_UNKNOWN


# ------------------------------------------------- utilization report
def _write_golden_trace(td):
    """A hand-built v6 trace with FIXED values — the golden's input."""
    os.makedirs(os.path.join(td, "telemetry"))
    lines = [
        {"kind": "meta", "schema_version": 7, "step": "TRAIN", "ts": 1.0,
         "pid": 7, "backend": {"platform": "cpu", "device_kind": "cpu"}},
        {"kind": "span", "name": "TRAIN", "id": 1, "parent": None,
         "ts": 1.0, "dur_s": 2.0, "tid": "MainThread", "attrs": {}},
        {"kind": "metric", "type": "counter", "name": "ingest.rows_emitted",
         "value": 9000.0},
        {"kind": "metric", "type": "counter", "name": "ingest.rows_padded",
         "value": 1000.0},
        {"kind": "metric", "type": "counter", "name": "xla.recompiles",
         "value": 1.0},
        {"kind": "cost", "name": "gbt.forest", "signature": "f32[100,8]",
         "flops": 4.0e9, "bytes_accessed": 1.0e9, "compiles": 1,
         "launches": 2, "analytic": False},
        {"kind": "cost", "name": "nn.step", "signature": "f32[100,8]",
         "flops": 1.0e9, "bytes_accessed": 4.0e9, "compiles": 1,
         "launches": 1, "analytic": False},
    ]
    with open(os.path.join(td, "telemetry", "trace.jsonl"), "w") as f:
        for ln in lines:
            f.write(json.dumps(ln) + "\n")


def test_utilization_report_golden(telemetry, tmp_path, monkeypatch):
    """CI/tooling satellite: the report renders DETERMINISTICALLY —
    stable plane sort, fixed float formats — so this golden is
    diff-stable."""
    monkeypatch.setenv("SHIFU_TPU_PEAK_FLOPS", "1e11")
    monkeypatch.setenv("SHIFU_TPU_PEAK_BW", "5e10")
    td = str(tmp_path)
    _write_golden_trace(td)
    text = util_mod.render_utilization(td)
    assert text == util_mod.render_utilization(td)   # deterministic
    lines = text.splitlines()
    assert lines[0].startswith("utilization: ")
    assert "== TRAIN  wall 2.000s" in lines
    # gbt: 8e9 flops (4e9 x 2 launches) / 2s = 4e9 FLOP/s = 4% of 1e11;
    # 2e9 B (1e9 x 2) / 2s = 1e9 B/s = 2% of 5e10; intensity 4 >= 2
    gbt = next(ln for ln in lines if ln.strip().startswith("gbt"))
    assert "8.000e+09" in gbt and "4.000e+09" in gbt
    assert "4.00%" in gbt and "2.00%" in gbt
    assert gbt.rstrip().endswith("compute-bound")
    # nn: 1e9/2s = 5e8 FLOP/s (0.5%); 4e9 B -> 2e9 B/s (4%); intensity
    # 0.25 < balance 2 -> bandwidth-bound
    nn = next(ln for ln in lines if ln.strip().startswith("nn"))
    assert "5.000e+08" in nn and nn.rstrip().endswith("bandwidth-bound")
    assert any("2 costed, 2 compile(s), 3 launch(es)" in ln
               and "1 RECOMPILE(S)" in ln for ln in lines)
    # padding waste: 1000 padded of 10000 window rows = 10%
    assert any("1,000 padded of 10,000" in ln and "10.00%" in ln
               for ln in lines)
    # pipeline closing line: MFU = 9e9 flops / (2s * 1e11)
    assert lines[-1].startswith("pipeline: ")
    assert "MFU 4.50%" in lines[-1]


def test_utilization_acceptance_gbt_plus_nn(telemetry, tmp_path, rng,
                                            monkeypatch):
    """ACCEPTANCE: `analysis --telemetry --utilization` on a GBT-train +
    NN-train run reports per-plane achieved FLOP/s, bytes/s,
    percent-of-peak and a roofline verdict — against peaks supplied for
    this CPU rig; without them the same trace renders "peak unknown"
    with achieved rates only."""
    monkeypatch.setenv("SHIFU_TPU_PEAK_FLOPS", "1e11")
    monkeypatch.setenv("SHIFU_TPU_PEAK_BW", "5e10")
    from shifu_tpu.models.nn import NNModelSpec
    from shifu_tpu.train.dt_trainer import DTSettings, train_gbt
    from shifu_tpu.train.nn_trainer import TrainSettings, train_ensemble

    n, d = 256, 8
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) < 0.4).astype(np.float32)
    w = np.ones((1, n), np.float32)
    spec = NNModelSpec(input_dim=d, hidden_nodes=[8],
                       activations=["tanh"])
    with obs.span("TRAIN", kind="step"):
        train_ensemble(x, y, w, w, spec,
                       TrainSettings(optimizer="ADAM", learning_rate=0.01,
                                     epochs=3))
    bins = rng.integers(0, 16, size=(512, d)).astype(np.int32)
    yt = (rng.random(512) < 0.3).astype(np.float32)
    wt = np.ones(512, np.float32)
    with obs.span("TRAIN", kind="step"):
        train_gbt(bins, yt, wt, 16, np.zeros(d, bool),
                  DTSettings(n_trees=3, depth=3, loss="log",
                             learning_rate=0.1))
    obs.flush(os.path.join(str(tmp_path), "telemetry", "trace.jsonl"),
              step="TRAIN")

    text = util_mod.render_utilization(str(tmp_path))
    lines = text.splitlines()
    nn_line = next(ln for ln in lines if ln.strip().startswith("nn"))
    gbt_line = next(ln for ln in lines if ln.strip().startswith("gbt"))
    for ln in (nn_line, gbt_line):
        assert "e+0" in ln or "e-0" in ln        # achieved rates render
        assert "%" in ln                         # percent-of-peak
        assert ln.rstrip().endswith(("compute-bound", "bandwidth-bound"))
    assert "launch(es)" in text
    monkeypatch.delenv("SHIFU_TPU_PEAK_FLOPS")
    monkeypatch.delenv("SHIFU_TPU_PEAK_BW")
    unknown = util_mod.render_utilization(str(tmp_path))
    assert "peaks[peak unknown]" in unknown
    assert "MFU not computed (peak unknown)" in unknown.splitlines()[-1]
    gbt_unknown = next(ln for ln in unknown.splitlines()
                       if ln.strip().startswith("gbt"))
    assert "e+0" in gbt_unknown or "e-0" in gbt_unknown
    assert "%" not in gbt_unknown                # no percent-of-peak
    assert gbt_unknown.rstrip().endswith("peak unknown")
    # the CLI surface returns 0 and prints the same payload
    from shifu_tpu.cli import main
    assert main(["--dir", str(tmp_path), "analysis", "--telemetry",
                 "--utilization"]) == 0


# -------------------------------------------------------- padding waste
def test_streamed_windows_count_padded_rows(telemetry, tmp_path):
    from shifu_tpu.data.shards import Shards
    from shifu_tpu.data.streaming import ShardStream

    rng = np.random.default_rng(0)
    sd = str(tmp_path / "shards")
    os.makedirs(sd)
    rows = 700                                  # 2 windows of 512: 324 pad
    np.savez(os.path.join(sd, "part-00000.npz"),
             bins=rng.integers(0, 16, (rows, 4)).astype(np.int16),
             y=np.zeros(rows, np.float32), w=np.ones(rows, np.float32))
    with open(os.path.join(sd, "schema.json"), "w") as f:
        json.dump({"columnNums": list(range(4)), "numShards": 1,
                   "numRows": rows}, f)
    stream = ShardStream(Shards.open(sd), ("bins", "y", "w"), 512,
                         spill=False)
    for _ in stream.windows():
        pass
    assert _metric("ingest.rows_emitted")["value"] == rows
    assert _metric("ingest.rows_padded")["value"] == 2 * 512 - rows


# ------------------------------------------- timeline costs + torn lines
def test_timeline_annotates_costs_and_tolerates_torn_tail(telemetry,
                                                          tmp_path):
    """Timeline-hardening satellite: a torn final trace.jsonl line is
    skipped (surfaced in otherData.torn_lines_skipped), and cost
    records annotate the export — root spans carry flops/bytes args,
    executables land as cost: instants."""
    _write_golden_trace(str(tmp_path))
    trace = os.path.join(str(tmp_path), "telemetry", "trace.jsonl")
    with open(trace, "a") as f:
        f.write('{"kind": "cost", "name": "torn')     # crash mid-write
    skipped = []
    out = timeline_mod.export_timeline(str(tmp_path),
                                       str(tmp_path / "tl.json"),
                                       skipped=skipped)
    assert out and len(skipped) == 1
    with open(out) as f:
        doc = json.load(f)
    assert doc["otherData"]["torn_lines_skipped"] == 1
    root = next(e for e in doc["traceEvents"]
                if e["ph"] == "X" and e["name"] == "TRAIN")
    assert root["args"]["flops"] == pytest.approx(9.0e9)   # 4e9*2 + 1e9
    assert root["args"]["bytes_accessed"] == pytest.approx(6.0e9)
    cost_ev = [e for e in doc["traceEvents"]
               if e["ph"] == "i" and e["name"].startswith("cost:")]
    assert {e["name"] for e in cost_ev} == {"cost:gbt.forest",
                                            "cost:nn.step"}
    assert cost_ev[0]["args"]["flops"] > 0


# --------------------------------------------------- monitor --json
def _health_rec(proc, ts, state="running", **kw):
    rec = {"proc": proc, "step": "TRAIN", "state": state, "ts": ts,
           "last_progress_ts": ts, "interval_s": 0.5, "rows": 10}
    rec.update(kw)
    return rec


def test_monitor_json_snapshot_and_exit_codes(tmp_path):
    """Satellite: `monitor --once --json` emits ONE machine-readable doc
    (per-proc health + quorum summary); exit 0 healthy, 3 when any proc
    is stalled or stale."""
    from shifu_tpu.obs.health import health_dir_for
    hd = health_dir_for(str(tmp_path))
    os.makedirs(hd)
    now = time.time()
    with open(os.path.join(hd, "a.json"), "w") as f:
        json.dump(_health_rec("train-1", now), f)
    with open(os.path.join(hd, "b.json"), "w") as f:
        json.dump(_health_rec("train-2", now, state="exited",
                              exit_code=0), f)
    doc, rc = monitor_mod.status_json(str(tmp_path), now=now)
    assert rc == 0
    assert doc["kind"] == "monitor" and doc["schema_version"] == 15
    assert doc["summary"]["counts"] == {"live": 1, "stalled": 0,
                                        "stale": 0, "exited": 1}
    assert doc["summary"]["quorum"] == 1.0
    assert {p["proc"] for p in doc["procs"]} == {"train-1", "train-2"}
    assert all("status" in p and "age_s" in p for p in doc["procs"])
    json.dumps(doc)                              # strictly serializable

    # one proc stops beating -> stale -> exit 3
    with open(os.path.join(hd, "a.json"), "w") as f:
        json.dump(_health_rec("train-1", now - 60), f)
    doc, rc = monitor_mod.status_json(str(tmp_path), now=now)
    assert rc == monitor_mod.EXIT_UNHEALTHY == 3
    assert doc["summary"]["counts"]["stale"] == 1

    # the CLI loop path prints exactly one JSON doc and returns the code
    printed = []
    rc = monitor_mod.run_monitor(str(tmp_path), once=True, json_mode=True,
                                 _print=printed.append)
    assert rc == 3 and len(printed) == 1
    assert json.loads(printed[0])["kind"] == "monitor"
    # empty dir: healthy (nothing running), exit 0, still a JSON doc
    doc, rc = monitor_mod.status_json(str(tmp_path / "none"))
    assert rc == 0 and doc["procs"] == []


def test_monitor_json_cli_exit_zero_empty(tmp_path):
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.cli", "--dir", str(tmp_path),
         "monitor", "--once", "--json"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert p.returncode == 0, p.stderr
    doc = json.loads(p.stdout)
    assert doc["kind"] == "monitor" and doc["procs"] == []
