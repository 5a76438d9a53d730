"""Observability plane v2 suite: thread-safe registry, heartbeats +
``monitor`` (incl. the SIGSTOP-staleness integration test), timeline
export (Chrome trace_event schema, ingest track), OpenMetrics/JSON
snapshots, the streaming drift monitor (incremental == batch PSI), the
``obs:heartbeat`` fault site, graceful ``analysis --telemetry`` on
missing/torn traces, and the metric-name manifest lint."""

import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from shifu_tpu import obs
from shifu_tpu.obs import drift as drift_mod
from shifu_tpu.obs import exporter as exporter_mod
from shifu_tpu.obs import health as health_mod
from shifu_tpu.obs import monitor as monitor_mod
from shifu_tpu.obs import timeline as timeline_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.obs        # `pytest -m obs` collects this suite


@pytest.fixture
def telemetry():
    obs.reset_for_tests()
    obs.set_enabled(True)
    yield obs
    obs.reset_for_tests()


def _subprocess_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_ENABLE_X64"] = "true"
    env.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/shifu_tpu_jax_cache")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("SHIFU_TPU_FAULTS", None)
    env.update(extra)
    return env


# ------------------------------------------------- registry thread-safety
def test_registry_concurrent_increments_exact(telemetry):
    """ingest.* counters increment from the prepared() prep thread while
    trainers update train.* on the main thread and the heartbeat thread
    snapshots — concurrent inc() must lose NO updates (a bare += is a
    non-atomic read-modify-write under the GIL)."""
    c = obs.counter("ingest.windows_emitted")
    h = obs.histogram("train.epoch_s")
    g = obs.gauge("train.valid_err")
    N, T = 20_000, 8
    stop = threading.Event()

    def snapshotter():
        while not stop.is_set():
            obs.snapshot(reset=False)        # heartbeat/exporter reader

    def worker(k):
        for i in range(N):
            c.inc()
            h.observe(float(i))
            g.set_max(float(k * N + i))

    reader = threading.Thread(target=snapshotter, daemon=True)
    reader.start()
    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    reader.join(timeout=5)
    snap = {m["name"]: m for m in obs.snapshot()}
    assert snap["ingest.windows_emitted"]["value"] == N * T
    assert snap["train.epoch_s"]["count"] == N * T
    assert snap["train.valid_err"]["value"] == T * N - 1


# ------------------------------------------------------------- heartbeats
def test_heartbeat_file_contents_and_progress(telemetry, tmp_path):
    hd = str(tmp_path / "health")
    hb = obs.start_heartbeat(hd, step="TRAIN", interval_s=0.1)
    assert hb is not None
    try:
        with obs.span("TRAIN", kind="step"):
            with obs.span("process", kind="phase"):
                obs.counter("ingest.rows_emitted").inc(1234)
                obs.counter("ingest.windows_emitted").inc(3)
                obs.counter("train.trees").inc(7)
                time.sleep(0.35)             # a few beats land
    finally:
        hb.stop(exit_code=0)
    (rec,) = obs.read_health(hd)
    assert rec["kind"] == "health"
    assert rec["schema_version"] == obs.SCHEMA_VERSION
    assert rec["step"] == "TRAIN" and rec["pid"] == os.getpid()
    assert rec["state"] == "exited" and rec["exit_code"] == 0
    assert rec["rows"] == 1234
    assert rec["windows"] == 3 and rec["trees"] == 7
    assert rec["beat"] >= 2                  # the thread really beat
    assert rec["interval_s"] == pytest.approx(0.1)
    # progress timestamps moved when counters moved
    assert rec["last_progress_ts"] >= rec["started_ts"]
    # mid-run beats captured the live phase (deepest main-thread span)
    mid = hb._record("running", None)
    assert mid["phase"] is None              # spans closed by now
    assert obs.classify(rec) == "exited"


def test_heartbeat_phase_tracks_live_spans(telemetry, tmp_path):
    hb = health_mod.HeartbeatWriter(str(tmp_path), step="STATS",
                                    interval_s=5.0)
    hb._started_ts = time.time()
    with obs.span("STATS", kind="step"):
        with obs.span("fused_sweep", kind="phase"):
            rec = hb._record("running", None)
    assert rec["phase"] == "fused_sweep"     # deepest main-thread span
    assert rec["spans"]["MainThread"] == "fused_sweep"


def test_classify_staleness_model():
    now = 1000.0
    base = {"state": "running", "interval_s": 0.5, "ts": now - 0.2,
            "last_progress_ts": now - 1.0}
    assert health_mod.classify(dict(base), now=now) == "live"
    # SIGSTOP'd: no heartbeat for > STALE_FACTOR x interval -> stale
    assert health_mod.classify(dict(base, ts=now - 1.5), now=now) == "stale"
    # alive but no progress-counter movement -> stalled (straggler flag)
    assert health_mod.classify(
        dict(base, last_progress_ts=now - 500), now=now) == "stalled"
    assert health_mod.classify(
        dict(base, state="exited"), now=now) == "exited"
    # the acceptance bound: staleness flips WITHIN 2 heartbeat intervals
    assert health_mod.STALE_FACTOR == 2.0


def test_monitor_renders_and_flags(telemetry, tmp_path):
    mdir = str(tmp_path)
    hd = health_mod.health_dir_for(mdir)
    os.makedirs(hd)
    now = time.time()
    with open(os.path.join(hd, "train-1.json"), "w") as f:
        json.dump({"proc": "train-1", "step": "TRAIN", "state": "running",
                   "ts": now, "last_progress_ts": now, "interval_s": 0.5,
                   "rows": 4096, "windows": 8, "trees": 12,
                   "phase": "process",
                   "spans": {"MainThread": "process",
                             "shifu-ingest": "ingest.window_prep"}}, f)
    with open(os.path.join(hd, "train-2.json"), "w") as f:
        json.dump({"proc": "train-2", "step": "TRAIN", "state": "running",
                   "ts": now - 60, "last_progress_ts": now - 60,
                   "interval_s": 0.5, "rows": 10}, f)
    text = monitor_mod.render_status(mdir, now=now)
    assert "train-1" in text and "live" in text
    assert "4,096" in text and "process" in text
    assert "ingest.window_prep" in text      # the ingest thread's span
    assert "STALE" in text                   # train-2 stopped beating
    assert "quorum 1/2" in text
    # empty dir: a message, not a traceback
    assert "no health records" in monitor_mod.render_status(
        str(tmp_path / "other"))


def test_monitor_cli_once_exit_zero(tmp_path):
    p = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.cli", "--dir", str(tmp_path),
         "monitor", "--once"],
        capture_output=True, text=True, env=_subprocess_env(), cwd=REPO,
        timeout=120)
    assert p.returncode == 0, p.stderr
    assert "no health records" in p.stdout


def test_monitor_flags_sigstopped_train_subprocess(prepared_set):
    """ACCEPTANCE: `shifu_tpu monitor` shows live per-process step/phase/
    rows during a streamed GBT train, and flags a SIGSTOP'd process as
    stale within 2 heartbeat intervals."""
    from shifu_tpu.config import ModelConfig
    mc_path = os.path.join(prepared_set, "ModelConfig.json")
    mc = ModelConfig.load(mc_path)
    mc.train.algorithm = "GBT"
    # big forest = the train outlives every assertion below; the parent
    # kills the subprocess once staleness is proven
    mc.train.params = {"TreeNum": 5000, "MaxDepth": 4}
    mc.save(mc_path)
    interval = 0.25
    env = _subprocess_env(SHIFU_TPU_TELEMETRY="1",
                          SHIFU_TPU_HEARTBEAT_S=str(interval))
    p = subprocess.Popen(
        [sys.executable, "-m", "shifu_tpu.cli", "--dir", prepared_set,
         "-Dshifu.train.streaming=on", "train"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, cwd=REPO)
    try:
        hd = os.path.join(prepared_set, "telemetry", "health")
        deadline = time.time() + 180        # covers a cold XLA compile

        def wait_for(pred, what):
            while time.time() < deadline:
                recs = obs.read_health(hd)
                if recs and pred(recs[0]):
                    return recs[0]
                assert p.poll() is None, \
                    (f"train exited rc={p.poll()} before {what}\n"
                     + p.stderr.read().decode(errors="replace"))
                time.sleep(0.05)
            raise AssertionError(f"timed out waiting for {what}")

        wait_for(lambda r: r.get("state") == "running", "first heartbeat")
        rec = wait_for(lambda r: (r.get("rows") or 0) > 0
                       and r.get("phase"), "streamed rows + phase")
        assert rec["step"] == "TRAIN"
        text = monitor_mod.render_status(prepared_set)
        assert "TRAIN" in text and "live" in text

        os.kill(p.pid, signal.SIGSTOP)
        time.sleep(health_mod.STALE_FACTOR * interval + 2 * interval)
        (rec,) = obs.read_health(hd)
        assert obs.classify(rec) == "stale"
        text = monitor_mod.render_status(prepared_set)
        assert "stale" in text and "STALE" in text
    finally:
        try:
            os.kill(p.pid, signal.SIGCONT)
        except OSError:
            pass
        p.kill()
        p.communicate(timeout=60)


# -------------------------------------------------- obs:heartbeat faults
def test_heartbeat_kill_leaves_no_torn_health_file(tmp_path):
    """Fault-site interaction: heartbeat writes ride ioutil's atomic
    path, so a hard death mid-heartbeat (obs:heartbeat=<b>:kill) leaves
    the PREVIOUS valid health file — never a torn one — and the next
    writer recovers in place."""
    hd = str(tmp_path / "health")
    script = (
        "import time\n"
        "from shifu_tpu import obs\n"
        "obs.set_enabled(True)\n"
        "obs.counter('train.trees').inc(3)\n"
        f"hb = obs.start_heartbeat({hd!r}, step='TRAIN', proc='train-x',\n"
        "                          interval_s=0.05)\n"
        "time.sleep(5)\n")
    p = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=_subprocess_env(SHIFU_TPU_FAULTS="obs:heartbeat=1:kill"),
        cwd=REPO, timeout=120)
    assert p.returncode == 137, p.stderr     # died ON beat 1's commit
    path = os.path.join(hd, "train-x.json")
    with open(path) as f:
        rec = json.load(f)                   # beat 0 intact, NOT torn
    assert rec["beat"] == 0 and rec["state"] == "running"
    assert rec["trees"] == 3
    # recovery: a fresh writer (same proc name) owns the file again
    p2 = subprocess.run(
        [sys.executable, "-c", script.replace("time.sleep(5)",
                                              "time.sleep(0.12)\n"
                                              "hb.stop(exit_code=0)")],
        capture_output=True, text=True, env=_subprocess_env(), cwd=REPO,
        timeout=120)
    assert p2.returncode == 0, p2.stderr
    with open(path) as f:
        rec = json.load(f)
    assert rec["state"] == "exited" and rec["beat"] >= 1
    # the orphan tmp the killed write may have left was swept on start
    assert [f for f in os.listdir(hd) if ".tmp" in f] == []


# --------------------------------------------------------------- timeline
def _make_stream_trace(td, telemetry):
    """A real telemetry trace containing main-thread AND ingest-thread
    spans: one prepared() sweep over tiny materialized shards."""
    from shifu_tpu.data.shards import Shards
    from shifu_tpu.data.streaming import ShardStream
    rng = np.random.default_rng(0)
    sd = os.path.join(td, "shards")
    os.makedirs(sd)
    for k in range(3):
        np.savez(os.path.join(sd, f"part-{k:05d}.npz"),
                 bins=rng.integers(0, 16, (512, 4)).astype(np.int16),
                 y=np.zeros(512, np.float32), w=np.ones(512, np.float32))
    with open(os.path.join(sd, "schema.json"), "w") as f:
        json.dump({"columnNums": list(range(4)), "numShards": 3,
                   "numRows": 1536}, f)
    stream = ShardStream(Shards.open(sd), ("bins", "y", "w"), 512,
                         spill=False)
    with obs.span("TRAIN", kind="step"):
        with obs.span("process", kind="phase"):
            for _ in stream.prepared(lambda w: w, depth=2):
                pass
    trace = os.path.join(td, "telemetry", "trace.jsonl")
    obs.flush(trace, step="TRAIN")
    return trace


def test_timeline_chrome_trace_event_schema(telemetry, tmp_path):
    """ACCEPTANCE: --timeline output is valid Chrome trace_event JSON
    with ingest-prep spans on a separate track from device compute."""
    _make_stream_trace(str(tmp_path), telemetry)
    out = timeline_mod.export_timeline(str(tmp_path),
                                       str(tmp_path / "tl.json"))
    with open(out) as f:
        doc = json.load(f)
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "i", "M")
        assert isinstance(ev["name"], str)
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], int) and ev["dur"] >= 1
        elif ev["ph"] == "i":
            assert ev["s"] in ("t", "p", "g")
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ingest_tids = {e["tid"] for e in spans
                   if e["name"].startswith("ingest.window_prep")}
    compute_tids = {e["tid"] for e in spans if e["name"] == "TRAIN"}
    assert ingest_tids and compute_tids
    assert ingest_tids.isdisjoint(compute_tids)
    # both tracks carry a thread_name metadata label
    labels = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "ingest" in labels[next(iter(ingest_tids))]
    # span wall-clock survives the unit conversion (us)
    train = next(e for e in spans if e["name"] == "TRAIN")
    assert train["dur"] < 60_000_000        # sane: < 60 s


def test_timeline_pre_v5_trace_routes_by_name(tmp_path):
    """Traces written before schema v5 carry no tid — ingest.* spans
    still route to the ingest track by name."""
    blocks = [{"meta": {"step": "TRAIN", "pid": 7, "ts": 1.0},
               "spans": [
                   {"kind": "span", "name": "TRAIN", "id": 1,
                    "parent": None, "ts": 1.0, "dur_s": 2.0, "attrs": {}},
                   {"kind": "span", "name": "ingest.window_prep", "id": 2,
                    "parent": None, "ts": 1.1, "dur_s": 0.5, "attrs": {}}],
               "events": [], "metrics": []}]
    doc = timeline_mod.to_trace_events(blocks)
    by_name = {e["name"]: e for e in doc["traceEvents"]
               if e["ph"] == "X"}
    assert by_name["TRAIN"]["tid"] == timeline_mod.TID_MAIN
    assert by_name["ingest.window_prep"]["tid"] == timeline_mod.TID_INGEST


def test_timeline_cli(telemetry, tmp_path, capsys):
    from shifu_tpu.cli import main
    _make_stream_trace(str(tmp_path), telemetry)
    out = str(tmp_path / "timeline.json")
    assert main(["--dir", str(tmp_path), "analysis", "--telemetry",
                 "--timeline", out]) == 0
    assert "timeline ->" in capsys.readouterr().out
    with open(out) as f:
        assert json.load(f)["traceEvents"]
    # no trace: hint + exit 0, no file
    assert main(["--dir", str(tmp_path / "none"), "analysis",
                 "--telemetry", "--timeline",
                 str(tmp_path / "no.json")]) == 0
    assert "no telemetry recorded" in capsys.readouterr().out
    assert not os.path.exists(str(tmp_path / "no.json"))


# ----------------------------------------------------- metrics snapshots
def test_openmetrics_rendering(telemetry):
    obs.counter("ingest.bytes_read").inc(4096)
    obs.gauge("drift.psi_max").set(0.125)
    obs.histogram("train.epoch_s").observe(0.5)
    obs.histogram("train.epoch_s").observe(1.5)
    text = exporter_mod.render_openmetrics()
    assert text.endswith("# EOF\n")
    # schema-versioned naming: the handshake gauge + sanitized names
    assert (f"shifu_tpu_telemetry_schema_version {obs.SCHEMA_VERSION}"
            in text)
    assert "# TYPE shifu_tpu_ingest_bytes_read counter" in text
    assert "shifu_tpu_ingest_bytes_read_total 4096" in text
    assert "shifu_tpu_drift_psi_max 0.125" in text
    assert "# TYPE shifu_tpu_train_epoch_s summary" in text
    assert "shifu_tpu_train_epoch_s_count 2" in text
    assert "shifu_tpu_train_epoch_s_sum 2" in text
    assert "shifu_tpu_train_epoch_s_max 1.5" in text
    # the OpenMetrics charset holds for every exposed name (quantile
    # sample lines carry a {quantile="..."} label set, v8)
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name = line.split(" ")[0].split("{")[0]
        assert re.fullmatch(r"[a-zA-Z_][a-zA-Z0-9_]*", name), name


def test_openmetrics_histogram_quantile_lines(telemetry):
    """Satellite: histogram summaries expose p50/p99 quantile sample
    lines (the registry's log-sketch estimates), not just count/sum."""
    h = obs.histogram("serve.batch_latency_ms")
    for _ in range(99):
        h.observe(2.0)
    h.observe(80.0)
    text = exporter_mod.render_openmetrics()
    q = {}
    for line in text.splitlines():
        if line.startswith("shifu_tpu_serve_batch_latency_ms{quantile="):
            key = line.split('quantile="')[1].split('"')[0]
            q[key] = float(line.split("} ")[1])
    assert set(q) == {"0.5", "0.99"}
    # sketch resolution is ~6.6%/bin: loose relative bounds
    assert q["0.5"] == pytest.approx(2.0, rel=0.15)
    assert q["0.99"] == pytest.approx(2.0, rel=0.15)
    h.observe(80.0)                          # now >1% of mass is at 80
    for _ in range(8):
        h.observe(80.0)
    text = exporter_mod.render_openmetrics()
    line = next(l for l in text.splitlines()
                if l.startswith("shifu_tpu_serve_batch_latency_ms"
                                '{quantile="0.99"}'))
    assert float(line.split("} ")[1]) == pytest.approx(80.0, rel=0.15)
    # pre-v8 snapshot records (no p50/p99 keys) still render summaries
    legacy = [{"kind": "metric", "type": "histogram", "name": "old.h",
               "count": 2, "sum": 3.0, "min": 1.0, "max": 2.0,
               "last": 2.0}]
    text = exporter_mod.render_openmetrics(legacy)
    assert "shifu_tpu_old_h_count 2" in text
    assert 'shifu_tpu_old_h{quantile' not in text


def test_exporter_periodic_and_final_write(telemetry, tmp_path):
    td = str(tmp_path / "telemetry")
    obs.counter("train.trees").inc(5)
    exp = obs.start_exporter(td, step="TRAIN", interval_s=0.05)
    assert exp is not None
    time.sleep(0.2)
    obs.counter("train.trees").inc(5)
    exp.stop()                               # final closing dump
    with open(os.path.join(td, "metrics.json")) as f:
        doc = json.load(f)
    assert doc["schema_version"] == obs.SCHEMA_VERSION
    assert doc["step"] == "TRAIN"
    metrics = {m["name"]: m for m in doc["metrics"]}
    assert metrics["train.trees"]["value"] == 10   # stop() re-dumped
    prom = open(os.path.join(td, "metrics.prom")).read()
    assert "shifu_tpu_train_trees_total 10" in prom
    assert [f for f in os.listdir(td) if ".tmp" in f] == []


# ---------------------------------------------------------- drift monitor
def _drift_columns(rng, n_cols=6, n_bins=8, n_train=4000):
    """ColumnConfigs with boundaries + training-time per-bin counts, and
    the training rows they summarize."""
    from shifu_tpu.config.column_config import ColumnConfig
    cols, train_bins = [], []
    for j in range(n_cols):
        # num_bins() == len(binBoundary): n_bins value bins + missing
        bnd = sorted(rng.normal(size=n_bins).tolist())
        tb = rng.integers(0, n_bins + 1, size=n_train)   # incl. missing
        counts = np.bincount(tb, minlength=n_bins + 1)
        pos = rng.binomial(counts, 0.3)
        cc = ColumnConfig(columnNum=j, columnName=f"c{j}")
        cc.columnBinning.binBoundary = bnd
        cc.columnBinning.binCountNeg = (counts - pos).tolist()
        cc.columnBinning.binCountPos = pos.tolist()
        cols.append(cc)
        train_bins.append(tb)
    return cols, np.stack(train_bins, axis=1)


def test_drift_incremental_matches_batch_psi(telemetry, rng):
    """ACCEPTANCE: the streaming monitor reproduces the batch PSI of the
    stats ``-psi`` formula (ops.stats_math.psi) on the same windows,
    within f32 tolerance."""
    from shifu_tpu.ops.stats_math import psi
    cols, _ = _drift_columns(rng)
    n_bins = 9                               # 8 value bins + missing
    live = rng.integers(0, n_bins, size=(5000, len(cols)))
    live[:, 0] = np.minimum(live[:, 0], 2)   # force drift on column 0

    mon = drift_mod.DriftMonitor(cols, threshold=0.25)
    for s in range(0, len(live), 700):       # ragged windows
        mon.update(live[s:s + 700])
    inc = mon.column_psi()

    for j, cc in enumerate(cols):
        expected = (np.asarray(cc.columnBinning.binCountNeg, float)
                    + np.asarray(cc.columnBinning.binCountPos, float))
        batch = psi(expected,
                    np.bincount(live[:, j], minlength=n_bins))
        assert inc[j] == pytest.approx(float(batch), abs=1e-6)
    summ = mon.summary()
    assert summ["rows"] == 5000
    assert "c0" in summ["flagged"]           # the forced drift
    assert summ["psi_max"] == pytest.approx(np.nanmax(inc))


def test_drift_update_respects_weights_and_shape(telemetry, rng):
    cols, _ = _drift_columns(rng, n_cols=3)
    mon = drift_mod.DriftMonitor(cols)
    win = rng.integers(0, 9, size=(64, 3))
    w = np.ones(64)
    w[32:] = 0.0                             # padded streamed tail
    mon.update(win, weights=w)
    assert mon.rows == 32
    with pytest.raises(ValueError):
        mon.update(rng.integers(0, 9, size=(8, 5)))


def test_drift_emit_gauges_and_json(telemetry, tmp_path, rng):
    cols, _ = _drift_columns(rng, n_cols=4)
    mon = drift_mod.DriftMonitor(cols)
    mon.update(rng.integers(0, 9, size=(512, 4)))
    path = str(tmp_path / "telemetry" / "drift.json")
    summ = mon.emit(path=path)
    snap = {m["name"]: m for m in obs.snapshot()}
    assert snap["drift.rows"]["value"] == 512
    assert snap["drift.psi_max"]["value"] == pytest.approx(
        summ["psi_max"])
    assert snap["drift.columns_tracked"]["value"] == 4
    with open(path) as f:
        doc = json.load(f)
    assert doc["kind"] == "drift" and len(doc["columns"]) == 4
    # the report renders a drift section from the artifact
    from shifu_tpu.obs.report import _render_drift
    out = []
    _render_drift(str(tmp_path), out)
    text = "\n".join(out)
    assert "drift:" in text and "psi c" in text


def test_drift_monitor_none_without_snapshot(telemetry):
    from shifu_tpu.config.column_config import ColumnConfig
    cc = ColumnConfig(columnNum=0, columnName="bare")   # no bin counts
    assert obs.start_drift_monitor([cc]) is None


def test_norm_rerun_emits_drift_artifact(telemetry, prepared_set):
    """End-to-end wiring: a norm re-run over the SAME data as training
    writes telemetry/drift.json with near-zero PSI (live == snapshot) —
    and the health + metrics surfaces appear beside it."""
    from shifu_tpu.pipeline.norm import NormalizeProcessor
    assert NormalizeProcessor(prepared_set, params={}).run() == 0
    tel = os.path.join(prepared_set, "telemetry")
    with open(os.path.join(tel, "drift.json")) as f:
        doc = json.load(f)
    assert doc["rows"] > 0 and doc["columns"]
    # same distribution as the snapshot: tiny PSI everywhere (norm
    # sampling may drop rows, so allow loose-but-small)
    assert doc["psi_max"] < 0.05
    assert doc["flagged"] == []
    # live plane artifacts from the same run
    recs = obs.read_health(os.path.join(tel, "health"))
    assert recs and recs[0]["step"] == "NORMALIZE"
    assert recs[0]["state"] == "exited" and recs[0]["exit_code"] == 0
    assert recs[0]["rows"] > 0
    prom = open(os.path.join(tel, "metrics.prom")).read()
    assert "shifu_tpu_norm_rows_total" in prom
    assert "shifu_tpu_drift_psi_max" in prom
    # and the telemetry report picks up the drift section
    from shifu_tpu.obs.report import render_telemetry
    assert "drift:" in render_telemetry(prepared_set)


# ------------------------------------- analysis --telemetry robustness
def test_analysis_telemetry_missing_empty_torn(tmp_path, capsys):
    from shifu_tpu.cli import main
    from shifu_tpu.obs.report import render_telemetry

    # missing: hint, exit 0
    assert main(["--dir", str(tmp_path), "analysis", "--telemetry"]) == 0
    assert "no telemetry recorded" in capsys.readouterr().out

    # empty file: hint, exit 0
    tel = tmp_path / "telemetry"
    tel.mkdir()
    trace = tel / "trace.jsonl"
    trace.write_text("")
    assert main(["--dir", str(tmp_path), "analysis", "--telemetry"]) == 0
    assert "no telemetry recorded" in capsys.readouterr().out

    # torn final line (crash mid-write): skipped with a warning, the
    # valid prefix still renders, exit 0
    trace.write_text(
        json.dumps({"kind": "meta", "schema_version": obs.SCHEMA_VERSION,
                    "step": "STATS", "ts": 1.0, "pid": 1}) + "\n"
        + json.dumps({"kind": "span", "name": "pass1", "id": 1,
                      "parent": None, "ts": 1.0, "dur_s": 0.5,
                      "attrs": {"rows": 10}}) + "\n"
        + '{"kind": "metric", "type": "coun')        # torn
    text = render_telemetry(str(tmp_path))
    assert "STATS" in text and "pass1" in text
    assert "torn line(s) skipped" in text
    assert main(["--dir", str(tmp_path), "analysis", "--telemetry"]) == 0
    assert "pass1" in capsys.readouterr().out

    # only torn lines: the hint names the skip count
    trace.write_text('{"kind": "meta", "schema_')
    out = render_telemetry(str(tmp_path))
    assert "no telemetry recorded" in out and "torn line" in out


# ----------------------------------------------------- manifest lint
# The grep-based metric/span scans that lived here through round 12 are
# now first-class AST rules in shifu_tpu/lint (metric-manifest,
# span-manifest, fault-site).  These thin tests keep the tier-1
# coverage — same contract, one framework — and pin the manifests'
# own well-formedness; rule MECHANICS (seeded violations, suppression,
# baseline) live in tests/test_lint.py.


def _manifest_findings(rule: str):
    from shifu_tpu.lint import run_lint
    findings, engine = run_lint(rules=[rule], full_tree=False)
    assert engine.files_scanned > 60         # the scan really sees the tree
    return findings


def test_every_metric_name_is_declared_in_manifest():
    """Lint: a typo'd metric name would silently mint a NEW metric (the
    registry creates on first use) — every counter/gauge/histogram name
    used anywhere in shifu_tpu/ must be declared in obs.manifest, with
    the declared instrument type; f-string families must start with a
    declared prefix.  Runs the metric-manifest rule through the engine."""
    from shifu_tpu.obs import manifest
    problems = _manifest_findings("metric-manifest")
    assert not problems, "\n".join(f.render() for f in problems)
    # the declared set itself is well-formed
    for name, (kind, help_) in manifest.MANIFEST.items():
        assert kind in ("counter", "gauge", "histogram"), name
        assert help_, name


def test_every_span_name_literal_is_declared_in_manifest():
    """Satellite lint: the timeline tracks / report sections / tests
    join on span-name literals, so a typo'd span name silently vanishes
    from every report — every obs.span("...") / obs.record_span("...")
    literal must resolve against obs.manifest.SPANS.  Step-root spans
    named by variable (obs.span(self.profile_name, ...)) ride outside
    the lint."""
    from shifu_tpu.obs import manifest
    problems = _manifest_findings("span-manifest")
    assert not problems, "\n".join(f.render() for f in problems)
    # the declared span set itself is well-formed, and the serve plane's
    # request/batch spans are present
    for name, help_ in manifest.SPANS.items():
        assert help_, name
    assert "serve.request" in manifest.SPANS
    assert "serve.batch" in manifest.SPANS
    assert not manifest.is_declared_span("bench.serve")    # no span families
    assert not manifest.is_declared_span("serve.requst")   # the typo case


def test_every_fault_site_literal_is_declared():
    """Every faults.fire(site, point, ...) literal resolves against the
    faults.SITES manifest (an undeclared site could never be armed from
    the documented spec grammar and would silently never fire)."""
    from shifu_tpu import faults
    problems = _manifest_findings("fault-site")
    assert not problems, "\n".join(f.render() for f in problems)
    for (site, point), help_ in faults.SITES.items():
        assert site and point and help_, (site, point)
    assert faults.is_declared_site("serve", "swap")
    assert not faults.is_declared_site("serve", "swapz")


def test_obs_reexport_audit():
    """obs/__init__ re-export audit: everything in __all__ resolves, and
    the v2-plane API is reachable from the package root."""
    for name in obs.__all__:
        assert getattr(obs, name, None) is not None, name
    for required in ("start_heartbeat", "start_exporter",
                     "start_drift_monitor", "read_health", "classify",
                     "render_openmetrics", "live_spans", "MANIFEST",
                     "is_declared"):
        assert required in obs.__all__, required
