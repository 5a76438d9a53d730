"""Model-quality observability suite (tier-1-fast except the subprocess
SIGKILL segment-rotation drill, which is additionally marked slow).

Crash-safe score-log segments (atomic rotation, orphan sweep, disk
budget, the ``obs:scorelog`` kill drill), the delayed-label join
(watermark eviction, scalar broadcast, split bursts, drop directory),
the streaming quality monitor (live AUC / ECE / score-PSI vs the
posttrain snapshot), the refresh controller's THIRD trigger source, the
fleet monitor's merged quality row (CLI-subprocess-tested) and the
byte-deterministic ``analysis --telemetry`` quality section.

The e2e drill is the acceptance path: an in-process ``ServeServer``
with sampled score logging on, delayed outcomes arriving with FLIPPED
labels, live AUC collapsing below the posttrain baseline, and the
refresh controller recording a ``quality`` trigger and entering a
retrain cycle — then judging the promoted generation on fresh windows.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from shifu_tpu import faults, obs
from shifu_tpu.config import environment
from shifu_tpu.eval.gate import GateResult
from shifu_tpu.models.nn import (IndependentNNModel, NNModelSpec,
                                 init_params)
from shifu_tpu.obs import monitor as monitor_mod
from shifu_tpu.obs import report as report_mod
from shifu_tpu.obs.outcomes import OutcomeJoiner, outcomes_drop_dir
from shifu_tpu.obs.quality import (QualityMonitor, load_posttrain_snapshot,
                                   start_quality_monitor,
                                   write_posttrain_snapshot)
from shifu_tpu.obs.scorelog import (ScoreLog, read_score_records,
                                    scorelog_dir)
from shifu_tpu.refresh import RefreshConfig, RefreshController
from shifu_tpu.serve import ModelRegistry
from shifu_tpu.serve.server import ServeServer

pytestmark = pytest.mark.quality

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_env():
    environment.reset_for_tests()
    faults.reset_for_tests()
    yield
    environment.reset_for_tests()
    faults.reset_for_tests()
    obs.set_enabled(False)


def _nn_models(n=2, n_features=8, seed0=0):
    spec = NNModelSpec(input_dim=n_features, hidden_nodes=[8],
                       activations=["relu"])
    return [IndependentNNModel(spec, init_params(
        jax.random.PRNGKey(seed0 + i), spec)) for i in range(n)]


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------------- score log
def test_scorelog_roundtrip_rotation_and_close(tmp_path):
    root = str(tmp_path / "scorelog")
    sl = ScoreLog(root, sample_rate=1.0, segment_bytes=96)
    for i in range(6):
        req = sl.log(f"r{i}", [0.25, 0.75], gen=1, ts=100.0 + i)
        assert req == f"r{i}"           # rate 1.0 logs every request
    assert sl.stats["segments"] >= 1    # tiny segments forced rotation
    sl.close()                          # clean shutdown commits the tail
    assert not [n for n in os.listdir(root) if n.endswith(".open")]
    assert "seg-000000.jsonl" in os.listdir(root)
    skipped = []
    recs = read_score_records(root, skipped=skipped)
    assert skipped == []
    assert [r["req"] for r in recs] == [f"r{i}" for i in range(6)]
    assert recs[0] == {"ts": 100.0, "gen": 1, "req": "r0",
                       "scores": [0.25, 0.75]}


def test_scorelog_sampling_off_writes_nothing(tmp_path):
    root = str(tmp_path / "scorelog")
    sl = ScoreLog(root, sample_rate=0.0, segment_bytes=64)
    for i in range(32):
        assert sl.log(f"r{i}", [0.5]) is None
    sl.close()
    assert sl.stats["records"] == 0
    assert os.listdir(root) == []       # no segment was ever opened


def test_scorelog_mints_req_id_when_caller_has_none(tmp_path):
    sl = ScoreLog(str(tmp_path / "sl"), sample_rate=1.0)
    req = sl.log(None, [0.5], gen=0)
    assert isinstance(req, str) and len(req) == 16
    sl.close()


def test_scorelog_budget_prunes_oldest_segments(tmp_path):
    root = str(tmp_path / "scorelog")
    sl = ScoreLog(root, sample_rate=1.0, segment_bytes=64,
                  budget_bytes=200)
    for i in range(40):
        sl.log(f"r{i:03d}", [0.125], gen=0, ts=float(i))
    sl.close()
    assert sl.stats["pruned"] > 0
    names = sorted(os.listdir(root))
    # the newest committed segment survives, the oldest ones are gone
    assert "seg-000000.jsonl" not in names
    recs = read_score_records(root)
    assert recs                          # recent history is intact
    assert recs[-1]["req"] == "r039"


def test_scorelog_reader_skips_torn_tail_and_writer_recovers(tmp_path):
    root = str(tmp_path / "scorelog")
    os.makedirs(root)
    with open(os.path.join(root, "seg-000000.jsonl"), "w") as f:
        f.write(json.dumps({"req": "a", "scores": [0.5]}) + "\n")
        f.write('{"req": "torn', )       # torn line inside a committed seg
    with open(os.path.join(root, "seg-000001.jsonl.open"), "w") as f:
        f.write('{"req": "b", "sco')     # a crashed writer's torn tail
    skipped = []
    recs = read_score_records(root, skipped=skipped)
    assert [r["req"] for r in recs] == ["a"]
    assert "seg-000001.jsonl.open" in skipped
    assert "seg-000000.jsonl:2" in skipped
    # the next writer sweeps the orphan and continues AFTER the committed
    sl = ScoreLog(root, sample_rate=1.0, segment_bytes=8)
    assert sl.recovered == 1
    sl.log("c", [0.25], gen=0, ts=1.0)
    sl.close()
    names = sorted(os.listdir(root))
    assert names == ["seg-000000.jsonl", "seg-000001.jsonl"]
    assert [r["req"] for r in read_score_records(root)] == ["a", "c"]


@pytest.mark.faults
@pytest.mark.slow
def test_scorelog_kill_mid_rotation_subprocess(tmp_path):
    """ACCEPTANCE (satellite): SHIFU_TPU_FAULTS=obs:scorelog=1:kill dies
    before segment 1's atomic commit — segment 0 stays intact, readers
    skip the torn ``.open`` tail with a surfaced count, and the next
    writer sweeps the orphan and keeps going."""
    root = str(tmp_path / "scorelog")
    child = (
        "import sys\n"
        "from shifu_tpu.obs.scorelog import ScoreLog\n"
        "sl = ScoreLog(sys.argv[1], sample_rate=1.0, segment_bytes=48)\n"
        "for i in range(64):\n"
        "    sl.log('r%03d' % i, [0.25, 0.75], gen=0, ts=float(i))\n"
        "sl.close()\n"
        "print('UNREACHABLE')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["SHIFU_TPU_FAULTS"] = "obs:scorelog=1:kill"
    p = subprocess.run([sys.executable, "-c", child, root],
                       capture_output=True, text=True, env=env,
                       cwd=REPO, timeout=120)
    assert p.returncode == 137, p.stdout + p.stderr
    assert "UNREACHABLE" not in p.stdout
    names = sorted(os.listdir(root))
    assert "seg-000000.jsonl" in names          # prior commit intact
    assert "seg-000001.jsonl.open" in names     # the torn final segment
    skipped = []
    recs = read_score_records(root, skipped=skipped)
    assert skipped == ["seg-000001.jsonl.open"]
    assert recs and recs[0]["req"] == "r000"
    sl = ScoreLog(root, sample_rate=1.0, segment_bytes=48)
    assert sl.recovered == 1                    # orphan swept
    sl.log("after-crash", [0.5], gen=1, ts=99.0)
    sl.close()
    assert not [n for n in os.listdir(root) if n.endswith(".open")]
    assert read_score_records(root)[-1]["req"] == "after-crash"


# ------------------------------------------------------ delayed-label join
def test_outcome_join_scalar_broadcast_and_split_burst():
    clock = Clock()
    joined = []
    j = OutcomeJoiner(watermark_s=100.0, clock=clock,
                      on_join=lambda g, s, lab: joined.append((g, s, lab)))
    j.record_prediction("r1", [0.1, 0.2, 0.3], gen=2)
    got = j.add_outcome("r1", 1.0)               # scalar broadcasts
    assert got is not None
    gen, scores, lab = got
    assert gen == 2 and len(scores) == 3
    assert lab.tolist() == [1.0, 1.0, 1.0]
    assert len(joined) == 1 and j.stats["joined_rows"] == 3
    # a burst split across launches concatenates chunks in order
    j.record_prediction("r2", [0.4, 0.5], gen=3)
    j.record_prediction("r2", [0.6], gen=3)
    _, scores, lab = j.add_outcome("r2", [1, 0, 1])
    assert scores.tolist() == pytest.approx([0.4, 0.5, 0.6])
    assert j.pending == 0


def test_outcome_join_watermark_late_eviction_and_malformed():
    clock = Clock()
    j = OutcomeJoiner(watermark_s=10.0, clock=clock)
    j.record_prediction("old", [0.5], gen=0)
    clock.advance(20.0)
    # never-sampled request id -> late
    assert j.add_outcome("unknown", [1.0]) is None
    # the watermark horizon passed -> late, never joined
    assert j.add_outcome("old", [1.0]) is None
    assert j.stats["late"] == 2
    # eviction happens on the feed path too
    j.record_prediction("stale", [0.5], gen=0)
    clock.advance(20.0)
    j.record_prediction("fresh", [0.5], gen=0)
    assert j.stats["evicted"] == 1 and j.pending == 1
    # label/score length mismatch -> malformed, dropped
    assert j.add_outcome("fresh", [1.0, 0.0]) is None
    assert j.stats["malformed"] == 1
    assert j.stats["joined_rows"] == 0


def test_outcome_drop_dir_ingests_wrapper_and_counts_torn(tmp_path):
    clock = Clock()
    j = OutcomeJoiner(watermark_s=100.0, clock=clock)
    j.record_prediction("a", [0.5], gen=0)
    j.record_prediction("b", [0.1, 0.9], gen=0)
    drop = str(tmp_path / "outcomes")
    os.makedirs(drop)
    with open(os.path.join(drop, "feed.jsonl"), "w") as f:
        f.write(json.dumps({"req": "a", "label": 1}) + "\n")
        f.write('{"req": "torn\n')               # torn line -> malformed
        f.write(json.dumps(
            {"outcomes": [{"req": "b", "labels": [0, 1]}]}) + "\n")
    n = j.ingest_drop_dir(drop)
    assert n == 2
    assert j.stats["joined_rows"] == 3
    assert j.stats["malformed"] == 1
    assert os.listdir(drop) == []                # consumed files removed


# -------------------------------------------------------- quality monitor
def _separable(n=512, seed=7, flip=False):
    """(scores, labels): a well-separated synthetic score stream."""
    rng = np.random.default_rng(seed)
    labels = (rng.random(n) < 0.5).astype(np.float32)
    scores = np.clip(np.where(labels > 0.5,
                              rng.normal(700.0, 120.0, n),
                              rng.normal(300.0, 120.0, n)),
                     0.0, 1000.0).astype(np.float32)
    return scores, (1.0 - labels) if flip else labels


def test_write_posttrain_snapshot_doc_and_load(tmp_path):
    scores, _ = _separable()
    path = str(tmp_path / "telemetry" / "posttrain.json")
    doc = write_posttrain_snapshot(path, scores, auc=0.93, scale=1000.0)
    assert doc["kind"] == "posttrain" and doc["rows"] == 512
    assert doc["auc"] == 0.93 and doc["score_scale"] == 1000.0
    assert sum(doc["score_hist"]) == 512
    assert load_posttrain_snapshot(str(tmp_path)) == doc


def test_quality_monitor_label_flip_degrades_live_auc(tmp_path):
    scores, labels = _separable()
    snap = write_posttrain_snapshot(
        str(tmp_path / "posttrain.json"), scores, auc=0.93, scale=1000.0)
    mon = QualityMonitor(snapshot=snap, psi_threshold=0.25,
                         auc_delta=0.05, min_joined=64)
    # matched labels first: healthy, no verdict below min_joined
    mon.observe_scores(0, scores[:32])
    mon.update(0, scores[:32], labels[:32])
    summ = mon.summary()
    assert summ["live_auc"] is None and not summ["degraded"]
    mon.observe_scores(0, scores[32:])
    mon.update(0, scores[32:], labels[32:])
    summ = mon.summary()
    assert summ["live_auc"] > 0.9 and not summ["degraded"]
    assert summ["score_psi"] is not None and summ["score_psi"] < 0.25
    assert summ["ece"] is not None
    # gen 1 serves the SAME scores but outcomes arrive flipped
    mon.observe_scores(1, scores)
    mon.update(1, scores, 1.0 - labels)
    summ = mon.summary()
    assert summ["current_gen"] == 1
    assert summ["live_auc"] < 0.1
    assert summ["degraded"] and summ["reasons"] == ["live-auc"]
    assert set(summ["generations"]) == {"0", "1"}
    c = mon.compact()
    assert c["degraded"] and c["generations"]["1"] == summ["live_auc"]
    mon.reset_windows()
    fresh = mon.summary()
    assert fresh["joined"] == 0 and not fresh["degraded"]


def test_quality_monitor_score_psi_reason_without_labels(tmp_path):
    scores, _ = _separable()
    snap = write_posttrain_snapshot(
        str(tmp_path / "posttrain.json"), scores, auc=0.93, scale=1000.0)
    mon = QualityMonitor(snapshot=snap, psi_threshold=0.25,
                         auc_delta=0.05, min_joined=64)
    # the live distribution collapses onto the top bin: PSI breaches
    # with NO joined labels at all (outputs drifted, outcomes pending)
    mon.observe_scores(0, np.full(256, 990.0, np.float32))
    summ = mon.summary()
    assert summ["live_auc"] is None and summ["joined"] == 0
    assert summ["score_psi"] >= 0.25
    assert summ["degraded"] and summ["reasons"] == ["score-psi"]
    # below the evidence floor the same shift stays verdict-free
    mon2 = QualityMonitor(snapshot=snap, psi_threshold=0.25,
                          auc_delta=0.05, min_joined=64)
    mon2.observe_scores(0, np.full(16, 990.0, np.float32))
    assert not mon2.summary()["degraded"]


def test_start_quality_monitor_is_none_when_plane_off(tmp_path):
    assert start_quality_monitor(str(tmp_path)) is None   # default rate 0
    environment.set_property("shifu.scorelog.sampleRate", "0.5")
    mon = start_quality_monitor(str(tmp_path), psi_threshold=0.25)
    assert isinstance(mon, QualityMonitor)
    assert start_quality_monitor(str(tmp_path), sample_rate=0.0) is None


def test_quality_knob_plumbing():
    environment.set_property("shifu.quality.aucDelta", "0.1")
    environment.set_property("shifu.quality.psiThreshold", "0.4")
    environment.set_property("shifu.quality.minJoined", "7")
    mon = QualityMonitor()
    assert mon.auc_delta == 0.1
    assert mon.psi_threshold == 0.4
    assert mon.min_joined == 7


# ------------------------------------------------- report (golden render)
def test_report_quality_section_byte_deterministic(tmp_path):
    tel = tmp_path / "telemetry"
    tel.mkdir()
    doc = {"kind": "quality", "joined": 1234, "baseline_auc": 0.951234,
           "auc_delta": 0.05, "psi_threshold": 0.25,
           "degraded": True, "reasons": ["score-psi"],
           "generations": {
               "0": {"live_auc": 0.91, "ece": 0.02, "psi": 0.01,
                     "joined": 1000, "scored": 2000},
               "1": {"live_auc": None, "ece": None, "psi": 0.5,
                     "joined": 34, "scored": 3000}}}
    with open(tel / "quality.json", "w") as f:
        json.dump(doc, f)
    out1, out2 = [], []
    report_mod._render_quality(str(tmp_path), out1)
    report_mod._render_quality(str(tmp_path), out2)
    assert out1 == out2                         # byte-deterministic
    assert out1 == [
        "quality: 1,234 joined rows vs posttrain baseline auc 0.9512 "
        "(delta threshold 0.0500, psi threshold 0.2500)",
        "  gen 1: auc=- ece=- psi=0.5000  34 joined / 3,000 scored",
        "  gen 0: auc=0.9100 ece=0.0200 psi=0.0100  1,000 joined / "
        "2,000 scored",
        "  << QUALITY DEGRADED (score-psi)",
        "",
    ]


def test_report_quality_absent_and_torn(tmp_path):
    out = []
    report_mod._render_quality(str(tmp_path), out)
    assert out == []                            # plane never ran: silent
    tel = tmp_path / "telemetry"
    tel.mkdir()
    with open(tel / "quality.json", "w") as f:
        f.write('{"torn')
    report_mod._render_quality(str(tmp_path), out)
    assert len(out) == 1 and "unreadable (torn write?)" in out[0]


# --------------------------------------------------------- fleet monitor
def _q_extras(degraded=False, auc=0.9, psi=0.01, joined=100, gens=None):
    return {"degraded": degraded, "live_auc": auc, "score_psi": psi,
            "joined": joined, "generations": gens or {"0": auc}}


# a record a `monitor` CLI child must read as live: its interval outlasts
# the child's cold start, where the default 0.5 s is stale (age > 2 x
# interval) before the CLI reads it
CLI_INTERVAL_S = 120.0


def _write_serve_health(d, proc, quality=None, age_s=0.0, interval_s=0.5):
    hd = os.path.join(d, "telemetry", "health")
    os.makedirs(hd, exist_ok=True)
    now = time.time()
    rec = {"proc": proc, "step": "SERVE", "state": "running",
           "ts": now - age_s, "last_progress_ts": now - age_s,
           "interval_s": interval_s, "rows": 10}
    if quality is not None:
        rec["quality"] = quality
    path = os.path.join(hd, f"{proc}.json")
    with open(path, "w") as f:
        json.dump(rec, f)
    os.utime(path, (now - age_s, now - age_s))


def test_fleet_quality_merges_worst_case():
    recs = [
        {"quality": _q_extras(auc=0.9, psi=0.01, joined=100,
                              gens={"0": 0.9})},
        {"quality": _q_extras(degraded=True, auc=0.7, psi=0.3, joined=50,
                              gens={"0": 0.8, "1": None})},
        {"proc": "no-quality-extras"},
    ]
    fq = monitor_mod.fleet_quality(recs)
    assert fq["procs"] == 2
    assert fq["live_auc"] == 0.7 and fq["score_psi"] == 0.3
    assert fq["joined"] == 150 and fq["degraded"] is True
    assert fq["generations"] == {0: 0.8, 1: None}
    assert monitor_mod.fleet_quality([{"proc": "p"}]) is None


def test_monitor_status_json_exits_unhealthy_on_degraded_quality(tmp_path):
    d = str(tmp_path)
    _write_serve_health(d, "serve-0", quality=_q_extras())
    doc, rc = monitor_mod.status_json(d)
    assert rc == 0 and doc["quality"]["degraded"] is False
    _write_serve_health(d, "serve-1",
                        quality=_q_extras(degraded=True, auc=0.6))
    doc, rc = monitor_mod.status_json(d)
    assert rc == monitor_mod.EXIT_UNHEALTHY
    assert doc["quality"]["degraded"] is True
    assert doc["quality"]["live_auc"] == 0.6
    text = monitor_mod.render_status(d)
    assert "<< QUALITY DEGRADED" in text
    assert "-- quality[serve-1]: auc=0.6000" in text


def test_monitor_aggregate_fleet_quality_row_and_exit(tmp_path):
    d0, d1 = str(tmp_path / "p0"), str(tmp_path / "p1")
    _write_serve_health(d0, "serve-0", quality=_q_extras(auc=0.92))
    _write_serve_health(d1, "serve-1",
                        quality=_q_extras(degraded=True, auc=0.61,
                                          psi=0.4, joined=70))
    doc, rc = monitor_mod.aggregate_json([d0, d1])
    assert rc == monitor_mod.EXIT_UNHEALTHY
    assert not doc["summary"]["quorum_lost"]     # quality, not quorum
    assert doc["quality"]["degraded"] and doc["quality"]["procs"] == 2
    text = monitor_mod.render_aggregate([d0, d1])
    assert "-- fleet quality (2 proc(s)): worst auc=0.6100" in text
    assert "worst psi=0.4000" in text
    assert "<< QUALITY DEGRADED" in text


def test_monitor_aggregate_quality_cli_subprocess(tmp_path):
    """ACCEPTANCE (satellite): `shifu-tpu monitor --once --aggregate`
    merges per-process quality extras, flags the degraded fleet and
    exits 3; a healthy fleet exits 0."""
    d0, d1 = str(tmp_path / "p0"), str(tmp_path / "p1")
    _write_serve_health(d0, "serve-0", quality=_q_extras(auc=0.92),
                        interval_s=CLI_INTERVAL_S)
    _write_serve_health(d1, "serve-1", interval_s=CLI_INTERVAL_S,
                        quality=_q_extras(degraded=True, auc=0.61))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("SHIFU_TPU_FAULTS", None)
    p = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.cli", "monitor", "--once",
         "--aggregate", d0, d1],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert p.returncode == monitor_mod.EXIT_UNHEALTHY, p.stdout + p.stderr
    assert "QUALITY DEGRADED" in p.stdout
    assert "fleet quality (2 proc(s))" in p.stdout
    # the fleet recovers: flag off, exit 0
    _write_serve_health(d1, "serve-1", quality=_q_extras(auc=0.9),
                        interval_s=CLI_INTERVAL_S)
    p = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.cli", "monitor", "--once",
         "--aggregate", d0, d1],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "QUALITY DEGRADED" not in p.stdout


# ------------------------------------------------- refresh quality trigger
def _controller(tmp_path, quality=None, drift=None, **cfg):
    reg = ModelRegistry()
    reg.load("m", _nn_models(seed0=0), buckets=(1, 4))
    clock = Clock()
    kw = {"psi_threshold": 0.25, "cooldown_s": 10.0, "probation_s": 5.0}
    kw.update(cfg)
    ctrl = RefreshController(
        str(tmp_path), registry=reg, key="m", config=RefreshConfig(**kw),
        clock=clock, sleep=lambda s: clock.advance(s),
        retrain_fn=lambda c, g: {"models": _nn_models(seed0=50 + 10 * g),
                                 "warm": True},
        gate_fn=lambda c, cand: GateResult(0.5, 0.6, 0.1, 0.0, True, 100),
        drift_fn=drift or (lambda: None),
        quality_fn=quality,
        slo_alerts_fn=lambda: [])
    return ctrl, reg, clock


def test_quality_trigger_starts_retrain_cycle(tmp_path):
    qdoc = {"degraded": True, "reasons": ["live-auc"], "live_auc": 0.61,
            "baseline_auc": 0.93, "score_psi": 0.02, "joined": 128}
    ctrl, reg, clock = _controller(tmp_path, quality=lambda: qdoc)
    rec = ctrl.tick()
    assert rec["kind"] == "promote" and reg.generation("m") == 1
    trig = ctrl.journal.decisions()[0]
    assert trig["kind"] == "trigger" and trig["source"] == "quality"
    assert trig["reasons"] == ["live-auc"]
    assert trig["live_auc"] == 0.61 and trig["baseline_auc"] == 0.93
    assert trig["joined"] == 128


def test_quality_healthy_no_trigger(tmp_path):
    qdoc = {"degraded": False, "reasons": [], "live_auc": 0.93,
            "joined": 500}
    ctrl, reg, clock = _controller(tmp_path, quality=lambda: qdoc)
    ctrl.tick()
    assert ctrl.journal.decisions() == []
    assert reg.generation("m") == 0


def test_quality_artifact_trigger_and_staleness_anchor(tmp_path):
    """The artifact path (controller daemon, serve fleet elsewhere): a
    degraded quality.json triggers ONCE — after the cycle it caused, the
    same stale table (ts <= the cycle's end) is that cycle's cause, not
    a new signal; a FRESH degraded table re-triggers."""
    ctrl, reg, clock = _controller(tmp_path)
    tel = os.path.join(str(tmp_path), "telemetry")
    os.makedirs(tel, exist_ok=True)

    def write_quality(ts):
        with open(os.path.join(tel, "quality.json"), "w") as f:
            json.dump({"degraded": True, "reasons": ["live-auc"],
                       "live_auc": 0.6, "baseline_auc": 0.93,
                       "score_psi": 0.02, "joined": 128, "ts": ts}, f)

    write_quality(clock.t)
    assert ctrl.tick()["kind"] == "promote"
    clock.advance(6.0)
    assert ctrl.tick()["kind"] == "complete"
    n_decisions = len(ctrl.journal.decisions())
    # past cooldown, the STALE artifact must not re-trigger
    clock.advance(30.0)
    ctrl.tick()
    assert len(ctrl.journal.decisions()) == n_decisions
    # a fresh degraded table (a later serve beat re-emitted it) does
    write_quality(clock.t)
    rec = ctrl.tick()
    assert rec["kind"] == "promote"
    trig = ctrl.journal.decisions()[n_decisions]
    assert trig["kind"] == "trigger" and trig["source"] == "quality"


# ------------------------------------------------------------- e2e drill
def test_server_quality_plane_off_by_default():
    server = ServeServer(models=_nn_models(), key="m")
    assert server.scorelog is None and server.quality is None
    assert server.outcomes is None and server.batcher.scorelog is None
    out = server.add_outcomes({"req": "x", "labels": [1.0]})
    assert out == {"kind": "outcome", "enabled": False, "joined_rows": 0}
    assert server.quality_doc()["enabled"] is False


def test_e2e_label_flip_drives_quality_trigger_and_retrain(tmp_path):
    """ACCEPTANCE: in-process serve with sampled score logging, delayed
    outcomes with FLIPPED labels, live AUC collapsing below the posttrain
    baseline, the controller recording a `quality` trigger and entering
    a retrain cycle — then judging the new generation on fresh windows."""
    models = _nn_models(n=2, n_features=8, seed0=0)
    rng = np.random.default_rng(11)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    # training-time truth: what the incumbent actually scores on x
    probe = ServeServer(models=models, key="m")
    base_scores = probe.score(x)
    labels = (base_scores > np.median(base_scores)).astype(np.float32)
    from shifu_tpu.eval.metrics import auc_trapezoid, sweep
    c = sweep(base_scores, labels)
    base_auc = float(auc_trapezoid(c.fp / max(c.neg_total, 1e-12),
                                   c.tp / max(c.pos_total, 1e-12)))
    assert base_auc > 0.9
    write_posttrain_snapshot(
        os.path.join(str(tmp_path), "telemetry", "posttrain.json"),
        base_scores, auc=base_auc)

    server = ServeServer(models=models, key="m",
                         model_set_dir=str(tmp_path),
                         scorelog_sample_rate=1.0)
    assert server.scorelog is not None and server.quality is not None
    scores = server.score(x, req_id="burst-0")
    assert server.scorelog.stats["records"] >= 1
    np.testing.assert_allclose(scores, base_scores, rtol=1e-5)
    # the chargeback feed lands with labels OPPOSITE the score order —
    # the model went stale even though the input distribution did not
    out = server.add_outcomes({"req": "burst-0",
                               "labels": (1.0 - labels).tolist()})
    assert out["enabled"] and out["joined_rows"] == 256
    summ = server.quality.summary()
    assert summ["degraded"] and "live-auc" in summ["reasons"]
    assert summ["live_auc"] < base_auc - 0.05
    assert "score-psi" not in summ["reasons"]    # inputs look fine

    clock = Clock()
    ctrl = RefreshController(
        str(tmp_path), server=server,
        config=RefreshConfig(psi_threshold=0.25, cooldown_s=10.0,
                             probation_s=5.0),
        clock=clock, sleep=lambda s: clock.advance(s),
        retrain_fn=lambda c, g: {"models": _nn_models(seed0=50 + 10 * g),
                                 "warm": True},
        gate_fn=lambda c, cand: GateResult(0.5, 0.6, 0.1, 0.0, True, 100),
        drift_fn=lambda: None,
        slo_alerts_fn=lambda: [])
    rec = ctrl.tick()
    assert rec["kind"] == "promote"
    assert server.registry.generation("m") == 1
    trig = ctrl.journal.decisions()[0]
    assert trig["kind"] == "trigger" and trig["source"] == "quality"
    assert "live-auc" in trig["reasons"]
    clock.advance(6.0)
    assert ctrl.tick()["kind"] == "complete"
    # the just-answered degradation must not re-trigger: the promoted
    # generation is judged only on its own traffic
    fresh = server.quality.summary()
    assert fresh["joined"] == 0 and not fresh["degraded"]
    # GET /quality and the heartbeat extras read the same monitor
    qdoc = server.quality_doc()
    assert qdoc["enabled"] and qdoc["joined"] == 0
