"""Multi-host/DCN story: 2 REAL processes (one per simulated host, 4
virtual CPU devices each) bootstrap via jax.distributed, build one global
(ensemble, data) mesh, feed per-host row blocks, and run a jitted global
reduction whose combine crosses the process boundary — the ICI/DCN split
the reference covers with Guagua ZooKeeper + NCCL/MPI.

The ELASTIC half (kill-one-controller-mid-train) needs NO cross-process
collectives: the quorum-gated combine rides the shared ``telemetry/
steps/`` control plane (parallel/elastic), so those tests run even on
jaxlib builds without gloo — only the jax.distributed bootstrap test
keeps its CPU-collectives skip guard."""

import json
import os
import socket
import subprocess
import sys
import tempfile

import numpy as np
import pytest

WORKER = os.path.join(os.path.dirname(__file__), "helpers",
                      "multihost_worker.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEMO_SHAPE = ["--rows", "1024", "--features", "8", "--epochs", "6"]
SYNC_MODE = ["--quorum-frac", "1.0", "--timeout-ms", "120000"]
QUORUM_MODE = ["--quorum-frac", "0.97", "--timeout-ms", "2000"]
KILL_STEP = 3


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _launch_demo(out: str, proc: int, nproc: int, mode_args,
                 heartbeat_s: float, faults_spec: str = None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["SHIFU_TPU_HEARTBEAT_S"] = str(heartbeat_s)
    env.pop("SHIFU_TPU_FAULTS", None)
    if faults_spec:
        env["SHIFU_TPU_FAULTS"] = faults_spec
    cmd = [sys.executable, "-m", "shifu_tpu.parallel.elastic_demo",
           "--out", out, "--proc", str(proc), "--nproc", str(nproc)] \
        + DEMO_SHAPE + list(mode_args)
    # output goes to a FILE, not a pipe: controllers run concurrently but
    # are waited on one at a time, and a controller blocked on a full
    # 64 KB pipe (XLA's cache loader can log that much) never reaches its
    # step boundary — its peers then wait on it forever
    os.makedirs(out, exist_ok=True)
    fd, log_path = tempfile.mkstemp(prefix=f"ctrl-{proc}-", suffix=".log",
                                    dir=out)
    with os.fdopen(fd, "w") as log:
        p = subprocess.Popen(cmd, env=env, stdout=log,
                             stderr=subprocess.STDOUT)
    p.log_path = log_path
    return p


def _wait(p, what: str, rc_expect: int = 0) -> str:
    try:
        p.wait(timeout=300)
    except subprocess.TimeoutExpired:
        p.kill()
        pytest.fail(f"{what} hung")
    with open(p.log_path) as f:
        out = f.read()
    assert p.returncode == rc_expect, \
        f"{what}: rc={p.returncode} (wanted {rc_expect})\n{out[-3000:]}"
    return out


def _params(out: str, proc: int) -> dict:
    with np.load(os.path.join(out, f"params-{proc}.npz")) as z:
        return {k: z[k] for k in z.files}


def _result(out: str, proc: int) -> dict:
    with open(os.path.join(out, f"result-{proc}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def elastic_control(tmp_path_factory):
    """The uninterrupted 2-controller sync-mode run every kill drill
    compares against (params bit-for-bit, AUC for the quorum bound)."""
    out = str(tmp_path_factory.mktemp("elastic_control"))
    procs = [_launch_demo(out, p, 2, SYNC_MODE, heartbeat_s=300)
             for p in range(2)]
    for i, p in enumerate(procs):
        _wait(p, f"control controller {i}")
    a, b = _params(out, 0), _params(out, 1)
    assert all(np.array_equal(a[k], b[k]) for k in a), \
        "control controllers diverged"
    return out


def test_two_process_mesh_and_global_reduction():
    # (own 150s communicate-timeout below; no pytest-timeout plugin here)
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    procs = [subprocess.Popen(
        [sys.executable, WORKER, str(pid), "2", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for pid in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost worker hung")
        outs.append(out)
    if any("Multiprocess computations aren't implemented" in out
           for out in outs):
        # this jaxlib's CPU client has no cross-process collectives —
        # the two-controller path is exercised on real multi-host rigs
        pytest.skip("CPU backend lacks multiprocess computations "
                    "(jaxlib build without gloo collectives)")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert "MULTIHOST-OK" in out
    # the trainer ran across the process boundary and both controllers
    # converged to the SAME weights (the psum crossed the DCN every step)
    import re
    sums = [re.search(r"MULTIHOST-TRAIN weights=([0-9.]+)", out).group(1)
            for out in outs]
    assert sums[0] == sums[1], sums
    # the stats plane also ran across the boundary with identical results
    # on both controllers (data-axis psum over the DCN)
    st = [re.search(r"MULTIHOST-STATS bnds=([0-9.]+)", out).group(1)
          for out in outs]
    assert st[0] == st[1], st
    # and the STREAMED trainer (ResidentCache + coalesced mega path)
    # built the same forest on both controllers
    tr = [re.search(r"MULTIHOST-STREAMED trees=([0-9.]+)", out).group(1)
          for out in outs]
    assert tr[0] == tr[1], tr


# ------------------------------------------------- elastic kill drills
def test_kill_one_controller_midtrain_sync_bit_identical(
        tmp_path, elastic_control):
    """ACCEPTANCE: SIGKILL one of 2 controllers at an injected
    ``dcn:step`` boundary mid-train.  In sync mode (quorumFrac 1.0) the
    survivor WAITS the step out, the restarted controller rejoins from
    the close journal WITHOUT a job restart (catch-up replay, no
    re-streaming), and the final model is BIT-identical on both
    controllers to the uninterrupted control run."""
    out = str(tmp_path / "job")
    # huge heartbeat interval: staleness must NOT evict the dead
    # controller before its restart, or the survivor would close the
    # step without it and sync bit-identity is (correctly) gone
    survivor = _launch_demo(out, 0, 2, SYNC_MODE, heartbeat_s=300)
    victim = _launch_demo(out, 1, 2, SYNC_MODE, heartbeat_s=300,
                          faults_spec=f"dcn:step={KILL_STEP}:kill")
    vout = _wait(victim, "victim controller", rc_expect=137)
    assert "injected hard exit at dcn:step" in vout
    # the rejoin: same --proc identity, no fault spec, job still live
    rejoiner = _launch_demo(out, 1, 2, SYNC_MODE, heartbeat_s=300)
    rout = _wait(rejoiner, "rejoined controller")
    _wait(survivor, "surviving controller")
    assert "rejoined=1" in rout
    rj = _result(out, 1)
    assert rj["dcn"]["rejoined"] and rj["dcn"]["incarnation"] == 2
    # the committed prefix (steps 0..KILL_STEP-1) replayed, not recomputed
    assert rj["dcn"]["catchup_steps"] >= KILL_STEP
    ctrl = _params(elastic_control, 0)
    for proc in (0, 1):
        got = _params(out, proc)
        assert all(np.array_equal(ctrl[k], got[k]) for k in ctrl), \
            f"controller {proc} diverged from the uninterrupted control"
    # monitor verdict: both controllers exited cleanly, no permanent
    # straggler in the step-lag table
    from shifu_tpu.obs.monitor import aggregate_records, step_lag_table
    recs, counts = aggregate_records([out])
    assert counts.get("exited", 0) == 2 and not counts.get("stale") \
        and not counts.get("stalled"), counts
    assert len(step_lag_table(recs)) == 2


def test_kill_one_controller_midtrain_quorum_bounded_auc(
        tmp_path, elastic_control):
    """Quorum mode (0.97 + 2 s timeout, fast heartbeats): the survivor
    does NOT wait — the dead controller is masked (staleness eviction
    shrinks the quorum) and the job finishes with its contributions
    dropped; |dAUC| vs the uninterrupted run stays <= 0.01.  The late
    restart still rejoins purely from the journal, landing bit-identical
    to the survivor."""
    out = str(tmp_path / "job")
    survivor = _launch_demo(out, 0, 2, QUORUM_MODE, heartbeat_s=0.25)
    victim = _launch_demo(out, 1, 2, QUORUM_MODE, heartbeat_s=0.25,
                          faults_spec=f"dcn:step={KILL_STEP}:kill")
    _wait(victim, "victim controller", rc_expect=137)
    _wait(survivor, "surviving controller")    # finishes under quorum
    sv = _result(out, 0)
    assert sv["epochs_run"] == 6
    auc_ctrl = _result(elastic_control, 0)["auc"]
    assert abs(sv["auc"] - auc_ctrl) <= 0.01, (sv["auc"], auc_ctrl)
    # late rejoin: the whole job is already closed — pure journal replay
    rejoiner = _launch_demo(out, 1, 2, QUORUM_MODE, heartbeat_s=0.25)
    rout = _wait(rejoiner, "late rejoiner")
    assert "rejoined=1" in rout
    rj = _result(out, 1)
    assert rj["dcn"]["catchup_steps"] >= 6     # every epoch + final eval
    a, b = _params(out, 0), _params(out, 1)
    assert all(np.array_equal(a[k], b[k]) for k in a), \
        "rejoiner's replay diverged from the survivor"
