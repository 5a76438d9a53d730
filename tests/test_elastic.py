"""Elastic multi-controller plane — fast in-process suite (tier-1).

Unit-tests the quorum state machine (close on quorum, close on timeout
with a straggler, bounded-staleness late handling, membership epoch
bumps on leave/rejoin), the exclusive close commit, the coordinator
connect-retry ladder, the monitor's QUORUM LOST flag, and the streamed
trainer's elastic hook — all without subprocesses (injectable clocks,
file boards under tmp_path).  The real kill-a-controller drill lives in
``tests/test_multihost.py``.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from shifu_tpu import faults, obs
from shifu_tpu.config import environment
from shifu_tpu.obs import monitor as monitor_mod
from shifu_tpu.parallel import elastic as el

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.dcn


@pytest.fixture(autouse=True)
def _clean_env():
    environment.reset_for_tests()
    faults.reset_for_tests()
    yield
    environment.reset_for_tests()
    faults.reset_for_tests()
    obs.set_enabled(False)


def _ctx(tmp_path, proc, cfg, clock=None):
    """In-process context: no heartbeat thread; optional fake clock
    (a one-element list advanced by sleep)."""
    kwargs = {}
    if clock is not None:
        kwargs = {"now_fn": lambda: clock[0],
                  "sleep_fn": lambda s: clock.__setitem__(0, clock[0] + s)}
    return el.ElasticContext(str(tmp_path), proc, cfg=cfg,
                             heartbeat=False, **kwargs)


def _pay(v, n=3):
    return {"g": np.full(n, float(v), np.float32)}


# ------------------------------------------------------ pure state machine
def test_quorum_needed_math():
    # the reference's shape: 97% of 1000 workers, 2s timeout
    assert el.quorum_needed(1000, 0.97) == 970
    assert el.quorum_needed(2, 0.97) == 2       # both of a pair
    assert el.quorum_needed(1, 0.97) == 1
    assert el.quorum_needed(0, 0.97) == 1       # a lone survivor proceeds
    assert el.quorum_needed(3, 0.5) == 2


def test_step_closes_on_quorum():
    cfg = el.ElasticConfig(quorum_frac=0.6, step_timeout_ms=2000)
    qs = el.QuorumStep(step=0, cfg=cfg, live={"a", "b", "c"},
                       opened_at=100.0)
    assert qs.needed == 2
    assert qs.decide(100.0) is None
    qs.offer("a")
    assert qs.decide(100.1) is None             # 1 of 2 needed
    qs.offer("b")
    assert qs.decide(100.2) == el.CLOSE_QUORUM  # quorum, before deadline
    assert qs.stragglers() == ["c"]


def test_step_closes_on_timeout_with_straggler():
    cfg = el.ElasticConfig(quorum_frac=1.0, step_timeout_ms=2000)
    qs = el.QuorumStep(step=0, cfg=cfg, live={"a", "b"}, opened_at=100.0)
    qs.offer("a")
    assert qs.decide(101.9) is None             # pre-deadline: wait
    assert qs.decide(102.1) == el.CLOSE_TIMEOUT
    assert qs.stragglers() == ["b"]
    # a timeout close still needs one contribution
    qs2 = el.QuorumStep(step=1, cfg=cfg, live={"a"}, opened_at=100.0)
    assert qs2.decide(200.0) is None


def test_live_set_shrink_unblocks_quorum():
    """The worker-loss masking primitive: when the dead peer drops out
    of the live set (heartbeat staleness), needed shrinks and the
    survivor closes by quorum, not timeout."""
    cfg = el.ElasticConfig(quorum_frac=0.97, step_timeout_ms=60000)
    qs = el.QuorumStep(step=0, cfg=cfg, live={"a", "b"}, opened_at=0.0)
    qs.offer("a")
    assert qs.decide(1.0) is None
    qs.update_live({"a"})                       # b declared dead
    assert qs.decide(1.0) == el.CLOSE_QUORUM


# ----------------------------------------------------------- file board
def test_payload_roundtrip_and_board_contributions(tmp_path):
    board = el.StepBoard(str(tmp_path / "steps"))
    board.ensure()
    pay = {"g": np.arange(5, dtype=np.float32),
           "stats": np.ones((2, 4), np.float32)}
    assert el.decode_payload(el.encode_payload(pay))["g"].tolist() == \
        pay["g"].tolist()
    board.contribute(3, "ctrl-0", pay, epoch=1)
    got = board.contributions(3)
    assert set(got) == {"ctrl-0"}
    dec = el.decode_payload(got["ctrl-0"]["payload"])
    assert np.array_equal(dec["g"], pay["g"])
    assert np.array_equal(dec["stats"], pay["stats"])
    assert board.has_contribution(3, "ctrl-0")
    assert not board.has_contribution(3, "ctrl-1")
    assert board.last_closed_step() == -1


def test_exclusive_close_single_winner(tmp_path):
    """Two racing closers: exactly ONE owns the close record; the loser
    reads the winner's aggregate (never two truths for one step)."""
    b1 = el.StepBoard(str(tmp_path / "steps"))
    b2 = el.StepBoard(str(tmp_path / "steps"))
    b1.ensure()
    d1 = {"step": 0, "by": "ctrl-0", "payload": el.encode_payload(_pay(1))}
    d2 = {"step": 0, "by": "ctrl-1", "payload": el.encode_payload(_pay(2))}
    won1 = b1.try_close(0, d1)
    won2 = b2.try_close(0, d2)
    assert won1 and not won2
    assert b2.close_doc(0)["by"] == "ctrl-0"
    assert b1.last_closed_step() == 0


# -------------------------------------------------------------- protocol
def test_two_controllers_close_and_adopt_same_bits(tmp_path):
    cfg = el.ElasticConfig(quorum_frac=1.0, step_timeout_ms=60000)
    a = _ctx(tmp_path, "ctrl-0", cfg).start()
    b = _ctx(tmp_path, "ctrl-1", cfg).start()
    b.board.contribute(0, "ctrl-1", _pay(2), epoch=1)
    res_a = a.step(0, _pay(1))
    assert res_a.reason == el.CLOSE_QUORUM
    assert res_a.contributors == ["ctrl-0", "ctrl-1"]
    assert np.array_equal(res_a.payload["g"],
                          np.full(3, 3.0, np.float32))
    # the slower controller ADOPTS the committed aggregate, bit-for-bit
    res_b = b.step(0, _pay(2))
    assert np.array_equal(res_b.payload["g"], res_a.payload["g"])
    assert res_b.closed_by == "ctrl-0"
    assert a.steps_closed == 1 and b.steps_closed == 0


def test_timeout_close_with_fake_clock(tmp_path):
    cfg = el.ElasticConfig(quorum_frac=1.0, step_timeout_ms=2000)
    clock = [1000.0]
    a = _ctx(tmp_path, "ctrl-0", cfg, clock).start()
    a.board.announce("ctrl-1")                  # a peer that never shows
    res = a.step(0, _pay(1))
    assert res.reason == el.CLOSE_TIMEOUT
    assert res.contributors == ["ctrl-0"]
    assert res.stragglers == ["ctrl-1"]
    assert a.step_timeouts == 1
    assert clock[0] >= 1002.0                   # the deadline was honored


def test_late_contribution_applied_within_staleness(tmp_path):
    cfg = el.ElasticConfig(quorum_frac=1.0, step_timeout_ms=2000,
                           staleness=2)
    clock = [0.0]
    a = _ctx(tmp_path, "ctrl-0", cfg, clock).start()
    a.board.announce("ctrl-1")
    r0 = a.step(0, _pay(1))                     # times out without b
    assert r0.reason == el.CLOSE_TIMEOUT
    # b's step-0 work lands LATE, inside the staleness window
    a.board.contribute(0, "ctrl-1", _pay(10), late=True)
    r1 = a.step(1, _pay(2))
    assert (0, "ctrl-1") in r1.late_applied
    # step 1 aggregate = own 2s + b's late 10s
    assert np.array_equal(r1.payload["g"], np.full(3, 12.0, np.float32))
    assert a.late_applied == 1 and a.late_dropped == 0


def test_late_contribution_dropped_beyond_staleness(tmp_path):
    cfg = el.ElasticConfig(quorum_frac=1.0, step_timeout_ms=2000,
                           staleness=1)
    clock = [0.0]
    a = _ctx(tmp_path, "ctrl-0", cfg, clock).start()
    a.board.announce("ctrl-1")
    a.step(0, _pay(1))
    a.step(1, _pay(2))                          # window for step 0 passes
    a.board.contribute(0, "ctrl-1", _pay(10), late=True)
    r2 = a.step(2, _pay(3))                     # 2 - 0 > staleness=1
    assert r2.late_applied == []
    assert np.array_equal(r2.payload["g"], np.full(3, 3.0, np.float32))
    assert a.late_dropped == 1


def test_quorum_mode_drops_all_late(tmp_path):
    cfg = el.ElasticConfig(quorum_frac=1.0, step_timeout_ms=2000,
                           staleness=0)
    clock = [0.0]
    a = _ctx(tmp_path, "ctrl-0", cfg, clock).start()
    a.board.announce("ctrl-1")
    a.step(0, _pay(1))
    a.board.contribute(0, "ctrl-1", _pay(10), late=True)
    r1 = a.step(1, _pay(2))
    assert r1.late_applied == []
    assert np.array_equal(r1.payload["g"], np.full(3, 2.0, np.float32))
    assert a.late_dropped == 1


def test_membership_epoch_bumps_on_leave_and_rejoin(tmp_path):
    from shifu_tpu.obs.health import health_dir_for
    cfg = el.ElasticConfig()
    a = _ctx(tmp_path, "ctrl-0", cfg).start()
    b = _ctx(tmp_path, "ctrl-1", cfg).start()
    e0, members = a.board.current_epoch()
    assert set(members) == {"ctrl-0", "ctrl-1"}
    # ---- LEAVE: b's heartbeat goes stale -> it drops out, epoch bumps
    hd = health_dir_for(str(tmp_path))
    os.makedirs(hd, exist_ok=True)
    now = time.time()
    with open(os.path.join(hd, "ctrl-1.json"), "w") as f:
        json.dump({"proc": "ctrl-1", "state": "running",
                   "ts": now - 60, "last_progress_ts": now - 60,
                   "interval_s": 0.5}, f)
    a._refresh_live(reason="test-leave")
    e1, members = a.board.current_epoch()
    assert e1 == e0 + 1 and set(members) == {"ctrl-0"}
    # ---- REJOIN: b comes back (fresh beat, incarnation 2) -> bump again
    with open(os.path.join(hd, "ctrl-1.json"), "w") as f:
        json.dump({"proc": "ctrl-1", "state": "running",
                   "ts": time.time(), "last_progress_ts": time.time(),
                   "interval_s": 0.5}, f)
    b2 = _ctx(tmp_path, "ctrl-1", cfg).start()
    assert b2.rejoined and b2.incarnation == 2
    e2, members = a.board.current_epoch()
    assert e2 >= e1 + 1 and members.get("ctrl-1") == 2


def test_masked_straggler_adopts_committed_history(tmp_path):
    """A controller that starts LATE (or rejoins) walks the committed
    step prefix: every step() finds the close record and adopts the
    winner's aggregate — bit-identical history, no divergence."""
    cfg = el.ElasticConfig(quorum_frac=0.4, step_timeout_ms=60000)
    a = _ctx(tmp_path, "ctrl-0", cfg).start()
    front = [a.step(s, _pay(s + 1)) for s in range(3)]
    b = _ctx(tmp_path, "ctrl-1", cfg).start()
    for s in range(3):
        got = b.step(s, _pay(100))              # its own work arrives late
        assert np.array_equal(got.payload["g"], front[s].payload["g"])
    assert b.steps_closed == 0
    # closed_step() is the journal read a rejoiner replays
    assert b.closed_step(1) is not None
    assert b.closed_step(99) is None


# ----------------------------------------------- streamed trainer hook
def test_streamed_nn_elastic_single_controller_bit_equal(tmp_path):
    """The elastic hook must not perturb the math: a 1-controller
    elastic run (quorum of itself, f32 transport round-trips exactly)
    trains BIT-identical params to the plain streamed path."""
    from shifu_tpu.data.shards import Shards
    from shifu_tpu.data.streaming import ShardStream, mask_fn_from_settings
    from shifu_tpu.models.nn import NNModelSpec
    from shifu_tpu.parallel.mesh import device_mesh
    from shifu_tpu.train.nn_trainer import (TrainSettings,
                                            train_ensemble_streamed)
    from shifu_tpu import ioutil

    rng = np.random.default_rng(3)
    N, D = 256, 6
    x = rng.normal(size=(N, D)).astype(np.float32)
    y = (rng.random(N) < 0.4).astype(np.float32)
    ddir = tmp_path / "data"
    os.makedirs(ddir)
    ioutil.atomic_savez(str(ddir / "part-00000.npz"), x=x, y=y,
                        w=np.ones(N, np.float32))
    ioutil.atomic_write_json(str(ddir / "schema.json"), {
        "outputNames": [f"c{i}" for i in range(D)],
        "columnNums": list(range(D)), "numShards": 1, "numRows": N})
    spec = NNModelSpec(input_dim=D, hidden_nodes=[4],
                       activations=["tanh"], loss="log")
    settings = TrainSettings(optimizer="ADAM", learning_rate=0.05,
                             epochs=3, batch_size=0, seed=5)
    mask_fn = mask_fn_from_settings(1, valid_rate=0.25, seed=5)
    mesh = device_mesh(n_ensemble=1)

    def run(elastic):
        stream = ShardStream(Shards.open(str(ddir)), ("x", "y", "w"), 128)
        return train_ensemble_streamed(stream, spec, settings, 1,
                                       mask_fn, mesh=mesh,
                                       elastic=elastic)
    plain = run(None)
    ctx = _ctx(tmp_path / "job", "ctrl-0",
               el.ElasticConfig(quorum_frac=1.0,
                                step_timeout_ms=60000)).start()
    elas = run(ctx)
    for pl, ell in zip(plain.params[0], elas.params[0]):
        for k in ("w", "b"):
            assert np.array_equal(np.asarray(pl[k]), np.asarray(ell[k]))
    assert plain.history == elas.history
    # epoch steps 0..2 + the final eval step all closed on the board
    assert ctx.board.last_closed_step() == settings.epochs


def test_streamed_nn_elastic_rejects_minibatch(tmp_path):
    from shifu_tpu.models.nn import NNModelSpec
    from shifu_tpu.train.nn_trainer import (TrainSettings,
                                            train_ensemble_streamed)
    ctx = _ctx(tmp_path, "ctrl-0", el.ElasticConfig())
    with pytest.raises(ValueError, match="full-batch"):
        train_ensemble_streamed(
            None, NNModelSpec(input_dim=2, hidden_nodes=[2],
                              activations=["tanh"]),
            TrainSettings(batch_size=32), 1, None, elastic=ctx)


def test_grad_codec_roundtrip_and_dtype_restore():
    import jax.numpy as jnp
    zero = [{"w": jnp.zeros((3, 2), jnp.bfloat16),
             "b": jnp.zeros((2,), jnp.float32)}]
    ravel, unravel = el.grad_codec(zero)
    tree = [{"w": jnp.full((3, 2), 1.5, jnp.bfloat16),
             "b": jnp.arange(2, dtype=jnp.float32)}]
    flat = ravel(tree)
    assert flat.dtype == np.float32 and flat.shape == (8,)
    back = unravel(flat)
    assert back[0]["w"].dtype == jnp.bfloat16
    assert back[0]["b"].dtype == jnp.float32
    assert np.array_equal(np.asarray(back[0]["b"]),
                          np.asarray(tree[0]["b"]))


# --------------------------------------------------- connect retry ladder
def test_initialize_distributed_retries_then_coded_error(monkeypatch):
    from shifu_tpu.config.errors import ShifuError
    from shifu_tpu.parallel.mesh import initialize_distributed

    calls = []

    def boom(*a, **k):
        calls.append(1)
        raise RuntimeError("connection refused (injected)")

    import jax
    monkeypatch.setattr(jax.distributed, "initialize", boom)
    environment.set_property("shifu.io.retries", "2")
    environment.set_property("shifu.io.retryBaseMs", "1")
    with pytest.raises(ShifuError) as e:
        initialize_distributed("localhost:1", num_processes=2,
                               process_id=0)
    assert e.value.error_code.code == 1063
    assert "after 3 attempt" in str(e.value)
    assert len(calls) == 3                      # 1 try + 2 retries


def test_initialize_distributed_succeeds_after_transient(monkeypatch):
    from shifu_tpu.parallel.mesh import initialize_distributed

    calls = []

    def flaky(*a, **k):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("coordinator not up yet (injected)")

    import jax
    monkeypatch.setattr(jax.distributed, "initialize", flaky)
    environment.set_property("shifu.io.retryBaseMs", "1")
    initialize_distributed("localhost:1", num_processes=2, process_id=0)
    assert len(calls) == 2


# ----------------------------------------------------- monitor QUORUM LOST
# a record a `python -m shifu_tpu.cli monitor` subprocess must read as
# live: its declared interval outlasts the child's cold start (and its
# 120 s timeout), where the default 0.5 s is stale after one second
CLI_INTERVAL_S = 120.0


def _write_health(d, proc, age_s, state="running", interval_s=0.5):
    hd = os.path.join(d, "telemetry", "health")
    os.makedirs(hd, exist_ok=True)
    now = time.time()
    path = os.path.join(hd, f"{proc}.json")
    with open(path, "w") as f:
        json.dump({"proc": proc, "step": "TRAIN", "state": state,
                   "ts": now - age_s, "last_progress_ts": now - age_s,
                   "interval_s": interval_s, "rows": 100}, f)
    # age the mtime WITH the embedded ts: a genuinely dead process left
    # both behind (a mismatched pair reads as clock skew and the
    # aggregate's offset normalization would "revive" the record)
    os.utime(path, (now - age_s, now - age_s))


def test_monitor_quorum_lost_flag_and_exit(tmp_path):
    d0, d1 = str(tmp_path / "p0"), str(tmp_path / "p1")
    _write_health(d0, "ctrl-0", 0.0)
    _write_health(d1, "ctrl-1", 0.0)
    doc, rc = monitor_mod.aggregate_json([d0, d1])
    assert rc == 0 and not doc["summary"]["quorum_lost"]
    assert "QUORUM LOST" not in monitor_mod.render_aggregate([d0, d1])
    # one controller stops heartbeating: 1/2 = 50% < quorumFrac 0.97
    _write_health(d1, "ctrl-1", 60.0)
    doc, rc = monitor_mod.aggregate_json([d0, d1])
    assert rc == monitor_mod.EXIT_UNHEALTHY
    assert doc["summary"]["quorum_lost"] is True
    text = monitor_mod.render_aggregate([d0, d1])
    assert "QUORUM LOST" in text and "quorumFrac" in text
    # the threshold IS the protocol knob
    environment.set_property("shifu.dcn.quorumFrac", "0.4")
    doc, rc = monitor_mod.aggregate_json([d0, d1])
    assert not doc["summary"]["quorum_lost"]


def test_monitor_quorum_lost_cli_subprocess(tmp_path):
    """ACCEPTANCE (satellite): `shifu-tpu monitor --aggregate` flags
    QUORUM LOST and exits 3 when live members fall below quorumFrac."""
    d0, d1 = str(tmp_path / "p0"), str(tmp_path / "p1")
    _write_health(d0, "ctrl-0", 0.0, interval_s=CLI_INTERVAL_S)
    _write_health(d1, "ctrl-1", 60.0)           # dead without a final beat
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("SHIFU_TPU_FAULTS", None)
    p = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.cli", "monitor", "--once",
         "--aggregate", d0, d1],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert p.returncode == monitor_mod.EXIT_UNHEALTHY, p.stdout + p.stderr
    assert "QUORUM LOST" in p.stdout
    # healthy pair: flag off, exit 0
    _write_health(d1, "ctrl-1", 0.0, interval_s=CLI_INTERVAL_S)
    p = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.cli", "monitor", "--once",
         "--aggregate", d0, d1],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "QUORUM LOST" not in p.stdout
