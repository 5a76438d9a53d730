"""Online-serving plane suite (tier-1-fast: in-process batcher drains,
injectable clock, tiny models — zero sleeps, zero ports).

Covers the serve acceptance surface: padded-bucket launches trim to
bit-identical scores across NN / GBT / WDL model groups, a warmed
server performs ZERO recompiles over a randomized request-size sweep,
deadline/full flush semantics, fault sites (a killed in-flight batch
leaves the registry serviceable; a crashed hot-swap leaves the previous
model live and bit-identical), and the stacked-NN-group cache
invalidation regression in ``eval/scorer.py``.
"""

import json
import os

import numpy as np
import pytest

import jax

from shifu_tpu import faults, obs
from shifu_tpu.config import environment
from shifu_tpu.models.nn import (IndependentNNModel, NNModelSpec,
                                 init_params)
from shifu_tpu.serve import (AOTScorer, MicroBatcher, ModelRegistry,
                             ServeServer, bucket_ladder, covering_bucket,
                             infer_dims, serve_recompile_count)

pytestmark = pytest.mark.serve


@pytest.fixture(autouse=True)
def _clean_env():
    environment.reset_for_tests()
    faults.reset_for_tests()
    yield
    environment.reset_for_tests()
    faults.reset_for_tests()
    obs.set_enabled(False)


def _nn_models(n=3, n_features=8, hidden=(8,), seed0=0):
    spec = NNModelSpec(input_dim=n_features, hidden_nodes=list(hidden),
                       activations=["relu"] * len(hidden))
    return [IndependentNNModel(spec, init_params(
        jax.random.PRNGKey(seed0 + i), spec)) for i in range(n)]


def _gbt_model(n_features=6, n_bins=8, n_trees=4, depth=3, seed=0):
    from shifu_tpu.models.tree import IndependentTreeModel, TreeModelSpec
    from shifu_tpu.train.dt_trainer import DTSettings, train_gbt
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, n_bins, size=(512, n_features)).astype(np.int32)
    y = (rng.random(512) < 0.4).astype(np.float32)
    w = np.ones(512, np.float32)
    settings = DTSettings(n_trees=n_trees, depth=depth, loss="log",
                          learning_rate=0.1)
    res = train_gbt(bins, y, w, n_bins, np.zeros(n_features, bool),
                    settings)
    spec = TreeModelSpec(n_trees=len(res.trees), depth=depth,
                         n_bins=n_bins, **res.spec_kwargs)
    return IndependentTreeModel(spec, res.trees)


def _wdl_model(n_features=8, n_bins_cols=6, seed=3):
    from shifu_tpu.models.wdl import IndependentWDLModel, WDLModelSpec
    from shifu_tpu.models.wdl import init_params as wdl_init
    spec = WDLModelSpec(numeric_dim=3, cat_cardinalities=[8, 8],
                        embed_dim=4, hidden_nodes=[8],
                        activations=["relu"],
                        extra={"num_feat_idx": [0, 2, 4],
                               "cat_col_idx": [1, 3]})
    return IndependentWDLModel(spec, wdl_init(jax.random.PRNGKey(seed),
                                              spec))


# ----------------------------------------------------------- bucket math
def test_bucket_ladder_property_and_default():
    assert bucket_ladder() == (1, 8, 64, 512)
    environment.set_property("shifu.serve.buckets", "4,1,32,4")
    assert bucket_ladder() == (1, 4, 32)
    environment.set_property("shifu.serve.buckets", "junk")
    assert bucket_ladder() == (1, 8, 64, 512)     # unparseable -> default


def test_covering_bucket():
    b = (1, 8, 64)
    assert covering_bucket(b, 1) == 1
    assert covering_bucket(b, 2) == 8
    assert covering_bucket(b, 8) == 8
    assert covering_bucket(b, 64) == 64
    assert covering_bucket(b, 1000) == 64         # caller chunks oversize


def test_infer_dims_mixed_ensemble():
    models = _nn_models(n_features=8) + [_gbt_model(n_features=6)] \
        + [_wdl_model()]
    f, c = infer_dims(models)
    assert f == 8
    assert c >= 4            # gbt split features + wdl cat cols


# ---------------------------------------------------- bucket-pad parity
def _rand_xb(rng, n, scorer, n_bins=8):
    x = rng.normal(size=(n, scorer.n_features)).astype(np.float32)
    b = rng.integers(0, n_bins,
                     size=(n, scorer.n_bins_cols)).astype(np.int32)
    return x, (b if scorer.needs_bins else None)


@pytest.mark.parametrize("kind", ["nn", "gbt", "wdl", "mixed"])
def test_padded_bucket_scores_bit_identical(kind):
    """Scores from a padded bucket launch, after trim, are BIT-identical
    to an exact-size launch of the same rows — across NN, GBT and WDL
    model groups (padding must be invisible, not merely close)."""
    if kind == "nn":
        models = _nn_models()
    elif kind == "gbt":
        models = [_gbt_model(seed=i) for i in range(2)]
    elif kind == "wdl":
        models = [_wdl_model()]
    else:
        models = _nn_models(2) + [_gbt_model(), _wdl_model()]
    scorer = AOTScorer(models, buckets=(1, 4, 16))
    scorer.warm(launch=False)
    rng = np.random.default_rng(7)
    x, bins = _rand_xb(rng, 16, scorer)
    # pad 3 rows -> bucket 4 vs the same executable launched exactly full
    # with the same leading rows: trimmed scores must match bitwise
    exact = scorer.score_batch(x[:4], None if bins is None else bins[:4])
    padded = scorer.score_batch(x[:3], None if bins is None else bins[:3])
    assert padded.tobytes() == exact[:3].tobytes()
    # same at the 16 rung: 13 padded vs 16 exact
    exact16 = scorer.score_batch(x, bins)
    pad16 = scorer.score_batch(x[:13],
                               None if bins is None else bins[:13])
    assert pad16.tobytes() == exact16[:13].tobytes()


def test_oversize_batch_chunks_through_top_bucket():
    models = _nn_models()
    scorer = AOTScorer(models, buckets=(1, 4))
    rng = np.random.default_rng(1)
    x, _ = _rand_xb(rng, 11, scorer)
    full = scorer.score_batch(x)
    assert full.shape == (11, len(models))
    parts = np.concatenate([scorer.score_batch(x[:4]),
                            scorer.score_batch(x[4:8]),
                            scorer.score_batch(x[8:])], axis=0)
    assert full.tobytes() == parts.tobytes()


# -------------------------------------------------- recompile sentinel
def test_warmed_server_zero_recompiles_over_random_sizes():
    """A warmed server performs ZERO xla.recompiles over a randomized
    request-size sweep — every request size pads into a pre-compiled
    rung."""
    models = _nn_models(2) + [_gbt_model()]
    scorer = AOTScorer(models, buckets=(1, 4, 16))
    scorer.warm()
    obs.set_enabled(True)
    rng = np.random.default_rng(11)
    before = serve_recompile_count()
    ctr = obs.counter("xla.recompiles")
    xla_before = ctr.value
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0)
    for n in rng.integers(1, 17, size=40):
        x, bins = _rand_xb(rng, int(n), scorer)
        t = b.submit_burst(x, bins)
        b.drain()
        assert t.wait(10.0).shape == (int(n),)
    assert serve_recompile_count() - before == 0
    assert ctr.value - xla_before == 0


# --------------------------------------------------------- micro-batcher
class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def test_batcher_deadline_flush_with_fake_clock():
    """No flush before the oldest request's deadline; flush after —
    driven entirely by an injected clock, no sleeps."""
    models = _nn_models()
    scorer = AOTScorer(models, buckets=(1, 4, 16))
    scorer.warm(launch=False)
    clk = FakeClock()
    b = MicroBatcher(lambda: scorer, max_delay_s=0.002, clock=clk)
    rng = np.random.default_rng(0)
    t1 = b.submit(rng.normal(size=scorer.n_features))
    clk.t += 0.001
    assert b.pump() == 0 and not t1.done()        # deadline not reached
    t2 = b.submit(rng.normal(size=scorer.n_features))
    clk.t += 0.0015                               # oldest is now 2.5ms old
    assert b.pump() == 2                          # deadline flush, both
    assert t1.done() and t2.done()
    assert b.stats["flush_deadline"] == 1 and b.stats["flush_full"] == 0
    # both coalesced into ONE bucket-4 launch, 2 pad rows counted
    assert b.stats["batches"] == 1
    assert b.stats["rows_padded"] == 2
    assert b.bucket_counts == {4: 1}


def test_batcher_full_bucket_flushes_without_deadline():
    models = _nn_models()
    scorer = AOTScorer(models, buckets=(1, 4))
    scorer.warm(launch=False)
    clk = FakeClock()
    b = MicroBatcher(lambda: scorer, max_delay_s=10.0, clock=clk)
    rng = np.random.default_rng(0)
    t = b.submit_burst(rng.normal(size=(4, scorer.n_features))
                       .astype(np.float32))
    assert b.pump() == 4                          # full top bucket, no wait
    assert b.stats["flush_full"] == 1
    assert t.wait(1.0).shape == (4,)


def test_burst_split_across_launches_keeps_row_order():
    models = _nn_models()
    scorer = AOTScorer(models, buckets=(1, 4))
    scorer.warm(launch=False)
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0)
    rng = np.random.default_rng(5)
    x = rng.normal(size=(10, scorer.n_features)).astype(np.float32)
    t = b.submit_burst(x)
    b.drain()
    got = t.wait(5.0)
    want = scorer.score_batch(x).mean(axis=1)
    assert got.tobytes() == want.astype(np.float32).tobytes()
    assert b.stats["batches"] == 3                # 4 + 4 + 2(padded)


def test_threaded_batcher_serves_closed_loop():
    """One real-thread smoke: worker flushes on its own (small deadline,
    bounded wall time)."""
    models = _nn_models()
    server = ServeServer(models=models, key="t", buckets=(1, 4, 16),
                         max_delay_ms=1.0).start()
    try:
        rng = np.random.default_rng(2)
        x = rng.normal(size=(5, 8)).astype(np.float32)
        out = server.score(x, timeout=10.0)
        assert out.shape == (5,) and np.isfinite(out).all()
        st = server.status()
        assert st["state"] == "serving" and st["models"] == 3
    finally:
        server.stop()


def test_http_front_end_scores_and_reports_health():
    """POST /score + GET /healthz on an ephemeral loopback port (the
    stdlib front-end `shifu-tpu serve` binds)."""
    import threading
    import urllib.request
    from http.server import ThreadingHTTPServer

    from shifu_tpu.serve.server import _make_handler
    server = ServeServer(models=_nn_models(), key="h", buckets=(1, 4),
                         max_delay_ms=1.0).start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler(server))
    port = httpd.server_address[1]
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    try:
        rng = np.random.default_rng(9)
        rows = rng.normal(size=(3, 8)).round(4).tolist()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/score",
            data=json.dumps({"rows": rows}).encode(),
            headers={"Content-Type": "application/json"})
        doc = json.load(urllib.request.urlopen(req, timeout=15))
        assert len(doc["scores"]) == 3
        want = server.score(np.asarray(rows, np.float32), timeout=15.0)
        assert np.allclose(doc["scores"], want, atol=1e-4)
        health = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=15))
        assert health["state"] == "serving" and health["models"] == 3
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.stop()


# ----------------------------------------------------------- fault sites
def _set_faults(spec: str) -> None:
    environment.set_property("shifu.faults", spec)
    faults.reset_for_tests()


def test_killed_inflight_batch_leaves_registry_serviceable():
    """serve:request ioerror fails exactly that batch's tickets; the
    next request scores bit-identically to an undisturbed scorer."""
    models = _nn_models()
    reg = ModelRegistry()
    reg.load("m", models, buckets=(1, 4))
    b = MicroBatcher(reg.provider("m"), max_delay_s=0.0)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 8)).astype(np.float32)
    want = reg.get("m").score_batch(x).mean(axis=1)
    _set_faults("serve:request=0:ioerror")
    t = b.submit_burst(x)
    b.drain()
    with pytest.raises(faults.InjectedFault):
        t.wait(1.0)
    assert b.stats["errors"] == 1
    t2 = b.submit_burst(x)                         # next batch is clean
    b.drain()
    got = t2.wait(1.0)
    assert got.tobytes() == want.astype(np.float32).tobytes()
    assert reg.generation("m") == 0


def test_malformed_burst_fails_batch_not_batcher():
    """Concurrent bursts with mismatched row widths coalesce into one
    batch whose ASSEMBLY raises — that error must complete the batch's
    tickets, and the batcher must stay serviceable for the next
    request (regression: assembly errors escaped ``_launch``)."""
    models = _nn_models()
    scorer = AOTScorer(models, buckets=(1, 4))
    scorer.warm(launch=False)
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0)
    rng = np.random.default_rng(12)
    good = rng.normal(size=(2, scorer.n_features)).astype(np.float32)
    bad = rng.normal(size=(2, scorer.n_features - 3)).astype(np.float32)
    t1 = b.submit_burst(good)
    t2 = b.submit_burst(bad)          # same batch: concatenate raises
    b.drain()
    with pytest.raises(ValueError):
        t1.wait(1.0)
    with pytest.raises(ValueError):
        t2.wait(1.0)
    assert b.stats["errors"] == 1
    t3 = b.submit_burst(good)          # batcher is still serviceable
    b.drain()
    assert t3.wait(1.0).shape == (2,)


def test_missing_bins_burst_fails_batch_not_batcher():
    """One client sends bins, another omits them (needs_bins scorer):
    the mixed batch fails its tickets, the next well-formed request
    scores."""
    scorer = AOTScorer([_gbt_model()], buckets=(1, 4))
    scorer.warm(launch=False)
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0)
    rng = np.random.default_rng(13)
    x, bins = _rand_xb(rng, 2, scorer)
    t1 = b.submit_burst(x, bins)
    t2 = b.submit_burst(x, None)       # omitted bins
    b.drain()
    with pytest.raises((ValueError, TypeError)):
        t1.wait(1.0)
    with pytest.raises((ValueError, TypeError)):
        t2.wait(1.0)
    t3 = b.submit_burst(x, bins)
    b.drain()
    assert t3.wait(1.0).shape == (2,)


class _FlakyScorer:
    """Wraps an AOTScorer; raises an UN-tolerated error type on demand."""

    def __init__(self, inner):
        self.inner = inner
        self.boom = False

    @property
    def buckets(self):
        return self.inner.buckets

    @property
    def needs_bins(self):
        return self.inner.needs_bins

    def score_batch(self, rows, bins=None):
        if self.boom:
            raise KeyError("unexpected per-batch failure")
        return self.inner.score_batch(rows, bins)


def test_worker_thread_survives_unexpected_batch_error():
    """An error OUTSIDE the tolerated set (here a KeyError) fails its
    own batch's tickets but must NOT kill the worker thread — the next
    request still scores (regression: the re-raise propagated through
    ``_run`` and permanently stopped serving)."""
    scorer = AOTScorer(_nn_models(), buckets=(1, 4))
    scorer.warm(launch=False)
    flaky = _FlakyScorer(scorer)
    b = MicroBatcher(lambda: flaky, max_delay_s=0.0005).start()
    try:
        rng = np.random.default_rng(14)
        x = rng.normal(size=(2, scorer.n_features)).astype(np.float32)
        flaky.boom = True
        t = b.submit_burst(x)
        with pytest.raises(KeyError):
            t.wait(10.0)
        flaky.boom = False
        t2 = b.submit_burst(x)         # worker thread must still be alive
        assert t2.wait(10.0).shape == (2,)
        assert b.stats["errors"] == 1
    finally:
        b.stop()


def test_requests_counted_per_submit_not_per_row():
    """``stats['requests']`` counts accepted submit calls; row volume
    is ``stats['rows']`` (regression: bursts counted rows as
    requests, duplicating rows_scored)."""
    scorer = AOTScorer(_nn_models(), buckets=(1, 4))
    scorer.warm(launch=False)
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0)
    rng = np.random.default_rng(16)
    b.submit_burst(rng.normal(size=(3, scorer.n_features))
                   .astype(np.float32))
    b.submit(rng.normal(size=scorer.n_features))
    b.drain()
    assert b.stats["requests"] == 2
    assert b.stats["rows"] == 4


def test_failed_journal_leaves_previous_model_live(tmp_path, monkeypatch):
    """swap() journals BEFORE the flip: if the journal commit fails
    (disk full, perms) the swap raises and the OLD model is still live,
    matching the docstring contract."""
    import shifu_tpu.serve.registry as regmod
    reg = ModelRegistry(state_dir=str(tmp_path))
    reg.load("m", _nn_models(seed0=0), buckets=(1, 4))
    rng = np.random.default_rng(15)
    x = rng.normal(size=(2, 8)).astype(np.float32)
    before = reg.get("m").score_batch(x)

    def boom(path, doc):
        raise OSError("disk full")

    monkeypatch.setattr(regmod, "atomic_write_json", boom)
    with pytest.raises(OSError):
        reg.swap("m", _nn_models(seed0=50), buckets=(1, 4))
    assert reg.generation("m") == 0
    assert reg.get("m").score_batch(x).tobytes() == before.tobytes()
    with open(os.path.join(str(tmp_path), "serving.json")) as f:
        assert json.load(f)["m"]["generation"] == 0
    monkeypatch.undo()                 # journal healthy again: promote
    reg.swap("m", _nn_models(seed0=50), buckets=(1, 4))
    assert reg.generation("m") == 1


def test_crashed_swap_leaves_previous_model_live():
    """serve:swap ioerror after the candidate is built but before the
    flip: the OLD model stays live and scores bit-identical to the
    pre-swap scorer."""
    old_models = _nn_models(seed0=0)
    new_models = _nn_models(seed0=50)
    reg = ModelRegistry()
    reg.load("m", old_models, buckets=(1, 4))
    rng = np.random.default_rng(4)
    x = rng.normal(size=(3, 8)).astype(np.float32)
    before = reg.get("m").score_batch(x)
    _set_faults("serve:swap=m:ioerror")
    with pytest.raises(faults.InjectedFault):
        reg.swap("m", new_models, buckets=(1, 4))
    after = reg.get("m").score_batch(x)
    assert after.tobytes() == before.tobytes()
    assert reg.generation("m") == 0
    # the disarmed site lets the next promote through, and scores change
    faults.reset_for_tests()
    environment.reset_for_tests()
    reg.swap("m", new_models, buckets=(1, 4))
    assert reg.generation("m") == 1
    assert reg.get("m").score_batch(x).tobytes() != before.tobytes()


def test_swap_journal_is_atomic_and_resolvable(tmp_path):
    reg = ModelRegistry(state_dir=str(tmp_path))
    reg.load("m", _nn_models(), buckets=(1, 4))
    reg.swap("m", _nn_models(seed0=9), buckets=(1, 4))
    with open(os.path.join(str(tmp_path), "serving.json")) as f:
        doc = json.load(f)
    assert doc["m"]["generation"] == 1
    assert not [f for f in os.listdir(str(tmp_path)) if ".tmp" in f]


def test_hot_swap_between_batches_drops_nothing():
    reg = ModelRegistry()
    reg.load("m", _nn_models(seed0=0), buckets=(1, 4))
    b = MicroBatcher(reg.provider("m"), max_delay_s=0.0)
    rng = np.random.default_rng(6)
    x = rng.normal(size=(2, 8)).astype(np.float32)
    t1 = b.submit_burst(x)
    b.drain()
    reg.swap("m", _nn_models(seed0=77), buckets=(1, 4))
    t2 = b.submit_burst(x)
    b.drain()
    a, c = t1.wait(1.0), t2.wait(1.0)
    assert np.isfinite(a).all() and np.isfinite(c).all()
    assert a.tobytes() != c.tobytes()              # new model answered


# ------------------------------------- generation history / rollback
def test_rollback_restores_previous_generation_bit_identical():
    reg = ModelRegistry()
    reg.load("m", _nn_models(seed0=0), buckets=(1, 4))
    rng = np.random.default_rng(21)
    x = rng.normal(size=(3, 8)).astype(np.float32)
    gen0 = reg.get("m").score_batch(x).tobytes()
    reg.swap("m", _nn_models(seed0=50), buckets=(1, 4))
    assert reg.generation("m") == 1
    assert reg.get("m").score_batch(x).tobytes() != gen0
    reg.rollback("m")
    assert reg.generation("m") == 0
    assert reg.get("m").score_batch(x).tobytes() == gen0
    # generation numbers are monotonic: the next promotion is 2, not 1
    assert reg.next_generation("m") == 2
    reg.swap("m", _nn_models(seed0=60), buckets=(1, 4))
    assert reg.generation("m") == 2


def test_rollback_without_history_raises_current_stays():
    reg = ModelRegistry()
    reg.load("m", _nn_models(seed0=0), buckets=(1, 4))
    rng = np.random.default_rng(22)
    x = rng.normal(size=(2, 8)).astype(np.float32)
    before = reg.get("m").score_batch(x).tobytes()
    with pytest.raises(LookupError):
        reg.rollback("m")
    assert reg.generation("m") == 0
    assert reg.get("m").score_batch(x).tobytes() == before


def test_crashed_rollback_leaves_current_model_live():
    """serve:swap fires on the rollback path too: an injected error
    before the journal+flip leaves the CURRENT (promoted) model live
    and bit-identical; the disarmed site lets the rollback through."""
    reg = ModelRegistry()
    reg.load("m", _nn_models(seed0=0), buckets=(1, 4))
    rng = np.random.default_rng(23)
    x = rng.normal(size=(3, 8)).astype(np.float32)
    gen0 = reg.get("m").score_batch(x).tobytes()
    reg.swap("m", _nn_models(seed0=50), buckets=(1, 4))
    gen1 = reg.get("m").score_batch(x).tobytes()
    _set_faults("serve:swap=m:ioerror")
    with pytest.raises(faults.InjectedFault):
        reg.rollback("m")
    assert reg.generation("m") == 1
    assert reg.get("m").score_batch(x).tobytes() == gen1
    faults.reset_for_tests()
    environment.reset_for_tests()
    reg.rollback("m")
    assert reg.generation("m") == 0
    assert reg.get("m").score_batch(x).tobytes() == gen0


def test_generation_history_bounded_and_journaled(tmp_path):
    environment.set_property("shifu.serve.generations", "2")
    reg = ModelRegistry(state_dir=str(tmp_path))
    reg.load("m", _nn_models(seed0=0), buckets=(1, 4))
    for s in (10, 20, 30, 40):
        reg.swap("m", _nn_models(seed0=s), buckets=(1, 4))
    hist = reg.generation_history("m")
    assert [h["generation"] for h in hist] == [2, 3]   # bounded at 2
    with open(os.path.join(str(tmp_path), "serving.json")) as f:
        doc = json.load(f)["m"]
    assert doc["generation"] == 4
    assert [h["generation"] for h in doc["history"]] == [2, 3]


def test_restore_resolves_journal_and_rollback_from_dirs(tmp_path):
    """A restarted process restores the promoted generation AND the
    rollback history from serving.json; rollback rebuilds the previous
    scorer from its recorded model dir."""
    from shifu_tpu.models.nn import save_model

    def save_dir(name, seed0):
        d = str(tmp_path / name)
        os.makedirs(d, exist_ok=True)
        for i, m in enumerate(_nn_models(seed0=seed0)):
            save_model(os.path.join(d, f"model{i}.nn"), m.spec, m.params)
        return d

    d0, d1 = save_dir("g0", 0), save_dir("g1", 50)
    state = str(tmp_path / "serving")
    reg = ModelRegistry(state_dir=state)
    reg.load("m", d0, buckets=(1, 4))
    reg.swap("m", d1, buckets=(1, 4))
    rng = np.random.default_rng(24)
    x = rng.normal(size=(3, 8)).astype(np.float32)
    gen0 = Scorer_from_dir_scores(d0, x)
    # fresh process: restore from the journal
    reg2 = ModelRegistry(state_dir=state)
    reg2.restore("m", d0, buckets=(1, 4))
    assert reg2.generation("m") == 1
    assert [h["generation"] for h in reg2.generation_history("m")] == [0]
    reg2.rollback("m")
    assert reg2.generation("m") == 0
    assert reg2.get("m").score_batch(x).tobytes() == gen0


def Scorer_from_dir_scores(d, x):
    from shifu_tpu.eval.scorer import Scorer
    s = AOTScorer(Scorer.from_dir(d).models, buckets=(1, 4))
    return s.score_batch(x).tobytes()


# ------------------------------------------- eval Scorer cache (satellite)
def test_scorer_stacked_groups_rebuild_when_models_change():
    """Regression: ``Scorer._stacked_nn_groups`` cached forever — a
    hot-swap that replaces ``self.models`` on a reused Scorer instance
    must rebuild the stacks, not keep scoring the old ensemble."""
    from shifu_tpu.eval.scorer import Scorer
    rng = np.random.default_rng(8)
    x = rng.normal(size=(4, 8)).astype(np.float32)
    old = _nn_models(2, seed0=0)
    new = _nn_models(2, seed0=123)
    s = Scorer(old)
    first = s.score(x).scores
    s.models = list(new)                          # the hot-swap pattern
    swapped = s.score(x).scores
    fresh = Scorer(new).score(x).scores
    assert swapped.tobytes() == fresh.tobytes()
    assert swapped.tobytes() != first.tobytes()


# ----------------------------------------------------------- CLI surface
def test_cli_serve_selfcheck_on_trained_modelset(prepared_set, capsys):
    """`shifu-tpu serve --selfcheck` loads the trained ensemble from
    <dir>/models, warms the buckets, scores synthetic rows in-process
    and exits 0 — the CI smoke for the production surface."""
    from shifu_tpu.cli import main as cli_main
    from shifu_tpu.config import ModelConfig
    mc = ModelConfig.load(os.path.join(prepared_set, "ModelConfig.json"))
    mc.train.numTrainEpochs = 3
    mc.save(os.path.join(prepared_set, "ModelConfig.json"))
    from shifu_tpu.pipeline.train import TrainProcessor
    assert TrainProcessor(prepared_set, params={}).run() == 0
    rc = cli_main(["--dir", prepared_set,
                   "-Dshifu.serve.buckets=1,4,16", "serve",
                   "--selfcheck", "4"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["selfcheck_rows"] == 4
    assert len(doc["scores_head"]) == 4
    assert doc["buckets"] == [1, 4, 16]
    # journal-style promote wrote the serving manifest atomically
    with open(os.path.join(prepared_set, "serving", "serving.json")) as f:
        j = json.load(f)
    assert list(j.values())[0]["generation"] == 0


# ------------------------------------------------- raw-record serving
def _raw_configs():
    """2 numeric ZSCALE columns + 1 categorical: the minimal mixed
    ColumnConfig snapshot the fused transform has to replay exactly."""
    from shifu_tpu.config import ColumnConfig
    ccs = []
    for j, name in enumerate(("a", "b")):
        cc = ColumnConfig(columnNum=j, columnName=name, finalSelect=True)
        cc.columnBinning.binBoundary = [float("-inf"), 0.0, 1.0]
        cc.columnBinning.binCountNeg = [5, 5, 5]
        cc.columnBinning.binCountPos = [2, 3, 4]
        cc.columnBinning.binPosRate = [2 / 7., 3 / 8., 4 / 9.]
        cc.columnBinning.binCountWoe = [0.1, -0.2, 0.3, 0.0]
        cc.columnStats.mean = 0.4 + j
        cc.columnStats.stdDev = 1.3
        ccs.append(cc)
    cc = ColumnConfig(columnNum=2, columnName="c", finalSelect=True)
    cc.columnBinning.binCategory = ["red", "green", "blue"]
    cc.columnBinning.binCountNeg = [4, 4, 4]
    cc.columnBinning.binCountPos = [1, 2, 3]
    cc.columnBinning.binPosRate = [.2, 1 / 3., 3 / 7.]
    cc.columnBinning.binCountWoe = [0.05, -0.1, 0.2, 0.0]
    ccs.append(cc)
    return ccs


#: raw records exercising every parse edge the offline reader has:
#: missing field, unparseable numeric, unknown category, empty record,
#: string-typed number, int-typed number
_RAW_RECORDS = [
    {"a": 0.5, "b": 1.5, "c": "green"},
    {"a": None, "b": "not-a-number", "c": "chartreuse"},
    {"a": -3, "b": 0.0, "c": "red"},
    {},
    {"a": "2.25", "b": 7, "c": "blue"},
]


def _offline_oracle(mc, ccs, models, records):
    """The offline norm+eval pipeline over JSON records: stringify the
    fields exactly as the CSV reader would, run the host
    DatasetTransformer, score with the batch Scorer, mean-reduce in f32
    — the bit-parity reference for ``score_raw``."""
    import pandas as pd

    from shifu_tpu.data.reader import RawChunk, record_field_str
    from shifu_tpu.data.transform import DatasetTransformer
    from shifu_tpu.eval.scorer import Scorer
    tf = DatasetTransformer(mc, ccs)
    names = [c.columnName for c in tf.columns]
    data = pd.DataFrame({n: [record_field_str(r.get(n)) for r in records]
                         for n in names}, dtype=object)
    tc = tf.transform(RawChunk(columns=names, data=data))
    res = Scorer(models).score(tc.x, bins=tc.bins)
    return np.asarray(res.select("mean"), np.float32)


def _raw_models(kind):
    """A tiny ensemble over the 3-column transform output (x width 3,
    bins width 3) for each model family the serve plane hosts."""
    if kind == "nn":
        return _nn_models(n=2, n_features=3)
    if kind == "gbt":
        from shifu_tpu.models.tree import (IndependentTreeModel,
                                           TreeModelSpec)
        from shifu_tpu.train.dt_trainer import DTSettings, train_gbt
        rng = np.random.default_rng(7)
        bins = rng.integers(0, 4, size=(256, 3)).astype(np.int32)
        y = (rng.random(256) < 0.4).astype(np.float32)
        res = train_gbt(bins, y, np.ones(256, np.float32), 5,
                        np.zeros(3, bool),
                        DTSettings(n_trees=3, depth=3, loss="log",
                                   learning_rate=0.1))
        spec = TreeModelSpec(n_trees=len(res.trees), depth=3, n_bins=5,
                             **res.spec_kwargs)
        return [IndependentTreeModel(spec, res.trees)]
    from shifu_tpu.models.wdl import (IndependentWDLModel, WDLModelSpec)
    from shifu_tpu.models.wdl import init_params as wdl_init
    extra = {"num_feat_idx": [0, 1], "cat_col_idx": [2]}
    cards = [6]
    if kind == "wdl_hashed":
        from shifu_tpu.ops.hashing import column_hash_key
        extra = {**extra, "hash_buckets": 4, "hashed_cols": [0],
                 "hash_keys": [column_hash_key(2)]}
        cards = [4]
    spec = WDLModelSpec(numeric_dim=2, cat_cardinalities=cards,
                        embed_dim=4, hidden_nodes=[8],
                        activations=["relu"], extra=extra)
    return [IndependentWDLModel(spec, wdl_init(jax.random.PRNGKey(5),
                                               spec))]


@pytest.mark.parametrize("kind", ["nn", "gbt", "wdl", "wdl_hashed"])
def test_raw_records_score_bit_identical_to_offline(kind):
    """``score_raw`` over the fused transform is BIT-identical to the
    offline norm+eval pipeline — across NN, GBT, WDL and hashed-ID WDL
    ensembles, including missing/invalid/unknown-category records."""
    from shifu_tpu.config.model_config import ModelConfig
    from shifu_tpu.serve.transform import FusedTransform
    mc, ccs = ModelConfig(), _raw_configs()
    models = _raw_models(kind)
    want = _offline_oracle(mc, ccs, models, _RAW_RECORDS)
    server = ServeServer(models=models, key="raw", buckets=(8,),
                         transform=FusedTransform(mc, ccs))
    out = server.score_raw(_RAW_RECORDS)
    assert out["errors"] == []
    got = np.asarray(out["scores"], np.float32)
    assert got.tobytes() == want.tobytes()


def test_raw_modelset_dir_parity_and_offline_oracle(tmp_path):
    """End-to-end from a modelset DIRECTORY: ``ServeServer(dir)`` wires
    the fused transform from the ModelConfig/ColumnConfig snapshot and
    ``score_records_offline`` (the module-level oracle) agrees bitwise."""
    from shifu_tpu.config import save_column_configs
    from shifu_tpu.config.model_config import ModelConfig
    from shifu_tpu.models.nn import NNModelSpec, init_params, save_model
    from shifu_tpu.pipeline.evaluate import score_records_offline
    d = str(tmp_path)
    ModelConfig().save(os.path.join(d, "ModelConfig.json"))
    save_column_configs(_raw_configs(), os.path.join(d,
                                                     "ColumnConfig.json"))
    spec = NNModelSpec(input_dim=3, hidden_nodes=[4],
                       activations=["tanh"])
    os.makedirs(os.path.join(d, "models"))
    for i in range(2):
        save_model(os.path.join(d, "models", f"model{i}.nn"), spec,
                   init_params(jax.random.PRNGKey(i), spec))
    want = score_records_offline(d, _RAW_RECORDS)
    server = ServeServer(d, key="m", buckets=(8,)).start()
    try:
        assert server.status()["accepts_raw"] is True
        out = server.score_raw(_RAW_RECORDS)
        got = np.asarray(out["scores"], np.float32)
        assert got.tobytes() == want.tobytes()
    finally:
        server.stop()


def test_raw_warmed_server_zero_recompiles():
    """A warmed raw server performs ZERO recompiles over a randomized
    record-count sweep — the fused-transform signature is part of the
    warmed executable set, not a per-request compile."""
    from shifu_tpu.config.model_config import ModelConfig
    from shifu_tpu.serve.transform import FusedTransform
    server = ServeServer(models=_nn_models(n=2, n_features=3),
                         key="raw", buckets=(1, 4, 16),
                         transform=FusedTransform(ModelConfig(),
                                                  _raw_configs()),
                         max_delay_ms=0.0).start()
    try:
        rng = np.random.default_rng(13)
        obs.set_enabled(True)
        before = serve_recompile_count()
        ctr = obs.counter("xla.recompiles")
        xla_before = ctr.value
        for n in rng.integers(1, 17, size=25):
            recs = [{"a": float(rng.normal()), "b": float(rng.normal()),
                     "c": ["red", "green", "blue", "?"][int(rng.integers(4))]}
                    for _ in range(int(n))]
            out = server.score_raw(recs)
            assert all(s is not None for s in out["scores"])
        assert serve_recompile_count() - before == 0
        assert ctr.value - xla_before == 0
    finally:
        server.stop()


def test_raw_malformed_records_rejected_per_record():
    """One bad record never poisons its neighbours: non-object records
    and non-scalar fields get coded errors + null score slots while the
    parseable records around them score BIT-identically to a clean
    batch (the ``-Dshifu.data.badThreshold`` philosophy, per request)."""
    from shifu_tpu.config.model_config import ModelConfig
    from shifu_tpu.serve.transform import (ERR_BAD_FIELD, ERR_BAD_RECORD,
                                           FusedTransform)
    server = ServeServer(models=_nn_models(n=2, n_features=3),
                         key="raw", buckets=(4,),
                         transform=FusedTransform(ModelConfig(),
                                                  _raw_configs()))
    good = [{"a": 0.5, "b": 1.5, "c": "green"},
            {"a": -1.0, "b": 0.25, "c": "red"}]
    mixed = [good[0], 123, {"a": [1, 2], "b": 0.0, "c": "red"}, good[1]]
    out = server.score_raw(mixed)
    assert out["scores"][1] is None and out["scores"][2] is None
    codes = {e["index"]: e["code"] for e in out["errors"]}
    assert codes == {1: ERR_BAD_RECORD, 2: ERR_BAD_FIELD}
    clean = server.score_raw(good)
    assert clean["errors"] == []
    got = np.asarray([out["scores"][0], out["scores"][3]], np.float32)
    assert got.tobytes() == np.asarray(clean["scores"],
                                       np.float32).tobytes()
    # an all-bad request still answers (every slot null, every error
    # coded) — the HTTP front-end maps this shape to a 400
    allbad = server.score_raw([None, 7])
    assert allbad["scores"] == [None, None]
    assert len(allbad["errors"]) == 2


def test_raw_http_records_healthz_and_all_bad_400(tmp_path):
    """``POST /score {"records": ...}`` end-to-end on a loopback port:
    partial rejection answers 200 with null slots + coded errors,
    an all-bad payload answers 400, and ``GET /healthz`` advertises
    ``accepts_raw`` (the bit the fleet router refuses to mix)."""
    import threading
    import urllib.error
    import urllib.request
    from http.server import ThreadingHTTPServer

    from shifu_tpu.config import save_column_configs
    from shifu_tpu.config.model_config import ModelConfig
    from shifu_tpu.models.nn import NNModelSpec, init_params, save_model
    from shifu_tpu.serve.server import _make_handler
    d = str(tmp_path)
    ModelConfig().save(os.path.join(d, "ModelConfig.json"))
    save_column_configs(_raw_configs(), os.path.join(d,
                                                     "ColumnConfig.json"))
    spec = NNModelSpec(input_dim=3, hidden_nodes=[4],
                       activations=["tanh"])
    os.makedirs(os.path.join(d, "models"))
    save_model(os.path.join(d, "models", "model0.nn"), spec,
               init_params(jax.random.PRNGKey(0), spec))
    server = ServeServer(d, key="m", buckets=(4,),
                         max_delay_ms=1.0).start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler(server))
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    def post(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/score",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        return json.load(urllib.request.urlopen(req, timeout=15))
    try:
        doc = post({"records": [{"a": 0.5, "b": 1.5, "c": "green"},
                                17]})
        assert doc["scores"][0] is not None and doc["scores"][1] is None
        assert doc["errors"][0]["index"] == 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            post({"records": [17, None]})
        assert ei.value.code == 400
        health = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=15))
        assert health["accepts_raw"] is True
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.stop()


def test_prebinned_modelset_refuses_raw_and_reports_it():
    """A models-only server (no ColumnConfig snapshot) advertises
    ``accepts_raw: false`` and refuses ``score_raw`` with a pointed
    error instead of scoring garbage."""
    server = ServeServer(models=_nn_models(), key="pb", buckets=(4,))
    assert server.status()["accepts_raw"] is False
    with pytest.raises(ValueError, match="pre-binned"):
        server.score_raw([{"a": 1.0}])
