"""Per-request tracing suite (serve plane, tier-1-fast): trace-id
propagation end to end, the latency decomposition summing to measured
e2e, batch spans linking member ids (fan-in causality), the zero-cost
guards for sampling off, the ``X-Shifu-Trace`` HTTP header, and the
``shifu-serve`` timeline track."""

import json
import os

import numpy as np
import pytest

import jax

from shifu_tpu import obs
from shifu_tpu.config import environment
from shifu_tpu.models.nn import (IndependentNNModel, NNModelSpec,
                                 init_params)
from shifu_tpu.serve import AOTScorer, MicroBatcher, ServeServer
from shifu_tpu.serve.batcher import configured_trace_sample_rate

pytestmark = pytest.mark.serve


@pytest.fixture(autouse=True)
def _clean_env():
    environment.reset_for_tests()
    obs.reset_for_tests()
    yield
    environment.reset_for_tests()
    obs.reset_for_tests()


def _nn_models(n=3, n_features=8, seed0=0):
    spec = NNModelSpec(input_dim=n_features, hidden_nodes=[8],
                       activations=["relu"])
    return [IndependentNNModel(spec, init_params(
        jax.random.PRNGKey(seed0 + i), spec)) for i in range(n)]


def _warm_scorer(buckets=(1, 4, 16)):
    scorer = AOTScorer(_nn_models(), buckets=buckets)
    scorer.warm()
    return scorer


def _request_spans():
    return [r for r in obs.pending_records()
            if r.get("kind") == "span" and r["name"] == "serve.request"]


def _batch_spans():
    return [r for r in obs.pending_records()
            if r.get("kind") == "span" and r["name"] == "serve.batch"]


# ------------------------------------------------------------- sampling
def test_sample_rate_property_reader():
    assert configured_trace_sample_rate() == 0.0
    environment.set_property("shifu.serve.traceSampleRate", "0.25")
    assert configured_trace_sample_rate() == 0.25
    environment.set_property("shifu.serve.traceSampleRate", "7")
    assert configured_trace_sample_rate() == 1.0    # clamped
    environment.set_property("shifu.serve.traceSampleRate", "-1")
    assert configured_trace_sample_rate() == 0.0


def test_sample_rate_zero_writes_zero_request_records():
    """ACCEPTANCE: sampling off (the default) writes NO request/batch
    records even with telemetry fully enabled, and scoring is
    unaffected."""
    obs.set_enabled(True)
    scorer = _warm_scorer()
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0)
    assert b.trace_sample_rate == 0.0
    rng = np.random.default_rng(0)
    for n in (1, 3, 7):
        t = b.submit_burst(rng.normal(size=(n, 8)).astype(np.float32))
        b.drain()
        assert t.wait(10.0).shape == (n,)
    assert _request_spans() == [] and _batch_spans() == []
    snap = {m["name"]: m for m in obs.snapshot()}
    assert "serve.trace_sampled" not in snap


def test_sampled_scores_bit_identical_to_unsampled():
    """Tracing must OBSERVE the batch path, never perturb it: the same
    rows scored with and without a trace id produce bit-identical
    scores."""
    obs.set_enabled(True)
    scorer = _warm_scorer()
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(5, 8)).astype(np.float32)
    t1 = b.submit_burst(x)
    b.drain()
    plain = t1.wait(10.0)
    t2 = b.submit_burst(x, trace_id="parity-check")
    b.drain()
    traced = t2.wait(10.0)
    assert traced.tobytes() == plain.tobytes()


def test_trace_id_minted_when_head_sampled():
    obs.set_enabled(True)
    scorer = _warm_scorer()
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0,
                     trace_sample_rate=1.0)
    rng = np.random.default_rng(4)
    t = b.submit_burst(rng.normal(size=(2, 8)).astype(np.float32))
    b.drain()
    t.wait(10.0)
    (req,) = _request_spans()
    assert req["attrs"]["trace"]                 # minted, non-empty
    assert req["tid"] == "shifu-serve"
    snap = {m["name"]: m for m in obs.snapshot()}
    assert snap["serve.trace_sampled"]["value"] == 1


def test_sampling_disabled_without_telemetry():
    """Head sampling requires telemetry (records would go nowhere);
    rate > 0 with obs off emits nothing and costs nothing."""
    obs.set_enabled(False)
    scorer = _warm_scorer()
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0,
                     trace_sample_rate=1.0)
    t = b.submit_burst(np.random.default_rng(5).normal(
        size=(2, 8)).astype(np.float32))
    b.drain()
    t.wait(10.0)
    assert t.trace is None
    assert obs.pending_records() == []


# ------------------------------------------------------- decomposition
def test_request_span_segments_sum_to_e2e():
    """ACCEPTANCE: a sampled burst's decomposition (queue-wait + pad +
    launch + device) sums, within tolerance, to the measured end-to-end
    latency; every segment is non-negative."""
    obs.set_enabled(True)
    scorer = _warm_scorer()
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(5, 8)).astype(np.float32)
    # a couple of warm loops so lazy one-time costs (fault-site property
    # parse, dispatch path) sit outside the measured request
    for _ in range(3):
        t0 = b.submit_burst(x)
        b.drain()
        t0.wait(10.0)
    t = b.submit_burst(x, trace_id="sum-check")
    b.drain()
    t.wait(10.0)
    measured_e2e = float(t.latencies().max())
    req = next(r for r in _request_spans()
               if r["attrs"]["trace"] == "sum-check")
    a = req["attrs"]
    segments = (a["queue_wait_s"], a["pad_s"], a["launch_s"],
                a["device_s"])
    assert all(s >= 0.0 for s in segments)
    assert a["deadline_wait_s"] <= a["queue_wait_s"] + 1e-9
    total = sum(segments)
    # segments are nested inside e2e: they must not exceed it, and the
    # unattributed remainder (scheduler hops, completion bookkeeping)
    # stays small
    assert total <= a["e2e_s"] + 1e-6
    slack = max(0.5 * a["e2e_s"], 0.02)
    assert a["e2e_s"] - total <= slack, (a, total)
    # the span's own duration agrees with the measured ticket latency
    assert a["e2e_s"] == pytest.approx(measured_e2e,
                                       rel=0.5, abs=0.02)


def test_batch_span_links_all_member_trace_ids():
    """ACCEPTANCE: requests coalescing into one batch produce ONE
    serve.batch span whose links carry every sampled member's trace id,
    and each member's request span points back at the batch index."""
    obs.set_enabled(True)
    scorer = _warm_scorer(buckets=(1, 4, 16))
    clk_rows = np.random.default_rng(8).normal(size=(2, 8)) \
        .astype(np.float32)
    b = MicroBatcher(lambda: scorer, max_delay_s=10.0)
    t1 = b.submit_burst(clk_rows, trace_id="alpha")
    t2 = b.submit_burst(clk_rows, trace_id="beta")
    b.pump(force=True)                       # one coalesced launch
    t1.wait(10.0), t2.wait(10.0)
    (batch,) = _batch_spans()
    assert sorted(batch["attrs"]["links"]) == ["alpha", "beta"]
    assert batch["attrs"]["rows"] == 4
    assert batch["attrs"]["flush"] == "forced"
    reqs = _request_spans()
    assert {r["attrs"]["trace"] for r in reqs} == {"alpha", "beta"}
    assert all(r["attrs"]["batch"] == batch["attrs"]["batch"]
               for r in reqs)


def test_split_burst_emits_one_request_span_after_final_batch():
    obs.set_enabled(True)
    scorer = _warm_scorer(buckets=(1, 4))
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0)
    x = np.random.default_rng(9).normal(size=(10, 8)).astype(np.float32)
    t = b.submit_burst(x, trace_id="split")
    b.drain()
    t.wait(10.0)
    (req,) = _request_spans()
    assert req["attrs"]["batches"] == 3          # 4 + 4 + 2
    assert len(_batch_spans()) == 3
    assert all("split" in bs["attrs"]["links"] for bs in _batch_spans())


def test_failed_batch_marks_trace_error():
    from shifu_tpu import faults
    obs.set_enabled(True)
    scorer = _warm_scorer(buckets=(1, 4))
    environment.set_property("shifu.faults", "serve:request=0:ioerror")
    faults.reset_for_tests()
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0)
    t = b.submit_burst(np.random.default_rng(10).normal(
        size=(2, 8)).astype(np.float32), trace_id="boom")
    b.drain()
    with pytest.raises(faults.InjectedFault):
        t.wait(10.0)
    (req,) = _request_spans()
    assert req["attrs"]["error"] == "InjectedFault"
    (batch,) = _batch_spans()
    assert batch["attrs"]["error"] == "InjectedFault"
    environment.reset_for_tests()
    faults.reset_for_tests()


# ------------------------------------------------------- server / HTTP
def test_http_trace_header_propagates_and_flushes(tmp_path):
    """X-Shifu-Trace rides the HTTP front-end onto the batch pipeline
    (forcing sampling), echoes in the response, and stop() flushes the
    sampled spans into <modelset>/telemetry/trace.jsonl as a SERVE
    block."""
    import threading
    import urllib.request
    from http.server import ThreadingHTTPServer

    from shifu_tpu.serve.server import _make_handler
    obs.set_enabled(True)
    mdir = str(tmp_path)
    server = ServeServer(model_set_dir=mdir, models=_nn_models(),
                         key="h", buckets=(1, 4), max_delay_ms=1.0)
    server.start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler(server))
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        rows = np.random.default_rng(11).normal(size=(2, 8)) \
            .round(4).tolist()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/score",
            data=json.dumps({"rows": rows}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Shifu-Trace": "edge-42"})
        doc = json.load(urllib.request.urlopen(req, timeout=15))
        assert doc["trace"] == "edge-42" and len(doc["scores"]) == 2
        # /slo is live on the same front-end
        slo = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/slo", timeout=15))
        assert slo["kind"] == "slo" and "horizons" in slo
        health = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=15))
        assert "queue_depth" in health and "slo" in health
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.stop()
    trace = os.path.join(mdir, "telemetry", "trace.jsonl")
    lines = [json.loads(ln) for ln in open(trace)]
    metas = [ln for ln in lines if ln["kind"] == "meta"]
    assert any(m["step"] == "SERVE" for m in metas)
    spans = [ln for ln in lines if ln.get("kind") == "span"]
    assert any(ln["name"] == "serve.request"
               and ln["attrs"]["trace"] == "edge-42" for ln in spans)


def test_timeline_routes_serve_spans_to_own_track(tmp_path):
    """The exported timeline puts serve.request/serve.batch spans on the
    shifu-serve track, separate from compute and ingest."""
    from shifu_tpu.obs import timeline as timeline_mod
    obs.set_enabled(True)
    scorer = _warm_scorer(buckets=(1, 4))
    b = MicroBatcher(lambda: scorer, max_delay_s=0.0)
    t = b.submit_burst(np.random.default_rng(12).normal(
        size=(2, 8)).astype(np.float32), trace_id="tl")
    b.drain()
    t.wait(10.0)
    trace = os.path.join(str(tmp_path), "telemetry", "trace.jsonl")
    obs.flush(trace, step="SERVE")
    out = timeline_mod.export_timeline(str(tmp_path),
                                       str(tmp_path / "tl.json"))
    doc = json.load(open(out))
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    serve_tids = {e["tid"] for e in spans
                  if e["name"].startswith("serve.")}
    assert serve_tids == {timeline_mod.TID_SERVE}
    labels = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "shifu-serve" in labels[timeline_mod.TID_SERVE]


# ---------------------------------------------------- zero-cost guard
def test_serve_rate_zero_overhead_within_noise(monkeypatch):
    """CI guard (the PR 1 convention extended to the serve path): with
    sampling OFF and telemetry ON, the submit->pump->complete hot path
    never enters the tracing branch — rate 0 short-circuits on one float
    compare before the sampler's RNG is drawn, no trace state is built
    and no batch folds a decomposition.  Counted, not timed: a wall-clock
    ratio on a CPU shared with other test workers is not a verdict."""
    from shifu_tpu.serve import batcher as batcher_mod
    calls = {"rng": 0, "req_trace": 0, "emit": 0}

    def counted(key, fn):
        def wrapper(*a, **k):
            calls[key] += 1
            return fn(*a, **k)
        return wrapper

    monkeypatch.setattr(batcher_mod, "_ReqTrace",
                        counted("req_trace", batcher_mod._ReqTrace))
    monkeypatch.setattr(
        MicroBatcher, "_emit_trace_spans",
        counted("emit", MicroBatcher._emit_trace_spans))
    scorer = _warm_scorer(buckets=(1, 4))
    x = np.random.default_rng(13).normal(size=(4, 8)).astype(np.float32)

    def loop(rate):
        b = MicroBatcher(lambda: scorer, max_delay_s=0.0,
                         trace_sample_rate=rate)
        b._trace_rng.random = counted("rng", b._trace_rng.random)
        tickets = [b.submit_burst(x) for _ in range(50)]
        b.drain()
        for t in tickets:
            t.wait(10.0)
        return tickets

    obs.set_enabled(True)
    tickets = loop(0.0)
    assert calls == {"rng": 0, "req_trace": 0, "emit": 0}
    assert all(t.trace is None for t in tickets)
    assert _request_spans() == [] and _batch_spans() == []
    # the counters do see the branch: the same loop, every request sampled
    loop(1.0)
    assert calls["rng"] == 50 and calls["req_trace"] == 50
    assert calls["emit"] >= 1 and len(_request_spans()) == 50
