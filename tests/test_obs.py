"""Tests for the ``shifu_tpu/obs`` telemetry subsystem: span
nesting/ordering, JSONL schema round-trip, registry aggregation (host-side
only — recording from inside ``jit`` must fail), zero-output no-op mode,
and the disabled-path overhead guard."""

import json
import logging
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from shifu_tpu import obs

pytestmark = pytest.mark.obs        # `pytest -m obs` collects this suite


@pytest.fixture
def telemetry():
    """Telemetry force-enabled with clean collector/registry; restores
    the (disabled) env default afterwards so other tests stay no-op."""
    obs.reset_for_tests()
    obs.set_enabled(True)
    yield obs
    obs.reset_for_tests()


@pytest.fixture
def telemetry_off():
    obs.reset_for_tests()
    obs.set_enabled(False)
    yield obs
    obs.reset_for_tests()


# ------------------------------------------------------------------ spans
def test_span_nesting_and_ordering(telemetry):
    with obs.span("root", kind="step") as root:
        with obs.span("child_a"):
            obs.event("tick", i=1)
        with obs.span("child_b") as b:
            with obs.span("grandchild"):
                pass
            b.set(rows=10)
    recs = obs.pending_records()
    spans = {r["name"]: r for r in recs if r["kind"] == "span"}
    assert set(spans) == {"root", "child_a", "child_b", "grandchild"}
    assert spans["root"]["parent"] is None
    assert spans["child_a"]["parent"] == spans["root"]["id"]
    assert spans["child_b"]["parent"] == spans["root"]["id"]
    assert spans["grandchild"]["parent"] == spans["child_b"]["id"]
    assert spans["child_b"]["attrs"]["rows"] == 10
    # children close before parents: record order is completion order
    names = [r["name"] for r in recs if r["kind"] == "span"]
    assert names.index("child_a") < names.index("root")
    assert names.index("grandchild") < names.index("child_b")
    # a parent's duration bounds its children's sum
    assert spans["root"]["dur_s"] >= \
        spans["child_a"]["dur_s"] + spans["child_b"]["dur_s"] - 1e-6
    ev = [r for r in recs if r["kind"] == "event"]
    assert ev[0]["name"] == "tick"
    assert ev[0]["parent"] == spans["child_a"]["id"]


def test_span_error_marked(telemetry):
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    (rec,) = [r for r in obs.pending_records() if r["kind"] == "span"]
    assert rec["attrs"]["error"] == "ValueError"


# ------------------------------------------------------- JSONL round-trip
def test_jsonl_schema_roundtrip(telemetry, tmp_path):
    with obs.span("STATS", kind="step") as sp:
        with obs.span("pass1", rows=1000):
            obs.counter("stats.rows").inc(1000)
        sp.set(exit_code=0)
    obs.gauge("stats.rows_per_sec").set(12345.6)
    obs.histogram("epoch_s").observe(0.5)
    path = str(tmp_path / "telemetry" / "trace.jsonl")
    assert obs.flush(path, step="STATS")
    lines = [json.loads(line) for line in open(path)]
    assert lines[0]["kind"] == "meta"
    assert lines[0]["schema_version"] == obs.SCHEMA_VERSION
    assert lines[0]["step"] == "STATS"
    kinds = {ln["kind"] for ln in lines}
    assert kinds == {"meta", "span", "metric"}
    metrics = {ln["name"]: ln for ln in lines if ln["kind"] == "metric"}
    assert metrics["stats.rows"]["type"] == "counter"
    assert metrics["stats.rows"]["value"] == 1000
    assert metrics["epoch_s"]["count"] == 1
    # flush drained: a second flush adds an empty block, not duplicates
    assert obs.flush(path, step="EMPTY")
    lines2 = [json.loads(line) for line in open(path)]
    assert sum(1 for ln in lines2 if ln["kind"] == "span") == \
        sum(1 for ln in lines if ln["kind"] == "span")
    # the report renders it
    from shifu_tpu.obs.report import render_telemetry
    text = render_telemetry(str(tmp_path))
    assert "STATS" in text and "pass1" in text
    assert "stats.rows" in text and "rows/s" in text


# ---------------------------------------------------------------- registry
def test_registry_aggregation_host_side(telemetry):
    @jax.jit
    def f(x):
        return (x * 2).sum()

    total = 0.0
    for i in range(3):
        v = float(f(jnp.ones((4,)) * (i + 1)))   # value-forced fetch
        obs.counter("work").inc(v)
        obs.histogram("step_val").observe(v)
        total += v
    snap = {m["name"]: m for m in obs.snapshot()}
    assert snap["work"]["value"] == total
    assert snap["step_val"]["count"] == 3
    assert snap["step_val"]["min"] == 8.0 and snap["step_val"]["max"] == 24.0


def test_registry_rejects_tracers(telemetry):
    """Metrics are host-side only: recording from INSIDE jit passes a
    tracer, which the float() coercion must reject loudly instead of
    silently burying a tracer in the registry."""
    @jax.jit
    def bad(x):
        obs.counter("from_jit").inc(x)     # x is a tracer here
        return x

    with pytest.raises(Exception):         # ConcretizationTypeError
        bad(jnp.ones(()))


def test_registry_gauge_high_water_and_type_guard(telemetry):
    g = obs.gauge("hbm")
    g.set_max(10)
    g.set_max(5)
    assert obs.snapshot()[0]["value"] == 10
    with pytest.raises(TypeError):
        obs.counter("hbm")                  # name already bound to a gauge


# ----------------------------------------------------------- no-op mode
def test_disabled_mode_writes_nothing(telemetry_off, tmp_path):
    assert obs.span("x") is obs.span("y")    # shared null singleton
    with obs.span("root") as sp:
        sp.set(a=1)
        obs.event("tick")
        obs.counter("c").inc()
        obs.gauge("g").set(1)
        obs.histogram("h").observe(1)
    assert obs.pending_records() == []
    assert obs.snapshot() == []
    assert obs.live_spans() == []
    path = str(tmp_path / "telemetry" / "trace.jsonl")
    assert obs.flush(path) is False
    assert not os.path.exists(os.path.dirname(path))
    # v2 observability plane: every factory is a None-returning no-op
    # when disabled — no thread, no file, no directory
    assert obs.start_heartbeat(str(tmp_path / "health"), step="X") is None
    assert obs.start_exporter(str(tmp_path / "telemetry")) is None
    assert obs.start_drift_monitor([]) is None
    assert not os.path.exists(str(tmp_path / "health"))
    assert not os.path.exists(str(tmp_path / "telemetry"))
    # v6 cost plane: analytic-model recording is a no-op too
    obs.record_model_launch("pallas.hist", rows=8, n_feat=2, n_bins=4,
                            n_nodes=1)
    assert obs.cost_snapshot() == []


def test_disabled_processor_writes_no_telemetry_files(telemetry_off,
                                                      model_set):
    from shifu_tpu.pipeline.create import InitProcessor
    assert InitProcessor(model_set).run() == 0
    assert not os.path.exists(os.path.join(model_set, "telemetry"))


def test_enabled_processor_writes_root_span(telemetry, model_set):
    from shifu_tpu.pipeline.create import InitProcessor
    assert InitProcessor(model_set).run() == 0
    trace = os.path.join(model_set, "telemetry", "trace.jsonl")
    assert os.path.isfile(trace)
    lines = [json.loads(line) for line in open(trace)]
    spans = {ln["name"]: ln for ln in lines if ln["kind"] == "span"}
    assert "INIT" in spans and spans["INIT"]["parent"] is None
    assert spans["INIT"]["attrs"]["exit_code"] == 0
    assert spans["setup"]["parent"] == spans["INIT"]["id"]
    assert spans["process"]["parent"] == spans["INIT"]["id"]
    from shifu_tpu.obs.report import render_telemetry
    assert "INIT" in render_telemetry(model_set)


# ------------------------------------------------------- trainer metrics
def test_nn_trainer_emits_per_epoch_events(telemetry):
    from shifu_tpu.models.nn import NNModelSpec
    from shifu_tpu.train.nn_trainer import TrainSettings, train_ensemble

    rng = np.random.default_rng(0)
    n, d = 64, 4
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) < 0.4).astype(np.float32)
    w = np.ones((1, n), np.float32)
    spec = NNModelSpec(input_dim=d, hidden_nodes=[4],
                       activations=["tanh"])
    settings = TrainSettings(optimizer="ADAM", learning_rate=0.01,
                             epochs=3)
    train_ensemble(x, y, w, w, spec, settings)
    epochs = [r for r in obs.pending_records()
              if r["kind"] == "event" and r["name"] == "epoch"]
    assert len(epochs) == 3
    assert epochs[0]["attrs"]["trainer"] == "nn"
    assert epochs[-1]["attrs"]["epoch"] == 2
    assert epochs[0]["attrs"]["rows"] == n
    assert epochs[0]["attrs"]["rows_per_sec"] > 0
    snap = {m["name"]: m for m in obs.snapshot()}
    assert snap["train.epochs"]["value"] == 3
    assert snap["train.epoch_s"]["count"] == 3


# -------------------------------------------------- overhead / handshake
def test_disabled_telemetry_overhead_within_noise(telemetry_off):
    """CI guard: with telemetry disabled, an instrumented micro-train
    loop must run within noise of the same loop uninstrumented — the
    no-op span/instrument path may not add per-step work that survives
    timing jitter (generous 1.5x bound, best-of-5 each)."""
    @jax.jit
    def step(p, x):
        return p - 0.01 * (p * x).sum()

    x = jnp.ones((256,))
    p = jnp.ones(())
    step(p, x).block_until_ready()          # compile outside the window

    def plain(p):
        for _ in range(200):
            p = step(p, x)
        return float(p)

    def instrumented(p):
        for i in range(200):
            with obs.span("train_step", i=i):
                # the v2 plane's per-window hot-path additions: the
                # ingest prep/wait spans (null singletons when off) —
                # they must cost one call + one branch, nothing more
                with obs.span("ingest.window_prep", window=i):
                    p = step(p, x)
                obs.counter("steps").inc()
                obs.histogram("loss").observe(0.0)
        return float(p)

    def best(fn):
        out = []
        for _ in range(5):
            t0 = time.perf_counter()
            fn(p)
            out.append(time.perf_counter() - t0)
        return min(out)

    t_plain, t_inst = best(plain), best(instrumented)
    assert t_inst <= t_plain * 1.5 + 1e-3, \
        (f"disabled-telemetry overhead too high: {t_inst:.4f}s vs "
         f"{t_plain:.4f}s uninstrumented")
    assert obs.pending_records() == []       # and truly recorded nothing


def test_disabled_costed_jit_is_bare_jit(telemetry_off):
    """The cost plane rides the same zero-overhead guarantee: telemetry
    off at wrap time ⇒ costed_jit returns THE bare jax.jit callable (no
    wrapper frames), the lazy (module-scope) form costs one branch per
    call, and neither writes a cost record."""
    from shifu_tpu.obs import costs

    def f(x):
        return (x * 2.0).sum()

    bare = costs.costed_jit("test.bare", f)
    # not a wrapper: the exact type jax.jit returns
    assert type(bare) is type(jax.jit(f))
    assert not isinstance(bare, costs.CostedJit)
    x = jnp.ones((256,))
    float(bare(x))
    assert costs.cost_snapshot() == []       # no registry writes

    lz = costs.costed_jit("test.lazy", f, lazy=True)
    jb = jax.jit(f)
    float(lz(x)), float(jb(x))               # compile both outside timing

    def best(fn):
        out = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(200):
                fn(x)
            out.append(time.perf_counter() - t0)
        return min(out)

    t_plain, t_lazy = best(jb), best(lz)
    assert t_lazy <= t_plain * 1.5 + 1e-3, \
        (f"disabled lazy costed_jit overhead too high: {t_lazy:.4f}s vs "
         f"{t_plain:.4f}s bare jit")
    assert costs.cost_snapshot() == []
    assert obs.pending_records() == []


# ----------------------------------------------------------------- logging
def test_library_logging_null_handler():
    """Programmatic use must neither print nor warn 'no handlers':
    the package root logger carries a NullHandler."""
    lg = logging.getLogger("shifu_tpu")
    assert any(isinstance(h, logging.NullHandler) for h in lg.handlers)


def test_configure_logging_honors_env(monkeypatch):
    import shifu_tpu
    monkeypatch.setenv("SHIFU_TPU_LOG", "WARNING")
    root_before = logging.getLogger().level
    try:
        shifu_tpu.configure_logging(verbose=True)   # env beats -v
        assert logging.getLogger("shifu_tpu").level == logging.WARNING
    finally:
        monkeypatch.delenv("SHIFU_TPU_LOG")
        logging.getLogger().setLevel(root_before)
        logging.getLogger("shifu_tpu").setLevel(logging.NOTSET)
