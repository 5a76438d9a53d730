"""chip_smoke.py — the quickest proof that shifu-tpu still starts on the chip.

    python chip_smoke.py          # from the checkout root, on a TPU host

ONE process (a chip belongs to one process; nothing here spawns a child
that needs it) drives the main path through the entry points a user calls,
at the one width this program has had on a chip — 131,072 rows x 64
numeric columns (+2 categorical), ``stats.maxNumBin = 64`` — with data and
weights made from a seed:

1. device     platform / device_kind / count / versions / x64 (must be off).
              Not ``tpu`` -> exit 1, nothing else runs, no result line.
2. data       ``examples/make_fraud_data.make_wide`` (seeded).
3. pipeline   ``shifu_tpu.cli.main``: new, init, stats, norm;
              (a) train GBT (``init -model`` defaults, TreeNum 16 = two
                  8-tree rounds, log loss) + ``eval -run``;
              (b) streamed GBT with a device cache below the binned plane
                  (TreeNum 2): the disk tail and its off-CPU default
                  schedule run once;
              (c) train NN [512, 256] relu ADAM, 3 epochs + ``eval -run``.
4. serve      ``ServeServer`` on port 0 in a thread, the GBT model of (a),
              ``POST /score`` with 1, 8 and 100 raw records.
5. kernels    each Pallas kernel at the pipeline's shapes against its jnp
              reference.

Every check is a plain ``assert``: the first failure ends the run with a
traceback and a non-zero exit, and no result line.  What was lowered is
read from the StableHLO JAX dumps per compile (``jax_dump_ir_to``), not
inferred from the platform: a leg that should have run a Mosaic kernel
must show a ``tpu_custom_call``, and on several chips the training step
must be partitioned over all of them with the kernel wrappers' psum.

The last stdout line is ``{"ok": true, "device": {...}}``.
"""

import glob
import http.client
import importlib.util
import json
import logging
import os
import re
import shutil
import sys
import threading
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)            # the package comes from this checkout

from shifu_tpu import compile_cache  # noqa: E402  (no jax import yet)

CACHE_DIR = compile_cache.configure()

import numpy as np  # noqa: E402

import jax  # noqa: E402

# everything the run writes: data, the model set, the IR dump (gitignored)
WORK = os.path.join(ROOT, ".chip_smoke")
IR_DIR = os.path.join(WORK, "ir")
SUMMARY = os.path.join(ROOT, "chiprun_out", "chip_smoke_summary.json")

ROWS, NUMERIC, MAX_BINS = 131072, 64, 64
GBT_TREES = 16                      # two TreeBatch/EarlyStopCheckInterval rounds
NN_HIDDEN, NN_EPOCHS = [512, 256], 3
# AUC floors against the generator's ceiling (the AUC of the TRUE
# probability, 0.839 at this seed).  5% of the cells are missing and both
# models are cut short (16 trees at LearningRate 0.05; 3 epochs), so they
# sit below it: a CPU rehearsal at full width gave GBT 0.808 / NN 0.830 —
# the floors leave ~0.04 under those for the chip's matmul precision.
AUC_BELOW_BAYES = {"gbt": 0.07, "nn": 0.05}
# serve vs offline eval, on the 0..1000 score scale: EvalScore keeps three
# decimals (5e-4 rounding), the HTTP edge six; the rest is f32 link math
# (sigmoid on the host in eval, in-graph in serve) — ulp(1000) is 6e-5.
SERVE_TOL = 2e-3

summary = {"phases": {}, "legs": {}}


def say(msg: str) -> None:
    print(f"[smoke {time.perf_counter() - T_START:7.1f}s] {msg}", flush=True)


# ------------------------------------------------------------------ device
def phase_device() -> dict:
    import importlib.metadata as md

    import jaxlib
    devs = jax.devices()
    d0 = devs[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    x64 = bool(jax.config.jax_enable_x64)
    say(f"platform={d0.platform} device_kind={d0.device_kind} "
        f"count={len(devs)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu} x64={x64} "
        f"compile_cache={CACHE_DIR}")
    if d0.platform != "tpu":
        say("no TPU: this smoke proves the chip path and does not run "
            "anywhere else")
        sys.exit(1)
    assert not x64, "deployment configuration is x32 (JAX_ENABLE_X64 off)"
    from shifu_tpu.obs.costs import backend_info, resolve_peaks
    flops, bw, label = resolve_peaks(backend_info())
    assert flops and bw, f"device_kind {d0.device_kind!r} has no peak row"
    say(f"peaks[{label}]: {flops:.3e} FLOP/s, {bw:.3e} B/s")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------- IR dump
_ir_seen: set = set()


def lowered_since_last() -> dict:
    """What JAX lowered since the previous call, from the per-compile
    StableHLO dump: module count, modules holding a Mosaic kernel, the
    widest partitioning, modules with an explicit all_reduce (the
    shard_map'd kernel wrappers' psum), and for each Mosaic module the
    mesh ``data`` axis it was lowered for with how many of its operands
    are row-sharded over it (where the resident planes live)."""
    out = {"modules": 0, "mosaic": [], "partitions": 1, "all_reduce": [],
           "planes": {}}
    for path in sorted(glob.glob(os.path.join(IR_DIR, "*.mlir"))):
        if path in _ir_seen:
            continue
        _ir_seen.add(path)
        with open(path) as f:
            text = f.read()
        name = re.sub(r"^jax_ir\d+_|_compile\.mlir$", "",
                      os.path.basename(path))
        out["modules"] += 1
        if "tpu_custom_call" in text:
            out["mosaic"].append(name)
            m = re.search(r'sdy\.mesh @mesh = <\[.*?"data"=(\d+)', text)
            out["planes"][name] = (int(m.group(1)) if m else 1,
                                   text.count('@mesh, [{"data"}'))
        if "all_reduce" in text:
            out["all_reduce"].append(name)
        m = re.search(r"mhlo\.num_partitions = (\d+)", text)
        if m:
            out["partitions"] = max(out["partitions"], int(m.group(1)))
    return out


def close_leg(name: str, t0: float, n_dev: int, want_mosaic: int,
              want_sharded: bool = False) -> dict:
    """Record a leg: wall, what it lowered, where its live arrays sit.
    ``want_mosaic`` = how many lowered modules must hold a Mosaic kernel;
    ``want_sharded`` = on several chips the leg must be partitioned over
    all of them and pair the kernel with the wrappers' psum."""
    ir = lowered_since_last()
    live = jax.live_arrays()
    dev_ids = sorted({d.id for a in live for d in a.devices()})
    leg = {"wall_s": round(time.perf_counter() - t0, 1),
           "modules": ir["modules"], "mosaic_modules": len(ir["mosaic"]),
           "partitions": ir["partitions"],
           "all_reduce_modules": len(ir["all_reduce"]),
           "live_arrays": len(live), "live_on_devices": dev_ids}
    summary["legs"][name] = leg
    say(f"leg {name}: {leg['wall_s']}s, {ir['modules']} modules lowered, "
        f"{len(ir['mosaic'])} with tpu_custom_call "
        f"{sorted(set(ir['mosaic']))[:6]}, partitions={ir['partitions']}, "
        f"explicit all_reduce in {len(ir['all_reduce'])}; "
        f"{len(live)} live arrays on devices {dev_ids}")
    for mod, (axis, n_sharded) in sorted(ir["planes"].items()):
        say(f"  {mod}: lowered for a data axis of {axis} device(s), "
            f"{n_sharded} operand(s) row-sharded over it")
    assert len(ir["mosaic"]) >= want_mosaic, \
        f"{name}: {len(ir['mosaic'])} lowered module(s) hold a " \
        f"tpu_custom_call, expected >= {want_mosaic} — the Mosaic " \
        "kernel did not run on this leg"
    if want_sharded and n_dev > 1:
        assert ir["partitions"] == n_dev, \
            f"{name}: widest program spans {ir['partitions']} devices, " \
            f"host has {n_dev}"
        both = set(ir["mosaic"]) & set(ir["all_reduce"])
        assert both, f"{name}: no module pairs the kernel with a psum " \
            "(the shard_map wrappers did not run)"
        assert any(ir["planes"][m][0] == n_dev and ir["planes"][m][1]
                   for m in both), \
            f"{name}: the kernel's operands are not row-sharded over " \
            f"{n_dev} devices: {ir['planes']}"
    return leg


# ---------------------------------------------------------------- pipeline
def cli(*args: str) -> None:
    from shifu_tpu.cli import main
    t0 = time.perf_counter()
    rc = main(list(args))
    say(f"cli {' '.join(args[2:])} -> rc={rc} "
        f"({time.perf_counter() - t0:.1f}s)")
    assert rc == 0, f"shifu-tpu {' '.join(args)} returned {rc}"


def progress_errors(mdir: str) -> list:
    """[(train_err, valid_err)] per tree/epoch from tmp/train.progress."""
    out = []
    with open(os.path.join(mdir, "tmp", "train.progress")) as f:
        for line in f:
            m = re.search(r"Train Error: (\S+) Validation Error: (\S+)",
                          line)
            if m:
                out.append((float(m.group(1)), float(m.group(2))))
    return out


def check_progress(mdir: str, what: str, n_min: int) -> list:
    errs = progress_errors(mdir)
    assert len(errs) >= n_min, f"{what}: {len(errs)} progress lines"
    assert np.isfinite(np.asarray(errs)).all(), f"{what}: {errs}"
    assert errs[-1][0] < errs[0][0], \
        f"{what}: train error did not fall ({errs[0][0]} -> {errs[-1][0]})"
    say(f"{what}: train error {errs[0][0]:.6f} -> {errs[-1][0]:.6f}, "
        f"validation {errs[0][1]:.6f} -> {errs[-1][1]:.6f} "
        f"over {len(errs)} lines")
    return errs


def eval_auc(mdir: str, what: str, floor: float) -> float:
    with open(os.path.join(mdir, "evals", "Eval1",
                           "EvalPerformance.json")) as f:
        auc = float(json.load(f)["areaUnderRoc"])
    say(f"{what}: eval AUC {auc:.4f} (floor {floor:.4f})")
    assert np.isfinite(auc) and auc >= floor, (what, auc, floor)
    return auc


def eval_scores(mdir: str, n: int) -> np.ndarray:
    """The first ``n`` mean scores of EvalScore (``eval -run`` keeps input
    row order)."""
    out = []
    with open(os.path.join(mdir, "evals", "Eval1", "EvalScore")) as f:
        header = f.readline().strip().split("|")
        col = header.index("mean")
        for line in f:
            out.append(float(line.split("|")[col]))
            if len(out) == n:
                break
    return np.asarray(out)


def set_train(mdir: str, algorithm: str, params: dict,
              epochs: int = None) -> None:
    from shifu_tpu.config import ModelConfig
    from shifu_tpu.config.model_config import Algorithm
    path = os.path.join(mdir, "ModelConfig.json")
    mc = ModelConfig.load(path)
    mc.train.algorithm = Algorithm[algorithm]
    mc.train.params = params
    if epochs is not None:
        mc.train.numTrainEpochs = epochs
    mc.save(path)


def phase_pipeline(truth: dict, n_dev: int) -> dict:
    from shifu_tpu.config import ModelConfig
    from shifu_tpu.parallel.mesh import device_mesh
    mesh = device_mesh()
    say(f"device_mesh() every step builds: {dict(mesh.shape)} over "
        f"{[d.id for d in mesh.devices.flat]}")
    assert mesh.size == n_dev

    cli("--dir", WORK, "new", "smoke", "-t", "GBT")
    mdir = os.path.join(WORK, "smoke")
    path = os.path.join(mdir, "ModelConfig.json")
    mc = ModelConfig.load(path)
    ds = mc.dataSet
    ds.dataPath, ds.dataDelimiter = truth["path"], "|"
    ds.targetColumnName, ds.posTags, ds.negTags = "tag", ["bad"], ["good"]
    ds.weightColumnName = "weight"
    ds.metaColumnNameFile = truth["meta"]
    ds.categoricalColumnNameFile = truth["categorical"]
    mc.stats.maxNumBin = MAX_BINS
    mc.train.baggingNum = 1
    mc.evals[0].dataSet.dataPath = truth["path"]
    mc.evals[0].dataSet.dataDelimiter = "|"
    mc.save(path)

    t0 = time.perf_counter()
    cli("--dir", mdir, "init")
    cli("--dir", mdir, "init", "-model")        # GBT default train#params
    cli("--dir", mdir, "stats", "--telemetry")
    close_leg("stats", t0, n_dev, want_mosaic=1, want_sharded=True)
    t0 = time.perf_counter()
    cli("--dir", mdir, "norm", "--telemetry")
    close_leg("norm", t0, n_dev, want_mosaic=0)

    # (a) resident GBT at the init -model defaults, cut to 16 trees
    gbt = dict(ModelConfig.load(path).train.params)
    assert gbt["MaxDepth"] == 7, gbt
    gbt.update(TreeNum=GBT_TREES, Loss="log")
    set_train(mdir, "GBT", gbt)
    t0 = time.perf_counter()
    cli("--dir", mdir, "train", "--telemetry")
    close_leg("train_gbt", t0, n_dev, want_mosaic=1, want_sharded=True)
    check_progress(mdir, "GBT", GBT_TREES)
    t0 = time.perf_counter()
    cli("--dir", mdir, "eval", "-run", "--telemetry")
    # one chip: the tree-scoring kernel; several: eval shards rows over
    # the mesh and mesh-sharded bins take the jnp walk on purpose
    leg = close_leg("eval_gbt", t0, n_dev, want_mosaic=int(n_dev == 1))
    say("eval_gbt tree scoring ran " + (
        "the Pallas traversal kernel" if leg["mosaic_modules"] else
        "the jnp walk (mesh-sharded bins) — the kernel is NOT covered "
        "by this leg"))
    auc_gbt = eval_auc(mdir, "GBT",
                       truth["bayes_auc"] - AUC_BELOW_BAYES["gbt"])
    offline = eval_scores(mdir, 100)
    # freeze (a)'s model + config snapshot for the serve phase: legs (b)
    # and (c) retrain in this model set
    serve_dir = os.path.join(WORK, "serve_gbt")
    os.makedirs(serve_dir)
    shutil.copytree(os.path.join(mdir, "models"),
                    os.path.join(serve_dir, "models"))
    for f in ("ModelConfig.json", "ColumnConfig.json"):
        shutil.copy(os.path.join(mdir, f), serve_dir)

    # (b) streamed, device cache at ~half the uint8 binned plane
    n_cols = NUMERIC + 2
    cache_bytes = ROWS * n_cols // 2
    set_train(mdir, "GBT", dict(gbt, TreeNum=2))
    t0 = time.perf_counter()
    cli("-Dshifu.train.streaming=on",
        f"-Dshifu.train.deviceCacheBytes={cache_bytes}",
        "--dir", mdir, "train", "--telemetry")
    close_leg("train_gbt_tail", t0, n_dev, want_mosaic=1,
              want_sharded=True)
    check_progress(mdir, "GBT disk tail", 2)
    from shifu_tpu.train.dt_trainer import _tail_coarse_to_fine
    tail = trace_counts(mdir)
    say(f"disk tail: schedule "
        f"{'coarse-to-fine' if _tail_coarse_to_fine() else 'exact'} (the "
        f"backend's default), train.tail_sweeps={tail['tail_sweeps']:.0f} "
        f"tail_repairs={tail['tail_repairs']:.0f} c2f_fallbacks="
        f"{tail['tail_c2f_fallbacks']:.0f}, pallas.hist cost-model "
        f"launches={tail['pallas_hist_launches']}")
    assert tail["tail_sweeps"] > 0, "the run stayed resident: no disk tail"
    assert tail["pallas_hist_launches"] > 0

    # (c) NN; empty -D values clear (b)'s overrides
    set_train(mdir, "NN", {"NumHiddenLayers": 2,
                           "NumHiddenNodes": NN_HIDDEN,
                           "ActivationFunc": ["relu", "relu"],
                           "LearningRate": 0.001, "Propagation": "ADAM",
                           "Loss": "log", "MiniBatchs": 4096},
              epochs=NN_EPOCHS)
    t0 = time.perf_counter()
    cli("-Dshifu.train.streaming=", "-Dshifu.train.deviceCacheBytes=",
        "--dir", mdir, "train", "--telemetry")
    close_leg("train_nn", t0, n_dev, want_mosaic=0)
    check_progress(mdir, "NN", NN_EPOCHS)
    t0 = time.perf_counter()
    cli("--dir", mdir, "eval", "-run", "--telemetry")
    close_leg("eval_nn", t0, n_dev, want_mosaic=0)
    auc_nn = eval_auc(mdir, "NN", truth["bayes_auc"] - AUC_BELOW_BAYES["nn"])
    return {"mdir": mdir, "serve_dir": serve_dir, "offline": offline,
            "auc_gbt": auc_gbt, "auc_nn": auc_nn}


def trace_counts(mdir: str) -> dict:
    """Totals over the model set's telemetry trace (each step's flush
    resets the registry, so sums run over the per-step blocks)."""
    from shifu_tpu.obs.report import load_blocks, trace_path
    names = {"xla.compile_count": "compile_count",
             "xla.compile_time_s": "compile_time_s",
             "train.tail_sweeps": "tail_sweeps",
             "train.tail_repairs": "tail_repairs",
             "train.tail_c2f_fallbacks": "tail_c2f_fallbacks"}
    out = dict.fromkeys(names.values(), 0.0)
    out["pallas_hist_launches"] = 0
    for block in load_blocks(trace_path(mdir)):
        for m in block["metrics"]:
            if m.get("name") in names:
                out[names[m["name"]]] += float(m.get("value") or 0)
        for c in block["costs"]:
            if c.get("name") == "pallas.hist":
                out["pallas_hist_launches"] += int(c.get("launches") or 0)
    return out


# ------------------------------------------------------------------- serve
def phase_serve(pipe: dict, truth: dict, n_dev: int) -> dict:
    from http.server import ThreadingHTTPServer

    from shifu_tpu.serve.scorer import serve_recompile_count
    from shifu_tpu.serve.server import ServeServer, _make_handler

    with open(truth["path"]) as f:
        header = f.readline().rstrip("\n").split("|")
        records = [dict(zip(header, f.readline().rstrip("\n").split("|")))
                   for _ in range(100)]
    t0 = time.perf_counter()
    server = ServeServer(pipe["serve_dir"])     # loads, AOT-compiles, warms
    server.start()
    scorer = server.registry.get(server.key)
    assert scorer.accepts_raw and scorer.needs_bins
    say(f"serve: buckets {list(scorer.buckets)} compiled and warmed for "
        f"the default device — serving uses ONE chip of {n_dev}")
    recompiles0 = serve_recompile_count()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler(server))
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1",
                                          httpd.server_address[1],
                                          timeout=120)

        def post(n: int) -> np.ndarray:
            body = json.dumps({"records": records[:n]})
            conn.request("POST", "/score", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            doc = json.loads(resp.read())
            assert resp.status == 200, (n, resp.status, doc)
            assert not doc["errors"], doc["errors"]
            got = np.asarray(doc["scores"], np.float64)
            assert got.shape == (n,) and np.isfinite(got).all(), doc
            diff = float(np.abs(got - pipe["offline"][:n]).max())
            say(f"POST /score {n:3d} raw records -> 200, max |serve - "
                f"offline eval| = {diff:.2e} (tolerance {SERVE_TOL:g})")
            assert diff <= SERVE_TOL, (n, diff)
            return got

        first = {n: post(n) for n in (1, 8, 100)}
        # the same buckets again: a donated input buffer must not be
        # reused, and the answers must not move
        for n in (1, 8, 100):
            assert np.array_equal(post(n), first[n]), n
        conn.close()
    finally:
        httpd.shutdown()
        thread.join(timeout=30)
        httpd.server_close()
        server.stop()
    assert not thread.is_alive()
    delta = serve_recompile_count() - recompiles0
    say(f"serve_recompile_count() delta after warm: {delta}")
    assert delta == 0
    leg = close_leg("serve", t0, n_dev, want_mosaic=1)
    assert leg["live_on_devices"] == [jax.devices()[0].id], leg
    return {"recompiles_after_warm": delta}


# ----------------------------------------------------------------- kernels
def phase_kernels(pipe: dict, n_dev: int) -> dict:
    import jax.numpy as jnp

    from shifu_tpu.data.shards import Shards
    from shifu_tpu.models.tree import IndependentTreeModel
    from shifu_tpu.ops import binning, hist_pallas, tree_quant
    from shifu_tpu.ops.tree import _hist_scatter

    t0 = time.perf_counter()
    data = Shards.open(os.path.join(pipe["mdir"], "tmp", "CleanedData")) \
        .load_all()
    bins_h, y, w = data["bins"], data["y"], data["w"]
    model = IndependentTreeModel.load(
        os.path.join(pipe["serve_dir"], "models", "model0.gbt"))
    n, c = bins_h.shape
    n_bins = model.spec.n_bins
    say(f"kernel operands: the pipeline's binned plane {n} x {c} "
        f"{bins_h.dtype}, n_bins={n_bins}; all on device 0")
    rng = np.random.default_rng(0)
    bins = jnp.asarray(bins_h, jnp.int32)
    stats = jnp.asarray(np.stack([w, w * y], axis=1), jnp.float32)
    res = {}

    def close(a, b, what, rtol, atol):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and np.isfinite(a).all(), what
        err = float(np.abs(a - b).max())
        say(f"kernel {what}: max abs err {err:.3e} vs jnp reference")
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                                   err_msg=what)
        res[what] = err

    # tree histogram: one level of the trainer's K (32 = the widest
    # left-child level at MaxDepth 7) at the pipeline's n_bins, plus the
    # paired-lane variant n_bins <= 64 selects.  The kernel accumulates
    # bf16 hi/lo-split products in f32 on the MXU; the scatter reference
    # adds f32 in row order — same tolerance the CPU tests pin, scaled by
    # the cell magnitude (weights up to 2 x ~n/(K*B) rows per cell).
    k = 32
    node = jnp.asarray(rng.integers(-1, k, n), jnp.int32)
    for nb in sorted({n_bins, 64}):
        b = jnp.minimum(bins, nb - 1)
        ref = _hist_scatter(b, node, stats, k, nb)
        got = hist_pallas.build_histograms_pallas(b, node, stats, k, nb)
        close(got, ref, f"build_histograms_pallas[n_bins={nb}]",
              rtol=2e-5, atol=2e-4 * float(np.abs(np.asarray(ref)).max()))
    # integer stats are exact in bf16: counts must match bit for bit
    ones = jnp.ones((n, 2), jnp.float32)
    got = hist_pallas.build_histograms_pallas(bins, node, ones, k, n_bins)
    assert np.array_equal(np.asarray(got),
                          np.asarray(_hist_scatter(bins, node, ones, k,
                                                   n_bins)))
    say("kernel build_histograms_pallas[counts]: bit-identical")

    # multi-tree grid: each tree's slice is BIT-identical to its
    # sequential launch (the batched==sequential contract)
    tb = 8
    node_b = jnp.asarray(rng.integers(-1, k, (tb, n)), jnp.int32)
    stats_b = jnp.asarray(
        rng.uniform(0.5, 2.0, (tb, n, 2)), jnp.float32)
    got_b = np.asarray(hist_pallas.build_histograms_pallas_batch(
        bins, node_b, stats_b, k, n_bins))
    for t in (0, tb - 1):
        seq = np.asarray(hist_pallas.build_histograms_pallas(
            bins, node_b[t], stats_b[t], k, n_bins))
        assert np.array_equal(got_b[t], seq), f"batch tree {t}"
    say(f"kernel build_histograms_pallas_batch[TB={tb}]: bit-identical "
        "to sequential launches")
    ref = _hist_scatter(bins, node_b[0], stats_b[0], k, n_bins)
    close(got_b[0], ref, "build_histograms_pallas_batch[tree 0]",
          rtol=2e-5, atol=2e-4 * float(np.abs(np.asarray(ref)).max()))

    # stats fine histogram through its dispatcher: counts exact, weighted
    # channels within the bf16 hi/lo-split residual (the CPU test's bound)
    x = jnp.asarray(rng.normal(size=(n, NUMERIC)) * 10, jnp.float32)
    valid = jnp.asarray(rng.random((n, NUMERIC)) > 0.05)
    lo = jnp.asarray(np.asarray(x).min(0) - 1e-3)
    hi = jnp.asarray(np.asarray(x).max(0) + 1e-3)
    args = (x, valid, jnp.asarray(y), jnp.asarray(w), lo, hi, 4096)
    ref = np.asarray(binning._histogram_kernel(*args, use_pallas=False))
    got = np.asarray(binning._histogram_kernel(*args, use_pallas=True))
    assert np.array_equal(ref[..., :2], got[..., :2]), "stats counts"
    say("kernel stats_histograms_pallas[counts]: bit-identical")
    close(got, ref, "stats_histograms_pallas[weighted]", rtol=1e-4,
          atol=1e-4)

    # tree scoring: integer routing + exact one-hot selects — bit-identical
    q = tree_quant.stack_forest_quant(model.trees)
    depth = model.trees[0].depth
    b8 = jnp.asarray(bins_h.astype(np.uint8))
    assert tree_quant.quant_lowering(b8, q[0].shape[1]) == "pallas"
    got = np.asarray(tree_quant.predict_forest_quant(*q, b8, depth))
    ref = np.asarray(tree_quant._predict_quant_ref(*q, b8, depth))
    assert got.shape == (len(model.trees), n) and np.isfinite(got).all()
    assert np.array_equal(got, ref), \
        f"tree kernel diverged: {np.abs(got - ref).max()}"
    say(f"kernel tree traversal [T={len(model.trees)} depth={depth}]: "
        "bit-identical to _predict_quant_ref")
    # two tree-histogram variants, the batch grid, the stats kernel (the
    # traversal kernel too where no earlier leg already compiled it)
    close_leg("kernels", t0, n_dev, want_mosaic=4)
    return res


# -------------------------------------------------------------------- main
def main() -> int:
    device = phase_device()
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(IR_DIR)
    jax.config.update("jax_dump_ir_to", IR_DIR)
    # one INFO line per dumped module would bury the run's own output
    logging.getLogger("jax._src.compiler").setLevel(logging.WARNING)
    n_dev = device["count"]

    t0 = time.perf_counter()
    spec = importlib.util.spec_from_file_location(
        "make_fraud_data",
        os.path.join(ROOT, "examples", "make_fraud_data.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    truth = gen.make_wide(os.path.join(WORK, "data"), n=ROWS,
                          n_numeric=NUMERIC, seed=7)
    summary["phases"]["data_s"] = round(time.perf_counter() - t0, 1)

    t0 = time.perf_counter()
    pipe = phase_pipeline(truth, n_dev)
    summary["phases"]["pipeline_s"] = round(time.perf_counter() - t0, 1)
    t0 = time.perf_counter()
    summary["serve"] = phase_serve(pipe, truth, n_dev)
    summary["phases"]["serve_s"] = round(time.perf_counter() - t0, 1)
    t0 = time.perf_counter()
    summary["kernels"] = phase_kernels(pipe, n_dev)
    summary["phases"]["kernels_s"] = round(time.perf_counter() - t0, 1)

    counts = trace_counts(pipe["mdir"])
    # serve + kernels ran after the last step flush: still in the registry
    from shifu_tpu import obs
    live = {m.get("name"): m.get("value") for m in obs.snapshot()}
    counts["compile_count"] += float(live.get("xla.compile_count") or 0)
    counts["compile_time_s"] += float(live.get("xla.compile_time_s") or 0)
    wall = time.perf_counter() - T_START
    summary.update(device=device, auc_gbt=pipe["auc_gbt"],
                   auc_nn=pipe["auc_nn"], bayes_auc=truth["bayes_auc"],
                   wall_s=round(wall, 1),
                   compile_count=int(counts["compile_count"]),
                   compile_time_s=round(counts["compile_time_s"], 1),
                   compile_cache=CACHE_DIR,
                   cache_entries=len(os.listdir(CACHE_DIR))
                   if os.path.isdir(CACHE_DIR) else 0)
    say(f"wall {wall:.1f}s; xla.compile_count={summary['compile_count']} "
        f"xla.compile_time_s={summary['compile_time_s']} (trace + lower + "
        f"backend compile); compile cache {CACHE_DIR} holds "
        f"{summary['cache_entries']} entries")
    os.makedirs(os.path.dirname(SUMMARY), exist_ok=True)
    with open(SUMMARY, "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
