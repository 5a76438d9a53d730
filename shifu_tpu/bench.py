"""Benchmark body: flagship-model training throughput on device.

Baseline (measured — see BASELINE.md "Measured baselines" and
tools/measure_baseline.py): the reference's LOCAL trainer is single-threaded
Encog float64 backprop; the same computation measured on this rig
(float64 NumPy backprop, bench shapes 256->512->256->1, batch 4096) runs at
28,850 rows/s/worker.  The driver-set north star is beating a 100-node YARN
cluster 10×, so the cluster-scale baseline is 100 workers × the measured
per-worker rate = 2.885e6 rows/s.  ``vs_baseline`` = device rows/s over that
measured cluster rate.

Also reports GBT training throughput (resident and streamed modes) as extra
keys — same headline JSON line, richer payload.
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from . import ioutil, obs

# the JSONL/metric schema THIS bench emits its per-plane numbers in.
# Hand-maintained on purpose: if obs/ bumps SCHEMA_VERSION without the
# bench being updated (re-validated against the new field layout),
# run_benchmark refuses to run rather than silently emitting records the
# round's BENCH_r0N.json consumers would mis-join with telemetry traces.
# v2: ingest.* counters (spill cache / H2D stall instrumentation).
# v3: varsel_* extras + varsel.* counters (streamed mask-batched
# sensitivity plane: host_syncs / mask_batches / windows / rows_per_sec).
# v4: disk-tail super-batch round — tail_* extras (disk passes / tail
# sweeps / bytes read PER TREE, dual-schedule c2f vs exact rates, RF
# super-batch width) + train.tail_sweeps / tail_repairs counters.
# v5: observability plane v2 — span/event records carry tid (ingest
# track), drift.* gauges, health heartbeats + OpenMetrics snapshots
# derive from the same registry records; bench gains --compare (the
# BENCH_r0N regression differ, which parses exactly these payloads).
# v6: device cost-attribution plane — "cost" records per named
# executable (obs/costs), xla.recompiles / xla.launches +
# ingest.rows_padded counters; bench emits *_mfu / *_achieved_bw extras
# (XLA cost analysis of the timed executable over the device peak
# table) and --compare TRACKS them; --compare with no arguments diffs
# the two newest BENCH_r*.json in the repo root.
# v7: online serving plane — serve.* counters/gauges (requests, batches,
# rows_padded, flush_full/deadline, swaps, bucket_occupancy,
# batch_latency_ms), serve_* extras (sustained QPS + p50/p99 per offered
# load, padding waste, zero-recompile guard); --compare learns the
# LOWER-is-better metric class (*_p50*/*_p99* latency extras regress
# when new > old / threshold).
# v8: request/SLO observability plane — sampled serve.request /
# serve.batch span records (per-request queue/pad/launch/device
# decomposition), slo.* gauges, histogram p50/p99 sketch quantiles; the
# serve bench runs a 1%-sampled traced pass (serve_traced_qps guarded
# at >= 0.95x the QPS floor) and emits latency-decomposition extras
# (serve_queue_frac / serve_device_frac / serve_pad_frac); --compare
# tracks the queue/pad fracs in the lower-is-better class.
# v9: roofline speed round — serve.bucket_occupancy becomes a histogram
# (p50/p99 in metrics.prom), serve.bucket_rungs_added counter, and the
# bench emits nn_train_mixed_* (bf16-ladder training throughput + MFU,
# tracked beside the f32 rows) and serve_quantized_* (uint8-traversal
# AOT scorer throughput + bit-parity flag) extras; --compare picks the
# new *_mfu / *_per_sec / *_qps names up via the existing classes.
# v10: elastic multi-controller plane — dcn.* instruments + the
# quorum_lost monitor field; the bench gains --plane multihost
# (multihost_{1,2,4}p_rows_per_sec scaling curve, tracked by --compare,
# and multihost_recover_s time-to-recover-after-kill, tracked in the
# lower-is-better class via the new *_recover_s suffix).
# v11: model-quality observability plane — scorelog.* / quality.*
# instruments, crash-safe scorelog segments + delayed-label join +
# posttrain.json / quality.json artifacts, the quality heartbeat extra
# and the refresh controller's "quality" trigger source; the bench
# gains --plane quality (serve_scorelog_qps_frac, the on/off saturation
# ratio guarded >= 0.95 and tracked via the new *_qps_frac throughput
# suffix, plus quality_label_flip_detect_s, tracked LOWER-is-better via
# the new *_detect_s suffix).
#
# v12: raw-record serving + fleet — serve_raw_qps_frac (fused-transform
# saturation vs the pre-binned path on the same warmed bucket, guarded
# >= 0.8), and --plane fleet: subprocess replica fleets behind
# serve.router.ServeRouter (serve_fleet_{1,2,4}r_qps aggregate QPS,
# serve_fleet_scaling_frac tracked via the new *_scaling_frac
# throughput suffix, and the replica-SIGKILL drill whose p99 rides the
# lower-is-better latency class while every accepted request completes
# by requeue).
#
# v13: overload protection — --plane overload drives a bounded-queue,
# deadline-propagating server at 1x/2x/4x of its measured saturation
# with an open-loop shed-tolerant client: serve_overload_goodput (the
# 2x headline, tracked via the new *_goodput throughput suffix and
# guarded >= SHIFU_BENCH_OVERLOAD_FLOOR x saturation QPS),
# serve_overload_shed_frac, and serve_overload_p99_ms of ADMITTED
# requests (lower-is-better latency class) — under overload the right
# p99 is the one clients who got answers saw, sheds are coded
# fast-fails counted separately.
#
# v14: one-parse offline pipeline — rawcache.* counters (hits / misses /
# bytes_written) + the ingest.parse_stall_frac gauge; ingest.disk_passes
# now counts RAW STRING-PLANE traversals (cache-served passes never
# touch the reader, so the counter drops when the raw cache engages);
# the bench gains --plane ingest (stats_throughput / norm_throughput:
# pooled parse + raw cache + direct-to-wire norm vs the serial knobs-off
# path in one run, tracked via the existing "throughput" class) and the
# e2e plane emits pipeline_e2e_wall_s (tracked LOWER-is-better via the
# new *_wall_s suffix) + pipeline_e2e_disk_passes (the telemetry-backed
# raw-plane pass count across the whole scripted pipeline).
# v15: spans also ride the jax.profiler clock; no record this bench
# emits changed
BENCH_TELEMETRY_SCHEMA = 15

# measured on this rig (tools/measure_baseline.py); provenance in
# BASELINE.md — every headline divides by a MEASURED reference-class
# single-worker rate x the north-star cluster size
MEASURED_CPU_ROWS_PER_SEC = 28850.5          # f64 backprop (2026-07-29)
MEASURED_CPU_TREE_ROWS_TREES_PER_SEC = 43068.1   # np.add.at hist GBT (07-30)
MEASURED_CPU_SCORE_ROWS_PER_SEC = 1505.9     # per-row bagged scorer (07-30)
MEASURED_CPU_STATS_ROWS_PER_SEC = 30872.1    # np.add.at stats pass, 256 cols
                                             # x 4096 buckets (07-31)
MEASURED_CPU_VARSEL_ROWS_COLS_PER_SEC = 510610.6  # f64 per-column frozen-
                                             # forward SE loop, 256-col
                                             # plane x 1x16-tanh net (08-04)
BASELINE_CLUSTER_WORKERS = 100          # north-star cluster size (BASELINE.json)
BASELINE_ROWS_PER_SEC = MEASURED_CPU_ROWS_PER_SEC * BASELINE_CLUSTER_WORKERS
BASELINE_TREE_RATE = (MEASURED_CPU_TREE_ROWS_TREES_PER_SEC
                      * BASELINE_CLUSTER_WORKERS)
BASELINE_SCORE_RATE = (MEASURED_CPU_SCORE_ROWS_PER_SEC
                       * BASELINE_CLUSTER_WORKERS)
BASELINE_STATS_RATE = (MEASURED_CPU_STATS_ROWS_PER_SEC
                       * BASELINE_CLUSTER_WORKERS)
BASELINE_VARSEL_RATE = (MEASURED_CPU_VARSEL_ROWS_COLS_PER_SEC
                        * BASELINE_CLUSTER_WORKERS)


def bench_nn(n_rows: int = 1 << 17, n_features: int = 256,
             hidden: tuple = (512, 256), batch: int = 1 << 12,
             steps: int = 8000, collect: Dict[str, Any] = None) -> float:
    import jax
    import jax.numpy as jnp

    from shifu_tpu.models.nn import NNModelSpec, init_params, make_train_step

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n_rows, n_features)), dtype=jnp.float32)
    w = jnp.asarray((rng.normal(size=(n_features,)) / np.sqrt(n_features)), jnp.float32)
    logits = x @ w
    y = jnp.asarray(rng.random(n_rows) < jax.nn.sigmoid(logits), jnp.float32)[:, None]
    wgt = jnp.ones((n_rows, 1), jnp.float32)

    from functools import partial

    spec = NNModelSpec(input_dim=n_features, hidden_nodes=list(hidden),
                       activations=["relu"] * len(hidden), output_dim=1)
    params = init_params(jax.random.PRNGKey(0), spec)
    # bfloat16 matmul inputs with f32 accumulation — the MXU's native rate
    # (the framework's Precision="bfloat16" train param; ~+10% measured on
    # this chip over the backend default)
    with jax.default_matmul_precision("bfloat16"):
        step_fn, opt_state = make_train_step(spec, params, optimizer="adam",
                                             learning_rate=1e-3)
        n_batches = n_rows // batch

        # the whole timing window is ONE executable (lax.scan over steps):
        # per-step dispatch latency over the device link would otherwise
        # dominate the sub-ms step compute
        @partial(jax.jit, static_argnames=("n_steps",), donate_argnums=(0, 1))
        def run_steps(params, opt_state, n_steps: int):
            def body(carry, i):
                p, o = carry
                b = (i % n_batches) * batch
                p, o, loss = step_fn(
                    p, o, jax.lax.dynamic_slice_in_dim(x, b, batch),
                    jax.lax.dynamic_slice_in_dim(y, b, batch),
                    jax.lax.dynamic_slice_in_dim(wgt, b, batch))
                return (p, o), loss
            (p, o), losses = jax.lax.scan(
                body, (params, opt_state),
                jnp.arange(n_steps, dtype=jnp.int32))
            return p, o, losses[-1]

        params, opt_state, loss = run_steps(params, opt_state, steps)
        float(loss)                                  # full warmup sync
        _collect_window_cost(collect, run_steps, (params, opt_state),
                             {"n_steps": steps}, steps * batch)
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            params, opt_state, loss = run_steps(params, opt_state, steps)
            float(loss)                              # value-forcing sync
            best = max(best, steps * batch / (time.perf_counter() - t0))
        return best


def bench_nn_mixed(n_rows: int = 1 << 17, n_features: int = 256,
                   hidden: tuple = (512, 256), batch: int = 1 << 12,
                   steps: int = 4000,
                   collect: Dict[str, Any] = None) -> float:
    """NN training throughput under the MIXED-precision ladder
    (``shifu.train.precision=mixed``): bf16 params/activations through
    forward/backward, f32 master copy stepped by the optimizer — the
    bench twin of the trainer path, same scanned-window harness as
    :func:`bench_nn` so ``nn_train_mixed_*`` rows compare directly
    against the f32 ``nn_train_*`` rows."""
    import jax
    import jax.numpy as jnp

    from shifu_tpu.models.nn import NNModelSpec, init_params, weighted_loss
    from shifu_tpu.train.optimizers import (cast_tree, make_optimizer,
                                            mixed_apply, mixed_init)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n_rows, n_features)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n_features,)) / np.sqrt(n_features),
                    jnp.float32)
    y = jnp.asarray(rng.random(n_rows)
                    < jax.nn.sigmoid(x @ w), jnp.float32)[:, None]
    wgt = jnp.ones((n_rows, 1), jnp.float32)
    spec = NNModelSpec(input_dim=n_features, hidden_nodes=list(hidden),
                       activations=["relu"] * len(hidden), output_dim=1)
    params = cast_tree(init_params(jax.random.PRNGKey(0), spec),
                       jnp.bfloat16)
    opt = make_optimizer("ADAM", 1e-3)
    state = mixed_init(opt, params)
    n_batches = n_rows // batch

    from functools import partial

    @partial(jax.jit, static_argnames=("n_steps",), donate_argnums=(0, 1))
    def run_steps(params, state, n_steps: int):
        def body(carry, i):
            p, st = carry
            b = (i % n_batches) * batch
            loss, grads = jax.value_and_grad(weighted_loss)(
                p, spec, jax.lax.dynamic_slice_in_dim(x, b, batch),
                jax.lax.dynamic_slice_in_dim(y, b, batch),
                jax.lax.dynamic_slice_in_dim(wgt, b, batch))
            p, st = mixed_apply(opt, grads, st)
            return (p, st), loss
        (p, st), losses = jax.lax.scan(
            body, (params, state), jnp.arange(n_steps, dtype=jnp.int32))
        return p, st, losses[-1]

    params, state, loss = run_steps(params, state, steps)
    float(loss)                                      # full warmup sync
    _collect_window_cost(collect, run_steps, (params, state),
                         {"n_steps": steps}, steps * batch)
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        params, state, loss = run_steps(params, state, steps)
        float(loss)                                  # value-forcing sync
        best = max(best, steps * batch / (time.perf_counter() - t0))
    return best


def _collect_window_cost(collect, jitted, args, kwargs, rows: int) -> None:
    """XLA cost analysis of the timed executable (one lowering, no
    second compile): flops / bytes per timing window, for the *_mfu /
    *_achieved_bw extras.  Lowering reads only avals, so donated (dead)
    buffers from the warmup call are fine."""
    if collect is None:
        return
    try:
        ca = jitted.lower(*args, **kwargs).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            collect["flops_per_window"] = float(ca.get("flops") or 0.0)
            collect["bytes_per_window"] = float(
                ca.get("bytes accessed") or 0.0)
            collect["rows_per_window"] = rows
    except Exception as e:                          # pragma: no cover
        collect["cost_error"] = str(e)[:120]


def _mfu_extras(prefix: str, rows_per_sec: float, col: Dict[str, Any],
                extras: Dict[str, Any]) -> None:
    """Fold a collected window cost into *_mfu / *_achieved_bw extras:
    achieved = window cost / (window rows / best rows-per-sec); MFU =
    achieved FLOP/s over the device peak (obs.costs table,
    SHIFU_TPU_PEAK_FLOPS / SHIFU_TPU_PEAK_BW override).  A device the
    table does not know has no peak: achieved rates only, no MFU."""
    rows = col.get("rows_per_window")
    if not rows or not rows_per_sec:
        return
    from .obs.costs import resolve_peaks
    peak_f, peak_b, label = resolve_peaks()
    wall = rows / rows_per_sec
    fl, by = col.get("flops_per_window"), col.get("bytes_per_window")
    if fl:
        achieved = fl / wall
        extras[f"{prefix}_achieved_flops"] = round(achieved, 1)
        if peak_f:
            extras[f"{prefix}_mfu"] = round(achieved / peak_f, 6)
    if by:
        bw = by / wall
        extras[f"{prefix}_achieved_bw"] = round(bw, 1)
        if peak_b:
            extras[f"{prefix}_bw_frac_of_peak"] = round(bw / peak_b, 6)
    extras.setdefault(
        "peaks_provenance", label if not (peak_f and peak_b)
        else f"{label}: {peak_f:.3e} FLOP/s, {peak_b:.3e} B/s")


def _bench_forest(train_fn, settings, n_rows: int, n_features: int,
                  n_bins: int) -> float:
    """Shared forest-trainer harness: synthetic rows, compile warmup with
    identical settings, best-of-5 value-synced windows (train_* fetches
    packed trees to host internally, so the window measures real work)."""
    rng = np.random.default_rng(0)
    bins = rng.integers(0, n_bins, size=(n_rows, n_features)).astype(np.int32)
    y = (rng.random(n_rows) < 0.3).astype(np.float32)
    w = np.ones(n_rows, np.float32)
    cat = np.zeros(n_features, bool)
    train_fn(bins, y, w, n_bins, cat, settings)         # compile warmup
    best = 0.0
    for _ in range(5):       # best-of-5 over sub-second windows
        t0 = time.perf_counter()
        res = train_fn(bins, y, w, n_bins, cat, settings)
        dt = time.perf_counter() - t0
        assert res.trees_built == settings.n_trees
        best = max(best, n_rows * settings.n_trees / dt)
    return best


def bench_gbt(n_rows: int = 1 << 17, n_features: int = 64, n_bins: int = 64,
              n_trees: int = 100, depth: int = 6) -> float:
    """GBT training throughput, device-resident rows: rows*trees processed
    per wall-clock second (each tree is a full pass over the rows).
    ``n_trees=100`` = the default model size (``init -model`` GBT TreeNum,
    same as the reference's default) — since r5; was 32, which
    under-amortized the one-time ingest against the per-tree work."""
    from shifu_tpu.train.dt_trainer import DTSettings, train_gbt
    return _bench_forest(
        train_gbt,
        DTSettings(n_trees=n_trees, depth=depth, loss="log",
                   learning_rate=0.1),
        n_rows, n_features, n_bins)


def _bench_tree_rows(rng, n_rows: int, n_features: int, n_bins: int,
                     learnable: bool):
    """Synthetic binned rows.  ``learnable=True`` derives y from a sparse
    logit over a few binned columns (fraud-style signal, like the e2e
    plane) instead of pure label noise — the regime real training runs
    in, and the design point of the coarse-to-fine tail: under pure
    noise every split is a coin toss on f32 summation order, so
    resident-prefix speculation diverges adversarially often."""
    bins = rng.integers(0, n_bins, size=(n_rows, n_features)) \
        .astype(np.int16)
    if learnable:
        logit = (0.12 * bins[:, 0] + 0.08 * bins[:, 3]
                 - 0.10 * bins[:, 7] + 0.05 * bins[:, 11]) / n_bins * 8 - 2
        y = (rng.random(n_rows) < 1 / (1 + np.exp(-logit))) \
            .astype(np.float32)
    else:
        y = (rng.random(n_rows) < 0.3).astype(np.float32)
    return bins, y


def bench_gbt_streamed(n_rows: int = 1 << 18, n_features: int = 64,
                       n_bins: int = 64, n_trees: int = 100,
                       depth: int = 5,
                       cache_budget: int = None,
                       learnable: bool = False,
                       reps: int = 5,
                       collect: Dict[str, Any] = None) -> float:
    """GBT throughput in out-of-core streamed mode (windows re-read from the
    stream; measures the full IO+compute path).  ``cache_budget`` caps the
    HBM-resident window cache — pass a budget smaller than the dataset to
    force the disk-tail path (windows past the budget re-stream per level),
    the configuration the 1TB-dataset scenario actually runs.  ``collect``
    (optional dict) receives the ingest accounting of the last timed run:
    disk_passes / tail_sweeps / bytes_read / trees."""
    import json
    import os
    import tempfile

    from shifu_tpu.data.shards import Shards
    from shifu_tpu.data.streaming import ShardStream
    from shifu_tpu.train.dt_trainer import DTSettings, train_gbt_streamed

    rng = np.random.default_rng(0)
    bins, y = _bench_tree_rows(rng, n_rows, n_features, n_bins, learnable)
    w = np.ones(n_rows, np.float32)
    cat = np.zeros(n_features, bool)
    with tempfile.TemporaryDirectory() as td:
        shard_rows = 8192
        n_shards = 0
        for s in range(0, n_rows, shard_rows):
            e = min(s + shard_rows, n_rows)
            ioutil.atomic_savez(
                os.path.join(td, f"part-{n_shards:05d}.npz"),
                bins=bins[s:e], y=y[s:e], w=w[s:e])
            n_shards += 1
        ioutil.atomic_write_json(
            os.path.join(td, "schema.json"),
            {"columnNums": list(range(n_features)),
             "numShards": n_shards, "numRows": n_rows})
        stream = ShardStream(Shards.open(td), ("bins", "y", "w"),
                             window_rows=16384)
        settings = DTSettings(n_trees=n_trees, depth=depth, loss="log",
                              learning_rate=0.1)
        # compile warmup: identical settings so every executable (fused
        # tree, batched drain) is cached before timing
        train_gbt_streamed(stream, n_bins, cat, settings,
                           cache_budget=cache_budget)
        best = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            res = train_gbt_streamed(stream, n_bins, cat, settings,
                                     cache_budget=cache_budget)
            dt = time.perf_counter() - t0
            assert res.trees_built == n_trees
            if cache_budget is not None:
                assert res.disk_passes > 1   # the tail really re-streamed
            best = max(best, n_rows * n_trees / dt)
        if collect is not None:
            collect.update(disk_passes=res.disk_passes,
                           tail_sweeps=res.tail_sweeps,
                           bytes_read=res.bytes_read,
                           trees=res.trees_built)
    return best


def bench_rf(n_rows: int = 1 << 17, n_features: int = 64, n_bins: int = 64,
             n_trees: int = 32, depth: int = 6) -> float:
    """RF training throughput (Poisson bagging + oob validation),
    rows*trees per second — same harness as bench_gbt."""
    from shifu_tpu.train.dt_trainer import DTSettings, train_rf
    return _bench_forest(
        train_rf,
        DTSettings(n_trees=n_trees, depth=depth, impurity="entropy",
                   loss="log", feature_subset="SQRT"),
        n_rows, n_features, n_bins)


def bench_wdl(n_rows: int = 1 << 17, n_num: int = 64, n_cat: int = 32,
              card: int = 64, batch: int = 1 << 12,
              steps: int = 2000, collect: Dict[str, Any] = None) -> float:
    """Wide&deep training-step throughput, same harness shape as
    :func:`bench_nn`: the timing window is ONE scanned executable of
    dual-plane minibatch updates (embedding gathers + wide sparse path +
    deep MLP backprop), value-force synced.  (Reference
    ``core/dtrain/wdl/`` worker backprop; the measured NN-backprop
    baseline is the same reference-class computation and serves as the
    denominator.)"""
    import jax
    import jax.numpy as jnp

    from shifu_tpu.models.wdl import WDLModelSpec, init_params, weighted_loss
    from shifu_tpu.train.optimizers import make_optimizer

    rng = np.random.default_rng(0)
    x_num = jnp.asarray(rng.normal(size=(n_rows, n_num)), jnp.float32)
    x_cat = jnp.asarray(rng.integers(0, card, (n_rows, n_cat)), jnp.int32)
    logit = np.asarray(x_num)[:, 0] * 0.8 \
        + (np.asarray(x_cat)[:, 0] < card // 2) * 0.7 - 0.3
    y = jnp.asarray(rng.random(n_rows) < 1 / (1 + np.exp(-logit)),
                    jnp.float32)
    w = jnp.ones(n_rows, jnp.float32)
    spec = WDLModelSpec(numeric_dim=n_num,
                        cat_cardinalities=[card] * n_cat, embed_dim=16,
                        hidden_nodes=[128, 64],
                        activations=["relu", "relu"])
    params = init_params(jax.random.PRNGKey(0), spec)
    opt = make_optimizer("ADAM", 1e-3)
    opt_state = opt.init(params)
    n_batches = n_rows // batch

    from functools import partial

    with jax.default_matmul_precision("bfloat16"):
        @partial(jax.jit, static_argnames=("n_steps",),
                 donate_argnums=(0, 1))
        def run_steps(params, opt_state, n_steps: int):
            def body(carry, i):
                p, o = carry
                b = (i % n_batches) * batch
                xnb = jax.lax.dynamic_slice_in_dim(x_num, b, batch)
                xcb = jax.lax.dynamic_slice_in_dim(x_cat, b, batch)
                yb = jax.lax.dynamic_slice_in_dim(y, b, batch)
                wb = jax.lax.dynamic_slice_in_dim(w, b, batch)
                loss, grads = jax.value_and_grad(weighted_loss)(
                    p, spec, xnb, xcb, yb[:, None], wb, 0.0)
                delta, o = opt.update(grads, o, p)
                p = jax.tree_util.tree_map(lambda a, d: a + d, p, delta)
                return (p, o), loss
            (p, o), losses = jax.lax.scan(
                body, (params, opt_state),
                jnp.arange(n_steps, dtype=jnp.int32))
            return p, o, losses[-1]

        params, opt_state, loss = run_steps(params, opt_state, steps)
        float(loss)                                  # full warmup sync
        _collect_window_cost(collect, run_steps, (params, opt_state),
                             {"n_steps": steps}, steps * batch)
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            params, opt_state, loss = run_steps(params, opt_state, steps)
            float(loss)                              # value-forcing sync
            best = max(best, steps * batch / (time.perf_counter() - t0))
        return best


def bench_wdl_sharded(n_rows: int = 1 << 17, n_num: int = 64,
                      n_cat: int = 32, card: int = 0, batch: int = 1 << 12,
                      steps: int = 2000,
                      collect: Dict[str, Any] = None) -> float:
    """Sharded-table WDL training-step throughput: the same dual-plane
    minibatch updates as :func:`bench_wdl`, but with every embed/wide
    table (and its Adam moments) row-sharded over the data axis and the
    lookups running the sparse per-minibatch gather
    (``train/wdl_shard``).  The timing window is ONE scanned epoch
    executable over pre-batched blocks.

    ``card`` (or ``SHIFU_BENCH_WDL_TABLE_ROWS``) sets the per-table
    cardinality — raise it past single-device HBM to exercise the
    oversized-table scenario sharding exists for; the default matches
    :func:`bench_wdl` so the rows compare the mechanism alone."""
    import jax
    import jax.numpy as jnp
    import os

    from jax.sharding import NamedSharding, PartitionSpec as P

    from shifu_tpu.models.wdl import WDLModelSpec, init_params
    from shifu_tpu.parallel import mesh as meshlib
    from shifu_tpu.train import wdl_shard
    from shifu_tpu.train.optimizers import make_optimizer

    card = card or int(os.environ.get("SHIFU_BENCH_WDL_TABLE_ROWS",
                                      0) or 0) or 64
    if jax.default_backend() == "cpu":
        # host shard_map collectives run ~1000x slower than ICI; a full
        # accelerator-sized window would take tens of minutes on the CI
        # rig for the same steady-state number
        steps = min(steps, 100)
        n_rows = min(n_rows, 1 << 14)
    mesh = meshlib.device_mesh(n_ensemble=1)
    d = mesh.shape["data"]
    batch = max(batch - batch % d, d)
    n_rows = max((n_rows // batch) * batch, batch)
    nb = n_rows // batch

    rng = np.random.default_rng(0)
    x_num = rng.normal(size=(n_rows, n_num)).astype(np.float32)
    x_cat = rng.integers(0, card, (n_rows, n_cat)).astype(np.int32)
    logit = x_num[:, 0] * 0.8 + (x_cat[:, 0] < card // 2) * 0.7 - 0.3
    y = (rng.random(n_rows) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    spec = WDLModelSpec(numeric_dim=n_num,
                        cat_cardinalities=[card] * n_cat, embed_dim=16,
                        hidden_nodes=[128, 64],
                        activations=["relu", "relu"])
    plane = wdl_shard.WDLShardPlane(mesh, spec, 1)
    member = plane.pad_params(init_params(jax.random.PRNGKey(0), spec))
    opt = make_optimizer("ADAM", 1e-3)
    stacked = jax.tree_util.tree_map(lambda a: a[None], member)
    opt_state = jax.tree_util.tree_map(lambda a: a[None], opt.init(member))
    stacked, opt_state = plane.put(stacked, opt_state)
    fns = wdl_shard.build_inram_fns(plane, stacked, opt_state, opt,
                                    "f32", 0.0)

    sh = lambda s: NamedSharding(mesh, s)          # noqa: E731
    xn3 = jax.device_put(x_num.reshape(nb, batch, n_num),
                         sh(P(None, "data", None)))
    xc3 = jax.device_put(x_cat.reshape(nb, batch, n_cat),
                         sh(P(None, "data", None)))
    y3 = jax.device_put(y.reshape(nb, batch), sh(P(None, "data")))
    tw3 = jax.device_put(np.ones((1, nb, batch), np.float32),
                         sh(P("ensemble", None, "data")))
    border = jnp.asarray(np.arange(steps, dtype=np.int32) % nb)

    with jax.default_matmul_precision("bfloat16"):
        epoch = fns["epoch_steps"]
        stacked, opt_state = epoch(stacked, opt_state, xn3, xc3, y3, tw3,
                                   border)
        jax.block_until_ready(stacked)               # full warmup sync
        _collect_window_cost(collect, epoch,
                             (stacked, opt_state, xn3, xc3, y3, tw3,
                              border), {}, steps * batch)
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            stacked, opt_state = epoch(stacked, opt_state, xn3, xc3, y3,
                                       tw3, border)
            jax.block_until_ready(stacked)           # value-forcing sync
            best = max(best, steps * batch / (time.perf_counter() - t0))
        return best


def bench_eval(n_rows: int = 1 << 20, n_features: int = 256,
               n_models: int = 5) -> float:
    """Eval-stack throughput: a bagged NN scored + confusion-swept (the
    ``EvalScoreUDF`` → ``ConfusionMatrix`` path), rows/sec.

    Device-plane end to end (round 4): the eval matrix is generated in
    HBM (an eval set ingests once; timing the one-time ingest would
    measure the host link), scoring stays in HBM
    (``Scorer.score_device``), and the confusion sweep runs on device
    (``evaluate_scores_device``) — the only transfer per window is the
    packed [5*1024+7]-float curve.  The round-3 harness fetched every
    score and argsorted on host, which capped eval ~2 orders below the
    train plane."""
    import jax
    import jax.numpy as jnp

    from shifu_tpu.eval.metrics import evaluate_scores_device
    from shifu_tpu.eval.scorer import Scorer
    from shifu_tpu.models.nn import (IndependentNNModel, NNModelSpec,
                                     init_params)

    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    xd = jax.random.normal(kx, (n_rows, n_features), jnp.float32)
    y = (jax.random.uniform(ky, (n_rows,)) < 0.3).astype(jnp.float32)
    wgt = jnp.ones(n_rows, jnp.float32)
    spec = NNModelSpec(input_dim=n_features, hidden_nodes=[512, 256],
                       activations=["relu", "relu"], output_dim=1)
    models = [IndependentNNModel(spec, init_params(jax.random.PRNGKey(i),
                                                   spec))
              for i in range(n_models)]
    scorer = Scorer(models)
    _, mean_d = scorer.score_device(xd)          # compile warmup
    evaluate_scores_device(mean_d, y, wgt)
    best = 0.0
    for _ in range(5):
        t0 = time.perf_counter()
        _, mean_d = scorer.score_device(xd)
        _, result = evaluate_scores_device(mean_d, y, wgt)
        assert np.isfinite(result.areaUnderRoc)  # packed fetch = the sync
        best = max(best, n_rows / (time.perf_counter() - t0))
    return best


def bench_stats(chunk_rows: int = 1 << 18, n_cols: int = 256,
                n_chunks: int = 16, num_buckets: int = 4096) -> float:
    """Stats/ETL-plane throughput: the two-pass per-column sweep (moments +
    fine histogram + missing aggregation with pos/neg channels — the
    ``StatsSpdtI.pig`` + ``UpdateBinningInfo`` MR pair) in rows/sec at 256
    columns, run through the REAL streaming accumulator
    (``ops.binning.NumericAccumulator``): per-chunk kernel outputs
    accumulate on device and drain to host float64 in one packed fetch
    per pass — the round-3 harness fetched per chunk, which billed a full
    ~100 ms link round trip to every 262k rows.  Chunk data is generated
    in HBM (a stats job ingests once; the host link is not the subject);
    the histogram runs the two-level one-hot MXU kernel with packed
    bf16-exact count channels (``ops/hist_pallas``)."""
    import jax
    import jax.numpy as jnp

    from shifu_tpu.ops.binning import NumericAccumulator

    kx, kv, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (chunk_rows, n_cols), jnp.float32)
    valid = jax.random.uniform(kv, (chunk_rows, n_cols)) > 0.05
    t = (jax.random.uniform(kt, (chunk_rows,)) < 0.3).astype(jnp.float32)
    w = jnp.ones(chunk_rows, jnp.float32)
    n_rows = chunk_rows * n_chunks

    def sweep() -> None:
        from shifu_tpu.config.model_config import BinningMethod
        acc = NumericAccumulator(n_cols=n_cols, num_buckets=num_buckets,
                                 unit_weight=True)
        for _ in range(n_chunks):                # pass 1, device-pending
            acc.update_moments(x, valid)
        acc.finalize_range()                     # one packed moments drain
        for _ in range(n_chunks):                # pass 2, device-pending
            acc.update_histogram(x, valid, t, w)
        # device-side finalize: boundaries/bin-stats/percentiles in one
        # [C, max_bins]-sized fetch — the fine histogram stays in HBM
        bnds, aggs, _, _ = acc.finalize_sketch(BinningMethod.EqualTotal, 20)
        assert len(bnds) == n_cols and acc.total_rows == n_rows

    sweep()                                      # compile warmup
    best = 0.0
    for _ in range(5):
        t0 = time.perf_counter()
        sweep()                                  # drains force all values
        best = max(best, n_rows / (time.perf_counter() - t0))
    return best


# disk-tail forced: the budget fits ~half the 16384-row windows, the rest
# re-streams per level — the real out-of-core configuration.  Per-window
# accounting since r6: bins ride the compact uint8 wire INTO HBM (1 B/cell
# instead of the old int32's 4), so a prepared GBT window is
# W*(C*1 + 4*4) bytes (bins + y/tw/vw/f f32).
TAIL_BENCH_BUDGET = 2 * 16384 * (64 * 1 + 4 * 4)

# quick-mode throughput floor (rows*trees/s, SHIFU_BENCH_TAIL_FLOOR to
# override): deliberately far below any functioning rig's rate — it
# exists to catch a catastrophic schedule regression (e.g. silent
# fallback to per-(depth x tree) re-streams), not to benchmark the rig
TAIL_BENCH_FLOOR = 5000.0


def bench_gbt_streamed_tail(n_rows: int = 1 << 16, n_trees: int = 4,
                            depth: int = 5) -> Dict[str, Any]:
    """The disk-tail quick mode (`bench.py --plane tail`): small forest,
    budget forces half the windows past the resident cache — the
    out-of-core configuration the super-batched tail schedule exists
    for.  Reports BOTH GBT schedules (coarse-to-fine default vs exact
    per-level sweeps) plus the RF super-batch probe, with per-tree disk
    passes / tail sweeps / bytes read, and enforces the schedule guards:
    c2f tail sweeps per tree bounded (~1 + repairs, >> cheaper than the
    old depth+2), RF passes per tree <= ceil(depth/SB)+1, and a
    conservative throughput floor (SHIFU_BENCH_TAIL_FLOOR)."""
    import os

    from shifu_tpu.train.dt_trainer import _tail_coarse_to_fine

    # both schedules, knob pinned per run, on a learnable fraud-style
    # target — see _bench_tree_rows on why label noise is adversarial
    # for speculation and unrepresentative of training.  The headline is
    # whichever schedule the rig's DEFAULT resolves to (c2f on
    # accelerator backends, exact on CPU — see _tail_coarse_to_fine).
    default_c2f = _tail_coarse_to_fine()
    rates: Dict[str, float] = {}
    stats: Dict[str, Dict[str, Any]] = {}
    prev = os.environ.get("SHIFU_TREE_TAIL_C2F")
    try:
        for tag, knob in (("c2f", "1"), ("exact", "0")):
            os.environ["SHIFU_TREE_TAIL_C2F"] = knob
            col: Dict[str, Any] = {}
            rates[tag] = bench_gbt_streamed(
                n_rows=n_rows, n_trees=n_trees, depth=depth,
                cache_budget=TAIL_BENCH_BUDGET, learnable=True,
                reps=5 if (knob == "1") == default_c2f else 3,
                collect=col)
            stats[tag] = col
    finally:
        if prev is None:
            del os.environ["SHIFU_TREE_TAIL_C2F"]
        else:
            os.environ["SHIFU_TREE_TAIL_C2F"] = prev
    rf = bench_rf_streamed_tail(n_rows=n_rows, depth=depth)

    head = "c2f" if default_c2f else "exact"
    v = rates[head]
    rep = {
        "tail_rows_trees_per_sec": round(v, 1),
        "tail_default_schedule": head,
        "tail_disk_passes_per_tree": round(
            stats[head]["disk_passes"] / stats[head]["trees"], 3),
        "tail_bytes_read_per_tree": int(
            stats[head]["bytes_read"] // stats[head]["trees"]),
        "tail_c2f_rows_trees_per_sec": round(rates["c2f"], 1),
        "tail_c2f_sweeps_per_tree": round(
            stats["c2f"]["tail_sweeps"] / stats["c2f"]["trees"], 3),
        "tail_c2f_bytes_read_per_tree": int(
            stats["c2f"]["bytes_read"] // stats["c2f"]["trees"]),
        "tail_exact_rows_trees_per_sec": round(rates["exact"], 1),
        "tail_exact_sweeps_per_tree": round(
            stats["exact"]["tail_sweeps"] / stats["exact"]["trees"], 3),
        "tail_exact_bytes_read_per_tree": int(
            stats["exact"]["bytes_read"] // stats["exact"]["trees"]),
        "tail_shape": f"{n_rows} rows x {n_trees} trees depth {depth}, "
                      "budget fits ~half the windows (uint8 wire), "
                      "learnable logit target since r9",
    }
    rep.update(rf)
    # schedule guards — the quick mode's job is to fail loudly if the
    # super-batch schedule silently degrades to per-(depth x tree)
    # re-streams (e.g. a knob regression or an always-on repair path)
    floor = float(os.environ.get("SHIFU_BENCH_TAIL_FLOOR",
                                 TAIL_BENCH_FLOOR))
    spt = rep["tail_c2f_sweeps_per_tree"]
    if spt > depth:
        raise AssertionError(
            f"GBT coarse-to-fine tail swept {spt:.2f}x per tree "
            f"(> depth {depth}) — speculation is repairing at the root "
            "near-always; on learnable data the stale-evidence gate "
            "should confirm the upper levels")
    if rep["tail_exact_sweeps_per_tree"] > depth + 2:
        raise AssertionError(
            f"GBT exact tail swept "
            f"{rep['tail_exact_sweeps_per_tree']:.2f}x per tree (> "
            f"depth+2 = {depth + 2}) — the subtraction/leaf-sum "
            "schedule regressed toward per-(depth x tree) re-streams")
    if rep["tail_rf_sweeps_per_tree"] > rep["tail_rf_sweeps_bound"]:
        raise AssertionError(
            f"RF tail swept {rep['tail_rf_sweeps_per_tree']:.2f}x per "
            f"tree > ceil(depth/SB)+1 = {rep['tail_rf_sweeps_bound']} — "
            "the super-batch schedule regressed toward per-tree sweeps")
    if v < floor:
        raise AssertionError(
            f"disk-tail throughput {v:.0f} rows*trees/s below the "
            f"floor {floor:.0f} (SHIFU_BENCH_TAIL_FLOOR)")
    return rep


def bench_rf_streamed_tail(n_rows: int = 1 << 16, n_features: int = 64,
                           n_bins: int = 64, n_trees: int = 16,
                           depth: int = 5) -> Dict[str, Any]:
    """RF disk-tail probe: one super-batch of trees per (depth+2) tail
    sweeps — the acceptance-criterion measurement (passes per tree <=
    ceil(depth/SB)+1) plus throughput."""
    import json
    import math
    import os
    import tempfile

    from shifu_tpu.data.shards import Shards
    from shifu_tpu.data.streaming import ShardStream
    from shifu_tpu.train.dt_trainer import (DTSettings, _tail_super_batch,
                                            train_rf_streamed)

    rng = np.random.default_rng(1)
    bins, y = _bench_tree_rows(rng, n_rows, n_features, n_bins,
                               learnable=True)
    w = np.ones(n_rows, np.float32)
    cat = np.zeros(n_features, bool)
    settings = DTSettings(n_trees=n_trees, depth=depth,
                          impurity="entropy", loss="log",
                          feature_subset="SQRT")
    with tempfile.TemporaryDirectory() as td:
        shard_rows = 8192
        n_shards = 0
        for s in range(0, n_rows, shard_rows):
            e = min(s + shard_rows, n_rows)
            ioutil.atomic_savez(
                os.path.join(td, f"part-{n_shards:05d}.npz"),
                bins=bins[s:e], y=y[s:e], w=w[s:e])
            n_shards += 1
        ioutil.atomic_write_json(
            os.path.join(td, "schema.json"),
            {"columnNums": list(range(n_features)),
             "numShards": n_shards, "numRows": n_rows})
        stream = ShardStream(Shards.open(td), ("bins", "y", "w"),
                             window_rows=16384)
        train_rf_streamed(stream, n_bins, cat, settings,
                          cache_budget=TAIL_BENCH_BUDGET)  # warmup
        best, res = 0.0, None
        for _ in range(3):
            t0 = time.perf_counter()
            res = train_rf_streamed(stream, n_bins, cat, settings,
                                    cache_budget=TAIL_BENCH_BUDGET)
            dt = time.perf_counter() - t0
            assert res.trees_built == n_trees
            assert res.disk_passes > 1
            best = max(best, n_rows * n_trees / dt)
    sb = min(n_trees, _tail_super_batch(settings, n_features, n_bins, 2))
    return {
        "tail_rf_rows_trees_per_sec": round(best, 1),
        "tail_rf_super_batch": sb,
        "tail_rf_sweeps_per_tree": round(res.tail_sweeps / n_trees, 3),
        "tail_rf_sweeps_bound": math.ceil(depth / sb) + 1,
        "tail_rf_bytes_read_per_tree": int(res.bytes_read // n_trees),
        "tail_rf_shape": f"{n_rows} rows x {n_trees} trees depth {depth}",
    }


def bench_rf_repeat(n_rows: int = 1 << 17, n_features: int = 64,
                    n_bins: int = 64, n_trees: int = 32, depth: int = 6,
                    repeats: int = 7) -> Dict[str, Any]:
    """RF variance triage (`bench.py --plane rf-repeat`): decompose the
    RF band's run-to-run spread (1.1–2.3x observed across rounds) into

    - COMPILE/CACHE effects: the cold window timed right after
      ``jax.clear_caches()`` (a fresh process's recompile cost — the
      headline harness warms up first, but cross-round drift in compile
      count lands here), vs
    - RUNTIME noise: min/median/max + CV over ``repeats`` warm
      windows of the identical executable.

    The headline ``bench_rf`` keeps best-of-5; this mode is the
    methodology probe behind the README band (BASELINE.md records the
    decomposition)."""
    import jax

    from shifu_tpu.train.dt_trainer import DTSettings, train_rf

    rng = np.random.default_rng(0)
    bins = rng.integers(0, n_bins, size=(n_rows, n_features)) \
        .astype(np.int32)
    y = (rng.random(n_rows) < 0.3).astype(np.float32)
    w = np.ones(n_rows, np.float32)
    cat = np.zeros(n_features, bool)
    settings = DTSettings(n_trees=n_trees, depth=depth, impurity="entropy",
                          loss="log", feature_subset="SQRT")

    def window() -> float:
        t0 = time.perf_counter()
        res = train_rf(bins, y, w, n_bins, cat, settings)
        assert res.trees_built == n_trees
        return time.perf_counter() - t0

    jax.clear_caches()
    cold_s = window()                      # includes trace + compile
    warm = [window() for _ in range(repeats)]
    rates = sorted(n_rows * n_trees / d for d in warm)
    med_s = sorted(warm)[len(warm) // 2]
    mean_r = float(np.mean(rates))
    return {
        "rf_repeat_shape": f"{n_rows} rows x {n_trees} trees, "
                           f"{repeats} warm windows",
        "rf_repeat_cold_s": round(cold_s, 3),
        "rf_repeat_warm_median_s": round(med_s, 3),
        "rf_repeat_compile_overhead_s": round(cold_s - med_s, 3),
        "rf_repeat_warm_min": round(rates[0], 1),
        "rf_repeat_warm_median": round(rates[len(rates) // 2], 1),
        "rf_repeat_warm_max": round(rates[-1], 1),
        "rf_repeat_warm_cv": round(float(np.std(rates)) / mean_r, 4),
        "rf_repeat_warm_median_vs_baseline": round(
            rates[len(rates) // 2] / BASELINE_TREE_RATE, 3),
        "rf_repeat_warm_band_vs_baseline": [
            round(rates[0] / BASELINE_TREE_RATE, 3),
            round(rates[-1] / BASELINE_TREE_RATE, 3)],
    }


def bench_pipeline_e2e(n_rows: int = None,
                       nn_epochs: int = 10) -> Dict[str, Any]:
    """End-to-end pipeline rehearsal (`bench.py --plane e2e`): scripted
    ``init → stats → norm → train (GBT, TreeNum=100) → train (NN) →
    eval`` over generated fraud-style data
    (``examples/make_fraud_data.py``), per-step wall-clock as
    ``pipeline_e2e_*`` extras.  Unlike the per-plane benches this times
    the REAL pipeline — CSV parse, spill/streamed ingest, validator,
    model serialization — the path a user's ``shifu train`` actually
    takes.  Default ~10M rows (``SHIFU_BENCH_E2E_ROWS`` overrides; CI
    rigs run smaller)."""
    import importlib.util
    import os
    import tempfile

    n_rows = n_rows or int(os.environ.get("SHIFU_BENCH_E2E_ROWS",
                                          10_000_000))
    spec = importlib.util.spec_from_file_location(
        "make_fraud_data",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "examples", "make_fraud_data.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)

    from shifu_tpu.config import ModelConfig
    from shifu_tpu.config.model_config import Algorithm
    from shifu_tpu.pipeline.create import InitProcessor, create_new_model
    from shifu_tpu.pipeline.evaluate import EvalProcessor
    from shifu_tpu.pipeline.norm import NormalizeProcessor
    from shifu_tpu.pipeline.stats import StatsProcessor
    from shifu_tpu.pipeline.train import TrainProcessor

    out: Dict[str, Any] = {"pipeline_e2e_rows": n_rows}
    # telemetry stays on for the run so ingest.disk_passes (raw string-
    # plane traversals, schema v14) accumulates — the cache/wire win is
    # claimed as a COUNTED pass drop, not a narrative.  Each step's
    # flush snapshots-and-resets the registry, so the total is summed
    # from the per-step metric records in the trace afterwards.
    prev_enabled = obs.enabled()
    obs.set_enabled(True)
    t_all = time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        t0 = time.perf_counter()
        csv = gen.make(os.path.join(td, "data"), n=n_rows)
        out["pipeline_e2e_datagen_s"] = round(time.perf_counter() - t0, 2)
        mdir = create_new_model("e2e", base_dir=td)
        mc = ModelConfig.load(os.path.join(mdir, "ModelConfig.json"))
        mc.dataSet.dataPath = csv
        mc.dataSet.dataDelimiter = "|"
        mc.dataSet.targetColumnName = "tag"
        mc.dataSet.posTags = ["bad"]
        mc.dataSet.negTags = ["good"]
        mc.dataSet.weightColumnName = "weight"
        mc.dataSet.metaColumnNameFile = os.path.join(
            os.path.dirname(csv), "meta.names")
        mc.evals[0].dataSet.dataPath = csv
        mc.evals[0].dataSet.dataDelimiter = "|"
        mc.save(os.path.join(mdir, "ModelConfig.json"))

        def timed(key: str, proc) -> None:
            t0 = time.perf_counter()
            rc = proc.run()
            assert rc == 0, f"{key} failed rc={rc}"
            out[f"pipeline_e2e_{key}_s"] = round(
                time.perf_counter() - t0, 2)

        timed("init", InitProcessor(mdir))
        timed("stats", StatsProcessor(mdir, params={}))
        timed("norm", NormalizeProcessor(mdir, params={}))

        mc = ModelConfig.load(os.path.join(mdir, "ModelConfig.json"))
        mc.train.algorithm = Algorithm.GBT
        mc.train.params = {"TreeNum": 100, "MaxDepth": 6, "Loss": "log",
                           "LearningRate": 0.1}
        mc.save(os.path.join(mdir, "ModelConfig.json"))
        timed("train_gbt", TrainProcessor(mdir, params={}))
        timed("eval_gbt", EvalProcessor(mdir, params={}))

        mc = ModelConfig.load(os.path.join(mdir, "ModelConfig.json"))
        mc.train.algorithm = Algorithm.NN
        mc.train.params = {"NumHiddenLayers": 2,
                           "NumHiddenNodes": [64, 32],
                           "ActivationFunc": ["relu", "relu"],
                           "LearningRate": 0.001, "Propagation": "ADAM",
                           "Loss": "log"}
        mc.train.numTrainEpochs = nn_epochs
        mc.save(os.path.join(mdir, "ModelConfig.json"))
        timed("train_nn", TrainProcessor(mdir, params={}))
        timed("eval_nn", EvalProcessor(mdir, params={}))

        from shifu_tpu.obs.report import load_blocks, trace_path
        dp = 0.0
        try:
            for block in load_blocks(trace_path(mdir)):
                for m in block["metrics"]:
                    if m.get("name") == "ingest.disk_passes":
                        dp += float(m.get("value") or 0)
        except OSError:
            dp = -1.0                  # no trace — surfaced, not hidden
        out["pipeline_e2e_disk_passes"] = round(dp, 1)
    total = time.perf_counter() - t_all
    out["pipeline_e2e_total_s"] = round(total, 2)
    out["pipeline_e2e_rows_per_sec"] = round(n_rows / total, 1)
    # wall_s duplicates total_s under the *_wall_s suffix --compare
    # tracks LOWER-is-better — the cold end-to-end wall clock IS the
    # one-parse round's headline contract
    out["pipeline_e2e_wall_s"] = round(total, 2)
    obs.set_enabled(True if prev_enabled else None)
    return out


def bench_ingest(n_rows: int = None) -> Dict[str, Any]:
    """One-parse ingest plane (``bench.py --plane ingest``): the scripted
    ``init → stats → norm`` front half over generated fraud-style data,
    run TWICE in one invocation — first with the one-parse machinery
    knobbed OFF (``parseWorkers=0``, ``rawCache=false``,
    ``wireOnly=false``: the serial parse-per-step baseline every round
    before this one ran), then with the defaults (parse pool + columnar
    raw cache + direct-to-wire norm).  Headlines ``stats_throughput`` /
    ``norm_throughput`` are the POOLED raw-rows/sec (tracked by
    ``--compare`` via the throughput class); the serial wall-clocks and
    the speedup ratios ride along informational.  Default ~2M rows
    (``SHIFU_BENCH_INGEST_ROWS`` overrides)."""
    import importlib.util
    import os
    import tempfile

    n_rows = n_rows or int(os.environ.get("SHIFU_BENCH_INGEST_ROWS",
                                          2_000_000))
    spec = importlib.util.spec_from_file_location(
        "make_fraud_data",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "examples", "make_fraud_data.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)

    from shifu_tpu.config import ModelConfig, environment
    from shifu_tpu.pipeline.create import InitProcessor, create_new_model
    from shifu_tpu.pipeline.norm import NormalizeProcessor
    from shifu_tpu.pipeline.stats import StatsProcessor

    KNOBS = {"shifu.ingest.parseWorkers": "0",
             "shifu.ingest.rawCache": "false",
             "shifu.norm.wireOnly": "false"}
    # knob defaults to restore after the serial leg (set_property has no
    # delete — writing the registry default back is equivalent to unset)
    DEFAULTS = {"shifu.ingest.parseWorkers": "-1",
                "shifu.ingest.rawCache": "true",
                "shifu.norm.wireOnly": "true"}

    out: Dict[str, Any] = {"ingest_rows": n_rows}
    with tempfile.TemporaryDirectory() as td:
        csv = gen.make(os.path.join(td, "data"), n=n_rows)

        def run_leg(name: str, knobs: dict) -> Dict[str, float]:
            prior = {k: environment.get_property(k) for k in knobs}
            for k, v in knobs.items():
                environment.set_property(k, v)
            try:
                mdir = create_new_model(f"ingest_{name}", base_dir=td)
                mc = ModelConfig.load(os.path.join(mdir,
                                                   "ModelConfig.json"))
                mc.dataSet.dataPath = csv
                mc.dataSet.dataDelimiter = "|"
                mc.dataSet.targetColumnName = "tag"
                mc.dataSet.posTags = ["bad"]
                mc.dataSet.negTags = ["good"]
                mc.dataSet.weightColumnName = "weight"
                mc.dataSet.metaColumnNameFile = os.path.join(
                    os.path.dirname(csv), "meta.names")
                mc.save(os.path.join(mdir, "ModelConfig.json"))
                assert InitProcessor(mdir).run() == 0
                t0 = time.perf_counter()
                assert StatsProcessor(mdir, params={}).run() == 0
                stats_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                assert NormalizeProcessor(mdir, params={}).run() == 0
                norm_s = time.perf_counter() - t0
                return {"stats_s": stats_s, "norm_s": norm_s}
            finally:
                for k, v in prior.items():
                    environment.set_property(
                        k, v if v is not None else DEFAULTS[k])

        # untimed warmup leg compiles the stats/norm kernels at the real
        # chunk shapes so the timed serial leg doesn't bill XLA compile
        # to "serial parse" (which would inflate the speedup ratios)
        run_leg("warmup", KNOBS)
        serial = run_leg("serial", KNOBS)
        pooled = run_leg("pooled", DEFAULTS)

    out["ingest_serial_stats_s"] = round(serial["stats_s"], 2)
    out["ingest_serial_norm_s"] = round(serial["norm_s"], 2)
    out["ingest_pooled_stats_s"] = round(pooled["stats_s"], 2)
    out["ingest_pooled_norm_s"] = round(pooled["norm_s"], 2)
    out["stats_throughput"] = round(n_rows / pooled["stats_s"], 1)
    out["norm_throughput"] = round(n_rows / pooled["norm_s"], 1)
    out["ingest_speedup_stats"] = round(
        serial["stats_s"] / pooled["stats_s"], 3)
    out["ingest_speedup_norm"] = round(
        serial["norm_s"] / pooled["norm_s"], 3)
    return out


def bench_resume(n_rows: int = 1 << 16, n_features: int = 64,
                 n_bins: int = 64, n_trees: int = 24,
                 depth: int = 5) -> Dict[str, Any]:
    """Resume-overhead plane (``bench.py --plane resume``): how long until
    the FIRST NEW TREE lands after a restart from a mid-forest checkpoint
    vs a start from scratch.  Three windows:

    - ``cold_first_tree_s``   fresh process state: XLA compile + ingest +
      tree 0 (what a cold `train` pays);
    - ``warm_first_tree_s``   second from-scratch run, executables cached
      (isolates compile from the comparison);
    - ``resume_first_tree_s`` restore 2/3 of the forest and grow the next
      tree — the checkpoint-replay overhead (f rebuilt by replaying the
      committed trees) plus one tree.

    ``resume_overhead_vs_warm`` is the honest headline: the replay cost a
    restarted run pays before producing new work."""
    from shifu_tpu.train.dt_trainer import DTSettings, train_gbt

    rng = np.random.default_rng(0)
    bins = rng.integers(0, n_bins, size=(n_rows, n_features)).astype(np.int32)
    y = (rng.random(n_rows) < 0.3).astype(np.float32)
    w = np.ones(n_rows, np.float32)
    cat = np.zeros(n_features, bool)
    settings = DTSettings(n_trees=n_trees, depth=depth, loss="log",
                          learning_rate=0.1)

    def window(init_trees=None, start_history=None):
        marks = {}
        t0 = time.perf_counter()

        def progress(ti, tr, va):
            marks.setdefault("first", time.perf_counter() - t0)
        res = train_gbt(bins, y, w, n_bins, cat, settings,
                        progress=progress, init_trees=init_trees,
                        start_history=start_history)
        return res, marks["first"], time.perf_counter() - t0

    cold_res, cold_first, cold_total = window()
    _, warm_first, warm_total = window()
    k = (2 * n_trees) // 3                 # the "checkpoint" restore point
    _, resume_first, resume_total = window(
        init_trees=list(cold_res.trees[:k]),
        start_history=list(cold_res.history[:k]))
    return {
        "resume_first_tree_s": round(resume_first, 4),
        "cold_first_tree_s": round(cold_first, 4),
        "warm_first_tree_s": round(warm_first, 4),
        "resume_overhead_vs_warm": round(resume_first - warm_first, 4),
        "resume_total_s": round(resume_total, 4),
        "cold_total_s": round(cold_total, 4),
        "warm_total_s": round(warm_total, 4),
        "restored_trees": k,
        "shape": f"{n_rows} rows x {n_features} feats, {n_trees} trees "
                 f"depth {depth}, restore at {k}",
    }


def bench_varsel(n_rows: int = 1 << 15, n_features: int = 256,
                 n_candidates: int = 128, hidden: int = 16,
                 filter_num: int = 24,
                 mask_batch: int = None) -> Dict[str, Any]:
    """Variable-selection plane (``bench.py --plane varsel``): the
    streamed, mask-batched SE sensitivity job vs the single-worker NumPy
    per-column loop — the reference's ``VarSelectMapper.java:93-120`` MR
    computation, f64 forwards, one frozen column at a time — timed live
    on the same rig AT IDENTICAL SELECTIONS (the top-``filter_num``
    candidate sets must agree, else the speedup is meaningless).

    Rates are rows*candidates/sec (every candidate mask re-scores every
    row, like rows*trees for forests).  The recorded BASELINE.md
    denominator (``MEASURED_CPU_VARSEL_ROWS_COLS_PER_SEC``) comes from
    ``tools/measure_baseline.py`` at the bench NN shapes; the live loop
    here runs the *same* computation at this bench's smaller shape so the
    selections can be compared in seconds."""
    import json
    import os
    import tempfile

    import jax

    from shifu_tpu.data.shards import Shards
    from shifu_tpu.data.streaming import ShardStream, stream_window_rows
    from shifu_tpu.models.nn import NNModelSpec, init_params
    from shifu_tpu.ops import sensitivity as sens
    from shifu_tpu.parallel.mesh import device_mesh

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    wv = rng.normal(size=n_features) / np.sqrt(n_features)
    y = (rng.random(n_rows) < 1 / (1 + np.exp(-(x @ wv)))) \
        .astype(np.float32)
    spec = NNModelSpec(input_dim=n_features, hidden_nodes=[hidden],
                       activations=["tanh"])
    params = init_params(jax.random.PRNGKey(0), spec)
    masks = sens.mask_matrix(n_features,
                             [[c] for c in range(n_candidates)])

    # ---- single-worker NumPy f64 per-column loop (reference-class)
    w0 = np.asarray(params[0]["w"], np.float64)
    b0 = np.asarray(params[0]["b"], np.float64)
    w1 = np.asarray(params[1]["w"], np.float64)
    b1 = np.asarray(params[1]["b"], np.float64)
    x64 = x.astype(np.float64)
    y64 = y.astype(np.float64)[:, None]

    def np_mse(m):
        h = np.tanh(m @ w0 + b0)
        p = 1.0 / (1.0 + np.exp(-(h @ w1 + b1)))
        return float(((p - y64) ** 2).mean())

    mean_x = x64.mean(axis=0)
    t0 = time.perf_counter()
    base64 = np_mse(x64)
    loop_mse = np.empty(n_candidates)
    for c in range(n_candidates):
        xf = x64.copy()
        xf[:, c] = mean_x[c]
        loop_mse[c] = np_mse(xf)
    loop_dt = time.perf_counter() - t0
    loop_rate = n_rows * n_candidates / loop_dt
    sel_loop = set(np.argsort(-(loop_mse - base64))[:filter_num])

    # ---- streamed mask-batched device job over materialized shards
    with tempfile.TemporaryDirectory() as td:
        shard_rows = 8192
        k = 0
        for s in range(0, n_rows, shard_rows):
            e = min(s + shard_rows, n_rows)
            ioutil.atomic_savez(os.path.join(td, f"part-{k:05d}.npz"),
                                x=x[s:e], y=y[s:e])
            k += 1
        ioutil.atomic_write_json(
            os.path.join(td, "schema.json"),
            {"outputNames": [f"c{i}" for i in range(n_features)],
             "columnNums": list(range(n_features)),
             "numShards": k, "numRows": n_rows})
        shards = Shards.open(td)
        mesh = device_mesh()
        window_rows = stream_window_rows(4 * (n_features + 2),
                                         int(mesh.shape["data"]), shards)

        def run():
            stream = ShardStream(shards, ("x", "y"), window_rows)
            return sens.streamed_sensitivity(stream, spec, params, masks,
                                             mesh=mesh,
                                             mask_batch=mask_batch)

        run()                    # compile warmup + spill-cache build
        best, mse, base = 0.0, None, None
        for _ in range(3):
            t0 = time.perf_counter()
            mse, base, nr = run()
            dt = time.perf_counter() - t0
            assert nr == n_rows
            best = max(best, n_rows * n_candidates / dt)
    sel_stream = set(np.argsort(-(mse - base))[:filter_num])

    return {
        "varsel_stream_rows_cols_per_sec": round(best, 1),
        "varsel_loop_rows_cols_per_sec": round(loop_rate, 1),
        "varsel_speedup_vs_loop": round(best / loop_rate, 2),
        "varsel_selections_match": sel_stream == sel_loop,
        "varsel_shape": f"{n_rows} rows x {n_features} feats, "
                        f"{n_candidates} candidates, top {filter_num}",
    }


# quick-mode catastrophic floor for the serve plane (sustained QPS at the
# top offered load; SHIFU_BENCH_SERVE_FLOOR overrides) — far below any
# functioning rig, exists to catch e.g. a silent per-request-tracing
# regression, not to benchmark the rig
SERVE_BENCH_FLOOR = 5000.0
# low-load p99 must stay bounded by the deadline knob; the slop absorbs
# CI-rig scheduler noise (SHIFU_BENCH_SERVE_P99_SLOP_MS overrides)
SERVE_P99_SLOP_MS = 50.0
# the traced pass head-samples this fraction of requests and must still
# sustain TRACE_OVERHEAD_FLOOR_FRAC x the QPS floor — the acceptance
# bound on per-request tracing overhead at load
TRACE_BENCH_SAMPLE_RATE = 0.01
TRACE_OVERHEAD_FLOOR_FRAC = 0.95


def _trace_decomposition(request_spans) -> Dict[str, float]:
    """Mean latency-decomposition fractions over sampled
    ``serve.request`` span records: where a request's end-to-end time
    went (queue wait / device compute / padding+assembly).  Empty input
    yields no extras."""
    fracs = {"serve_queue_frac": [], "serve_device_frac": [],
             "serve_pad_frac": []}
    for rec in request_spans:
        a = rec.get("attrs") or {}
        e2e = float(a.get("e2e_s") or 0.0)
        if e2e <= 0:
            continue
        fracs["serve_queue_frac"].append(
            float(a.get("queue_wait_s") or 0.0) / e2e)
        fracs["serve_device_frac"].append(
            float(a.get("device_s") or 0.0) / e2e)
        fracs["serve_pad_frac"].append(
            float(a.get("pad_s") or 0.0) / e2e)
    return {k: round(float(np.mean(v)), 4)
            for k, v in fracs.items() if v}


def _serve_open_loop(batcher, pool: np.ndarray, qps: float,
                     duration_s: float):
    """Offered-load open-loop client: arrivals on an ideal schedule in
    ~1 ms bursts (each burst = the single-record requests that landed in
    that tick), stamps = IDEAL arrival times so the latency percentiles
    are free of coordinated omission.  Returns (achieved_qps,
    latencies_s)."""
    clock = batcher.clock
    n_target = int(qps * duration_s)
    period = 1.0 / qps
    pool_n = len(pool)
    tickets, sent = [], 0
    t0 = clock()
    while sent < n_target:
        due = min(n_target, int((clock() - t0) / period) + 1)
        if due <= sent:
            time.sleep(0.0002)
            continue
        idx = np.arange(sent, due)
        tickets.append(batcher.submit_burst(
            pool[idx % pool_n], stamps=t0 + idx * period))
        sent = due
    for t in tickets:
        t.wait(30.0)
    wall = clock() - t0
    lats = np.concatenate([t.latencies() for t in tickets])
    return sent / wall, lats


def _serve_saturation(batcher, pool: np.ndarray, duration_s: float):
    """Top offered load: keep ~4 top-bucket bursts outstanding so the
    device never starves — achieved QPS is the plane's sustained
    capacity.  Returns (achieved_qps, latencies_s)."""
    clock = batcher.clock
    top = batcher._top_bucket()
    pool_n = len(pool)
    tickets, done, sent = [], 0, 0
    t0 = clock()
    while clock() - t0 < duration_s:
        while len(tickets) - done > 4:
            tickets[done].wait(30.0)
            done += 1
        idx = (np.arange(sent, sent + top)) % pool_n
        tickets.append(batcher.submit_burst(pool[idx]))
        sent += top
    for t in tickets[done:]:
        t.wait(30.0)
    wall = clock() - t0
    lats = np.concatenate([t.latencies() for t in tickets])
    return sent / wall, lats


def _serve_closed_loop(batcher, pool: np.ndarray, n_threads: int,
                       duration_s: float):
    """Closed-loop client fleet: N threads each scoring ONE record at a
    time synchronously — the reference's per-row production pattern.
    Returns (achieved_qps, latencies_s)."""
    import threading
    clock = batcher.clock
    lats: list = [[] for _ in range(n_threads)]
    counts = [0] * n_threads

    def worker(i: int) -> None:
        j = i * 97
        end = clock() + duration_s
        while clock() < end:
            t = batcher.submit(pool[j % len(pool)])
            t.wait(10.0)
            lats[i].append(float(t.latencies()[0]))
            counts[i] += 1
            j += 1

    t0 = clock()
    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = clock() - t0
    return sum(counts) / wall, np.asarray(
        [v for ls in lats for v in ls], np.float64)


def bench_serve_quantized(n_rows_grow: int = 1 << 13, n_feat: int = 32,
                          n_bins: int = 64, n_trees: int = 50,
                          depth: int = 6,
                          bucket: int = 512) -> Dict[str, Any]:
    """Quantized-traversal serving micro-bench: a GBT forest behind the
    AOT scorer, scored on uint8 bin batches (``serve_quantized_qps`` =
    ``score_batch`` rows/s at the top bucket), with the bit-parity
    guard the quant path is contracted to: AOT quantized scores must be
    BIT-identical to the classic widened-traversal math."""
    import jax.numpy as jnp

    from shifu_tpu.models.tree import IndependentTreeModel, TreeModelSpec
    from shifu_tpu.ops.tree import (grow_tree, predict_forest_stacked,
                                    stack_forest)
    from shifu_tpu.serve.scorer import AOTScorer

    rng = np.random.default_rng(7)
    gbins = rng.integers(0, n_bins,
                         size=(n_rows_grow, n_feat)).astype(np.int32)
    y = (rng.random(n_rows_grow) < 0.3).astype(np.float32)
    w = np.ones(n_rows_grow, np.float32)
    trees = [grow_tree(gbins, y * (0.8 + 0.4 * rng.random()), w, n_bins,
                       depth) for _ in range(n_trees)]
    spec = TreeModelSpec(algorithm="GBT", n_trees=n_trees, depth=depth,
                         n_bins=n_bins, loss="log", learning_rate=0.1,
                         init_score=-0.5)
    model = IndependentTreeModel(spec, trees)
    scorer = AOTScorer([model], buckets=(bucket,), name="serve.score.quant")
    scorer.warm()
    # the AOT signature covers exactly the features the forest reads
    batch = rng.integers(0, n_bins, size=(bucket, scorer.n_bins_cols)) \
        .astype(np.uint8)
    x = np.zeros((bucket, scorer.n_features), np.float32)
    # classic reference: widened int32 traversal + the same GBT link,
    # in-graph f32 end to end (a host float64 reference would differ in
    # rounding, not in routing)
    import jax

    stacked = stack_forest(trees)
    scale = scorer.scorer.scale

    @jax.jit
    def classic(b):
        preds = predict_forest_stacked(*stacked, b, depth)
        f = spec.init_score + spec.learning_rate * preds.sum(axis=0)
        return (1.0 / (1.0 + jnp.exp(-f))) * scale

    ref = np.asarray(classic(jnp.asarray(batch, jnp.int32)))
    got = scorer.score_batch(x, batch)[:, 0]
    parity = bool(np.array_equal(ref, got))
    best = 0.0
    reps = 5
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(20):
            scorer.score_batch(x, batch)
        best = max(best, 20 * bucket / (time.perf_counter() - t0))
    return {
        "serve_quantized_qps": round(best, 1),
        "serve_quantized_parity": parity,
        "serve_quantized_bins_dtype": str(scorer.bins_dtype),
        "serve_quantized_shape": f"{n_trees} trees depth {depth} x "
                                 f"{n_bins} bins, bucket {bucket}",
    }


def bench_serve(n_features: int = 32, n_models: int = 5,
                hidden: tuple = (64,), low_qps: float = 2000.0,
                mid_qps: float = 20000.0,
                duration_s: float = 0.8) -> Dict[str, Any]:
    """Online-serving plane (``bench.py --plane serve``): the AOT
    device-resident bagged scorer behind the padded-bucket micro-batcher
    (``shifu_tpu/serve/``), driven by closed-loop and open-loop clients
    at several offered loads.

    The reference-class denominator is the measured per-row bagged
    scorer (``MEASURED_CPU_SCORE_ROWS_PER_SEC`` = 1,505.9 rows/s/worker,
    BASELINE.md) — the production surface this plane replaces.  Reports
    sustained QPS, p50/p99 per load, bucket occupancy / padding waste,
    and enforces the plane's two SLO guards: a warmed server performs
    ZERO recompiles across the sweep (the shape-churn sentinel), and
    low-load p99 stays bounded by the ``maxDelayMs`` deadline."""
    import os

    import jax

    from shifu_tpu.models.nn import (IndependentNNModel, NNModelSpec,
                                     init_params)
    from shifu_tpu.serve import ServeServer, serve_recompile_count

    spec = NNModelSpec(input_dim=n_features, hidden_nodes=list(hidden),
                       activations=["relu"] * len(hidden), output_dim=1)
    models = [IndependentNNModel(spec,
                                 init_params(jax.random.PRNGKey(i), spec))
              for i in range(n_models)]
    server = ServeServer(models=models, key="bench").start()
    batcher = server.batcher
    scorer = server.registry.get("bench")
    deadline_ms = batcher.max_delay_s * 1000.0
    rng = np.random.default_rng(0)
    pool = rng.normal(size=(4096, n_features)).astype(np.float32)
    try:
        # warm: every bucket compiled + launched, dispatch paths hot
        for n in (1, 3, *scorer.buckets):
            batcher.score_sync(pool[:n])
        recompiles0 = serve_recompile_count()
        stats0 = dict(batcher.stats)

        # collector pauses land straight in the tail percentiles (20 ms
        # p99 spikes at low load measured on this rig) — standard
        # latency-bench hygiene: no GC inside the measured window
        import gc
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            closed_qps, closed_lats = _serve_closed_loop(
                batcher, pool, n_threads=8, duration_s=duration_s / 2)
            low_ach, low_lats = _serve_open_loop(batcher, pool, low_qps,
                                                 duration_s)
            mid_ach, mid_lats = _serve_open_loop(batcher, pool, mid_qps,
                                                 duration_s)
            max_ach, max_lats = _serve_saturation(batcher, pool,
                                                  duration_s)
        finally:
            if gc_was_enabled:
                gc.enable()
        recompiles = serve_recompile_count() - recompiles0

        # traced pass: head-sample 1% of requests (telemetry on) and
        # re-measure sustained QPS — the per-request-tracing overhead
        # acceptance — then read the sampled serve.request records for
        # the latency-decomposition extras
        prev_enabled = obs.enabled()
        obs.set_enabled(True)
        rec_before = len(obs.pending_records())
        batcher.trace_sample_rate = TRACE_BENCH_SAMPLE_RATE
        try:
            traced_qps, _ = _serve_saturation(batcher, pool,
                                              duration_s / 2)
            # one explicit-id burst so even a tiny sweep yields a
            # decomposition sample (an explicit id forces sampling)
            batcher.submit_burst(pool[:37],
                                 trace_id="bench-decomp").wait(30.0)
        finally:
            batcher.trace_sample_rate = 0.0
            request_spans = [
                r for r in obs.pending_records()[rec_before:]
                if r.get("kind") == "span"
                and r.get("name") == "serve.request"]
            obs.set_enabled(True if prev_enabled else None)
    finally:
        server.stop()

    def pct(lats, q):
        return round(float(np.percentile(lats, q)) * 1000.0, 3)

    rows = batcher.stats["rows"] - stats0["rows"]
    padded = batcher.stats["rows_padded"] - stats0["rows_padded"]
    batches = batcher.stats["batches"] - stats0["batches"]
    rep: Dict[str, Any] = {
        "serve_qps_sustained": round(max_ach, 1),
        "serve_deadline_ms": deadline_ms,
        "serve_low_qps_offered": low_qps,
        "serve_low_qps": round(low_ach, 1),
        "serve_low_p50_ms": pct(low_lats, 50),
        "serve_low_p99_ms": pct(low_lats, 99),
        "serve_mid_qps_offered": mid_qps,
        "serve_mid_qps": round(mid_ach, 1),
        "serve_mid_p50_ms": pct(mid_lats, 50),
        "serve_mid_p99_ms": pct(mid_lats, 99),
        "serve_max_p50_ms": pct(max_lats, 50),
        "serve_max_p99_ms": pct(max_lats, 99),
        "serve_closed_qps": round(closed_qps, 1),
        "serve_closed_p50_ms": pct(closed_lats, 50),
        "serve_closed_p99_ms": pct(closed_lats, 99),
        "serve_recompiles_after_warm": int(recompiles),
        "serve_traced_qps": round(traced_qps, 1),
        "serve_trace_sample_rate": TRACE_BENCH_SAMPLE_RATE,
        "serve_trace_sampled": len(request_spans),
        **_trace_decomposition(request_spans),
        "serve_batches": int(batches),
        "serve_rows_padded": int(padded),
        "serve_padding_waste_frac": round(
            padded / max(rows + padded, 1), 4),
        "serve_bucket_ladder": ",".join(map(str, scorer.buckets)),
        "serve_bucket_counts": ",".join(
            f"{b}:{c}" for b, c in sorted(batcher.bucket_counts.items())),
        "serve_shape": f"{n_models} NN models {n_features}->"
                       f"{list(hidden)}->1 stacked, pool 4096 rows, "
                       f"clients: closed 8-thread / open "
                       f"{low_qps:.0f}+{mid_qps:.0f} QPS / saturation",
    }
    # quantized-traversal serving rows ride beside the NN-plane rows
    rep.update(bench_serve_quantized())
    if rep.get("serve_quantized_parity") is False:
        raise AssertionError(
            "quantized AOT traversal diverged from the classic "
            "widened-traversal scores — the bit-parity contract of "
            "ops.tree_quant is broken")
    # fused raw-record rows: the in-graph transform's overhead acceptance
    rep.update(bench_serve_raw())
    # plane guards — fail loudly, like the tail bench's schedule guards
    if recompiles > 0:
        raise AssertionError(
            f"warmed serve plane recompiled {recompiles}x across the "
            "load sweep — request shapes leaked past the bucket ladder "
            "(the exact shape-churn hazard xla.recompiles exists for)")
    slop = float(os.environ.get("SHIFU_BENCH_SERVE_P99_SLOP_MS",
                                SERVE_P99_SLOP_MS))
    if rep["serve_low_p99_ms"] > deadline_ms + slop:
        raise AssertionError(
            f"low-load p99 {rep['serve_low_p99_ms']:.1f} ms exceeds the "
            f"deadline bound {deadline_ms:.1f}+{slop:.0f} ms — the "
            "deadline flush is not bounding tail latency")
    floor = float(os.environ.get("SHIFU_BENCH_SERVE_FLOOR",
                                 SERVE_BENCH_FLOOR))
    if max_ach < floor:
        raise AssertionError(
            f"sustained serve QPS {max_ach:.0f} below the catastrophic "
            f"floor {floor:.0f} (SHIFU_BENCH_SERVE_FLOOR)")
    if traced_qps < TRACE_OVERHEAD_FLOOR_FRAC * floor:
        raise AssertionError(
            f"serve QPS with {TRACE_BENCH_SAMPLE_RATE:.0%} request "
            f"tracing fell to {traced_qps:.0f} — below "
            f"{TRACE_OVERHEAD_FLOOR_FRAC}x the {floor:.0f} floor; "
            "head sampling is no longer bounding tracing overhead")
    return rep


# fused raw-record acceptance: the raw path runs the WHOLE norm
# transform in-graph ahead of the ensemble inside one executable, and
# must hold this fraction of the pre-binned saturation rate — the
# transform must stay a fused prelude, not a second model
SERVE_RAW_FLOOR_FRAC = 0.8


def _raw_bench_configs(n_features: int):
    """Synthetic ZSCALE ColumnConfigs for the raw/fleet serving rows."""
    from shifu_tpu.config import ColumnConfig
    ccs = []
    for j in range(n_features):
        cc = ColumnConfig(columnNum=j, columnName=f"f{j}",
                          finalSelect=True)
        cc.columnBinning.binBoundary = [float("-inf"), -0.5, 0.0, 0.5]
        cc.columnBinning.binCountNeg = [10, 10, 10, 10]
        cc.columnBinning.binCountPos = [2, 4, 6, 8]
        cc.columnBinning.binPosRate = [1 / 6., 2 / 7., 3 / 8., 4 / 9.]
        cc.columnBinning.binCountWoe = [0.1, -0.1, 0.2, -0.2, 0.0]
        cc.columnStats.mean = 0.0
        cc.columnStats.stdDev = 1.0
        ccs.append(cc)
    return ccs


def bench_serve_raw(n_features: int = 32, n_models: int = 5,
                    hidden: tuple = (128, 64), batch: int = 512,
                    duration_s: float = 0.5) -> Dict[str, Any]:
    """Fused raw-record rows (merged into the serve plane): device
    throughput of ``score_batch_raw`` — searchsorted binning + table
    gathers + z-score clip fused AHEAD of the ensemble in the same
    executable — vs the pre-binned ``score_batch`` on the same warmed
    bucket.  ``serve_raw_qps_frac`` (tracked via the ``*_qps_frac``
    throughput suffix) must hold SERVE_RAW_FLOOR_FRAC."""
    import os

    import jax

    from shifu_tpu.config.model_config import ModelConfig
    from shifu_tpu.models.nn import (IndependentNNModel, NNModelSpec,
                                     init_params)
    from shifu_tpu.serve.scorer import AOTScorer
    from shifu_tpu.serve.transform import FusedTransform

    tf = FusedTransform(ModelConfig(), _raw_bench_configs(n_features))
    spec = NNModelSpec(input_dim=n_features, hidden_nodes=list(hidden),
                       activations=["relu"] * len(hidden), output_dim=1)
    models = [IndependentNNModel(spec,
                                 init_params(jax.random.PRNGKey(i), spec))
              for i in range(n_models)]
    scorer = AOTScorer(models, buckets=(batch,), transform=tf,
                       name="bench.serve.raw")
    rng = np.random.default_rng(3)
    x = rng.normal(size=(batch, n_features)).astype(np.float32)
    c = tf.n_columns
    packed = np.zeros((batch, tf.wire_width), tf.wire_dtype)
    packed[:, :c] = x
    packed[:, c:2 * c] = 1.0

    def rate(fn, arg):
        fn(arg)                             # compile + warm off the clock
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < duration_s:
            fn(arg)
            n += batch
        return n / (time.perf_counter() - t0)

    pre = rate(scorer.score_batch, x)
    raw = rate(scorer.score_batch_raw, packed)
    frac = raw / max(pre, 1e-9)
    rep = {
        "serve_raw_qps": round(raw, 1),
        "serve_prebinned_qps": round(pre, 1),
        "serve_raw_qps_frac": round(frac, 4),
    }
    floor = float(os.environ.get("SHIFU_BENCH_SERVE_RAW_FLOOR",
                                 SERVE_RAW_FLOOR_FRAC))
    if frac < floor:
        raise AssertionError(
            f"fused raw-record scoring holds only {frac:.2f}x the "
            f"pre-binned rate (floor {floor}, "
            "SHIFU_BENCH_SERVE_RAW_FLOOR) — the in-graph transform "
            "prelude is taxing the scorer it was fused into")
    return rep


# the fleet's closed-loop clients are deadline-bound ON PURPOSE: each
# client thread keeps exactly one request in flight, so most of every
# request is maxDelayMs deadline wait and aggregate QPS measures how
# many replicas the router keeps concurrently busy — near-linear
# replica scaling is observable without N cores
FLEET_DEADLINE_MS = 40.0
FLEET_SCALING_FLOOR = 0.8


def _fleet_modelset(n_features: int, n_models: int, hidden: tuple) -> str:
    """Scratch model-set dir (config snapshot + models) fleet workers
    load — the raw path end to end, subprocess boundary included."""
    import os
    import tempfile

    import jax

    from shifu_tpu.config import save_column_configs
    from shifu_tpu.config.model_config import ModelConfig
    from shifu_tpu.models.nn import NNModelSpec, init_params, save_model

    d = tempfile.mkdtemp(prefix="shifu-bench-fleet-")
    ModelConfig().save(os.path.join(d, "ModelConfig.json"))
    save_column_configs(_raw_bench_configs(n_features),
                        os.path.join(d, "ColumnConfig.json"))
    spec = NNModelSpec(input_dim=n_features, hidden_nodes=list(hidden),
                       activations=["relu"] * len(hidden), output_dim=1)
    os.makedirs(os.path.join(d, "models"))
    for i in range(n_models):
        save_model(os.path.join(d, "models", f"model{i}.nn"), spec,
                   init_params(jax.random.PRNGKey(i), spec))
    return d


def _fleet_up(model_set_dir: str, n: int):
    """n subprocess serve workers + a router balancing over them."""
    import os

    from shifu_tpu.serve.router import (ServeRouter, spawn_worker,
                                        wait_for_announce)

    fleet_dir = os.path.join(model_set_dir, "serving", "fleet")
    os.makedirs(fleet_dir, exist_ok=True)
    router = ServeRouter(poll_ms=250.0, stale_s=10.0)
    started = []
    for i in range(n):
        ann = os.path.join(fleet_dir, f"bench-{n}r-{i}.json")
        if os.path.exists(ann):
            os.unlink(ann)
        started.append((f"r{i}", ann,
                        spawn_worker(model_set_dir, f"r{i}", ann,
                                     max_delay_ms=FLEET_DEADLINE_MS)))
    for name, ann, p in started:
        doc = wait_for_announce(ann, p, timeout=300.0)
        router.add_backend(name, doc["port"], proc=p)
    router.poll_once()
    router.ensure_uniform()
    return router, [p for _, _, p in started]


def _fleet_closed_loop(router, record: dict, n_threads: int,
                       duration_s: float, kill=None):
    """Closed-loop clients through the router; returns
    ``(qps, latencies, failures)``.  ``kill=(proc, at_frac)`` SIGKILLs
    that worker mid-window — the replica-death drill: the router must
    requeue, so ``failures`` staying empty IS the acceptance."""
    import threading

    lats: list = []
    failures: list = []
    lock = threading.Lock()
    stop = threading.Event()

    def client():
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                router.score({"records": [record]}, timeout=30.0)
            except RuntimeError as e:
                with lock:
                    failures.append(str(e))
                continue
            dt = time.perf_counter() - t0
            with lock:
                lats.append(dt)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(n_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    if kill is not None:
        proc, at_frac = kill
        time.sleep(duration_s * at_frac)
        proc.kill()
        time.sleep(duration_s * (1.0 - at_frac))
    else:
        time.sleep(duration_s)
    wall = time.perf_counter() - t0
    stop.set()
    for t in threads:
        t.join(timeout=30.0)
    return len(lats) / wall, lats, failures


def bench_fleet(n_features: int = 8, n_models: int = 3,
                hidden: tuple = (16,), duration_s: float = 4.0
                ) -> Dict[str, Any]:
    """Serving-fleet plane (``bench.py --plane fleet``): subprocess
    worker fleets of 1/2/4 replicas behind
    :class:`~shifu_tpu.serve.router.ServeRouter`, each driven by one
    closed-loop raw-record client per replica (deadline-bound — see
    FLEET_DEADLINE_MS).  Reports aggregate QPS per fleet width, the
    2-replica scaling acceptance ``serve_fleet_scaling_frac`` =
    qps_2r / (2 x qps_1r) (tracked via the ``*_scaling_frac`` suffix;
    floor FLEET_SCALING_FLOOR == the >=1.6x aggregate criterion), and
    the replica-death drill on the widest fleet: one worker SIGKILLed
    mid-window, EVERY accepted request completes by requeue and the
    p99 under the kill rides the lower-is-better latency class."""
    import os
    import shutil

    d = _fleet_modelset(n_features, n_models, hidden)
    record = {f"f{j}": round(float(j) / n_features - 0.4, 3)
              for j in range(n_features)}
    rep: Dict[str, Any] = {}
    qps: Dict[int, float] = {}
    try:
        for n in (1, 2, 4):
            router, procs = _fleet_up(d, n)
            try:
                q, lats, failures = _fleet_closed_loop(
                    router, record, n_threads=n, duration_s=duration_s)
                if failures:
                    raise AssertionError(
                        f"{len(failures)} fleet request(s) failed with "
                        f"every replica live: {failures[0]}")
                qps[n] = q
                rep[f"serve_fleet_{n}r_qps"] = round(q, 1)
                rep[f"serve_fleet_{n}r_p99_ms"] = round(
                    float(np.percentile(lats, 99)) * 1000.0, 3)
                if n == 4:
                    kq, klats, kfail = _fleet_closed_loop(
                        router, record, n_threads=n,
                        duration_s=duration_s, kill=(procs[0], 0.4))
                    if kfail:
                        raise AssertionError(
                            f"{len(kfail)} request(s) lost across the "
                            "replica SIGKILL — requeue-on-replica-death "
                            f"failed: {kfail[0]}")
                    survivors = router.poll_once()["up"]
                    rep["serve_fleet_kill_qps"] = round(kq, 1)
                    rep["serve_fleet_kill_p99_ms"] = round(
                        float(np.percentile(klats, 99)) * 1000.0, 3)
                    rep["serve_fleet_kill_survivors"] = int(survivors)
                    if survivors >= n:
                        raise AssertionError(
                            "SIGKILLed replica still counted up — the "
                            "router never noticed the death")
            finally:
                router.stop()
        scaling = qps[2] / max(2.0 * qps[1], 1e-9)
        rep["serve_fleet_scaling_frac"] = round(scaling, 4)
        rep["serve_fleet_shape"] = (
            f"{n_models} NN models {n_features}->{list(hidden)}->1, "
            f"subprocess workers, deadline {FLEET_DEADLINE_MS:.0f} ms, "
            f"1 closed-loop raw-record client/replica, "
            f"{duration_s:.0f}s windows")
        floor = float(os.environ.get("SHIFU_BENCH_FLEET_SCALING",
                                     FLEET_SCALING_FLOOR))
        if scaling < floor:
            raise AssertionError(
                f"2-replica fleet holds {qps[2]:.0f} QPS vs {qps[1]:.0f} "
                f"single-replica — scaling {scaling:.2f} below {floor} "
                "(SHIFU_BENCH_FLEET_SCALING; the >=1.6x aggregate-QPS "
                "acceptance)")
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return rep


# overload-plane acceptance: goodput at 2x the measured saturation must
# hold this fraction of the saturation QPS (SHIFU_BENCH_OVERLOAD_FLOOR
# overrides) — bounded admission + deadline sheds exist precisely so
# excess offered load costs ~nothing, instead of collapsing throughput
OVERLOAD_GOODPUT_FLOOR = 0.8
# per-request budget while the overload windows run; the admission cap
# is sized so queue wait alone cannot eat more than ~half of it
OVERLOAD_DEADLINE_MS = 150.0


def _serve_overload_load(batcher, pool: np.ndarray, qps: float,
                         duration_s: float) -> Dict[str, Any]:
    """Shed-tolerant open-loop client: same ideal-schedule arrivals as
    :func:`_serve_open_loop`, but admission rejects (429-class) are
    counted instead of fatal and deadline sheds surface as coded
    :class:`DeadlineExceededError` at ``wait()``.  A ``TimeoutError``
    is a HUNG client — the failure mode the overload plane exists to
    rule out — and is counted separately so the guard can demand zero."""
    from shifu_tpu.serve.overload import (DeadlineExceededError,
                                          OverloadedError)
    clock = batcher.clock
    n_target = int(qps * duration_s)
    period = 1.0 / qps
    pool_n = len(pool)
    tickets, sent, rejected = [], 0, 0
    t0 = clock()
    while sent < n_target:
        due = min(n_target, int((clock() - t0) / period) + 1)
        if due <= sent:
            time.sleep(0.0002)
            continue
        idx = np.arange(sent, due)
        try:
            tickets.append(batcher.submit_burst(pool[idx % pool_n],
                                                stamps=t0 + idx * period))
        except OverloadedError:
            rejected += len(idx)
        sent = due
    ok_lats, expired, hung = [], 0, 0
    for t in tickets:
        try:
            t.wait(30.0)
            ok_lats.append(t.latencies())
        except DeadlineExceededError:
            expired += t.n
        except TimeoutError:
            hung += t.n
    wall = clock() - t0
    completed = int(sum(len(ls) for ls in ok_lats))
    return {
        "offered": n_target, "rejected": int(rejected),
        "expired": int(expired), "hung": int(hung),
        "completed": completed, "goodput": completed / wall,
        "lats": (np.concatenate(ok_lats) if ok_lats
                 else np.zeros(0, np.float64)),
    }


def bench_overload(n_features: int = 32, n_models: int = 5,
                   hidden: tuple = (64,),
                   duration_s: float = 0.8) -> Dict[str, Any]:
    """Overload-protection plane (``bench.py --plane overload``): the
    serve plane's saturation QPS is measured unprotected, then the
    admission cap (``maxQueueRows`` sized to ~half the deadline of queue
    runway) and a per-request deadline are armed and the SAME server is
    driven at 1x / 2x / 4x of that saturation by shed-tolerant open-loop
    clients.

    Saturation is measured with the SAME open-loop client the windows
    use (unprotected, overdriven at the pipelined ceiling), so the
    denominator isolates the protection penalty from client-pattern
    differences.  Headline ``serve_overload_goodput`` = completed-
    request QPS at the 2x window, tracked via the ``*_goodput``
    throughput suffix and guarded >= ``SHIFU_BENCH_OVERLOAD_FLOOR`` x
    the saturation QPS — under bounded admission, doubling offered
    load may shed half the requests but must NOT collapse the rate of
    answered ones.
    ``serve_overload_p99_ms`` is the p99 of ADMITTED requests (the
    lower-is-better latency class): under overload the meaningful tail
    is the one clients who got answers saw; shed requests fast-fail
    with coded errors and are counted in ``serve_overload_shed_frac``.
    Three more guards: zero hung clients (every ticket resolves with a
    score or a coded error), zero recompiles after warm, and the 4x
    window must actually shed (a cap that never binds tests nothing)."""
    import os

    import jax

    from shifu_tpu.models.nn import (IndependentNNModel, NNModelSpec,
                                     init_params)
    from shifu_tpu.serve import ServeServer, serve_recompile_count

    spec = NNModelSpec(input_dim=n_features, hidden_nodes=list(hidden),
                       activations=["relu"] * len(hidden), output_dim=1)
    models = [IndependentNNModel(spec,
                                 init_params(jax.random.PRNGKey(i), spec))
              for i in range(n_models)]
    server = ServeServer(models=models, key="bench").start()
    batcher = server.batcher
    scorer = server.registry.get("bench")
    rng = np.random.default_rng(0)
    pool = rng.normal(size=(4096, n_features)).astype(np.float32)
    try:
        for n in (1, 3, *scorer.buckets):
            batcher.score_sync(pool[:n])
        # pipelined ceiling (4 bursts outstanding, client blocked in
        # wait): only the OVERDRIVE rate for the saturation window below
        pipe_qps, _ = _serve_saturation(batcher, pool, duration_s / 2)
        # the real denominator: what the SAME open-loop client drains
        # with no deadline, overdriven past the pipelined ceiling.  A
        # small queue bound (8 flushes of runway) keeps the client
        # shedding and submitting for the WHOLE window — an unbounded
        # queue would absorb the excess as backlog and then drain it
        # after the client went quiet, inflating the denominator with
        # interference-free QPS the protected windows never see
        batcher.max_queue_rows = 8 * batcher._top_bucket()
        batcher.default_deadline_s = 0.0
        sat = _serve_overload_load(batcher, pool, pipe_qps,
                                   duration_s)["goodput"]
        recompiles0 = serve_recompile_count()
        sheds0 = batcher.stats["shed_overload"] + \
            batcher.stats["shed_expired"]
        # arm the protection on the live batcher: queue runway = half
        # the deadline at the measured drain rate (so queue wait alone
        # can never eat the whole budget), deadline = the window knob
        deadline_s = OVERLOAD_DEADLINE_MS / 1000.0
        batcher.max_queue_rows = max(batcher._top_bucket(),
                                     int(sat * deadline_s / 2.0))
        batcher.default_deadline_s = deadline_s
        import gc
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            res = {m: _serve_overload_load(batcher, pool, m * sat,
                                           duration_s)
                   for m in (1, 2, 4)}
        finally:
            if gc_was_enabled:
                gc.enable()
        recompiles = serve_recompile_count() - recompiles0
        sheds = batcher.stats["shed_overload"] + \
            batcher.stats["shed_expired"] - sheds0
    finally:
        server.stop()

    def shed_frac(r):
        return (r["rejected"] + r["expired"]) / max(r["offered"], 1)

    r2 = res[2]
    rep: Dict[str, Any] = {
        "serve_overload_sat_qps_offered": round(sat, 1),
        "serve_overload_pipeline_qps_offered": round(pipe_qps, 1),
        "serve_overload_goodput": round(r2["goodput"], 1),
        "serve_overload_goodput_1x": round(res[1]["goodput"], 1),
        "serve_overload_goodput_4x": round(res[4]["goodput"], 1),
        "serve_overload_shed_frac": round(shed_frac(r2), 4),
        "serve_overload_shed_frac_4x": round(shed_frac(res[4]), 4),
        "serve_overload_p99_ms": round(
            float(np.percentile(r2["lats"], 99)) * 1000.0, 3)
        if len(r2["lats"]) else 0.0,
        "serve_overload_hung": sum(r["hung"] for r in res.values()),
        "serve_overload_deadline_ms": OVERLOAD_DEADLINE_MS,
        "serve_overload_max_queue_rows": int(batcher.max_queue_rows),
        "serve_recompiles_after_warm": int(recompiles),
        "serve_overload_sheds": int(sheds),
        "serve_overload_shape": f"{n_models} NN models {n_features}->"
                                f"{list(hidden)}->1, open-loop 1x/2x/4x "
                                f"of saturation, deadline "
                                f"{OVERLOAD_DEADLINE_MS:.0f} ms, "
                                f"{duration_s:.1f}s windows",
    }
    if rep["serve_overload_hung"]:
        raise AssertionError(
            f"{rep['serve_overload_hung']} overload-window request(s) "
            "hung past the 30s client timeout — a shed MUST resolve its "
            "ticket with a coded error, never leave the client waiting")
    if recompiles > 0:
        raise AssertionError(
            f"warmed serve plane recompiled {recompiles}x across the "
            "overload windows — shedding must not perturb the bucket "
            "ladder")
    if shed_frac(res[4]) <= 0.0:
        raise AssertionError(
            "4x offered load shed nothing — the admission cap never "
            "bound, so the overload plane measured a no-op")
    floor = float(os.environ.get("SHIFU_BENCH_OVERLOAD_FLOOR",
                                 OVERLOAD_GOODPUT_FLOOR))
    if r2["goodput"] < floor * sat:
        raise AssertionError(
            f"goodput at 2x offered load is {r2['goodput']:.0f} QPS vs "
            f"{sat:.0f} saturation — below the {floor} floor "
            "(SHIFU_BENCH_OVERLOAD_FLOOR); overload is collapsing "
            "throughput instead of shedding it")
    return rep


# the score-log bench runs the same head-sampling rate as the trace
# bench; scorelog-on QPS must hold this fraction of the scorelog-off
# saturation QPS (the v11 overhead acceptance)
SCORELOG_BENCH_SAMPLE_RATE = 0.01
SCORELOG_OVERHEAD_FLOOR_FRAC = 0.95
# detect-phase joined-batch size; min_joined stays the knob default (64)
QUALITY_DETECT_BATCH = 64


def bench_quality(n_features: int = 32, n_models: int = 3,
                  hidden: tuple = (64,), duration_s: float = 0.6
                  ) -> Dict[str, Any]:
    """Model-quality observability plane (``bench.py --plane quality``):
    two acceptances —

    - **score-log overhead**: saturation QPS with the serve-path score
      log OFF (the default) vs ON at a 1% head-sampling rate into a
      scratch model-set dir; ``serve_scorelog_qps_frac`` (on/off,
      tracked by ``--compare`` via the ``*_qps_frac`` suffix) must stay
      >= SCORELOG_OVERHEAD_FLOOR_FRAC — sampled logging must not tax
      the serving plane it observes;
    - **time-to-detect**: a :class:`~shifu_tpu.obs.quality.
      QualityMonitor` seeded with a synthetic posttrain snapshot is fed
      label-FLIPPED joined outcomes in QUALITY_DETECT_BATCH-row batches
      until its verdict turns degraded; ``quality_label_flip_detect_s``
      (wall, tracked LOWER-is-better via the ``*_detect_s`` suffix) is
      the streaming monitor's detection latency at bench scale."""
    import os
    import shutil
    import tempfile

    import jax

    from shifu_tpu.eval.metrics import auc_trapezoid, sweep
    from shifu_tpu.models.nn import (IndependentNNModel, NNModelSpec,
                                     init_params)
    from shifu_tpu.obs.quality import QualityMonitor
    from shifu_tpu.obs.scorelog import read_score_records, scorelog_dir
    from shifu_tpu.serve import ServeServer

    spec = NNModelSpec(input_dim=n_features, hidden_nodes=list(hidden),
                       activations=["relu"] * len(hidden), output_dim=1)
    models = [IndependentNNModel(spec,
                                 init_params(jax.random.PRNGKey(i), spec))
              for i in range(n_models)]
    rng = np.random.default_rng(0)
    pool = rng.normal(size=(4096, n_features)).astype(np.float32)

    def saturate(server) -> float:
        batcher = server.batcher
        try:
            # warm every bucket before the measured window
            for n in (1, 3, *server.registry.get("bench").buckets):
                batcher.score_sync(pool[:n])
            qps, _ = _serve_saturation(batcher, pool, duration_s)
        finally:
            server.stop()
        return qps

    off_qps = saturate(ServeServer(models=models, key="bench").start())
    scratch = tempfile.mkdtemp(prefix="shifu_bench_quality_")
    try:
        on_qps = saturate(ServeServer(
            models=models, key="bench", model_set_dir=scratch,
            scorelog_sample_rate=SCORELOG_BENCH_SAMPLE_RATE).start())
        logged = len(read_score_records(scorelog_dir(scratch)))
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    frac = on_qps / max(off_qps, 1e-9)

    # ---- detect phase: well-separated synthetic baseline, then the
    # live stream joins the SAME scores against FLIPPED labels
    n_base = 4096
    labels = (rng.random(n_base) < 0.5).astype(np.float64)
    scores = np.clip(np.where(labels > 0.5,
                              rng.normal(700.0, 120.0, n_base),
                              rng.normal(300.0, 120.0, n_base)),
                     0.0, 1000.0)
    c = sweep(scores, labels)
    base_auc = float(auc_trapezoid(c.fp / c.neg_total,
                                   c.tp / c.pos_total))
    from shifu_tpu.obs.quality import write_posttrain_snapshot
    snap_dir = tempfile.mkdtemp(prefix="shifu_bench_snap_")
    try:
        snap = write_posttrain_snapshot(
            os.path.join(snap_dir, "posttrain.json"), scores,
            auc=base_auc)
    finally:
        shutil.rmtree(snap_dir, ignore_errors=True)
    mon = QualityMonitor(snapshot=snap)
    t0 = time.perf_counter()
    detect_s = None
    fed = 0
    while fed < n_base:
        sl = slice(fed, fed + QUALITY_DETECT_BATCH)
        mon.observe_scores(1, scores[sl])
        mon.update(1, scores[sl], 1.0 - labels[sl])    # the label flip
        fed += len(scores[sl])
        if mon.summary()["degraded"]:
            detect_s = time.perf_counter() - t0
            break
    if detect_s is None:
        raise AssertionError(
            f"quality monitor never flagged a FULL label flip over "
            f"{n_base} joined rows (baseline AUC {base_auc:.3f}) — the "
            "live-AUC trigger is dead")

    rep: Dict[str, Any] = {
        "serve_scorelog_off_qps": round(off_qps, 1),
        "serve_scorelog_on_qps": round(on_qps, 1),
        "serve_scorelog_qps_frac": round(frac, 4),
        "serve_scorelog_sample_rate": SCORELOG_BENCH_SAMPLE_RATE,
        "serve_scorelog_records": int(logged),
        "quality_label_flip_detect_s": round(detect_s, 4),
        "quality_label_flip_detect_rows": int(fed),
        "quality_baseline_auc": round(base_auc, 4),
        "quality_shape": f"{n_models} NN models {n_features}->"
                         f"{list(hidden)}->1, pool 4096 rows, scorelog "
                         f"{SCORELOG_BENCH_SAMPLE_RATE:.0%} sampled, "
                         f"detect batches of {QUALITY_DETECT_BATCH}",
    }
    if frac < SCORELOG_OVERHEAD_FLOOR_FRAC:
        raise AssertionError(
            f"saturation QPS with the score log on fell to {frac:.3f}x "
            f"the scorelog-off rate ({on_qps:.0f} vs {off_qps:.0f}) — "
            f"below {SCORELOG_OVERHEAD_FLOOR_FRAC}x; sampled score "
            "logging is taxing the serve plane it observes")
    return rep


# --------------------------------------------------------------- compare
# `bench.py --compare OLD.json NEW.json [--threshold 0.9]`: the
# BENCH_r01..r05 trajectory exists in-repo but nothing read it — this is
# the reader.  Diffs two bench payloads metric-by-metric and exits 2
# when any TRACKED THROUGHPUT metric fell below threshold x old, so a
# perf regression fails CI instead of quietly becoming the new normal.

def load_bench_file(path: str) -> Dict[str, Any]:
    """A bench payload from either shape on disk: the raw JSON line
    ``bench.py`` prints, or the driver's BENCH_r0N wrapper (``{"n", ...,
    "parsed": {...}}``)."""
    import json
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict) or "metric" not in doc:
        raise ValueError(f"{path} is not a bench payload "
                         "(no 'metric' key)")
    return doc


def bench_multihost(rows: int = 8192, features: int = 16,
                    epochs: int = 6, kill_step: int = 3
                    ) -> Dict[str, Any]:
    """Elastic multi-controller plane (``bench.py --plane multihost``):
    the quorum-gated streamed NN job (parallel/elastic) measured two
    ways —

    - **scaling curve**: the SAME global dataset trained by 1, 2 and 4
      controller processes (each owning 1/N of the rows; the per-epoch
      combine rides the ``telemetry/steps/`` control plane), reported
      as global rows*epochs per second of the slowest controller
      (``multihost_{1,2,4}p_rows_per_sec``, tracked by ``--compare``)
      plus scaling efficiency vs the 1-process run;
    - **time-to-recover**: a 2-controller quorum-mode run
      (quorumFrac 0.97, 2 s step timeout) where one controller is
      SIGKILL-equivalently killed at an injected ``dcn:step`` boundary;
      the survivor finishes under quorum, the controller is relaunched,
      and ``multihost_recover_s`` is relaunch → rejoined-and-finished
      wall (journal catch-up + the remaining live steps; tracked
      LOWER-is-better via the ``*_recover_s`` suffix).

    The bench asserts the monitor's verdict of the recover run: every
    controller's final heartbeat is ``exited`` (no permanent straggler
    in the step-lag table) and the rejoiner replayed a non-empty
    committed prefix.  The elastic path needs no cross-process
    collectives, which is its point — but every controller is its own
    JAX process, so the plane runs on a CPU backend only and refuses on
    a chip host (``parallel.mesh.refuse_children_on_chip``)."""
    import json as _json
    import os
    import subprocess
    import sys
    import tempfile

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from shifu_tpu.parallel.mesh import refuse_children_on_chip
    refuse_children_on_chip("the multihost plane")

    def launch(out: str, proc: int, nproc: int, mode_args, env_extra=None):
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        env.setdefault("SHIFU_TPU_HEARTBEAT_S", "0.25")
        env.update(env_extra or {})
        cmd = [sys.executable, "-m", "shifu_tpu.parallel.elastic_demo",
               "--out", out, "--proc", str(proc), "--nproc", str(nproc),
               "--rows", str(rows), "--features", str(features),
               "--epochs", str(epochs)] + list(mode_args)
        return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    def wait_all(procs, what: str):
        for i, p in enumerate(procs):
            out_txt, _ = p.communicate(timeout=600)
            if p.returncode != 0:
                raise RuntimeError(
                    f"multihost bench: {what} controller {i} failed "
                    f"rc={p.returncode}:\n{out_txt[-2000:]}")

    def result(out: str, proc: int) -> Dict[str, Any]:
        with open(os.path.join(out, f"result-{proc}.json")) as f:
            return _json.load(f)

    sync_args = ["--quorum-frac", "1.0", "--timeout-ms", "120000"]
    extras: Dict[str, Any] = {}
    rates: Dict[int, float] = {}
    with tempfile.TemporaryDirectory(prefix="shifu_mh_bench_") as td:
        # ---- 1 -> 2 -> 4 controller scaling (sync mode: every step
        # waits for every live member, the worst case for the protocol)
        for nproc in (1, 2, 4):
            out = os.path.join(td, f"scale{nproc}")
            wait_all([launch(out, p, nproc, sync_args)
                      for p in range(nproc)], f"{nproc}p")
            slowest = max(result(out, p)["train_s"] for p in range(nproc))
            rates[nproc] = rows * epochs / slowest
            extras[f"multihost_{nproc}p_rows_per_sec"] = round(
                rates[nproc], 1)
        extras["multihost_scaling_eff_2p"] = round(rates[2] / rates[1], 3)
        extras["multihost_scaling_eff_4p"] = round(rates[4] / rates[1], 3)

        # ---- kill one controller mid-train, relaunch, time the recover
        quorum_args = ["--quorum-frac", "0.97", "--timeout-ms", "2000"]
        out = os.path.join(td, "recover")
        survivor = launch(out, 0, 2, quorum_args)
        victim = launch(out, 1, 2, quorum_args,
                        env_extra={"SHIFU_TPU_FAULTS":
                                   f"dcn:step={kill_step}:kill"})
        v_out, _ = victim.communicate(timeout=600)
        if victim.returncode != 137:
            raise RuntimeError(
                "multihost bench: victim controller did not die at the "
                f"injected dcn:step boundary (rc={victim.returncode}):\n"
                + v_out[-2000:])
        t0 = time.perf_counter()
        rejoiner = launch(out, 1, 2, quorum_args)
        wait_all([survivor, rejoiner], "recover")
        recover_s = time.perf_counter() - t0
        rj = result(out, 1)
        if not rj["dcn"]["rejoined"] or rj["dcn"]["catchup_steps"] <= 0:
            raise RuntimeError("multihost bench: relaunched controller "
                               f"did not rejoin from its journal: {rj}")
        extras["multihost_recover_s"] = round(recover_s, 3)
        extras["multihost_recover_catchup_steps"] = \
            rj["dcn"]["catchup_steps"]
        extras["multihost_kill_step"] = kill_step

        # ---- the monitor's verdict: no permanent straggler
        from shifu_tpu.obs.monitor import aggregate_records, step_lag_table
        recs, counts = aggregate_records([out])
        lag = step_lag_table(recs)
        bad = [r["proc"] for r in recs if r["status"] in ("stalled",
                                                          "stale")]
        if bad:
            raise RuntimeError("multihost bench: permanent straggler(s) "
                               f"after the recover run: {bad}")
        extras["multihost_recover_controllers_exited"] = \
            counts.get("exited", 0)
        extras["multihost_step_lag_rows"] = len(lag)
    extras["multihost_shape"] = (f"{rows} rows x {features} feats, "
                                 f"{epochs} epochs, kill at step "
                                 f"{kill_step}")
    return extras


def bench_refresh(n_rows: int = None, drift_rows: int = None,
                  n_trees: int = 24, extra_trees: int = 8
                  ) -> Dict[str, Any]:
    """Continual-refresh plane (``bench.py --plane refresh``): the cost
    of going from "the model is stale" to "a better model is serving".

    One scripted lifecycle on generated fraud data: init→stats→norm→
    train a GBT incumbent, serve it in-process, append a drifted stream
    (amounts scaled 2x) and re-norm, feed the controller's drift monitor
    until PSI breaches, then run ONE warm refresh cycle —
    checkpoint-resumed trees appended on the new data window, AUC gate,
    hot-swap, short probation.  A scoring pump drives real traffic
    through the swap the whole time.

    Reported (``--compare`` tracks the first as LOWER-is-better):

    - ``refresh_time_to_promoted_s``   trigger decision → promote
      decision (retrain + gate + swap; probation excluded);
    - ``refresh_cold_pipeline_s``      the alternative the reference
      pays: stats + norm + train from scratch on the same drifted
      stream;
    - ``refresh_warm_vs_cold``         cold / warm speedup;
    - ``refresh_slo_alerts_during_swap`` MUST be 0 — the serving
      plane's error budget does not page during a promotion.
    """
    import importlib.util
    import os
    import shutil
    import tempfile
    import threading

    # sized so data-proportional work dominates XLA compile on the CPU
    # rig (CI rigs can shrink it via SHIFU_BENCH_REFRESH_ROWS)
    n_rows = n_rows or int(os.environ.get("SHIFU_BENCH_REFRESH_ROWS",
                                          200_000))
    drift_rows = drift_rows or max(n_rows // 4, 1000)

    spec = importlib.util.spec_from_file_location(
        "make_fraud_data",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "examples", "make_fraud_data.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)

    from shifu_tpu.config import ModelConfig
    from shifu_tpu.config.model_config import Algorithm
    from shifu_tpu.pipeline.create import InitProcessor, create_new_model
    from shifu_tpu.pipeline.norm import NormalizeProcessor
    from shifu_tpu.pipeline.stats import StatsProcessor
    from shifu_tpu.pipeline.train import TrainProcessor
    from shifu_tpu.refresh import (RefreshConfig, RefreshController,
                                   drift_columns_for)
    from shifu_tpu.serve.server import ServeServer

    def configure(mdir: str, csv: str) -> None:
        mc = ModelConfig.load(os.path.join(mdir, "ModelConfig.json"))
        mc.dataSet.dataPath = csv
        mc.dataSet.dataDelimiter = "|"
        mc.dataSet.targetColumnName = "tag"
        mc.dataSet.posTags = ["bad"]
        mc.dataSet.negTags = ["good"]
        mc.dataSet.weightColumnName = "weight"
        mc.dataSet.metaColumnNameFile = os.path.join(
            os.path.dirname(csv), "meta.names")
        mc.train.algorithm = Algorithm.GBT
        mc.train.params = {"TreeNum": n_trees, "MaxDepth": 4,
                           "Loss": "log", "LearningRate": 0.1,
                           "CheckpointInterval": 8}
        mc.train.baggingNum = 1
        mc.save(os.path.join(mdir, "ModelConfig.json"))

    out: Dict[str, Any] = {"refresh_rows": n_rows,
                           "refresh_drift_rows": drift_rows}
    with tempfile.TemporaryDirectory() as td:
        csv = gen.make(os.path.join(td, "data"), n=n_rows)
        mdir = create_new_model("refresh", base_dir=td)
        configure(mdir, csv)
        assert InitProcessor(mdir).run() == 0
        assert StatsProcessor(mdir, params={}).run() == 0
        assert NormalizeProcessor(mdir, params={}).run() == 0
        assert TrainProcessor(mdir, params={}).run() == 0

        # drifted stream: fresh rows with 2x amounts appended, plane
        # re-materialized (the refresh loop's "new data window")
        drift_csv = gen.make(os.path.join(td, "drift"), n=drift_rows,
                             seed=1234)
        with open(csv) as f:
            n_before = sum(1 for _ in f) - 1
        # appending the drifted stream to the bench's own generated
        # dataset — an input fixture, not a pipeline artifact
        with open(drift_csv) as src, \
                open(csv, "a") as dst:  # shifu-lint: disable=atomic-write
            next(src)                                   # header
            for i, line in enumerate(src):
                parts = line.rstrip("\n").split("|")
                parts[0] = f"d{i}"
                if parts[1]:
                    parts[1] = f"{float(parts[1]) * 2.0:.4f}"
                dst.write("|".join(parts) + "\n")
        assert NormalizeProcessor(mdir, params={}).run() == 0

        # p99 objective sized for the CPU rig's launch cost: the guard
        # is "the SWAP must not burn the budget", not "CPU scoring
        # meets a TPU-sized latency objective"
        server = ServeServer(mdir, buckets=(1, 64), max_delay_ms=1.0,
                             slo_p99_ms=250.0).start()
        try:
            ctrl = RefreshController(
                mdir, server=server,
                config=RefreshConfig(psi_threshold=0.25, cooldown_s=0.0,
                                     probation_s=0.3, units=extra_trees,
                                     canary_rows=32),
                drift_columns=drift_columns_for(mdir))
            # earlier training consumed the pre-drift plane
            from shifu_tpu.data.shards import Shards
            total = Shards.open(os.path.join(mdir, "tmp",
                                             "CleanedData")).num_rows
            cursor = int(total * n_before / (n_before + drift_rows))
            ctrl.journal.set_cursor(cursor)

            # the drifted serving stream: skewed bin windows until the
            # live PSI breaches
            n_cols = len(ctrl._drift.columns)
            skew = np.zeros((512, n_cols), np.int64)
            for _ in range(64):
                ctrl.observe(skew)
                summ = ctrl._drift.summary()
                if (summ["psi_max"] or 0) >= 0.25:
                    break
            out["refresh_trigger_psi"] = round(
                float(ctrl._drift.summary()["psi_max"]), 4)

            # real traffic through the swap
            scorer = server.registry.get(server.key)
            rng = np.random.default_rng(0)
            pump_x = rng.normal(size=(32, scorer.n_features)) \
                .astype(np.float32)
            pump_b = rng.integers(
                0, 2, size=(32, scorer.n_bins_cols)).astype(np.int32) \
                if scorer.needs_bins else None
            stop_pump = threading.Event()

            def pump():
                while not stop_pump.is_set():
                    try:
                        server.score(pump_x, pump_b, timeout=30.0)
                    except Exception:       # noqa: BLE001 — bench pump
                        break

            t = threading.Thread(target=pump, daemon=True)
            t.start()
            t0 = time.perf_counter()
            outcome = ctrl.run_once(poll_s=0.05, timeout_s=600.0)
            warm_total = time.perf_counter() - t0
            stop_pump.set()
            t.join(timeout=10.0)
            if outcome != "promoted":
                raise RuntimeError(
                    f"refresh bench: warm cycle ended {outcome!r}, "
                    "expected a promotion")
            by_kind = {}
            for d in ctrl.journal.decisions():
                by_kind.setdefault(d["kind"], d)
            out["refresh_time_to_promoted_s"] = round(
                by_kind["promote"]["ts"] - by_kind["trigger"]["ts"], 3)
            out["refresh_warm_cycle_s"] = round(warm_total, 3)
            out["refresh_resumed_from_trees"] = \
                by_kind["train"].get("resumed_from", 0)
            out["refresh_warm_start"] = bool(
                by_kind["train"].get("warm"))
            out["refresh_generation"] = server.registry.generation(
                server.key)
            alerts = server.slo.alerts()
            out["refresh_slo_alerts_during_swap"] = len(alerts)
            if alerts:
                raise RuntimeError("refresh bench: the serving SLO "
                                   f"paged during the swap: {alerts}")
            if not out["refresh_warm_start"]:
                raise RuntimeError("refresh bench: the retrain cold-"
                                   "started (no checkpoint restored)")
        finally:
            server.stop()

        # the cold alternative: full stats+norm+train from scratch on
        # the SAME drifted stream (what the reference re-runs)
        cdir = create_new_model("refresh-cold", base_dir=td)
        configure(cdir, csv)
        assert InitProcessor(cdir).run() == 0
        t0 = time.perf_counter()
        assert StatsProcessor(cdir, params={}).run() == 0
        assert NormalizeProcessor(cdir, params={}).run() == 0
        assert TrainProcessor(cdir, params={}).run() == 0
        out["refresh_cold_pipeline_s"] = round(
            time.perf_counter() - t0, 3)
        shutil.rmtree(cdir, ignore_errors=True)
    out["refresh_warm_vs_cold"] = round(
        out["refresh_cold_pipeline_s"]
        / max(out["refresh_time_to_promoted_s"], 1e-9), 3)
    out["refresh_shape"] = (f"{n_rows}+{drift_rows} rows, GBT "
                            f"{n_trees}+{extra_trees} trees depth 4")
    return out


def bench_metrics(doc: Dict[str, Any]) -> Dict[str, float]:
    """Flatten a payload to {metric: value}: the headline plus every
    numeric top-level extra."""
    out: Dict[str, float] = {}
    if isinstance(doc.get("value"), (int, float)):
        out[str(doc["metric"])] = float(doc["value"])
    for k, v in (doc.get("extra") or {}).items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[str(k)] = float(v)
    return out


def is_tracked_throughput(name: str) -> bool:
    """Higher-is-better metrics gate the compare: throughputs, sustained
    QPS, plus the v6 utilization extras (*_mfu / *_achieved_bw — a drop
    means the same plane is doing the same math slower, exactly what the
    compare exists to catch).  Ratios, shapes and wall-clock extras
    inform but never fail."""
    if name.endswith("_vs_baseline") or name.endswith("_error") \
            or name.endswith("_offered"):
        return False
    return ("throughput" in name or name.endswith("_per_sec")
            or name.endswith("_qps") or name.endswith("_qps_sustained")
            or name.endswith("_qps_frac")
            or name.endswith("_scaling_frac")
            or name.endswith("_goodput")
            or name.endswith("_mfu") or name.endswith("_achieved_bw"))


def is_tracked_latency(name: str) -> bool:
    """LOWER-is-better metrics (v7/v8): latency percentiles plus the
    serve decomposition's queue/pad fractions (time a request spends
    waiting or being padded, not scored — growth is a regression).  A
    serve p99 that grows past old/threshold regresses the compare
    exactly like a throughput drop — tail latency is the serving
    plane's contract.  ``*_device_frac`` stays informational: a larger
    device share usually means LESS overhead, not more."""
    if name.endswith("_error") or name.endswith("_vs_baseline"):
        return False
    return ("_p50" in name or "_p99" in name
            or name.endswith("_queue_frac") or name.endswith("_pad_frac")
            or name.endswith("_recover_s") or name.endswith("_detect_s")
            or name.endswith("_time_to_promoted_s")
            or name.endswith("_wall_s"))


def compare_bench(old: Dict[str, Any], new: Dict[str, Any],
                  threshold: float = 0.9):
    """(rows, regressed): per-metric diff rows sorted tracked-first, and
    the tracked metrics that regressed — higher-is-better metrics when
    new < threshold x old, LOWER-is-better (latency) metrics when
    new > old / threshold."""
    om, nm = bench_metrics(old), bench_metrics(new)
    rows, regressed = [], []
    for name in sorted(set(om) | set(nm),
                       key=lambda n: (not (is_tracked_throughput(n)
                                           or is_tracked_latency(n)), n)):
        ov, nv = om.get(name), nm.get(name)
        lower_better = is_tracked_latency(name)
        tracked = is_tracked_throughput(name) or lower_better
        ratio = (nv / ov) if (ov and nv is not None) else None
        flag = ""
        if tracked and ov and nv is not None and (
                nv > ov / threshold if lower_better
                else nv < threshold * ov):
            flag = "REGRESSED"
            regressed.append(name)
        elif ov is None:
            flag = "new"
        elif nv is None:
            flag = "gone"
        rows.append({"metric": name, "old": ov, "new": nv, "ratio": ratio,
                     "tracked": tracked, "lower_better": lower_better,
                     "flag": flag})
    return rows, regressed


def format_compare_table(rows, threshold: float) -> str:
    def num(v):
        return "-" if v is None else f"{v:,.1f}"
    out = [f"{'metric':<46}{'old':>16}{'new':>16}{'ratio':>8}  flag",
           "-" * 92]
    for r in rows:
        ratio = "-" if r["ratio"] is None else f"{r['ratio']:.3f}"
        mark = "v" if r.get("lower_better") else \
            ("*" if r["tracked"] else " ")
        out.append(f"{mark}{r['metric']:<45}{num(r['old']):>16}"
                   f"{num(r['new']):>16}{ratio:>8}  {r['flag']}")
    out.append(f"(* = tracked throughput metric, v = tracked latency "
               f"metric [lower is better]; REGRESSED = new < "
               f"{threshold} x old, or latency new > old / {threshold})")
    return "\n".join(out)


def resolve_compare_paths(paths, root: str = None):
    """The ``--compare`` arguments resolved to (old, new).  Two explicit
    paths pass through; NONE switches to auto mode: pick the two newest
    ``BENCH_r*.json`` in the repo root (zero-padded round number = name
    order, so "newest" is deterministic regardless of checkout mtimes)
    and diff older -> newer.  Fewer than two on disk is a clear coded
    error, never a traceback."""
    import glob
    import os
    paths = list(paths or [])
    if len(paths) == 2:
        return paths[0], paths[1]
    if paths:
        raise ValueError("--compare takes exactly two payload paths, or "
                         "none to auto-diff the two newest BENCH_r*.json")
    root = root or os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))
    cands = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))
    if len(cands) < 2:
        raise ValueError(
            f"--compare auto mode needs at least two BENCH_r*.json under "
            f"{root} (found {len(cands)}) — run the bench twice or pass "
            "OLD.json NEW.json explicitly")
    return cands[-2], cands[-1]


def run_compare(old_path: str, new_path: str,
                threshold: float = 0.9, _print=print) -> int:
    """The `--compare` entry: print the regression table, return the
    exit code (0 clean, 2 = tracked throughput regression)."""
    old, new = load_bench_file(old_path), load_bench_file(new_path)
    rows, regressed = compare_bench(old, new, threshold=threshold)
    _print(f"bench compare: {old_path} -> {new_path} "
           f"(threshold {threshold})")
    _print(format_compare_table(rows, threshold))
    if regressed:
        _print(f"REGRESSION: {len(regressed)} tracked metric(s) below "
               f"{threshold} x old: {', '.join(regressed)}")
        return 2
    _print("no tracked throughput regressions")
    return 0


def _check_schema_handshake() -> None:
    if BENCH_TELEMETRY_SCHEMA != obs.SCHEMA_VERSION:
        raise RuntimeError(
            f"bench telemetry schema v{BENCH_TELEMETRY_SCHEMA} disagrees "
            f"with shifu_tpu.obs SCHEMA_VERSION v{obs.SCHEMA_VERSION} — "
            "update bench.py's per-plane metric emission for the new "
            "schema and bump BENCH_TELEMETRY_SCHEMA")


def run_benchmark(plane: str = None) -> Dict[str, Any]:
    """Full sweep by default; ``plane="tail"`` runs ONLY the disk-tail
    streamed-GBT benchmark (seconds, not minutes) so the out-of-core
    path can be iterated on in isolation."""
    _check_schema_handshake()
    if obs.enabled():
        obs.ensure_compile_listener()
    if plane == "tail":
        with obs.span("bench.gbt_train_throughput_streamed_tail",
                      kind="bench"):
            rep = bench_gbt_streamed_tail()
        v = rep["tail_rows_trees_per_sec"]
        for k, val in rep.items():
            if isinstance(val, (int, float)) and not isinstance(val, bool):
                obs.gauge(f"bench.{k}").set(float(val))
        obs.gauge("bench.gbt_train_throughput_streamed_tail").set(v)
        obs.gauge("bench.gbt_train_throughput_streamed_tail_vs_baseline") \
            .set(v / BASELINE_TREE_RATE)
        return {
            "metric": "gbt_train_throughput_streamed_tail",
            "value": round(v, 1),
            "unit": "rows*trees/sec",
            "plane": "tail",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "vs_baseline": round(v / BASELINE_TREE_RATE, 3),
            "baseline_rows_per_sec": BASELINE_TREE_RATE,
            "baseline_provenance": "measured 43068.1 rows*trees/s/worker "
                                   "np.add.at hist GBT on this rig x 100 "
                                   "north-star workers (BASELINE.md)",
            "shape": rep["tail_shape"],
            "extra": rep,
        }
    if plane == "rf-repeat":
        with obs.span("bench.rf_repeat", kind="bench"):
            rep = bench_rf_repeat()
        for k, v in rep.items():
            if isinstance(v, (int, float)):
                obs.gauge(f"bench.{k}").set(float(v))
        return {
            "metric": "rf_repeat_warm_median",
            "value": rep["rf_repeat_warm_median"],
            "unit": "rows*trees/sec",
            "plane": "rf-repeat",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "vs_baseline": rep["rf_repeat_warm_median_vs_baseline"],
            "baseline_rows_per_sec": BASELINE_TREE_RATE,
            "extra": rep,
        }
    if plane == "e2e":
        with obs.span("bench.pipeline_e2e", kind="bench"):
            rep = bench_pipeline_e2e()
        for k, v in rep.items():
            if isinstance(v, (int, float)):
                obs.gauge(f"bench.{k}").set(float(v))
        return {
            "metric": "pipeline_e2e_rows_per_sec",
            "value": rep["pipeline_e2e_rows_per_sec"],
            "unit": "rows/sec",
            "plane": "e2e",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "extra": rep,
        }
    if plane == "ingest":
        with obs.span("bench.ingest", kind="bench"):
            rep = bench_ingest()
        for k, v in rep.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                obs.gauge(f"bench.{k}").set(float(v))
        return {
            "metric": "stats_throughput",
            "value": rep["stats_throughput"],
            "unit": "rows/sec",
            "plane": "ingest",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "extra": rep,
        }
    if plane == "resume":
        with obs.span("bench.resume", kind="bench"):
            rep = bench_resume()
        for k, v in rep.items():
            if isinstance(v, (int, float)):
                obs.gauge(f"bench.resume_{k}").set(float(v))
        return {
            "metric": "resume_first_tree_s",
            "value": rep["resume_first_tree_s"],
            "unit": "seconds",
            "plane": "resume",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "extra": rep,
        }
    if plane == "varsel":
        with obs.span("bench.varsel", kind="bench"):
            rep = bench_varsel()
        for k, v in rep.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                obs.gauge(f"bench.{k}").set(float(v))
        v = rep["varsel_stream_rows_cols_per_sec"]
        return {
            "metric": "varsel_stream_rows_cols_per_sec",
            "value": v,
            "unit": "rows*cols/sec",
            "plane": "varsel",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "vs_baseline": round(v / BASELINE_VARSEL_RATE, 3),
            "baseline_rows_per_sec": BASELINE_VARSEL_RATE,
            "baseline_provenance": "measured 510610.6 rows*cols/s/worker "
                                   "f64 per-column frozen-forward loop on "
                                   "this rig x 100 north-star workers "
                                   "(BASELINE.md)",
            "extra": rep,
        }
    if plane == "serve":
        with obs.span("bench.serve", kind="bench"):
            rep = bench_serve()
        for k, v in rep.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                obs.gauge(f"bench.{k}").set(float(v))
        v = rep["serve_qps_sustained"]
        return {
            "metric": "serve_qps_sustained",
            "value": v,
            "unit": "rows/sec",
            "plane": "serve",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "vs_baseline": round(v / BASELINE_SCORE_RATE, 3),
            "baseline_rows_per_sec": BASELINE_SCORE_RATE,
            "baseline_provenance": "measured 1505.9 rows/s/worker per-row "
                                   "bagged scorer on this rig x 100 "
                                   "north-star workers (BASELINE.md)",
            "extra": rep,
        }
    if plane == "fleet":
        with obs.span("bench.fleet", kind="bench"):
            rep = bench_fleet()
        for k, v in rep.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                obs.gauge(f"bench.{k}").set(float(v))
        return {
            "metric": "serve_fleet_2r_qps",
            "value": rep["serve_fleet_2r_qps"],
            "unit": "requests/sec",
            "plane": "fleet",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "shape": rep["serve_fleet_shape"],
            "extra": rep,
        }
    if plane == "overload":
        with obs.span("bench.overload", kind="bench"):
            rep = bench_overload()
        for k, v in rep.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                obs.gauge(f"bench.{k}").set(float(v))
        return {
            "metric": "serve_overload_goodput",
            "value": rep["serve_overload_goodput"],
            "unit": "requests/sec",
            "plane": "overload",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "shape": rep["serve_overload_shape"],
            "extra": rep,
        }
    if plane == "multihost":
        with obs.span("bench.multihost", kind="bench"):
            rep = bench_multihost()
        for k, v in rep.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                obs.gauge(f"bench.{k}").set(float(v))
        return {
            "metric": "multihost_2p_rows_per_sec",
            "value": rep["multihost_2p_rows_per_sec"],
            "unit": "rows*epochs/sec",
            "plane": "multihost",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "shape": rep["multihost_shape"],
            "extra": rep,
        }
    if plane == "refresh":
        with obs.span("bench.refresh", kind="bench"):
            rep = bench_refresh()
        for k, v in rep.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                obs.gauge(f"bench.{k}").set(float(v))
        return {
            "metric": "refresh_time_to_promoted_s",
            "value": rep["refresh_time_to_promoted_s"],
            "unit": "seconds",
            "plane": "refresh",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "shape": rep["refresh_shape"],
            "extra": rep,
        }
    if plane == "quality":
        with obs.span("bench.quality", kind="bench"):
            rep = bench_quality()
        for k, v in rep.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                obs.gauge(f"bench.{k}").set(float(v))
        return {
            "metric": "serve_scorelog_qps_frac",
            "value": rep["serve_scorelog_qps_frac"],
            "unit": "ratio",
            "plane": "quality",
            "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
            "shape": rep["quality_shape"],
            "extra": rep,
        }
    if plane not in (None, "all"):
        raise ValueError(
            f"unknown bench plane {plane!r} "
            "(tail|rf-repeat|e2e|ingest|resume|varsel|serve|fleet|"
            "overload|multihost|refresh|quality|all)")
    nn_cost: Dict[str, Any] = {}
    nn_rows_per_sec = bench_nn(collect=nn_cost)
    obs.gauge("bench.nn_train_throughput").set(nn_rows_per_sec)
    extras: Dict[str, Any] = {}
    # utilization extras (schema v6): MFU + achieved bandwidth from the
    # timed executable's own XLA cost analysis — --compare tracks them
    _mfu_extras("nn_train", nn_rows_per_sec, nn_cost, extras)
    for k in ("nn_train_mfu", "nn_train_achieved_bw"):
        if k in extras:
            obs.gauge(f"bench.{k}").set(float(extras[k]))

    def record(key: str, fn, baseline: float) -> None:
        """Every extra carries its own measured-denominator ratio; the
        same numbers flow through the obs registry so BENCH_r0N.json and
        the telemetry JSONL share one schema.  A plane that raises
        fails the benchmark: no ``*_error`` extra beside an exit 0."""
        with obs.span(f"bench.{key}", kind="bench"):
            v = fn()
        extras[key] = round(v, 1)
        extras[key + "_vs_baseline"] = round(v / baseline, 3)
        obs.gauge(f"bench.{key}").set(v)
        obs.gauge(f"bench.{key}_vs_baseline").set(v / baseline)

    # mixed-precision ladder row (same harness/shape as the f32 row so
    # the pair reads as one before/after on the compare table)
    mixed_cost: Dict[str, Any] = {}
    record("nn_train_mixed_throughput",
           lambda: bench_nn_mixed(collect=mixed_cost),
           BASELINE_ROWS_PER_SEC)
    _mfu_extras("nn_train_mixed", extras["nn_train_mixed_throughput"],
                mixed_cost, extras)
    for k in ("nn_train_mixed_mfu", "nn_train_mixed_achieved_bw"):
        if k in extras:
            obs.gauge(f"bench.{k}").set(float(extras[k]))
    record("gbt_train_throughput_resident", bench_gbt, BASELINE_TREE_RATE)
    record("gbt_train_throughput_streamed", bench_gbt_streamed,
           BASELINE_TREE_RATE)
    with obs.span("bench.gbt_train_throughput_streamed_tail",
                  kind="bench"):
        tail_rep = bench_gbt_streamed_tail()
    v = tail_rep["tail_rows_trees_per_sec"]
    extras["gbt_train_throughput_streamed_tail"] = v
    extras["gbt_train_throughput_streamed_tail_vs_baseline"] = round(
        v / BASELINE_TREE_RATE, 3)
    extras.update(tail_rep)
    obs.gauge("bench.gbt_train_throughput_streamed_tail").set(v)
    obs.gauge("bench.gbt_train_throughput_streamed_tail_vs_baseline") \
        .set(v / BASELINE_TREE_RATE)
    for k, val in tail_rep.items():
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            obs.gauge(f"bench.{k}").set(float(val))
    record("rf_train_throughput", bench_rf, BASELINE_TREE_RATE)
    wdl_cost: Dict[str, Any] = {}
    record("wdl_train_throughput",
           lambda: bench_wdl(collect=wdl_cost), BASELINE_ROWS_PER_SEC)
    _mfu_extras("wdl_train", extras["wdl_train_throughput"], wdl_cost,
                extras)
    for k in ("wdl_train_mfu", "wdl_train_achieved_bw"):
        if k in extras:
            obs.gauge(f"bench.{k}").set(float(extras[k]))
    wdl_sh_cost: Dict[str, Any] = {}
    record("wdl_train_sharded_throughput",
           lambda: bench_wdl_sharded(collect=wdl_sh_cost),
           BASELINE_ROWS_PER_SEC)
    _mfu_extras("wdl_train_sharded",
                extras["wdl_train_sharded_throughput"], wdl_sh_cost, extras)
    extras["wdl_train_sharded_vs_replicated"] = round(
        extras["wdl_train_sharded_throughput"]
        / max(extras["wdl_train_throughput"], 1e-9), 3)
    for k in ("wdl_train_sharded_mfu", "wdl_train_sharded_achieved_bw",
              "wdl_train_sharded_vs_replicated"):
        if k in extras:
            obs.gauge(f"bench.{k}").set(float(extras[k]))
    record("eval_throughput", bench_eval, BASELINE_SCORE_RATE)
    record("stats_throughput", bench_stats, BASELINE_STATS_RATE)
    with obs.span("bench.varsel", kind="bench"):
        rep = bench_varsel()
    extras.update(rep)
    extras["varsel_throughput_vs_baseline"] = round(
        rep["varsel_stream_rows_cols_per_sec"] / BASELINE_VARSEL_RATE, 3)
    for k, v in rep.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            obs.gauge(f"bench.{k}").set(float(v))
    with obs.span("bench.serve", kind="bench"):
        rep = bench_serve()
    extras.update(rep)
    extras["serve_qps_vs_baseline"] = round(
        rep["serve_qps_sustained"] / BASELINE_SCORE_RATE, 3)
    for k, v in rep.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            obs.gauge(f"bench.{k}").set(float(v))
    extras["streamed_bench_shape"] = {
        "resident": "262144 rows x 100 trees (since r5; was x 8 — 100 = "
                    "the default TreeNum, amortizing the one-time ingest "
                    "a real default train amortizes)",
        "gbt_resident": "131072 rows x 100 trees (since r5; was x 32 — "
                        "100 = the default TreeNum)",
        "tail": "65536 rows x 4 trees, budget forces disk tail (uint8-"
                "resident bins accounting since r6; warm pass builds the "
                "mmap spill cache, tail sweeps re-read it zero-decode; "
                "learnable logit target + dual-schedule c2f/exact "
                "reporting since r9)"}
    extras["baselines"] = {
        "tree_rows_trees_per_sec_per_worker":
            MEASURED_CPU_TREE_ROWS_TREES_PER_SEC,
        "stats_rows_per_sec_per_worker":
            MEASURED_CPU_STATS_ROWS_PER_SEC,
        "score_rows_per_sec_per_worker": MEASURED_CPU_SCORE_ROWS_PER_SEC,
        "cluster_workers": BASELINE_CLUSTER_WORKERS,
        "provenance": "tools/measure_baseline.py on this rig (BASELINE.md)",
    }
    return {
        "metric": "nn_train_throughput",
        "value": round(nn_rows_per_sec, 1),
        "unit": "rows/sec",
        "telemetry_schema_version": BENCH_TELEMETRY_SCHEMA,
        "vs_baseline": round(nn_rows_per_sec / BASELINE_ROWS_PER_SEC, 3),
        "baseline_rows_per_sec": BASELINE_ROWS_PER_SEC,
        "baseline_provenance": "measured 28850.5 rows/s/worker f64 backprop "
                               "on this rig x 100 north-star workers "
                               "(BASELINE.md, tools/measure_baseline.py)",
        # timing is a value-forcing fetch around ONE scanned executable
        # per window (steps fused via lax.scan), best of 3 windows
        "harness": {"matmul_precision": "bfloat16",
                    "timing": "value-forced, scanned steps; best-of-3 (NN/"
                              "WDL long windows) / best-of-5 (sub-second "
                              "windows)",
                    "since_round": 3},
        "extra": extras,
    }
