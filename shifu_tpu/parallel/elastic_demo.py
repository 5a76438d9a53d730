"""Self-contained elastic-training demo controller — one process of an
N-controller quorum-gated NN job over a shared control-plane directory.

``tests/test_multihost.py`` launches this module as a subprocess per
controller::

    python -m shifu_tpu.parallel.elastic_demo --out DIR --proc I --nproc N

Each controller deterministically regenerates the SAME global dataset,
takes its contiguous row block (its "shard files"), trains the streamed
NN ensemble with the elastic step protocol (``parallel/elastic``), and
commits ``result-<proc>.json`` + ``params-<proc>.npz`` into ``--out``
so the caller can compare controllers bit-for-bit and read the AUC.
The cross-process combine rides the ``telemetry/steps/`` control plane
only — no jax.distributed, no cross-process collectives — which is the
point: this path works (and tests) on jaxlib builds without gloo.

A fault spec in ``SHIFU_TPU_FAULTS`` (e.g. ``dcn:step=3:kill``) turns a
controller into the worker-loss drill; relaunching it with the same
``--proc`` exercises the journal-backed rejoin.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _force_small_cpu() -> None:
    """Under ``JAX_PLATFORMS=cpu`` (what the tests export), pin the demo
    to 2 virtual CPU devices, replacing any inherited count (the test
    suite exports 8).  Any other platform is
    taken as given — the launcher decides which devices a controller
    owns (``parallel.mesh.refuse_children_on_chip``)."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=2")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def _auc(scores, y) -> float:
    """Rank-based ROC AUC (ties get average rank)."""
    import numpy as np
    scores = np.asarray(scores, np.float64)
    y = np.asarray(y) > 0.5
    order = np.argsort(scores, kind="mergesort")
    ranks = np.empty(len(scores), np.float64)
    ranks[order] = np.arange(1, len(scores) + 1, dtype=np.float64)
    # average tied ranks
    s_sorted = scores[order]
    i = 0
    while i < len(s_sorted):
        j = i
        while j + 1 < len(s_sorted) and s_sorted[j + 1] == s_sorted[i]:
            j += 1
        if j > i:
            ranks[order[i:j + 1]] = ranks[order[i:j + 1]].mean()
        i = j + 1
    npos = int(y.sum())
    nneg = len(y) - npos
    if npos == 0 or nneg == 0:
        return 0.5
    return float((ranks[y].sum() - npos * (npos + 1) / 2) / (npos * nneg))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", required=True,
                    help="shared job dir (control plane + results)")
    ap.add_argument("--proc", type=int, required=True)
    ap.add_argument("--nproc", type=int, required=True)
    ap.add_argument("--rows", type=int, default=4096,
                    help="GLOBAL row count (each controller owns 1/nproc)")
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--window", type=int, default=0,
                    help="stream window rows (0 = local rows / 2)")
    ap.add_argument("--quorum-frac", type=float, default=None)
    ap.add_argument("--timeout-ms", type=float, default=None)
    ap.add_argument("--staleness", type=int, default=None)
    args = ap.parse_args(argv)
    from .. import compile_cache
    compile_cache.configure()
    _force_small_cpu()

    import numpy as np

    from ..config import environment
    environment.set_property("shifu.dcn.elastic", "true")
    if args.quorum_frac is not None:
        environment.set_property("shifu.dcn.quorumFrac", args.quorum_frac)
    if args.timeout_ms is not None:
        environment.set_property("shifu.dcn.stepTimeoutMs",
                                 args.timeout_ms)
    if args.staleness is not None:
        environment.set_property("shifu.dcn.staleness", args.staleness)

    t_start = time.time()
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)

    # ---- the SAME global dataset on every controller (seeded), each
    # owning a contiguous row block — its "shard files"
    rng = np.random.default_rng(11)
    D = args.features
    x_all = rng.normal(size=(args.rows, D)).astype(np.float32)
    wvec = (rng.normal(size=D) / np.sqrt(D)).astype(np.float32)
    y_all = (1.0 / (1.0 + np.exp(-(x_all @ wvec) * 3))
             > rng.random(args.rows)).astype(np.float32)
    per = args.rows // args.nproc
    lo, hi = args.proc * per, (args.proc + 1) * per
    ddir = os.path.join(out, f"data-{args.proc}")
    os.makedirs(ddir, exist_ok=True)

    from .. import ioutil
    ioutil.atomic_savez(os.path.join(ddir, "part-00000.npz"),
                        x=x_all[lo:hi], y=y_all[lo:hi],
                        w=np.ones(hi - lo, np.float32))
    ioutil.atomic_write_json(os.path.join(ddir, "schema.json"), {
        "outputNames": [f"c{i}" for i in range(D)],
        "columnNums": list(range(D)), "numShards": 1, "numRows": hi - lo})

    from ..data.shards import Shards
    from ..data.streaming import ShardStream, mask_fn_from_settings
    from ..models.nn import NNModelSpec
    from ..parallel.elastic import ElasticContext
    from ..parallel.mesh import device_mesh
    from ..train.nn_trainer import TrainSettings, train_ensemble_streamed

    mesh = device_mesh(n_ensemble=1)
    data_size = int(mesh.shape["data"])
    window = args.window or max(data_size, (hi - lo) // 2)
    window -= window % data_size
    stream = ShardStream(Shards.open(ddir), ("x", "y", "w"), window)
    spec = NNModelSpec(input_dim=D, hidden_nodes=[8],
                       activations=["tanh"], loss="log")
    settings = TrainSettings(optimizer="ADAM", learning_rate=0.05,
                             epochs=args.epochs, batch_size=0, seed=7)
    mask_fn = mask_fn_from_settings(1, valid_rate=0.25, seed=7)

    ctx = ElasticContext(out, proc=f"ctrl-{args.proc}").start()
    # a FRESH job starts together: the first controller up would see
    # itself as the only live member and close the opening steps alone
    # (a lone survivor always proceeds), so results would depend on
    # process start skew.  A rejoiner never waits — the job is moving.
    deadline = time.time() + 120.0
    while not ctx.rejoined and ctx.board.last_closed_step() < 0 \
            and len(ctx.board.members()) < args.nproc \
            and time.time() < deadline:
        time.sleep(0.05)
    t_train = time.time()
    try:
        res = train_ensemble_streamed(stream, spec, settings, 1, mask_fn,
                                      mesh=mesh, elastic=ctx)
    except BaseException:
        ctx.stop(exit_code=1)
        raise
    train_s = time.time() - t_train
    dcn_stats = {"rejoined": ctx.rejoined, "incarnation": ctx.incarnation,
                 "catchup_steps": ctx.catchup_steps,
                 "steps_closed": ctx.steps_closed,
                 "step_timeouts": ctx.step_timeouts,
                 "late_applied": ctx.late_applied,
                 "late_dropped": ctx.late_dropped}
    ctx.stop(exit_code=0)

    # ---- results: bit-comparable params + an AUC on the GLOBAL plane
    import jax.numpy as jnp

    from ..models.nn import forward
    params = res.params[0]
    flat = {f"l{i}_{k}": np.asarray(layer[k])
            for i, layer in enumerate(params) for k in ("w", "b")}
    ioutil.atomic_savez(os.path.join(out, f"params-{args.proc}.npz"),
                        **flat)
    scores = np.asarray(forward(params, spec, jnp.asarray(x_all)))[:, 0]
    auc = _auc(scores, y_all)
    checksum = float(sum(np.abs(v).sum() for v in flat.values()))

    ioutil.atomic_write_json(os.path.join(out,
                                          f"result-{args.proc}.json"), {
        "proc": args.proc, "checksum": checksum, "auc": round(auc, 6),
        "epochs_run": res.epochs_run,
        "history": [[round(a, 6), round(b, 6)] for a, b in res.history],
        "dcn": dcn_stats, "wall_s": round(time.time() - t_start, 3),
        "train_s": round(train_s, 3), "rows_local": hi - lo,
        "window": window})
    print(f"ELASTIC-OK proc={args.proc} checksum={checksum:.8f} "
          f"auc={auc:.4f} catchup={dcn_stats['catchup_steps']} "
          f"rejoined={int(dcn_stats['rejoined'])}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
