"""Distributed NN/LR ensemble trainer — the Guagua BSP loop + bagging job
fan-out as ONE jitted SPMD program.

Reference mapping:
- Guagua iteration (workers sum gradients over their shard → master applies
  ``Weight`` update → broadcast): one full-batch jitted step over a row-
  sharded dataset; XLA's psum over the ``data`` mesh axis IS the master
  accumulate (``NNMaster.java:207-319``, ``AbstractNNWorker.java:521-588``).
- N bagging / k-fold / grid-like jobs (``TrainModelProcessor.java:684-945``):
  ensemble members stacked on a leading axis, trained by ``vmap`` and sharded
  over the ``ensemble`` mesh axis — every "job" advances each step.
- Full-batch per epoch matches the reference exactly (each Guagua iteration
  consumes every row once; RPROP — their default — requires it).  An optional
  mini-batch mode serves ADAM-style rules.
- Early stop windows, LR decay, per-epoch progress lines, and tmp-model
  checkpoints mirror ``NNMaster``/``NNOutput`` behavior host-side.
"""

from __future__ import annotations

import copy
import json
import logging
import time
from dataclasses import asdict, dataclass, field
from functools import lru_cache, partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import compile_cache, obs
from ..data.staging import RowLayout
from ..models import nn as nn_model
from ..parallel import mesh as meshlib
from .early_stop import WindowEarlyStop
from .optimizers import (cast_tree, make_optimizer, mixed_apply,
                         mixed_init, resolve_precision)

log = logging.getLogger(__name__)


@dataclass
class TrainSettings:
    optimizer: str = "R"               # reference default Propagation=R (RPROP)
    learning_rate: float = 0.1
    learning_decay: float = 0.0        # per-epoch multiplicative decay
    l2: float = 0.0
    l1: float = 0.0
    dropout_rate: float = 0.0
    epochs: int = 100
    batch_size: int = 0                # 0 = full batch (reference semantics)
    early_stop_window: int = 0         # 0 = disabled
    weight_initializer: str = "xavier"
    seed: int = 0
    tmp_model_every: int = 0           # epochs between tmp-model checkpoints
    checkpoint_dir: str = ""           # "" disables trainer-state checkpoints
    checkpoint_every: int = 25
    resume: bool = False               # restore latest trainer state
    resume_extra: int = 0              # refresh warm-start: train N MORE
                                       # epochs past the restored state
                                       # (0 = plain resume, keep budget)
    fixed_layers: Tuple[int, ...] = () # 1-based layer ids frozen during
    fixed_bias: bool = False           # continuous training (NNMaster
    matmul_precision: str = ""         # FIXED_LAYERS); ""=backend default,
    precision: str = ""                # bfloat16=MXU.  precision: f32|
    opt_kwargs: Dict[str, Any] = field(default_factory=dict)  # bf16|mixed
                                       # ("" = shifu.train.precision)


def _resume_epoch_target(settings: "TrainSettings", start_epoch: int,
                         stops) -> int:
    """Epoch budget after a checkpoint restore.  A refresh warm-start
    (``resume_extra`` > 0) trains that many MORE epochs past the
    restored state — and re-opens the early-stop patience, because a
    stopper that tripped on the OLD distribution must not veto learning
    the new data window (best-model tracking still carries over).  A
    plain crash resume (``resume_extra`` == 0) keeps the original
    budget and stop state untouched."""
    if settings.resume_extra <= 0:
        return settings.epochs
    for s in stops:
        s.since_best = 0
    return start_epoch + settings.resume_extra


@dataclass
class EnsembleResult:
    params: List[Any]                  # per-member best params (unstacked, host)
    train_errors: np.ndarray           # [bags] at best epoch
    valid_errors: np.ndarray           # [bags]
    epochs_run: int
    history: List[Tuple[float, float]]  # per-epoch (mean train, mean valid)


ProgressFn = Callable[[int, float, float], None]


def _stack(trees: List[Any]):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _gather_np(a) -> np.ndarray:
    """Host copy of a (possibly multi-host) array.  Under multiple
    controllers ``np.asarray`` can only read fully-addressable arrays;
    ``process_allgather`` assembles the global value over the DCN (the
    reference's master-side model collect, ``NNMaster.java:240-286``)."""
    import jax
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(a, tiled=True))
    return np.asarray(a)


def _pack_leaves_impl(leaves, mesh=None):
    """Flatten a tuple of 4-byte-dtype arrays into ONE f32 vector (bitcast,
    not convert — int leaves round-trip exactly).

    Each flat leaf is constrained to REPLICATED before the concatenate:
    this toolchain's partitioner mis-lowers a concatenate of
    ensemble-sharded flat vectors whose lengths don't divide the mesh —
    the output arrives as UNREDUCED partial sums (every value scaled by
    the data-axis size).  The explicit constraint forces the resharding
    BEFORE the concatenate, where it is a plain allgather."""
    if mesh is not None:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        rep = NamedSharding(mesh, P())
        return jnp.concatenate([
            jax.lax.with_sharding_constraint(
                jax.lax.bitcast_convert_type(l, jnp.float32).reshape(-1),
                rep)
            for l in leaves])
    return jnp.concatenate([
        jax.lax.bitcast_convert_type(l, jnp.float32).reshape(-1)
        for l in leaves])


@lru_cache(maxsize=None)
def _pack_leaves_meshed(mesh):
    """Single-controller packer pinned to ``mesh`` (see the partial-sum
    trap in :func:`_pack_leaves_impl`)."""
    # tiny packed-fetch glue (see _pack_leaves_impl): ~zero FLOPs,
    # shapes keyed by the lru_cache — sanctioned bare jit
    return jax.jit(partial(_pack_leaves_impl, mesh=mesh))  # shifu-lint: disable=recompile-hazard


_pack_leaves = jax.jit(_pack_leaves_impl)  # shifu-lint: disable=recompile-hazard


@lru_cache(maxsize=None)
def _pack_leaves_replicated(mesh):
    """Multi-controller :func:`_pack_leaves`: the REPLICATED out-sharding
    makes XLA fuse every leaf's cross-host allgather into the one packing
    program, after which each process reads its own addressable copy."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    return jax.jit(partial(_pack_leaves_impl, mesh=mesh),  # shifu-lint: disable=recompile-hazard
                   out_shardings=NamedSharding(mesh, P()))


def _to_host(tree):
    """Host copy of a whole pytree in ONE device fetch.  A per-leaf
    ``np.asarray`` walk costs one transfer per leaf — on a remote-device
    link at ~0.1-0.25 s per transfer, a WDL param tree (per-column
    embedding tables, ~70 leaves) made every epoch's best-params copy
    slower than the epoch's compute.  Leaves pack (bitcast) into one f32
    vector on device and split back on the host; multi-controller runs
    pack through :func:`_pack_leaves_replicated` (one program whose
    output every process holds) instead of the old per-leaf allgather
    walk."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves or any(l.dtype.itemsize != 4 for l in leaves):
        return jax.tree_util.tree_map(_gather_np, tree)
    shardings = [getattr(l, "sharding", None) for l in leaves]
    meshed = (all(hasattr(sh, "mesh") for sh in shardings)
              and len({sh.mesh for sh in shardings}) == 1)
    if jax.process_count() > 1:
        if not meshed:
            # heterogeneous/mesh-less leaves cannot ride one pinned
            # program — keep the conservative per-leaf gather for them
            return jax.tree_util.tree_map(_gather_np, tree)
        flat = np.asarray(
            _pack_leaves_replicated(shardings[0].mesh)(tuple(leaves)))
    elif meshed and shardings[0].mesh.size > 1:
        # mesh-sharded leaves take the constrained packer (see the
        # partial-sum trap in _pack_leaves_impl)
        flat = np.asarray(_pack_leaves_meshed(shardings[0].mesh)(
            tuple(leaves)))
    else:
        flat = np.asarray(_pack_leaves(tuple(leaves)))
    out, off = [], 0
    for l in leaves:
        size = int(np.prod(l.shape)) if l.shape else 1
        part = flat[off:off + size]
        off += size
        out.append(part.view(l.dtype).reshape(l.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def _unstack(tree, n: int) -> List[Any]:
    host = _to_host(tree)
    return [jax.tree_util.tree_map(lambda a: a[i], host) for i in range(n)]


# -------------------------------------------- trainer-state checkpointing
# The checkpoint must carry MORE than (params, opt_state, key): the final
# model is each member's BEST-epoch params, and early stop is a stateful
# window — dropping either made a resumed run pick a different model than
# the uninterrupted one whenever the global best predated the crash.
def _ckpt_template(stacked, opt_state, key, bags: int):
    zf = np.zeros(bags, np.float64)
    zi = np.zeros(bags, np.int64)
    return (stacked, opt_state, np.asarray(key), zf, zf.copy(), stacked,
            zf.copy(), zi)


def _ckpt_state(stacked, opt_state, key, best_valid, best_train,
                best_params, stops):
    host = _to_host(stacked)
    bp = [p if p is not None
          else jax.tree_util.tree_map(lambda a, i=i: a[i], host)
          for i, p in enumerate(best_params)]
    best_stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *bp)
    return (host, _to_host(opt_state), np.asarray(key),
            np.asarray(best_valid, np.float64),
            np.asarray(best_train, np.float64), best_stacked,
            np.asarray([s.best for s in stops], np.float64),
            np.asarray([s.since_best for s in stops], np.int64))


def _restore_tracking(state, best_valid, best_train, best_params,
                      stops) -> None:
    _, _, _, bv, bt, best_stacked, es_b, es_s = state
    best_valid[:] = bv
    best_train[:] = bt
    for i in range(len(best_params)):
        if np.isfinite(bv[i]):
            best_params[i] = jax.tree_util.tree_map(
                lambda a, i=i: a[i].copy(), best_stacked)
    for s, b, n in zip(stops, es_b, es_s):
        s.best = float(b)
        s.since_best = int(n)


def plane_layout(settings: TrainSettings, bags: int, mesh=None):
    """(mesh, minibatch rows, layout of x) of a resident job of ``bags``
    members.  x is sharded by rows over the mesh's data axis and ends in
    zero rows up to the minibatch (cut to a multiple of the data extent)
    when ``MiniBatchs`` is set, else up to the data extent: the one rule
    for the loader that builds x on the device (``Shards.load_all``'s
    ``on_device``) and for the trainer that pads everything else."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if mesh is None:
        mesh = meshlib.device_mesh(n_ensemble=bags)
    data_size = mesh.shape["data"]
    bs = settings.batch_size
    if bs:
        bs = max(bs - bs % data_size, data_size)
    return mesh, bs, RowLayout(NamedSharding(mesh, P("data", None)),
                               bs or data_size)


def _make_nn_programs(spec: nn_model.NNModelSpec, settings: TrainSettings,
                      precision: str, uniform: bool, dropout: bool,
                      y_axis: Optional[int]):
    """(step, epoch_steps, eval_errors), made anew: what
    :func:`_train_ensemble_impl` holds for the process's next job.  A
    function of its own, so that the closures capture the values read here
    (a copy of the spec, the update rule, the frozen layers, the uniform
    regularisers) and nothing of a job: its planes, parameters and hyper
    rows are arguments."""
    spec = copy.deepcopy(spec)
    opt = make_optimizer(settings.optimizer, settings.learning_rate,
                         **settings.opt_kwargs)
    l2, l1, rate = settings.l2, settings.l1, settings.dropout_rate
    fixed, fixed_bias = set(settings.fixed_layers), settings.fixed_bias

    def _freeze(delta):
        """Zero deltas of fixed layers (reference FIXED_LAYERS /
        FIXED_BIAS: frozen weights during continuous training; 1-based
        layer ids)."""
        if not fixed:
            return delta
        return [dl if (li + 1) not in fixed else
                {"w": jnp.zeros_like(dl["w"]),
                 "b": jnp.zeros_like(dl["b"]) if fixed_bias
                 else dl["b"]}
                for li, dl in enumerate(delta)]

    def member_update(params, opt_state, xb, yb, mw, rng, h, lr_scale):
        loss, grads = jax.value_and_grad(nn_model.weighted_loss)(
            params, spec, xb, yb[:, None], mw,
            l2=l2 if uniform else h[1],
            l1=l1 if uniform else h[2],
            dropout_rate=rate if uniform else h[3],
            rng=rng if dropout else None)
        if precision == "mixed":
            # bf16 grads widen once; the rule steps the f32 master and
            # the bf16 training copy is one rounding of it
            params, opt_state = mixed_apply(opt, grads, opt_state,
                                            scale=lr_scale * h[0],
                                            freeze=_freeze)
            return params, opt_state, loss
        delta, opt_state = opt.update(grads, opt_state, params)
        # apply in the PARAM dtype: the f32-strong lr_scale tracer would
        # otherwise silently widen a bf16 ladder back to f32 (no-op for
        # f32 params)
        params = jax.tree_util.tree_map(
            lambda p, d: p + (d * (lr_scale * h[0])).astype(p.dtype),
            params, _freeze(delta))
        return params, opt_state, loss

    # cost-attributed entry points: the full-batch step, the scanned
    # epoch sweep and the eval pass are THE nn-plane executables the
    # utilization report joins against the TRAIN span (obs/costs).  Data
    # arrays and the [B, 4] hyper rows enter as ARGUMENTS: closing over a
    # multi-host-sharded array is an error under multiple controllers, and
    # a held program must keep no job's array alive
    @partial(obs.costed_jit, "nn.step")
    def step(stacked, opt_state, xb, yb, tw, rngs, hd, lr_scale):
        return jax.vmap(member_update,
                        in_axes=(0, 0, None, y_axis, 0, 0, 0, None))(
            stacked, opt_state, xb, yb, tw, rngs, hd, lr_scale)

    @partial(obs.costed_jit, "nn.eval_errors")
    def eval_errors(stacked, tw, vw, xe, ys):
        def one(params, mw, ym):
            pred = nn_model.forward(params, spec, xe)
            per_row = nn_model.per_row_loss(pred, ym[:, None], spec)
            return (per_row * mw).sum() / jnp.maximum(mw.sum(), 1e-9)
        ev = jax.vmap(one, in_axes=(0, 0, y_axis))
        return ev(stacked, tw, ys), ev(stacked, vw, ys)

    # batch slicing happens INSIDE jit (dynamic_slice of sharded arrays
    # compiles into the SPMD program); an EAGER lax.slice on sharded inputs
    # does ad-hoc device-to-device copies the XLA:CPU runtime has been seen
    # to SIGABRT on
    def step_batch(stacked, opt_state, start, rngs, hd, lr_scale, blen: int,
                   xe, ye, twe):
        xb = jax.lax.dynamic_slice_in_dim(xe, start, blen, axis=0)
        yb = jax.lax.dynamic_slice_in_dim(ye, start, blen, axis=0) \
            if y_axis is None else \
            jax.lax.dynamic_slice_in_dim(ye, start, blen, axis=1)
        twb = jax.lax.dynamic_slice_in_dim(twe, start, blen, axis=1)
        return jax.vmap(member_update,
                        in_axes=(0, 0, None, y_axis, 0, 0, 0, None))(
            stacked, opt_state, xb, yb, twb, rngs, hd, lr_scale)

    @partial(obs.costed_jit, "nn.epoch_steps",
             static_argnames=("blen", "n_b"))
    def epoch_steps(stacked, opt_state, rngs, hd, lr_scale, xe, ye, twe,
                    blen: int, n_b: int):
        """A whole epoch's minibatch sweep as ONE executable (lax.scan over
        batches) — the per-batch dispatch loop costs one program execution
        per batch, which dominates wall-clock on a remote-device link."""
        def body(carry, bi):
            st, os_ = carry
            rngs_b = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                rngs, bi) if dropout else rngs
            st, os_, _ = step_batch(st, os_, bi * blen, rngs_b, hd, lr_scale,
                                    blen, xe, ye, twe)
            return (st, os_), None
        (st, os_), _ = jax.lax.scan(body, (stacked, opt_state),
                                    jnp.arange(n_b, dtype=jnp.int32))
        return st, os_

    return step, epoch_steps, eval_errors


def nn_programs_key(spec: nn_model.NNModelSpec, settings: TrainSettings,
                    precision: str, uniform: bool, dropout: bool,
                    y_axis: Optional[int], mesh) -> tuple:
    """Everything :func:`_make_nn_programs`' closures read or are
    specialised to.  The spec holds lists, so its canonical JSON stands for
    it; the regularisers are constants of the programs on the uniform path
    only (stacked grid trials carry theirs in the hyper rows); the mesh
    names the shardings the programs see; telemetry is in it because
    ``obs.costed_jit`` wraps differently with it on.  The row count and the
    minibatch are shapes and static arguments, which jax keys per
    callable."""
    return ("nn", json.dumps(asdict(spec), sort_keys=True, default=repr),
            str(settings.optimizer or "R").upper(),
            float(settings.learning_rate),
            json.dumps(settings.opt_kwargs, sort_keys=True, default=repr),
            (float(settings.l2), float(settings.l1),
             float(settings.dropout_rate)) if uniform else None,
            precision, tuple(sorted(set(settings.fixed_layers))),
            bool(settings.fixed_bias), uniform, dropout, y_axis,
            settings.matmul_precision, mesh, obs.enabled())


def train_ensemble(x: np.ndarray, y: np.ndarray,
                   train_w: np.ndarray, valid_w: np.ndarray,
                   spec: nn_model.NNModelSpec,
                   settings: TrainSettings,
                   init_params_list: Optional[List[Any]] = None,
                   progress: Optional[ProgressFn] = None,
                   checkpoint: Optional[Callable[[int, List[Any]],
                                                 None]] = None,
                   mesh=None,
                   y_members: Optional[np.ndarray] = None,
                   member_hypers: Optional[Dict[str, np.ndarray]] = None
                   ) -> EnsembleResult:
    """See :func:`_train_ensemble_impl`; wraps it in the configured matmul
    precision (bfloat16 inputs with f32 accumulation feed the MXU at full
    rate — the training math stays f32 elsewhere)."""
    if settings.matmul_precision:
        with jax.default_matmul_precision(settings.matmul_precision):
            return _train_ensemble_impl(
                x, y, train_w, valid_w, spec, settings, init_params_list,
                progress, checkpoint, mesh, y_members, member_hypers)
    return _train_ensemble_impl(
        x, y, train_w, valid_w, spec, settings, init_params_list,
        progress, checkpoint, mesh, y_members, member_hypers)


def _train_ensemble_impl(x: np.ndarray, y: np.ndarray,
                   train_w: np.ndarray, valid_w: np.ndarray,
                   spec: nn_model.NNModelSpec,
                   settings: TrainSettings,
                   init_params_list: Optional[List[Any]] = None,
                   progress: Optional[ProgressFn] = None,
                   checkpoint: Optional[Callable[[int, List[Any]], None]] = None,
                   mesh=None,
                   y_members: Optional[np.ndarray] = None,
                   member_hypers: Optional[Dict[str, np.ndarray]] = None
                   ) -> EnsembleResult:
    """Train ``B`` members; ``train_w``/``valid_w`` are ``[B, N]`` per-row
    weight matrices (bagging/fold masks × data weights).

    ``y_members`` ([B, N]) gives each member its OWN target — the one-vs-all
    fan-out (reference ``TrainModelProcessor.java:684-714`` runs one bagging
    job per class; here classes are members on the ensemble axis, trained
    simultaneously as one vmapped program).

    ``member_hypers`` gives each member its OWN scalar hypers ([B] arrays
    under keys ``lr_scale``/``l2``/``l1``/``dropout``) — how same-shape
    grid-search trials train as ONE compiled run instead of the reference's
    queue of jobs (``gs/GridSearch.java:62``).

    ``x`` may be on the device already, in :func:`plane_layout`'s layout
    (the resident loader put it there piece by piece): then only the
    small arrays go up here."""
    bags = train_w.shape[0]
    n = y.shape[0]
    from jax.sharding import NamedSharding, PartitionSpec as P
    with obs.span("nn.init", members=bags) as sp:
        mesh, bs, x_layout = plane_layout(settings, bags, mesh)
        key = jax.random.PRNGKey(settings.seed)
        if init_params_list is None:
            keys = jax.random.split(key, bags)
            init_params_list = [
                nn_model.init_params(k, spec, settings.weight_initializer)
                for k in keys]
        opt = make_optimizer(settings.optimizer, settings.learning_rate,
                             **settings.opt_kwargs)
        # ---- precision ladder (shifu.train.precision): bf16/mixed cast
        # the training params narrow; mixed keeps the f32 master in the
        # opt state
        precision = resolve_precision(settings.precision)
        if precision != "f32":
            init_params_list = [cast_tree(p, jnp.bfloat16)
                                for p in init_params_list]
        stacked = _stack(init_params_list)
        if precision == "mixed":
            opt_state = _stack([mixed_init(opt, p)
                                for p in init_params_list])
        else:
            opt_state = _stack([opt.init(p) for p in init_params_list])
        sh_ens = NamedSharding(mesh, P("ensemble"))
        stacked = jax.device_put(stacked, sh_ens)
        opt_state = jax.device_put(opt_state, sh_ens)

        # per-member hyper rows [B, 4]: lr_scale, l2, l1, dropout — uniform
        # from settings unless stacked grid trials supplied their own
        if member_hypers is None:
            hyp = np.tile(np.asarray(
                [[1.0, settings.l2, settings.l1, settings.dropout_rate]],
                np.float32), (bags, 1))
        else:
            hyp = np.stack([
                np.asarray(member_hypers.get("lr_scale", np.ones(bags)),
                           np.float32),
                np.asarray(member_hypers.get("l2",
                                             np.full(bags, settings.l2)),
                           np.float32),
                np.asarray(member_hypers.get("l1",
                                             np.full(bags, settings.l1)),
                           np.float32),
                np.asarray(member_hypers.get(
                    "dropout", np.full(bags, settings.dropout_rate)),
                    np.float32)], axis=1)
        dropout = float(hyp[:, 3].max()) > 0   # static gate: any member drops?
        uniform = member_hypers is None
        y_axis = None if y_members is None else 0  # per-member targets vmap over B
        # the programs outlive the job: an equal key finds them held
        # (compile_cache.PROGRAMS), built by the process's last job
        programs, reused = compile_cache.PROGRAMS.programs(
            nn_programs_key(spec, settings, precision, uniform, dropout,
                            y_axis, mesh),
            partial(_make_nn_programs, spec, settings, precision, uniform,
                    dropout, y_axis))
        step, epoch_steps, eval_errors = programs
        if reused:
            obs.counter("nn.programs_reused").inc()
        sp.set(programs_built=0 if reused else len(programs))

    on_device = isinstance(x, jax.Array)
    if on_device and x.shape[0] != n + meshlib.pad_rows(n, x_layout.multiple):
        # laid out for another job than this one: the host path
        x, on_device = np.asarray(x)[:n], False
    with obs.span("nn.h2d") as sp:
        # the final row multiple is known before the upload: one pad on
        # the host, one device_put; nothing of the plane comes back.
        # padded rows carry zero weight, so the tail is never dropped;
        # per-member targets (one-vs-all) fold through the same padding
        # (_pad_all returns them only when given: zip stops there)
        sh_members = NamedSharding(mesh, P("ensemble", "data"))
        plane = [jax.device_put(a, sh) for a, sh in zip(
            _pad_all(x, y, train_w, valid_w, x_layout.multiple, y_members),
            (x_layout.sharding, NamedSharding(mesh, P("data")),
             sh_members, sh_members, sh_members))]
        # shapes only: neither attr costs a sync.  x that came on the
        # device is not among the bytes sent here
        sp.set(bytes=sum(a.nbytes for a in (plane[1:] if on_device
                                            else plane)),
               pad_rows=plane[0].shape[0] - n)
    xd, yd, twd, vwd, ymd = (*plane, None)[:5]
    hd = jax.device_put(hyp, sh_ens)

    stops = [WindowEarlyStop(settings.early_stop_window) for _ in range(bags)]
    best_valid = np.full(bags, np.inf)
    best_train = np.full(bags, np.inf)
    best_params: List[Any] = [None] * bags
    history: List[Tuple[float, float]] = []
    lr_scale = 1.0
    epochs_run = 0
    tr = va = np.zeros(bags)

    start_epoch = 0
    epochs_target = settings.epochs
    if settings.resume and settings.checkpoint_dir:
        from . import checkpoint as ckpt
        restored = ckpt.restore_state(
            settings.checkpoint_dir,
            _ckpt_template(stacked, opt_state, key, bags),
            expect_precision=precision)
        if restored is not None:
            start_epoch, state = restored
            stacked = jax.device_put(state[0], sh_ens)
            opt_state = jax.device_put(state[1], sh_ens)
            key = jnp.asarray(state[2])
            _restore_tracking(state, best_valid, best_train, best_params,
                              stops)
            lr_scale = (1.0 - settings.learning_decay) ** start_epoch \
                if settings.learning_decay > 0 else 1.0
            epochs_target = _resume_epoch_target(settings, start_epoch,
                                                 stops)
            log.info("resumed trainer state at epoch %d (target %d)",
                     start_epoch, epochs_target)
            if settings.early_stop_window > 0 and \
                    all(s.since_best >= s.window_size for s in stops):
                # the interrupted run had already early-stopped — don't
                # grow past its stop point
                start_epoch = epochs_target

    n_padded = xd.shape[0]

    obs_on = obs.enabled()
    for epoch in range(start_epoch, epochs_target):
        with obs.span("nn.epoch", epoch=epoch):
            ep_t0 = time.perf_counter()
            with obs.span("nn.epoch.dispatch"):
                key, sub = jax.random.split(key)
                rngs = jax.random.split(sub, bags)
                if bs and bs < n_padded:
                    stacked, opt_state = epoch_steps(
                        stacked, opt_state, rngs, hd, lr_scale, xd,
                        yd if ymd is None else ymd, twd, bs,
                        (n_padded - bs) // bs + 1)
                else:
                    stacked, opt_state, _ = step(
                        stacked, opt_state, xd,
                        yd if ymd is None else ymd, twd, rngs, hd,
                        lr_scale)
                tr, va = eval_errors(stacked, twd, vwd, xd,
                                     yd if ymd is None else ymd)
                packed = jnp.stack([tr, va])
            with obs.span("nn.epoch.fetch"):
                tr, va = _gather_np(packed)            # one fetch
            history.append((float(tr.mean()), float(va.mean())))
            epochs_run = epoch + 1
            if obs_on:
                # host-side per-epoch metrics: the _gather_np fetch above
                # IS the value-forcing sync, so the wall-clock covers real
                # work
                dt = time.perf_counter() - ep_t0
                obs.counter("train.epochs").inc()
                obs.histogram("train.epoch_s").observe(dt)
                obs.gauge("train.valid_err").set(float(va.mean()))
                obs.event("epoch", trainer="nn", epoch=epoch,
                          train_err=round(float(tr.mean()), 6),
                          valid_err=round(float(va.mean()), 6), rows=n,
                          rows_per_sec=round(n / max(dt, 1e-9), 1))

            improved = np.flatnonzero(va < best_valid)
            if improved.size:
                with obs.span("nn.epoch.best_copy", members=improved.size):
                    host = _to_host(stacked)
                    for i in improved:
                        best_valid[i], best_train[i] = va[i], tr[i]
                        best_params[i] = jax.tree_util.tree_map(
                            lambda a: a[i].copy(), host)
            if progress:
                with obs.span("nn.epoch.progress"):
                    progress(epoch, float(tr.mean()), float(va.mean()))
            if checkpoint and settings.tmp_model_every and \
                    (epoch + 1) % settings.tmp_model_every == 0:
                with obs.span("nn.epoch.checkpoint"):
                    checkpoint(epoch, _unstack(stacked, bags))
            if settings.learning_decay > 0:
                lr_scale *= (1.0 - settings.learning_decay)
            stop_now = False
            if settings.early_stop_window > 0:
                # evaluate every member's window (no short-circuit: the
                # stop counters must advance uniformly) then stop when all
                # agree
                flags = [s.should_stop(float(v)) for s, v in zip(stops, va)]
                stop_now = all(flags)
            if settings.checkpoint_dir and settings.checkpoint_every and \
                    ((epoch + 1) % settings.checkpoint_every == 0
                     or stop_now):
                # saved AFTER the early-stop windows advanced (and forced
                # on the stop epoch): a resumed run replays the exact stop
                # state
                from . import checkpoint as ckpt
                with obs.span("nn.epoch.checkpoint"):
                    ckpt.save_state(settings.checkpoint_dir, epoch + 1,
                                    _ckpt_state(stacked, opt_state, key,
                                                best_valid, best_train,
                                                best_params, stops),
                                    precision=precision)
        if stop_now:
            obs.event("early_stop", trainer="nn", epoch=epoch,
                      window=settings.early_stop_window)
            log.info("early stop at epoch %d (window %d)", epoch,
                     settings.early_stop_window)
            break

    final = _to_host(stacked)
    for i in range(bags):
        if best_params[i] is None:
            best_params[i] = jax.tree_util.tree_map(lambda a: a[i], final)
            best_valid[i], best_train[i] = float(va[i]), float(tr[i])
    return EnsembleResult(params=best_params, train_errors=best_train,
                          valid_errors=best_valid, epochs_run=epochs_run,
                          history=history)


def _pad_all(x, y, train_w, valid_w, multiple, y_members=None):
    """Zero rows appended up to ``multiple``; an ``x`` on the device has
    them already."""
    extra = meshlib.pad_rows(y.shape[0], multiple)
    if extra:
        if not isinstance(x, jax.Array):
            x = np.concatenate([x, np.zeros((extra, x.shape[1]), x.dtype)])
        y = np.concatenate([y, np.zeros(extra, y.dtype)])
        zpad = np.zeros((train_w.shape[0], extra), train_w.dtype)
        train_w = np.concatenate([train_w, zpad], axis=1)
        valid_w = np.concatenate([valid_w, zpad], axis=1)
        if y_members is not None:
            y_members = np.concatenate(
                [y_members, np.zeros((y_members.shape[0], extra),
                                     y_members.dtype)], axis=1)
    if y_members is not None:
        return x, y, train_w, valid_w, y_members
    return x, y, train_w, valid_w


# ------------------------------------------------------------- streaming
def train_ensemble_streamed(stream, spec: nn_model.NNModelSpec,
                            settings: TrainSettings, bags: int, mask_fn,
                            init_params_list: Optional[List[Any]] = None,
                            progress: Optional[ProgressFn] = None,
                            checkpoint: Optional[Callable[[int, List[Any]],
                                                          None]] = None,
                            mesh=None,
                            member_classes: Optional[List[int]] = None,
                            elastic=None) -> EnsembleResult:
    """See :func:`_train_ensemble_streamed_impl`; precision wrapper as in
    :func:`train_ensemble`."""
    if settings.matmul_precision:
        with jax.default_matmul_precision(settings.matmul_precision):
            return _train_ensemble_streamed_impl(
                stream, spec, settings, bags, mask_fn, init_params_list,
                progress, checkpoint, mesh, member_classes, elastic)
    return _train_ensemble_streamed_impl(
        stream, spec, settings, bags, mask_fn, init_params_list,
        progress, checkpoint, mesh, member_classes, elastic)


def _train_ensemble_streamed_impl(stream, spec: nn_model.NNModelSpec,
                            settings: TrainSettings, bags: int, mask_fn,
                            init_params_list: Optional[List[Any]] = None,
                            progress: Optional[ProgressFn] = None,
                            checkpoint: Optional[Callable[[int, List[Any]], None]] = None,
                            mesh=None,
                            member_classes: Optional[List[int]] = None,
                            elastic=None) -> EnsembleResult:
    """Out-of-core ensemble training: one pass over ``stream.windows()`` per
    epoch, dataset never resident anywhere (the
    ``MemoryDiskFloatMLDataSet.java`` role, done the streaming-SPMD way).

    Full-batch semantics (RPROP & friends) hold exactly: per-window
    UNNORMALIZED gradient sums accumulate on device across windows; the
    optimizer applies once per epoch on ``sum(grads)/sum(weights)`` plus the
    regularizer — bit-for-bit the math of :func:`train_ensemble` up to fp
    reassociation.  With ``settings.batch_size > 0`` each window instead
    yields minibatch updates (ADAM-style), like the reference's in-epoch
    iteration.

    ``mask_fn(global_row_index, targets) -> (train_w, valid_w)`` supplies
    each window's ``[bags, rows]`` sampling masks (see
    ``data.streaming.window_member_masks``); they are multiplied by the data
    weight column inside.

    Reported errors for epoch e are measured during pass e+1 (same params,
    one pass later) so each epoch streams the data once, not twice; a final
    eval-only pass closes the ledger.  Early stop therefore lags one epoch.

    ``elastic`` (a :class:`parallel.elastic.ElasticContext`) switches the
    CROSS-PROCESS combine from the in-mesh psum to the quorum-gated step
    protocol: each controller streams its OWN shard set on its LOCAL
    mesh, per-epoch unnormalized grad sums + eval stat sums post as one
    contribution, and the epoch's update applies the committed quorum
    aggregate (summed in sorted-controller order — every survivor steps
    the same bits).  An epoch whose close record already exists is
    REPLAYED from the journal without streaming (rejoin catch-up).
    Elastic transport is f32; full-batch mode only.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    if elastic is not None and settings.batch_size != 0:
        raise ValueError("elastic multi-controller training requires the "
                         "full-batch streamed mode (batch_size=0): the "
                         "quorum step protocol closes once per epoch")
    if mesh is None:
        mesh = meshlib.device_mesh(n_ensemble=bags)
    data_size = mesh.shape["data"]
    assert stream.window_rows % data_size == 0, \
        f"window_rows {stream.window_rows} must divide data axis {data_size}"

    key = jax.random.PRNGKey(settings.seed)
    if init_params_list is None:
        keys = jax.random.split(key, bags)
        init_params_list = [nn_model.init_params(k, spec,
                                                 settings.weight_initializer)
                            for k in keys]
    opt = make_optimizer(settings.optimizer, settings.learning_rate,
                         **settings.opt_kwargs)
    precision = resolve_precision(settings.precision)
    if precision != "f32":
        init_params_list = [cast_tree(p, jnp.bfloat16)
                            for p in init_params_list]
    stacked = _stack(init_params_list)
    if precision == "mixed":
        opt_state = _stack([mixed_init(opt, p) for p in init_params_list])
    else:
        opt_state = _stack([opt.init(p) for p in init_params_list])
    sh_ens = NamedSharding(mesh, P("ensemble"))
    sh_x = NamedSharding(mesh, P("data", None))
    sh_y = NamedSharding(mesh, P("data"))
    sh_w = NamedSharding(mesh, P("ensemble", "data"))
    stacked = jax.device_put(stacked, sh_ens)
    opt_state = jax.device_put(opt_state, sh_ens)

    dropout = settings.dropout_rate
    l1, l2 = settings.l1, settings.l2

    def _loss_sum(params, xb, yb, mw, rng):
        pred = nn_model.forward(params, spec, xb,
                                dropout_rate=dropout,
                                rng=rng if dropout > 0 else None)
        return (nn_model.per_row_loss(pred, yb[:, None], spec) * mw).sum()

    def _eval_sums(params, xb, yb, mw, vw):
        pred = nn_model.forward(params, spec, xb)
        per_row = nn_model.per_row_loss(pred, yb[:, None], spec)
        return jnp.stack([(per_row * mw).sum(), mw.sum(),
                          (per_row * vw).sum(), vw.sum()])

    # OVA fan-out (``member_classes``): member m binarizes the shared
    # class-id window against its OWN class on device — the streamed
    # analogue of the in-RAM path's y_members (reference per-class jobs,
    # ``TrainModelProcessor.java:684-714``)
    cls_arr = None if member_classes is None else \
        jnp.asarray(member_classes, jnp.float32)

    # streamed nn-plane entry points, cost-attributed (obs/costs): the
    # per-window grad/eval programs are where streamed NN wall-clock goes
    @partial(obs.costed_jit, "nn.grad_eval_window")
    def grad_eval_window(stacked, grad_acc, stats_acc, xb, yb, tw, vw, rngs):
        def one(params, mw, vwm, rng, ci):
            ym = yb if cls_arr is None else (yb == ci).astype(yb.dtype)
            _, grads = jax.value_and_grad(_loss_sum)(params, xb, ym, mw, rng)
            return grads, _eval_sums(params, xb, ym, mw, vwm)
        cis = jnp.zeros(tw.shape[0]) if cls_arr is None else cls_arr
        grads, stats = jax.vmap(one)(stacked, tw, vw, rngs, cis)
        grad_acc = jax.tree_util.tree_map(jnp.add, grad_acc, grads)
        return grad_acc, stats_acc + stats

    @partial(obs.costed_jit, "nn.eval_window")
    def eval_window(stacked, stats_acc, xb, yb, tw, vw):
        def one(params, mw, vwm, ci):
            ym = yb if cls_arr is None else (yb == ci).astype(yb.dtype)
            return _eval_sums(params, xb, ym, mw, vwm)
        cis = jnp.zeros(tw.shape[0]) if cls_arr is None else cls_arr
        stats = jax.vmap(one)(stacked, tw, vw, cis)
        return stats_acc + stats

    @partial(obs.costed_jit, "nn.apply_update")
    def apply_update(stacked, opt_state, grad_acc, train_wsum, lr_scale):
        def one(params, ostate, grads, wsum):
            inv = 1.0 / jnp.maximum(wsum, 1e-9)
            g = [{"w": gl["w"] * inv + 2.0 * l2 * pl["w"]
                       + l1 * jnp.sign(pl["w"]),
                  "b": gl["b"] * inv}
                 for gl, pl in zip(grads, params)]
            if precision == "mixed":
                # accumulated-f32 grads step the f32 master; the bf16
                # training copy is one rounding of the new master
                return mixed_apply(opt, g, ostate, scale=lr_scale)
            delta, ostate = opt.update(g, ostate, params)
            params = jax.tree_util.tree_map(
                lambda p, d: p + (d * lr_scale).astype(p.dtype),
                params, delta)
            return params, ostate
        return jax.vmap(one)(stacked, opt_state, grad_acc, train_wsum)

    @partial(obs.costed_jit, "nn.minibatch_window",
             static_argnames=("blen",))
    def minibatch_window(stacked, opt_state, xw, yw, tww, rngs, lr_scale,
                         start, blen: int):
        # slice INSIDE jit: dynamic_slice of the sharded window compiles
        # into the SPMD program (an eager lax.slice would trigger ad-hoc
        # device copies the XLA:CPU runtime can SIGABRT on)
        xb = jax.lax.dynamic_slice_in_dim(xw, start, blen, axis=0)
        yb = jax.lax.dynamic_slice_in_dim(yw, start, blen, axis=0)
        tw = jax.lax.dynamic_slice_in_dim(tww, start, blen, axis=1)

        def one(params, ostate, mw, rng, ci):
            ym = yb if cls_arr is None else (yb == ci).astype(yb.dtype)
            def norm_loss(p):
                return _loss_sum(p, xb, ym, mw, rng) / jnp.maximum(mw.sum(), 1e-9) \
                    + l2 * sum((layer["w"] ** 2).sum() for layer in p) \
                    + l1 * sum(jnp.abs(layer["w"]).sum() for layer in p)
            grads = jax.grad(norm_loss)(params)
            if precision == "mixed":
                return mixed_apply(opt, grads, ostate, scale=lr_scale)
            delta, ostate = opt.update(grads, ostate, params)
            params = jax.tree_util.tree_map(
                lambda p, d: p + (d * lr_scale).astype(p.dtype),
                params, delta)
            return params, ostate
        cis = jnp.zeros(tw.shape[0]) if cls_arr is None else cls_arr
        return jax.vmap(one, in_axes=(0, 0, 0, 0, 0))(stacked, opt_state,
                                                      tw, rngs, cis)

    # mixed accumulates the cross-window gradient sums in f32 (bf16
    # accumulation over many windows loses low-order mass); jnp.add's
    # bf16+f32 promotion keeps the accumulator f32 per window
    zero_grads = jax.device_put(
        jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape,
                                jnp.float32 if precision == "mixed"
                                else a.dtype), stacked), sh_ens)

    if elastic is not None:
        from ..parallel.elastic import grad_codec
        _ravel_grads, _unravel_grads = grad_codec(zero_grads)

    full_batch = settings.batch_size == 0
    W = stream.window_rows
    if not full_batch:
        # sub-slice each window into ~batch_size minibatches (same update
        # granularity as the in-RAM loop); slice edges land on data_size
        # multiples so every slice shards cleanly — at most 2 distinct slice
        # shapes, so at most 2 compiles
        bs = max(settings.batch_size - settings.batch_size % data_size,
                 data_size)
        n_slices = max(1, W // bs)
        edges = [min(W, ((i * W // n_slices) // data_size) * data_size)
                 for i in range(n_slices)] + [W]
        slices = [(s, e) for s, e in zip(edges[:-1], edges[1:]) if e > s]
    stops = [WindowEarlyStop(settings.early_stop_window) for _ in range(bags)]
    best_valid = np.full(bags, np.inf)
    best_train = np.full(bags, np.inf)
    best_params: List[Any] = [None] * bags
    history: List[Tuple[float, float]] = []
    lr_scale = 1.0
    start_epoch = 0
    epochs_target = settings.epochs
    if settings.resume and settings.checkpoint_dir:
        from . import checkpoint as ckpt
        restored = ckpt.restore_state(
            settings.checkpoint_dir,
            _ckpt_template(stacked, opt_state, key, bags),
            expect_precision=precision)
        if restored is not None:
            start_epoch, state = restored
            stacked = jax.device_put(state[0], sh_ens)
            opt_state = jax.device_put(state[1], sh_ens)
            key = jnp.asarray(state[2])
            _restore_tracking(state, best_valid, best_train, best_params,
                              stops)
            lr_scale = (1.0 - settings.learning_decay) ** start_epoch \
                if settings.learning_decay > 0 else 1.0
            epochs_target = _resume_epoch_target(settings, start_epoch,
                                                 stops)
            log.info("resumed streamed trainer state at epoch %d "
                     "(target %d)", start_epoch, epochs_target)
            if settings.early_stop_window > 0 and \
                    all(s.since_best >= s.window_size for s in stops):
                start_epoch = epochs_target     # already early-stopped

    def put_window(win):
        xb = jax.device_put(win.arrays["x"].astype(np.float32), sh_x)
        yb = jax.device_put(win.arrays["y"].astype(np.float32), sh_y)
        tm, vm = mask_fn(win.index, win.arrays["y"])
        wcol = win.arrays["w"].astype(np.float32)
        if win.n_valid < win.rows:                 # zero out padded tail
            wcol = wcol.copy()
            wcol[win.n_valid:] = 0.0
        tw = jax.device_put(tm * wcol[None, :], sh_w)
        vw = jax.device_put(vm * wcol[None, :], sh_w)
        return xb, yb, tw, vw

    def bookkeep(epoch_done: int, stats: np.ndarray, params_snapshot) -> bool:
        """Record errors for ``epoch_done`` measured on ``params_snapshot``
        (device).  Returns True when every member's early-stop window fired."""
        tr = stats[:, 0] / np.maximum(stats[:, 1], 1e-9)
        va = stats[:, 2] / np.maximum(stats[:, 3], 1e-9)
        history.append((float(tr.mean()), float(va.mean())))
        improved = np.flatnonzero(va < best_valid)
        if improved.size:
            host = _to_host(params_snapshot)
            for i in improved:
                best_valid[i], best_train[i] = va[i], tr[i]
                best_params[i] = jax.tree_util.tree_map(
                    lambda a: a[i].copy(), host)
        if progress:
            progress(epoch_done, float(tr.mean()), float(va.mean()))
        obs.counter("train.epochs").inc()
        obs.event("epoch", trainer="nn_streamed", epoch=epoch_done,
                  train_err=round(float(tr.mean()), 6),
                  valid_err=round(float(va.mean()), 6),
                  rows=stream.num_rows)
        if settings.early_stop_window > 0:
            flags = [s.should_stop(float(v)) for s, v in zip(stops, va)]
            return all(flags)
        return False

    epochs_run = start_epoch
    stopped = False
    for epoch in range(start_epoch, epochs_target):
        key, sub = jax.random.split(key)
        rngs = jax.random.split(sub, bags)
        grad_flat = None
        params_entering = stacked   # params the epoch's stats are measured on
        replayed = elastic.closed_step(epoch) if elastic is not None \
            else None
        if replayed is not None:
            # rejoin catch-up: this epoch already closed across the job —
            # apply the committed aggregate (bit-identical to what the
            # survivors stepped) without streaming a single window
            stats = np.asarray(replayed.payload["stats"])
            grad_flat = replayed.payload["grads"]
        else:
            stats_acc = jnp.zeros((bags, 4))
            grad_acc = zero_grads
            n_win = 0
            for win in stream.windows():
                xb, yb, tw, vw = put_window(win)
                rngs_w = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                    rngs, n_win) if dropout > 0 else rngs
                if full_batch:
                    grad_acc, stats_acc = grad_eval_window(
                        stacked, grad_acc, stats_acc, xb, yb, tw, vw,
                        rngs_w)
                else:
                    stats_acc = eval_window(stacked, stats_acc, xb, yb,
                                            tw, vw)
                    for si, (s, e) in enumerate(slices):
                        rngs_s = jax.vmap(jax.random.fold_in,
                                          in_axes=(0, None))(
                            rngs_w, si) if dropout > 0 else rngs_w
                        stacked, opt_state = minibatch_window(
                            stacked, opt_state, xb, yb, tw, rngs_s,
                            lr_scale, jnp.int32(s), e - s)
                n_win += 1
            if n_win == 0:
                raise RuntimeError("streamed training: empty shard stream")
            if elastic is not None:
                # quorum-gated epoch close: local grad/stat sums post to
                # the control plane; everyone applies the SAME aggregate
                res = elastic.step(epoch, {
                    "grads": _ravel_grads(grad_acc),
                    "stats": np.asarray(stats_acc)})
                stats = np.asarray(res.payload["stats"])
                grad_flat = res.payload["grads"]
            else:
                stats = np.asarray(stats_acc)
        # stats were measured on the params entering this epoch => they close
        # the ledger of the PREVIOUS epoch (snapshot the matching params, not
        # the post-minibatch-update ones).  ``epoch > 0`` (not
        # ``> start_epoch``): a RESUMED epoch's stats close the ledger of
        # the last pre-crash epoch, which the checkpoint deliberately did
        # not record — skipping it would desync best-params tracking from
        # an uninterrupted run
        if epoch > 0:
            stopped = bookkeep(epoch - 1, stats, params_entering)
        if full_batch:
            stacked, opt_state = apply_update(
                stacked, opt_state,
                grad_acc if grad_flat is None else _unravel_grads(
                    grad_flat),
                jnp.asarray(stats[:, 1]), lr_scale)
        epochs_run = epoch + 1
        if checkpoint and settings.tmp_model_every and \
                (epoch + 1) % settings.tmp_model_every == 0:
            checkpoint(epoch, _unstack(stacked, bags))
        if settings.checkpoint_dir and settings.checkpoint_every and \
                ((epoch + 1) % settings.checkpoint_every == 0 or stopped):
            from . import checkpoint as ckpt
            ckpt.save_state(settings.checkpoint_dir, epoch + 1,
                            _ckpt_state(stacked, opt_state, key,
                                        best_valid, best_train,
                                        best_params, stops),
                            precision=precision)
        if settings.learning_decay > 0:
            lr_scale *= (1.0 - settings.learning_decay)
        if stopped:
            obs.event("early_stop", trainer="nn_streamed", epoch=epoch,
                      window=settings.early_stop_window)
            log.info("early stop at epoch %d (window %d, streamed)",
                     epoch, settings.early_stop_window)
            break

    # final eval-only pass: errors of the last params.  Elastic runs it
    # as one more quorum step (id ``epochs_run`` — past every epoch id,
    # and identical on all controllers since early stop reads the same
    # aggregated history) so best-model selection agrees job-wide; a
    # rejoiner that finds it already closed adopts the committed stats.
    final_close = elastic.closed_step(epochs_run) if elastic is not None \
        else None
    if final_close is None:
        stats_acc = jnp.zeros((bags, 4))
        for win in stream.windows():
            xb, yb, tw, vw = put_window(win)
            stats_acc = eval_window(stacked, stats_acc, xb, yb, tw, vw)
        if elastic is not None:
            final_close = elastic.step(
                epochs_run, {"stats": np.asarray(stats_acc)})
    final_stats = np.asarray(final_close.payload["stats"]) \
        if final_close is not None else np.asarray(stats_acc)
    bookkeep(epochs_run - 1, final_stats, stacked)

    final = _to_host(stacked)
    for i in range(bags):
        if best_params[i] is None:
            best_params[i] = jax.tree_util.tree_map(lambda a: a[i], final)
    return EnsembleResult(params=best_params, train_errors=best_train,
                          valid_errors=best_valid, epochs_run=epochs_run,
                          history=history)
