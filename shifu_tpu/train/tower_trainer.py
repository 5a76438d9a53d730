"""Tower trainer — ``algorithm: TENSORFLOW`` with ``train#params.Tower``.

Trains the tower ``train#params.Tower`` names (:mod:`shifu_tpu.models.towers`:
its module gives the spec, the initial parameters, the loss with its counters,
the scopes and the scorer) over the binned plane
(``tmp/CleanedData``, the plane the tree trainers read): rows tokenised once,
microbatches of ``MiniBatchs`` rows (each ``RowsPerSequence`` consecutive ones
laid end to end as one sequence, where the tower takes that), one jitted step
a microbatch (loss and gradients with each layer recomputed in the backward
pass, then the ``train/optimizers.py`` update rule over every parameter, then
the tower's own ``after_step`` if it has one), the epoch's loss
and MoE counters accumulated on the device and fetched once an epoch.  The
epoch hooks are the NN trainer's: a progress line, trainer-state checkpoints
every ``CheckpointInterval`` epochs (``train/checkpoint.py``) and resume from
the latest, bit-exactly: what an epoch does is a function of (seed, epoch,
step) and the restored state alone.

The three jitted programs (``tower.init``, ``tower.step``, ``tower.valid_step``)
outlive the job: ``compile_cache.PROGRAMS`` keeps them for the next job of
this process that asks under an equal :func:`programs_key`, which then
traces, lowers and loads nothing.  A program depends on what the key names —
the tower's spec, the optimizer and its settings, ``RowsPerSequence``, the id
plane's shape, the microbatch, telemetry on or off — and on nothing else: what
else a job has (the seed, the rows, the special ids, the epoch) is an argument.
A job with another key drops the held programs and builds its own; parameters
and optimizer state are never held, a job makes them from its seed or reads
them from its checkpoint.

What the seed decides, restated by the towers' references under
``benchmark/reference/``: the order of an epoch's training rows is
``permutation(fold_in(fold_in(key, epoch), 0))``; step ``i``'s loss gets the
key ``fold_in(fold_in(key, epoch), 1 + i)`` (``sdar_moe`` draws its noise from
it; ``nemotron_h`` draws nothing), a validation step ``fold_in(fold_in(key,
VALID_FOLD), 1 + i)``.
"""

from __future__ import annotations

import json
import logging
import os
import time
from dataclasses import asdict, dataclass
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import compile_cache, faults, obs
from ..config.errors import ErrorCode, ShifuError
from ..models import towers
from ..obs.costs import op_scopes
from . import checkpoint as ckpt
from .optimizers import make_optimizer, resolve_precision

log = logging.getLogger(__name__)

DEFAULT_MICROBATCH = 16
VALID_FOLD = 0x7FFFFFFF             # the validation noise's fold of the seed's key


@dataclass
class TowerResult:
    params: Any
    train_error: float
    valid_error: float
    epochs_run: int
    history: List[Tuple[float, float]]


def split_rows(n: int, valid_rate: float, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(train rows, validation rows), both ascending: the first
    ``round(n * valid_rate)`` of a seeded permutation validate."""
    perm = np.random.default_rng(seed).permutation(n)
    n_valid = int(round(n * valid_rate))
    return np.sort(perm[n_valid:]), np.sort(perm[:n_valid])


def _microbatches(rows: np.ndarray, mb: int) -> np.ndarray:
    """[steps, mb] row indices, the last step padded with -1."""
    steps = -(-len(rows) // mb)
    out = np.full(steps * mb, -1, np.int32)
    out[:len(rows)] = rows
    return out.reshape(steps, mb)


def _zero_acc(spec) -> Dict[str, jnp.ndarray]:
    """The epoch's accumulators: the loss's sums, training and validation,
    and the tower's own counters."""
    tower = towers.module(spec.tower)
    f32 = lambda *shape: jnp.zeros(shape, jnp.float32)
    return {"loss_sum": f32(), "positions": f32(), "valid_loss_sum": f32(),
            "valid_positions": f32(),
            **{k: f32(*shape) for k, shape in tower.counter_shapes(spec).items()}}


def build_programs(spec, opt, mb: int, rows_per_sequence: int = 1):
    """(step, valid_step): the two programs an epoch launches, made anew at
    every call (:func:`train_tower` keeps what it built for the process's
    next job, see the module's text).  State and accumulators are donated:
    16 bytes a parameter, updated in place.  A step takes its microbatch's
    row indices (-1 = padding), so the programs depend on the plane's rows
    and the microbatch, not on how many steps an epoch has.  They close over
    ``spec``, ``opt`` and ``rows_per_sequence`` and read nothing else from
    outside their arguments."""
    tower = towers.module(spec.tower)
    counters = tuple(tower.counter_shapes(spec))
    block = tower.sequence_block(spec) if hasattr(tower, "sequence_block") else None

    def gather(ids, w, rows, specials, key, fold, i):
        """(the microbatch's ids, its weights, the step's key): what a step
        does before its tower, under one scope."""
        with jax.named_scope("tower/input"):
            keep = rows >= 0
            rows = jnp.maximum(rows, 0)
            x0, row_w = ids[rows], jnp.where(keep, w[rows], 0.0)
            if block is not None:
                x0, row_w = towers.pack_rows(x0, row_w, rows_per_sequence, block,
                                             specials[towers.SPECIALS.index("PAD")])
            return x0, row_w, jax.random.fold_in(jax.random.fold_in(key, fold), 1 + i)

    @partial(obs.costed_jit, "tower.step", donate_argnums=(0, 1, 2))
    def tower_step(params, opt_state, acc, ids, w, rows, key, specials, epoch, i):
        x0, row_w, step_key = gather(ids, w, rows, specials, key, epoch, i)
        (_, aux), grads = jax.value_and_grad(tower.train_loss, has_aux=True)(
            params, spec, x0, row_w, step_key, specials)
        with jax.named_scope("tower/opt"):
            delta, opt_state = opt.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(jnp.add, params, delta)
            if hasattr(tower, "after_step"):
                params, aux = tower.after_step(params, aux, spec)
        with jax.named_scope("tower/acc"):
            acc = {**acc, **{k: acc[k] + aux[k] for k in ("loss_sum", "positions") + counters}}
        return params, opt_state, acc

    @partial(obs.costed_jit, "tower.valid_step", donate_argnums=(1,))
    def tower_valid_step(params, acc, ids, w, rows, key, specials, i):
        x0, row_w, step_key = gather(ids, w, rows, specials, key, VALID_FOLD, i)
        _, aux = tower.train_loss(params, spec, x0, row_w, step_key, specials)
        with jax.named_scope("tower/acc"):
            return {**acc, "valid_loss_sum": acc["valid_loss_sum"] + aux["loss_sum"],
                    "valid_positions": acc["valid_positions"] + aux["positions"]}

    return tower_step, tower_valid_step


def _make_programs(spec, settings, mb: int, rows_per_sequence: int):
    """(init, step, valid_step), made anew: what :func:`train_tower` holds for
    the process's next job.  A function of its own, so that the held closures
    capture these arguments and nothing of a job's planes or state."""
    tower = towers.module(spec.tower)
    opt = make_optimizer(settings.optimizer, settings.learning_rate, **settings.opt_kwargs)
    init = obs.costed_jit(
        "tower.init", lambda k: (lambda p: (p, opt.init(p)))(tower.init_params(k, spec)))
    return (init, *build_programs(spec, opt, mb, rows_per_sequence))


def programs_key(spec, settings, mb: int, rows_per_sequence: int, ids_shape) -> tuple:
    """Everything the three programs close over or are specialised to.  The
    specs are dataclasses that hold lists, so their canonical JSON stands for
    them; telemetry is in it because ``obs.costed_jit`` wraps differently
    with it on."""
    return ("tower", spec.tower, json.dumps(asdict(spec), sort_keys=True),
            str(settings.optimizer).upper(), float(settings.learning_rate),
            json.dumps(settings.opt_kwargs, sort_keys=True, default=repr),
            int(rows_per_sequence), tuple(ids_shape), int(mb), obs.enabled())


def _nbytes(tree) -> int:
    return int(sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree)))


def train_tower(bins: np.ndarray, y: np.ndarray, w: np.ndarray, spec, settings,
                valid_rate: float,
                progress: Optional[Callable[[int, float, float], None]] = None,
                rows_per_sequence: int = 1) -> TowerResult:
    tower = towers.module(spec.tower)
    precision = resolve_precision(settings.precision)
    if precision != "f32":
        raise ValueError(f"a tower trains under shifu.train.precision=f32; got {precision!r}")
    packs = hasattr(tower, "sequence_block")
    if rows_per_sequence != 1 and not packs:
        packers = [n for n in sorted(towers.TOWERS) if hasattr(towers.module(n), "sequence_block")]
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION,
                         f"train#params.RowsPerSequence {rows_per_sequence}: the {spec.tower} "
                         "tower takes one row a sequence (its mask and recurrence end with the "
                         f"row); {', '.join(f'`{n}`' for n in packers)} pack rows")
    with obs.span("tower.tokenize", rows=len(y), ids=spec.n_ids):
        ids = towers.tokenize(spec, bins, y)
        train_rows, valid_rows = split_rows(len(y), valid_rate, settings.seed)
    mb = min(settings.batch_size or DEFAULT_MICROBATCH, max(len(train_rows), 1))
    if packs:
        with obs.span("tower.pack") as sp:
            sp.set(**towers.pack_plan(spec, mb, rows_per_sequence, tower.sequence_block(spec)))
    valid_order = _microbatches(valid_rows, mb)
    steps = -(-len(train_rows) // mb)

    with obs.span("tower.init") as sp:
        key = jax.random.PRNGKey(settings.seed)
        programs, reused = compile_cache.PROGRAMS.programs(
            programs_key(spec, settings, mb, rows_per_sequence, ids.shape),
            partial(_make_programs, spec, settings, mb, rows_per_sequence))
        init, tower_step, tower_valid_step = programs
        if reused:
            obs.counter("tower.programs_reused").inc()
        start_epoch, state = 0, None
        if settings.resume and settings.checkpoint_dir:
            # shapes only: the restored state never shares the chip with a fresh one
            template = dict(zip(("params", "opt_state"), jax.eval_shape(init, key)))
            restored = ckpt.restore_state(settings.checkpoint_dir, template,
                                          expect_precision=precision)
            if restored is not None:
                start_epoch, state = restored
                log.info("tower: resumed from the checkpoint of epoch %d", start_epoch)
        params, opt_state = init(key) if state is None else \
            jax.device_put((state["params"], state["opt_state"]))
        del state
        ids_d, w_d = jax.device_put((ids, np.asarray(w, np.float32)))
        # the special ids follow the columns' bins: an argument, so that
        # another table's job finds these programs in the compile cache on disk
        specials = jnp.asarray([spec.special(n) for n in towers.SPECIALS], jnp.int32)
        n_par = towers.n_params(params)
        sp.set(params=n_par, bytes=_nbytes(params) + _nbytes(opt_state),
               programs_built=0 if reused else len(programs))
    log.info("tower %s: %d rows x %d positions (%d train, %d validation), %d parameters, "
             "microbatches of %d rows, %d steps an epoch", spec.tower, len(y), spec.seq_len,
             len(train_rows), len(valid_rows), n_par, mb, steps)

    epochs_target = settings.epochs
    if settings.resume_extra > 0:
        epochs_target = start_epoch + settings.resume_extra
    tr = va = float("nan")
    history: List[Tuple[float, float]] = []
    for epoch in range(start_epoch, epochs_target):
        with obs.span("tower.epoch", epoch=epoch):
            ep_t0 = time.perf_counter()
            with obs.span("tower.epoch.dispatch"):
                order_key = jax.random.fold_in(jax.random.fold_in(key, epoch), 0)
                order = _microbatches(
                    train_rows[np.asarray(jax.random.permutation(order_key, len(train_rows)))],
                    mb)
                acc = _zero_acc(spec)
                ep = jnp.int32(epoch)
                for i in range(steps):
                    params, opt_state, acc = tower_step(params, opt_state, acc, ids_d, w_d,
                                                        order[i], key, specials, ep, jnp.int32(i))
                for i in range(len(valid_order)):
                    acc = tower_valid_step(params, acc, ids_d, w_d, valid_order[i], key,
                                           specials, jnp.int32(i))
            with obs.span("tower.epoch.fetch"):
                got = jax.device_get(acc)              # the epoch's one fetch
            if epoch == start_epoch and obs.enabled():
                # which of the step's device ops belong to which scope: a
                # device trace names ops by HLO instruction only
                hlo = tower_step.hlo_text()
                if hlo:
                    obs.event("op_scopes", program="tower_step",
                              scopes=op_scopes(hlo, tower.SCOPES))
            tr = float(got["loss_sum"] / max(got["positions"], 1.0))
            va = float(got["valid_loss_sum"] / got["valid_positions"]) \
                if got["valid_positions"] > 0 else 0.0
            history.append((tr, va))
            if obs.enabled():
                dt = time.perf_counter() - ep_t0
                pairs = got["pairs"]
                obs.counter("train.epochs").inc()
                obs.histogram("train.epoch_s").observe(dt)
                obs.gauge("train.valid_err").set(va)
                obs.counter("tower.moe_pairs_max_expert").inc(float(pairs.max()))
                obs.counter("tower.moe_pairs_mean_expert").inc(float(pairs.mean()))
                obs.counter("tower.moe_rows_computed").inc(float(got["rows"].sum()))
                obs.counter("tower.dropped_pairs").inc(float(got["dropped"].sum()))
                obs.counter("tower.positions").inc(float(got["positions"]))
                for k, name in tower.OBS_COUNTERS.items():
                    obs.counter(name).inc(float(got[k]))
                obs.event("epoch", trainer="tower", epoch=epoch, train_err=round(tr, 6),
                          valid_err=round(va, 6), rows=len(train_rows),
                          rows_per_sec=round(len(train_rows) / max(dt, 1e-9), 1))
            if got["dropped"].any():
                raise RuntimeError(f"tower: {int(got['dropped'].sum())} routed pairs were dropped "
                                   f"in epoch {epoch + 1}")
            if progress:
                progress(epoch, tr, va)
            if settings.checkpoint_dir and settings.checkpoint_every and \
                    (epoch + 1) % settings.checkpoint_every == 0:
                with obs.span("tower.epoch.checkpoint") as sp:
                    path = ckpt.save_state(
                        settings.checkpoint_dir, epoch + 1,
                        {"params": params, "opt_state": opt_state},
                        keep=1, precision=precision)
                    sp.set(bytes=os.path.getsize(path))
    return TowerResult(params=params, train_error=tr, valid_error=va,
                       epochs_run=start_epoch + len(history), history=history)


def run_tower_training(proc) -> int:
    """Entry called by TrainProcessor for ``TENSORFLOW`` with ``Tower``."""
    from ..pipeline.train import settings_from_params
    mc = proc.model_config
    p = dict(mc.train.params or {})
    if mc.train.baggingNum > 1 or mc.train.isCrossValidation or mc.is_multi_class():
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION,
                         "a tower trains one binary model: baggingNum 1, no k-fold, "
                         "no multi-class")
    shards = proc._open_shards(proc.paths.clean_dir)
    with proc.phase("load_data"):
        data = shards.load_all()
    col_nums = list(shards.schema.get("columnNums", []))
    by_num = {c.columnNum: c for c in proc.column_configs}
    spec = towers.module(str(p["Tower"])).spec_from_params(
        p.get("TowerParams"), col_nums, [by_num[cn].num_bins() for cn in col_nums],
        [by_num[cn].columnName for cn in col_nums])
    settings = settings_from_params(p, mc.train, defaults={"Propagation": "ADAM",
                                                           "LearningRate": 1e-4})
    settings.checkpoint_dir = proc.paths.checkpoint_dir
    settings.resume = bool(proc.params.get("resume"))
    settings.resume_extra = int(proc.params.get("refresh_extra") or 0)

    if not settings.resume and os.path.isdir(settings.checkpoint_dir):
        # a fresh job's torn successor must never resume an older job's state
        for f in os.listdir(settings.checkpoint_dir):
            os.remove(os.path.join(settings.checkpoint_dir, f))
    with open(proc.paths.progress_path, "w") as pf:  # shifu-lint: disable=atomic-write
        def progress(epoch, tr, va):
            line = (f"Tower Epoch #{epoch + 1} Train Error: {tr:.6f} "
                    f"Validation Error: {va:.6f}")
            pf.write(line + "\n")
            pf.flush()
            faults.fire("train", "epoch", epoch + 1)
            log.info(line)
        with proc.phase("train"):
            res = train_tower(data["bins"], data["y"], data["w"], spec, settings,
                              mc.train.validSetRate, progress,
                              rows_per_sequence=int(p.get("RowsPerSequence", 1)))

    with proc.phase("save_models"), obs.span("tower.save") as sp:
        os.makedirs(proc.paths.models_dir, exist_ok=True)
        with obs.span("tower.save.clear") as part:
            old = [os.path.join(proc.paths.models_dir, f)
                   for f in os.listdir(proc.paths.models_dir) if f.startswith("model")]
            part.set(bytes=sum(os.path.getsize(f) for f in old))
            for f in old:
                os.remove(f)
        path = proc.paths.model_path(0, "tower")
        with obs.span("tower.save.fetch") as part:   # ends when the last array is on the host
            host = jax.device_get(res.params)
            part.set(bytes=_nbytes(host))
        sp.set(bytes=towers.save_model(path, spec, host))      # .write and .commit
    log.info("train tower done: %s, train error %.6f, validation error %.6f (%d epochs)",
             path, res.train_error, res.valid_error, res.epochs_run)
    return 0
