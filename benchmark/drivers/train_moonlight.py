"""The ``deepseek_v3`` tower's training cells: jobs of ``numTrainEpochs``
epochs through ``cli train`` with ``algorithm: TENSORFLOW``, ``Tower:
deepseek_v3`` on the binned plane, ``RowsPerSequence`` rows packed a sequence;
``correct`` holds what the CLI wrote — the saved tower's scores, the trainer
state after one step, the progress lines and the one-step job's balance-loss
counter — to the plain reference (``reference/deepseek_v3.py``), through the
``judge_*`` functions of ``drivers/train_tower.py``, ``train_lfm2.py``'s judge
of the selection bias and this file's judge of the balance loss; the cell's
files, its packing and its share are ``train_afmoe``'s (``AfmoeCell``, under
config.json's name for the experts held).

(b) sees the packing and latent attention at 8,192 positions: the one-step
job trains one microbatch of the timed shape, one packed sequence, with
telemetry on, so that its ``tower.moe_balance_loss_sum`` — the MoE layers'
sum_e f_e P_e, unscaled — is read back: at alpha = 1e-4 the balance loss is
4e-5 of the loss and its gradient hides under operand rounding, so it is
judged by its own value, relative to the reference's (``balance_limit``);
(a) scores one row a sequence.

Limits (the cell's ``correct`` block; ``PERF.md`` gives the two readings behind
each): each lies between what sound runs read over seeds and what a control
reads.  ``--check-seeds`` puts every control through the same limits
(:func:`controls`): the four every tower cell has (the reference one precision
lower, dropped pairs, half the microbatch, a state left unchanged), the two of
a packed tower with a moving bias (rows not packed, the bias never moved) and
this configuration's own — the scores scaled by 1 / sqrt(qk_nope) alone, the
latent's RMSNorm left out, the balance loss left out — each has to come out as
not correct, and a control that passes fails the check.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

from shifu_tpu.models.towers import module   # the towers' lookup: a program without the tower cannot run this cell

from .. import jobs, modelset as ms
from ..reference import deepseek_v3 as ref
from . import train_lfm2
from . import train_tower as tt
from .train_afmoe import COUNTERS, AfmoeCell, tower_params

BALANCE = "tower.moe_balance_loss_sum"


class MoonlightCell(AfmoeCell):
    """``AfmoeCell`` whose share counts ``n_routed_experts`` (config.json's
    name for the experts held here) and whose unit counts ``num_hidden_layers``."""

    def __init__(self, ctx):
        jobs.TrainCell.__init__(self, ctx)
        doc = self.config
        module(doc["tower"])            # a program without this tower fails here, at once
        self.tp = tp = tower_params(doc, ctx.cell.get("tower_params"))     # --rehearse: toy widths
        self.lo = int(tp["n_routed_experts"]) * int(tp["expert_parallel_index"])
        self.unit = tt.EPS * math.sqrt(6 * int(tp["num_hidden_layers"]) + 1)
        self.step_lr = float(doc["train"]["params"]["LearningRate"])
        self.config = {**doc, "train": {**doc["train"], "params": {
            **doc["train"]["params"], **ctx.cell.get("train_params", {}), "TowerParams": tp}}}


# ---------------------------------------------------------- (b) one step
def one_step(ctx, cell: MoonlightCell):
    """(b): a one-step job on the timed plane's shape — as many rows, so the
    step program is the timed one — with all but one microbatch given to
    validation, one epoch, a checkpoint after it and telemetry on (its
    departures), at the configuration's learning rate.  Returns (the job's
    files, the reference's side) for :func:`judge_step`, or None."""
    with ctx.part("correct"):
        rate = 1.0 - cell.microbatch / int(ctx.cell["correct"]["sample_job_rows"])
        data = cell.check_set(params={"CheckpointInterval": 1, "LearningRate": cell.step_lr},
                              validSetRate=rate, numTrainEpochs=1)
        cell.job(cell.cdir, telemetry=True)
        lines = ms.progress_lines(cell.cdir)
        if not ctx.check("step.epochs", len(lines) == 1, f"{len(lines)} progress lines"):
            return None
        # the arrays' names in the order jax flattens the nested tree
        files = np.load(os.path.join(cell.cdir, "models", "model0.tower")).files
        names = sorted((k for k in files if k != "__spec__"), key=lambda k: k.split("."))
        ck = np.load(os.path.join(cell.cdir, "tmp", "checkpoints", "ckpt-1.npz"))
        n = len(names)
        meta = json.loads(bytes(ck["__meta__"]).decode())
        if not ctx.check("step.state", meta["n_leaves"] == 3 * n + 1,
                         f"the trainer state has {meta['n_leaves']} leaves, expected m, t, v "
                         f"and the parameters of {n} arrays"):
            return None
        # {"opt_state": {"m", "t", "v"}, "params"} flattened with sorted keys
        at = {name: i for i, name in enumerate(names)}
        got = {"loss": lines[0][0], "balance": ms.telemetry_counter(cell.cdir, BALANCE),
               "state": lambda k: (ck[f"leaf{at[k]}"], ck[f"leaf{n + 1 + at[k]}"],
                                   ck[f"leaf{2 * n + 1 + at[k]}"])}
        # the reference's side: the seed's split, order and initial parameters
        # restated; its own packing, loss, gradient, Adam step, bias rule and balance sum
        train, _ = ref.split_rows(len(data["y"]), rate, 0)
        if not ctx.check("step.rows", len(train) == cell.microbatch, f"{len(train)} training rows"):
            return None
        rows = train[ref.epoch_order(0, 0, len(train))]
        ids = ref.rows_to_ids(data["bins"][rows], data["y"][rows], cell.column_bins)
        before = ref.flatten(ref.init_params(0, cell.tp))
        loss, grads, tokens, balance = ref.loss_and_grads(ref.nest(before), *cell.packed(ids),
                                                          cell.pad_id, cell.cfg(), cell.lo)
        bounds = ref.count_bounds(ref.nest(before), cell.packed(ids)[0], cell.cfg(), cell.lo,
                                  float(ctx.cell["correct"]["bias_margin_score"]))
        biases = [k for k in names if k.endswith(".bias")]         # no gradient reaches them
        want = {"names": [k for k in names if k not in biases], "bias_names": biases, "before": before,
                "loss": loss, "grads": ref.flatten(grads), "tokens": tokens, "bounds": bounds,
                "balance": balance, "batch": ids}
        judge_step(ctx, cell, got, want)
    return got, want


def judge_step(ctx, cell: MoonlightCell, got: dict, want: dict) -> None:
    """``train_lfm2.judge_step`` (every array a gradient reaches, then the
    selection bias), then the balance loss: the one-step job's sum_e f_e P_e
    over the MoE layers against the reference's, relative (a balance loss
    left out reads 1)."""
    train_lfm2.judge_step(ctx, cell, got, want)
    off = abs(got["balance"] - want["balance"]) / abs(want["balance"])
    ctx.say(f"balance loss: sum_e f_e P_e over the MoE layers {got['balance']:.6f} against the "
            f"reference's {want['balance']:.6f} (1.0 a layer at an even load)")
    ctx.margin("step.balance_vs_reference", off, float(ctx.cell["correct"]["balance_limit"]))


# ------------------------------------------------------------ (a) forward
def forward(ctx, cell: MoonlightCell, mdir: str):
    """(a): the one-step job's saved tower, scored by ``eval`` on the
    sample's rows (one row a sequence), against the reference's tag-logit
    difference for the same weights (a whole job pushes every score toward 0,
    where ``eval``'s three decimals are coarser than the differences judged).
    Returns (the weights, the reference's differences)."""
    with ctx.part("correct"):
        p = np.clip(cell.eval_step(mdir) / 1000.0, 1e-6, 1.0 - 1e-6)
        params = cell.saved_tower(mdir)
        want = ref.tag_logit_difference(params, cell.head["bins"], cell.cfg(), cell.lo,
                                        cell.column_bins)
        if ctx.check("forward.rows", len(p) == len(want), f"{len(p)} scores"):
            tt.judge_forward(ctx, cell, np.log(p / (1.0 - p)), want, params, decimals=True)
    return params, want


# --------------------------------------------------------------- controls
DROPPED = train_lfm2.DROPPED            # half the mean load
FAULTS = {"scale_of_nope_alone": {"scale_nope": True}, "latent_norm_left_out": {"no_kv_norm": True},
          "balance_loss_left_out": {"no_balance": True}}
SEEN_BY_EVAL = ("scale_of_nope_alone", "latent_norm_left_out")    # the balance loss moves no score
CONTROL_ARRAY = train_lfm2.CONTROL_ARRAY


def controls(ctx, cell: MoonlightCell, step, fwd) -> None:
    """What the limits are held against, each judged as a run's own files
    are: a trainer's files after one step as the reference would have left
    them, and the reference's own scores of the saved tower — computed one
    precision lower, with dropped pairs, and with each of ``FAULTS``; the step
    alone with the rows not packed, on half the microbatch, with the bias never
    moved and with the state left unchanged.  The step's judge sees the arrays
    of up to ``CONTROL_ARRAY`` elements (norms, routers, the latent attention's
    matrices, the shared experts: judging the experts', dense and vocabulary
    arrays of every control takes minutes a control on the host; fewer arrays
    can only pass more easily)."""
    (got, want), (params, scores) = step, fwd
    ids = want["batch"]
    before = ref.nest(want["before"])
    want = {**want, "names": [k for k in want["names"] if want["before"][k].size <= CONTROL_ARRAY]}
    coeff = float(cell.tp.get("load_balance_coeff", 0.001))

    def files(lower=False, rows=len(ids), fault=None, move_bias=True):
        loss, grads, tokens, balance = ref.loss_and_grads(
            before, *cell.packed(ids[:rows], min(rows, cell.pack)), cell.pad_id, cell.cfg(fault),
            cell.lo, lower=lower)
        grads = ref.flatten(grads)
        moved = {name: ref.bias_after(want["before"][name], t, coeff if move_bias else 0.0)
                 for name, t in zip(want["bias_names"], tokens)}

        def state(k):
            if k in moved:
                return np.zeros_like(moved[k]), np.zeros_like(moved[k]), moved[k]
            return ref.adam_first_step(want["before"][k], grads[k], cell.step_lr, lower)
        return {"loss": loss, "balance": balance, "state": state}

    def step_of(**kw):
        return lambda sub: judge_step(sub, cell, files(**kw), want)

    def forward_of(lower=False, fault=None):
        return lambda sub: tt.judge_forward(sub, cell, ref.tag_logit_difference(
            params, cell.head["bins"], cell.cfg(fault), cell.lo, cell.column_bins, lower=lower),
            scores, params)
    zeros = lambda k: np.zeros_like(want["before"][k])
    tt._refused(ctx, "lower_precision", step_of(lower=True), forward_of(lower=True))
    tt._refused(ctx, "dropped_pairs", step_of(fault=DROPPED), forward_of(fault=DROPPED))
    for name, fault in FAULTS.items():
        tt._refused(ctx, name, step_of(fault=fault),
                    *([forward_of(fault=fault)] if name in SEEN_BY_EVAL else []))
    tt._refused(ctx, "rows_not_packed", step_of(fault={"segment": len(cell.column_bins) + 1}))
    tt._refused(ctx, "half_batch", step_of(rows=len(ids) // 2))
    tt._refused(ctx, "bias_never_moved", step_of(move_bias=False))
    tt._refused(ctx, "state_unchanged", lambda sub: judge_step(sub, cell, {
        "loss": got["loss"], "balance": got["balance"],
        "state": lambda k: (zeros(k), zeros(k), want["before"][k])}, want))


# ------------------------------------------------------------ the window
def counters(ctx, cell: MoonlightCell) -> None:
    """What the traced jobs' telemetry counted, for the readers."""
    c = {k: ms.telemetry_counter(cell.mdir, "tower." + k) for k in COUNTERS + ("moe_balance_loss_sum",)}
    if not c["positions"] or not c["sequence_positions"]:
        return                                  # a program without these counters
    ctx.counters.update({"tower." + k: v for k, v in c.items()})
    ctx.check("moe.dropped_pairs", c["dropped_pairs"] == 0, f"{c['dropped_pairs']:.0f} pairs dropped")
    steps = -(-round(cell.rows * (1.0 - float(cell.config["train"]["validSetRate"]))) // cell.microbatch)
    epochs = ms.telemetry_counter(cell.mdir, "train.epochs")
    pairs = c["moe_pairs_mean_expert"] * int(cell.tp["n_routed_experts"]) / max(steps * epochs, 1)
    ctx.say(f"counters: {c['positions']:.0f} weighted targets, {c['sequence_positions']:.0f} positions "
            f"of which {c['pad_positions']:.0f} PAD, key blocks visited {c['attn_key_blocks']:.0f}, "
            f"max |selection bias| {c['router_bias_absmax']:.4f}, pairs a held expert max/mean "
            f"{c['moe_pairs_max_expert'] / c['moe_pairs_mean_expert']:.3f}, {pairs:.0f} pairs a layer "
            f"a step, dropped {c['dropped_pairs']:.0f}, balance sum a step "
            f"{c['moe_balance_loss_sum'] / max(steps * epochs, 1):.4f}")
    ctx.counters["params"] = {"tower": "deepseek_v3", "cfg": cell.tp, "seq": cell.seq,
                              "sequences": cell.microbatch // cell.pack, "pairs_per_layer": pairs}
    ctx.counters["op_scopes"] = tt._op_scopes(cell.mdir)


def _finish(ctx, cell: MoonlightCell, win: dict) -> dict:
    if ctx.trace:
        counters(ctx, cell)
    return jobs.finish(ctx, win, {"train_rate": (win["train_rate"], "rows.iters/s"),
                                  "setup_s": (win["setup_s"], "s")})


def run(ctx, t_start: float) -> dict:
    cell = MoonlightCell(ctx)       # a program without the tower fails here, at once
    cell.build()
    one_step(ctx, cell)
    cell.full_planes()
    cell.warm_up()
    tt.learning(ctx, cell)
    forward(ctx, cell, cell.cdir)
    return _finish(ctx, cell, cell.window(t_start))


def check_only(ctx, full_jobs: int = 0) -> None:
    """Set-up and ``correct`` alone, then every control through the same
    limits; with ``full_jobs`` also (c)'s control, one more full-size job."""
    cell = MoonlightCell(ctx)
    cell.build()
    step = one_step(ctx, cell)
    cell.full_planes()
    cell.warm_up()
    tt.learning(ctx, cell)
    fwd = forward(ctx, cell, cell.cdir)
    if step:
        controls(ctx, cell, step, fwd)
    if full_jobs:
        tt.unchanged_job(ctx, cell)
