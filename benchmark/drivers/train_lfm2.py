"""The ``lfm2_moe`` tower's training cells: jobs of ``numTrainEpochs`` epochs
through ``cli train`` with ``algorithm: TENSORFLOW``, ``Tower: lfm2_moe`` on
the binned plane, ``RowsPerSequence`` rows packed a sequence; ``correct`` holds
what the CLI wrote — the saved tower's scores, the trainer state after one
step, the progress lines — to the plain reference (``reference/lfm2_moe.py``),
through the ``judge_*`` functions of ``drivers/train_tower.py`` and this
file's judge of the selection bias; the cell's files, its packing and its
share are ``train_afmoe``'s (``AfmoeCell``).

(b) sees the packing: the one-step job trains one microbatch of the timed
shape, one packed sequence of 8,192 positions, in which a row's first
positions read the previous row's last tokens through the convolution's taps;
(a) scores one row a sequence.

The selection bias after the step is judged where the reference's scores
decide an expert's side of the mean with room (:func:`judge_step`): the
packed sequence ends in a run of like positions, the ``PAD`` tail, whose
top-k choice is nearly one decision, so where it lies near the edge operand
rounding moves an expert's count by up to 300 where scattered flips move it
by about 20, and no margin in tokens tells the two apart.

Limits (the cell's ``correct`` block; ``PERF.md`` gives the two readings behind
each): each lies between what sound runs read over seeds and what a control
reads.  ``--check-seeds`` puts every control through the same limits
(:func:`controls`): the four every tower cell has (the reference one precision
lower, dropped pairs, half the microbatch, a state left unchanged) and this
configuration's own — the convolution's taps reversed (w_0 on the current
position), rows not packed, the bias never moved — each has to come out as not
correct, and a control that passes fails the check.
"""

from __future__ import annotations

import json
import os

import numpy as np

from .. import jobs, modelset as ms
from ..reference import lfm2_moe as ref
from . import train_tower as tt
from .train_afmoe import COUNTERS, AfmoeCell


# ---------------------------------------------------------- (b) one step
def one_step(ctx, cell: AfmoeCell):
    """(b): a one-step job on the timed plane's shape — as many rows, so the
    step program is the timed one — with all but one microbatch given to
    validation, one epoch and a checkpoint after it (its departures), at the
    configuration's learning rate.  Returns (the job's files, the reference's
    side) for :func:`judge_step`, or None."""
    with ctx.part("correct"):
        rate = 1.0 - cell.microbatch / int(ctx.cell["correct"]["sample_job_rows"])
        data = cell.check_set(params={"CheckpointInterval": 1, "LearningRate": cell.step_lr},
                              validSetRate=rate, numTrainEpochs=1)
        cell.job(cell.cdir)
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
        got = {"loss": lines[0][0],
               "state": lambda k: (ck[f"leaf{at[k]}"], ck[f"leaf{n + 1 + at[k]}"],
                                   ck[f"leaf{2 * n + 1 + at[k]}"])}
        # the reference's side: the seed's split, order and initial parameters
        # restated; its own packing, loss, gradient, Adam step and bias rule
        train, _ = ref.split_rows(len(data["y"]), rate, 0)
        if not ctx.check("step.rows", len(train) == cell.microbatch, f"{len(train)} training rows"):
            return None
        rows = train[ref.epoch_order(0, 0, len(train))]
        ids = ref.rows_to_ids(data["bins"][rows], data["y"][rows], cell.column_bins)
        before = ref.flatten(ref.init_params(0, cell.tp))
        loss, grads, tokens = ref.loss_and_grads(ref.nest(before), *cell.packed(ids), cell.pad_id,
                                                 cell.cfg(), cell.lo)
        bounds = ref.count_bounds(ref.nest(before), cell.packed(ids)[0], cell.cfg(), cell.lo,
                                  float(ctx.cell["correct"]["bias_margin_score"]))
        biases = [k for k in names if k.endswith(".bias")]         # no gradient reaches them
        want = {"names": [k for k in names if k not in biases], "bias_names": biases, "before": before,
                "loss": loss, "grads": ref.flatten(grads), "tokens": tokens, "bounds": bounds,
                "batch": ids}
        judge_step(ctx, cell, got, want)
    return got, want


def judge_step(ctx, cell: AfmoeCell, got: dict, want: dict) -> None:
    """``train_tower.judge_step`` over every array a gradient reaches, then the
    selection bias: after the step it equals the rule's on the reference's
    counts, exactly, on every expert whose count no router could carry across
    the mean whose biased scores each lie within ``bias_margin_score`` / 2 of
    the reference's (``reference.count_bounds``: a position counts against an
    expert only where its own score lies that near the top-k's edge, so a run
    of like positions that sits at the edge leaves the expert unjudged,
    however many tokens it holds); Adam must have left the bias alone (m = v
    = 0)."""
    tt.judge_step(ctx, cell, got, want)
    coeff = float(cell.tp.get("load_balance_coeff", 0.001))
    margin = float(ctx.cell["correct"]["bias_margin_score"])
    wrong = sure = 0
    moments = 0.0
    for name, (tokens, low, high) in zip(want["bias_names"], want["bounds"]):
        m, v, after = got["state"](name)
        expect = ref.bias_after(want["before"][name], tokens, coeff)
        far = (low > tokens.mean()) | (high < tokens.mean())
        wrong += int(np.sum(after[far] != expect[far]))
        sure += int(far.sum())
        moments = max(moments, float(np.abs(m).max()), float(np.abs(v).max()))
    ctx.say(f"selection bias: {wrong} of {sure} entries (of {sum(len(t) for t in want['tokens'])}; "
            f"the others' counts could cross the mean with scores within {margin / 2:g} of the "
            f"reference's) differ from the reference's rule; Adam's moments of it at most {moments:.3g}")
    ctx.check("step.bias_vs_reference", wrong == 0 and sure > 0 and moments == 0.0,
              f"{wrong} of {sure} sure entries differ, Adam's moments {moments:.3g}")


# ------------------------------------------------------------ (a) forward
def forward(ctx, cell: AfmoeCell, mdir: str):
    """(a): a job's saved tower, scored by ``eval`` on the sample's rows
    (one row a sequence), against the reference's tag-logit difference for
    the same weights: the one-step job's tower, as ``trinity-train`` judges
    (a whole job pushes every score toward 0, where ``eval``'s three decimals
    are coarser than the differences judged).  Returns (the weights, the
    reference's differences)."""
    with ctx.part("correct"):
        p = np.clip(cell.eval_step(mdir) / 1000.0, 1e-6, 1.0 - 1e-6)
        params = cell.saved_tower(mdir)
        want = ref.tag_logit_difference(params, cell.head["bins"], cell.cfg(), cell.lo,
                                        cell.column_bins)
        if ctx.check("forward.rows", len(p) == len(want), f"{len(p)} scores"):
            tt.judge_forward(ctx, cell, np.log(p / (1.0 - p)), want, params, decimals=True)
    return params, want


# --------------------------------------------------------------- controls
# a dispatch that holds each expert to half its mean load: at 1.0 the pairs dropped past the mean
# move the router's gradient and the scores less than routing flips do (one seed of five passed)
DROPPED = {"capacity_factor": 0.5}
TAPS_REVERSED = {"taps_reversed": True}
CONTROL_ARRAY = 2 ** 23


def controls(ctx, cell: AfmoeCell, step, fwd) -> None:
    """What the limits are held against, each judged as a run's own files
    are: a trainer's files after one step as the reference would have left
    them, and the reference's own scores of the saved tower — computed one
    precision lower, with dropped pairs, and with the taps reversed; the step
    alone with the rows not packed (each its own sequence: no mask, no tap
    across a row's start), on half the microbatch, with the bias never moved
    and with the state left unchanged.  The step's judge sees the arrays of up
    to ``CONTROL_ARRAY`` elements (norms, taps, routers, attention, ``W_out``:
    judging the 400 M of expert, dense, ``W_in`` and vocabulary arrays of
    every control takes minutes a control on the host; fewer arrays can only
    pass more easily)."""
    (got, want), (params, scores) = step, fwd
    ids = want["batch"]
    before = ref.nest(want["before"])
    want = {**want, "names": [k for k in want["names"] if want["before"][k].size <= CONTROL_ARRAY]}
    coeff = float(cell.tp.get("load_balance_coeff", 0.001))

    def files(lower=False, rows=len(ids), fault=None, move_bias=True):
        loss, grads, tokens = ref.loss_and_grads(before, *cell.packed(ids[:rows], min(rows, cell.pack)),
                                                 cell.pad_id, cell.cfg(fault), cell.lo, lower=lower)
        grads = ref.flatten(grads)
        moved = {name: ref.bias_after(want["before"][name], t, coeff if move_bias else 0.0)
                 for name, t in zip(want["bias_names"], tokens)}

        def state(k):
            if k in moved:
                return np.zeros_like(moved[k]), np.zeros_like(moved[k]), moved[k]
            return ref.adam_first_step(want["before"][k], grads[k], cell.step_lr, lower)
        return {"loss": loss, "state": state}

    def step_of(**kw):
        return lambda sub: judge_step(sub, cell, files(**kw), want)

    def forward_of(lower=False, fault=None):
        return lambda sub: tt.judge_forward(sub, cell, ref.tag_logit_difference(
            params, cell.head["bins"], cell.cfg(fault), cell.lo, cell.column_bins, lower=lower),
            scores, params)
    zeros = lambda k: np.zeros_like(want["before"][k])
    tt._refused(ctx, "lower_precision", step_of(lower=True), forward_of(lower=True))
    tt._refused(ctx, "dropped_pairs", step_of(fault=DROPPED), forward_of(fault=DROPPED))
    tt._refused(ctx, "taps_reversed", step_of(fault=TAPS_REVERSED), forward_of(fault=TAPS_REVERSED))
    tt._refused(ctx, "rows_not_packed", step_of(fault={"segment": len(cell.column_bins) + 1}))
    tt._refused(ctx, "half_batch", step_of(rows=len(ids) // 2))
    tt._refused(ctx, "bias_never_moved", step_of(move_bias=False))
    tt._refused(ctx, "state_unchanged", lambda sub: judge_step(sub, cell, {
        "loss": got["loss"], "state": lambda k: (zeros(k), zeros(k), want["before"][k])}, want))


# ------------------------------------------------------------ the window
def counters(ctx, cell: AfmoeCell) -> None:
    """What the traced jobs' telemetry counted, for the readers."""
    c = {k: ms.telemetry_counter(cell.mdir, "tower." + k) for k in COUNTERS}
    if not c["positions"] or not c["sequence_positions"]:
        return                                  # a program without these counters
    ctx.counters.update({"tower." + k: v for k, v in c.items()})
    ctx.check("moe.dropped_pairs", c["dropped_pairs"] == 0, f"{c['dropped_pairs']:.0f} pairs dropped")
    steps = -(-round(cell.rows * (1.0 - float(cell.config["train"]["validSetRate"]))) // cell.microbatch)
    epochs = ms.telemetry_counter(cell.mdir, "train.epochs")
    pairs = c["moe_pairs_mean_expert"] * int(cell.tp["num_experts"]) / max(steps * epochs, 1)
    ctx.say(f"counters: {c['positions']:.0f} weighted targets, {c['sequence_positions']:.0f} positions "
            f"of which {c['pad_positions']:.0f} PAD, key blocks visited {c['attn_key_blocks']:.0f}, "
            f"max |selection bias| {c['router_bias_absmax']:.4f}, pairs a held expert max/mean "
            f"{c['moe_pairs_max_expert'] / c['moe_pairs_mean_expert']:.3f}, {pairs:.0f} pairs a layer "
            f"a step, dropped {c['dropped_pairs']:.0f}")
    ctx.counters["params"] = {"tower": "lfm2_moe", "cfg": cell.tp, "seq": cell.seq,
                              "sequences": cell.microbatch // cell.pack, "pairs_per_layer": pairs}
    ctx.counters["op_scopes"] = tt._op_scopes(cell.mdir)


def _finish(ctx, cell: AfmoeCell, win: dict) -> dict:
    if ctx.trace:
        counters(ctx, cell)
    return jobs.finish(ctx, win, {"train_rate": (win["train_rate"], "rows.iters/s"),
                                  "setup_s": (win["setup_s"], "s")})


def run(ctx, t_start: float) -> dict:
    cell = AfmoeCell(ctx)           # a program without the tower fails here, at once
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
    cell = AfmoeCell(ctx)
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
