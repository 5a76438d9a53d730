"""The ``nemotron_h`` tower's training cells: jobs of ``numTrainEpochs`` epochs
through ``cli train`` with ``algorithm: TENSORFLOW``, ``Tower: nemotron_h`` on the
binned plane; ``correct`` holds what the CLI wrote — the saved tower's scores,
the trainer state after one step, the progress lines — to the plain reference
(``reference/nemotron_h.py``), in the three parts and through the ``judge_*``
functions of ``drivers/train_tower.py``.

The configuration's file holds config.json's keys at its top level; the
driver hands them to the program verbatim as ``train#params.TowerParams``,
with the share's four keys from ``deployment``.  ``--rehearse`` overlays the
cell's toy ``tower_params`` and ``train_params``.

Units.  As in ``train_tower.py``: a matmul on bfloat16 operands carries a
relative error of about EPS = 1.6e-3 of its own scale; a layer puts four in
sequence on the residual path (Mamba-2: in, the chunk's two products, out;
attention: q/k/v, scores, values, out; LatentMoE: into the latent space, up,
down, out of it) and the head one more: UNIT = EPS sqrt(4 L + 1), 1.1e-2 at
the trunk's L = 11.

Limits (the cell's ``correct`` block; ``PERF.md`` gives the two readings behind
each): each lies between what sound runs read over seeds and what a control
reads.  ``--check-seeds`` puts every control through the same limits
(:func:`controls`): the reference one precision lower, dropped pairs, half the
microbatch, a state left unchanged, the recurrent state reset at every chunk
boundary, the MTP term left out, a softmax router — each has to come out as
not correct, and a control that passes fails the check.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

from shifu_tpu.models.towers import load_model      # the towers' public loader: a program
                                                    # without it cannot run this cell

from .. import jobs, modelset as ms
from ..reference import nemotron_h as ref
from . import train_tower as tt

NOT_CONFIG_JSON = ("name", "source", "reduced", "published", "deployment", "tower", "table",
                   "stats", "train", "layouts", "assumed")
SHARE = ("tensor_parallel_size", "tensor_parallel_index", "expert_parallel_size",
         "expert_parallel_index")


def tower_params(doc: dict, over=None) -> dict:
    """``train#params.TowerParams`` of a configuration's file: config.json's
    keys verbatim, the share's four keys from ``deployment``, then ``over``."""
    tp = {k: v for k, v in doc.items() if k not in NOT_CONFIG_JSON}
    tp.update({k: doc["deployment"][k] for k in SHARE})
    tp.update(over or {})
    return tp


class NemotronCell(jobs.TrainCell):
    def __init__(self, ctx):
        super().__init__(ctx)
        doc = self.config
        self.tp = tp = tower_params(doc, ctx.cell.get("tower_params"))     # --rehearse: toy widths
        self.lo = int(tp["n_routed_experts"]) * int(tp["expert_parallel_index"])
        self.unit = tt.EPS * math.sqrt(4 * int(tp["num_hidden_layers"]) + 1)
        self.step_lr = float(doc["train"]["params"]["LearningRate"])
        self.config = {**doc, "train": {**doc["train"], "params": {
            **doc["train"]["params"], **ctx.cell.get("train_params", {}), "TowerParams": tp}}}

    def build(self) -> None:
        super().build()
        self.column_bins = ms.column_bins(self.mdir, self.schema).tolist()
        self.microbatch = int(self.config["train"]["params"]["MiniBatchs"])
        self.seq = len(self.column_bins) + 1

    def saved_tower(self, mdir: str) -> dict:
        return load_model(os.path.join(mdir, "models", "model0.tower"))[1]

    def cfg(self, fault=None) -> dict:
        return {**self.tp, **(fault or {})}


# ---------------------------------------------------------- (b) one step
def one_step(ctx, cell: NemotronCell):
    """(b): a one-step job on the timed plane's shape — as many rows, so the
    step program is the timed one — with all but one microbatch given to
    validation, one epoch and a checkpoint after it (its departures), at the
    configuration's learning rate.  Returns (the job's files, the
    reference's side) for ``train_tower.judge_step``, or None."""
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
        # restated; its own loss, gradient and Adam step
        train, _ = ref.split_rows(len(data["y"]), rate, 0)
        if not ctx.check("step.rows", len(train) == cell.microbatch, f"{len(train)} training rows"):
            return None
        rows = train[ref.epoch_order(0, 0, len(train))]
        ids = ref.rows_to_ids(data["bins"][rows], data["y"][rows], cell.column_bins)
        before = ref.flatten(ref.init_params(0, cell.tp))
        loss, grads = ref.loss_and_grads(ref.nest(before), ids, cell.cfg(), cell.lo)
        want = {"names": names, "before": before, "loss": loss, "grads": ref.flatten(grads),
                "batch": ids}
        tt.judge_step(ctx, cell, got, want)
    return got, want


# ------------------------------------------------------------ (a) forward
def forward(ctx, cell: NemotronCell, mdir: str):
    """(a): the job's saved tower, scored by ``eval`` on the sample's rows,
    against the reference's tag-logit difference for the same weights.
    Returns (the weights, the reference's differences) for the controls."""
    with ctx.part("correct"):
        p = np.clip(cell.eval_step(mdir) / 1000.0, 1e-6, 1.0 - 1e-6)
        params = cell.saved_tower(mdir)
        want = ref.tag_logit_difference(params, cell.head["bins"], cell.cfg(), cell.lo,
                                        cell.column_bins)
        if ctx.check("forward.rows", len(p) == len(want), f"{len(p)} scores"):
            tt.judge_forward(ctx, cell, np.log(p / (1.0 - p)), want, params, decimals=True)
    return params, want


# --------------------------------------------------------------- controls
DROPPED = {"capacity_factor": 1.0}      # a dispatch that holds each expert to its mean load


def controls(ctx, cell: NemotronCell, step, fwd) -> None:
    """What the limits are held against, each judged as a run's own files
    are: a trainer's files after one step as the reference would have left
    them, and the reference's own scores of the saved tower — computed one
    precision lower, with dropped pairs, with the recurrent state reset at
    every chunk boundary, with a softmax router; the step alone with the MTP
    term left out, on half the microbatch, and with the state left unchanged."""
    (got, want), (params, scores) = step, fwd
    ids = want["batch"]
    before = ref.nest(want["before"])
    reset = {"reset_state_every": int(cell.tp["chunk_size"])}

    def files(lower=False, rows=len(ids), fault=None):
        loss, grads = ref.loss_and_grads(before, ids[:rows], cell.cfg(fault), cell.lo, lower=lower)
        grads = ref.flatten(grads)
        return {"loss": loss, "state": lambda k: ref.adam_first_step(
            want["before"][k], grads[k], cell.step_lr, lower)}

    def step_of(**kw):
        return lambda sub: tt.judge_step(sub, cell, files(**kw), want)

    def forward_of(lower=False, fault=None):
        return lambda sub: tt.judge_forward(sub, cell, ref.tag_logit_difference(
            params, cell.head["bins"], cell.cfg(fault), cell.lo, cell.column_bins, lower=lower),
            scores, params)
    zeros = lambda k: np.zeros_like(want["before"][k])
    tt._refused(ctx, "lower_precision", step_of(lower=True), forward_of(lower=True))
    tt._refused(ctx, "dropped_pairs", step_of(fault=DROPPED), forward_of(fault=DROPPED))
    tt._refused(ctx, "state_reset_every_chunk", step_of(fault=reset), forward_of(fault=reset))
    tt._refused(ctx, "softmax_router", step_of(fault={"softmax_router": True}),
                forward_of(fault={"softmax_router": True}))
    tt._refused(ctx, "mtp_left_out", step_of(fault={"no_mtp": True}))
    tt._refused(ctx, "half_batch", step_of(rows=len(ids) // 2))
    tt._refused(ctx, "state_unchanged", lambda sub: tt.judge_step(sub, cell, {
        "loss": got["loss"], "state": lambda k: (zeros(k), zeros(k), want["before"][k])}, want))


# ------------------------------------------------------------ the window
def counters(ctx, cell: NemotronCell) -> None:
    """What the traced jobs' telemetry counted, for the readers."""
    get = lambda name: ms.telemetry_counter(cell.mdir, name)
    c = {k: get("tower." + k) for k in ("moe_pairs_max_expert", "moe_pairs_mean_expert",
                                        "dropped_pairs", "positions", "mtp_loss_sum", "ssm_chunks")}
    if not c["positions"]:
        return                                  # a program without these counters
    ctx.counters.update({"tower." + k: v for k, v in c.items()})
    ctx.check("moe.dropped_pairs", c["dropped_pairs"] == 0, f"{c['dropped_pairs']:.0f} pairs dropped")
    steps = -(-round(cell.rows * (1.0 - float(cell.config["train"]["validSetRate"]))) // cell.microbatch)
    epochs = get("train.epochs")
    pairs = c["moe_pairs_mean_expert"] * int(cell.tp["n_routed_experts"]) / max(steps * epochs, 1)
    ctx.say(f"counters: {c['positions']:.0f} positions, {c['ssm_chunks']:.0f} scan chunks, MTP "
            f"cross-entropy {c['mtp_loss_sum'] / max(c['positions'], 1):.4f} a position, pairs a "
            f"held expert max/mean {c['moe_pairs_max_expert'] / c['moe_pairs_mean_expert']:.3f}, "
            f"{pairs:.0f} pairs a layer a step, dropped {c['dropped_pairs']:.0f}")
    ctx.counters["params"] = {"tower": "nemotron_h", "cfg": cell.tp, "rows": cell.microbatch,
                              "seq": cell.seq, "pairs_per_layer": pairs}
    ctx.counters["op_scopes"] = tt._op_scopes(cell.mdir)


def _finish(ctx, cell: NemotronCell, win: dict) -> dict:
    if ctx.trace:
        counters(ctx, cell)
    return jobs.finish(ctx, win, {"train_rate": (win["train_rate"], "rows.iters/s"),
                                  "setup_s": (win["setup_s"], "s")})


def run(ctx, t_start: float) -> dict:
    cell = NemotronCell(ctx)
    cell.build()
    one_step(ctx, cell)
    cell.full_planes()
    cell.warm_up()
    tt.learning(ctx, cell)
    forward(ctx, cell, cell.mdir)
    return _finish(ctx, cell, cell.window(t_start))


def check_only(ctx, full_jobs: int = 0) -> None:
    """Set-up and ``correct`` alone, then every control through the same
    limits; with ``full_jobs`` also (c)'s control, one more full-size job."""
    cell = NemotronCell(ctx)
    cell.build()
    step = one_step(ctx, cell)
    cell.full_planes()
    cell.warm_up()
    tt.learning(ctx, cell)
    fwd = forward(ctx, cell, cell.mdir)
    if step:
        controls(ctx, cell, step, fwd)
    if full_jobs:
        tt.unchanged_job(ctx, cell)
