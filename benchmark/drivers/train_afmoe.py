"""The ``afmoe`` tower's training cells: jobs of ``numTrainEpochs`` epochs
through ``cli train`` with ``algorithm: TENSORFLOW``, ``Tower: afmoe`` on the
binned plane, ``RowsPerSequence`` rows packed a sequence; ``correct`` holds
what the CLI wrote — the saved tower's scores, the trainer state after one
step, the progress lines — to the plain reference (``reference/afmoe.py``), in
the three parts and through the ``judge_*`` functions of
``drivers/train_tower.py``, and the selection bias after the step to the
reference's rule.

The configuration's file holds config.json's keys at its top level; the
driver hands them to the program verbatim as ``train#params.TowerParams``,
with the share's two keys from ``deployment``.  ``--rehearse`` overlays the
cell's toy ``tower_params`` and ``train_params``.

Units.  As in ``train_tower.py``: a matmul on bfloat16 operands carries a
relative error of about EPS = 1.6e-3 of its own scale; a layer puts six in
sequence on the residual path (q/k/v, scores, values, output; gate/up, down)
and the head one more: UNIT = EPS sqrt(6 L + 1), 8.9e-3 at L = 5.

(b) is the part that sees the packing and the window: ``eval`` scores one row
a sequence (432 positions, inside every window; (a) scores the one-step job's
tower, whose scores are not yet saturated), the one-step job trains one
packed sequence of the timed length.  The selection bias moves by
``load_balance_coeff x sign(mean(n) - n_e)``: it has to equal the reference's
exactly wherever the reference's count is further than ``bias_margin_tokens``
from the mean (operand rounding flips a few positions' last choice, so a
count within a few tokens of the mean may fall on either side).

The forward's 99th percentile is the third-worst row of 256: a held expert
flipped at the tag's own position moves a row by 15-40 units and one row in
fifty has one, so its limit holds only the gross faults and the 90th
percentile's the rest.

Limits (the cell's ``correct`` block; ``PERF.md`` gives the two readings behind
each): each lies between what sound runs read over seeds and what a control
reads.  ``--check-seeds`` puts every control through the same limits
(:func:`controls`): the four the other tower cells have (the reference one
precision lower, dropped pairs, half the microbatch, a state left unchanged)
and this configuration's own — every layer full, the window on the full layer
too, rotary on the full layer, the gate left out, the two post-norms left
out, the shared expert left out, rows not packed, the bias never moved — each
has to come out as not correct, and a control that passes fails the check.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

from shifu_tpu.models.towers import load_model, module   # the towers' public loader and lookup:
                                                         # a program without them cannot run this cell

from .. import jobs, modelset as ms
from ..reference import afmoe as ref
from . import train_tower as tt
from .train_nemotron import NOT_CONFIG_JSON     # a configuration file's keys that are not config.json's

SHARE = ("expert_parallel_size", "expert_parallel_index")


def tower_params(doc: dict, over=None) -> dict:
    """``train#params.TowerParams`` of a configuration's file: config.json's
    keys verbatim, the share's two keys from ``deployment``, then ``over``."""
    tp = {k: v for k, v in doc.items() if k not in NOT_CONFIG_JSON}
    tp.update({k: doc["deployment"][k] for k in SHARE})
    tp.update(over or {})
    return tp


class AfmoeCell(jobs.TrainCell):
    def __init__(self, ctx):
        super().__init__(ctx)
        doc = self.config
        module(doc["tower"])            # a program without this tower fails here, at once
        self.tp = tp = tower_params(doc, ctx.cell.get("tower_params"))     # --rehearse: toy widths
        self.lo = int(tp["num_experts"]) * int(tp["expert_parallel_index"])
        self.unit = tt.EPS * math.sqrt(6 * int(tp["num_hidden_layers"]) + 1)
        self.step_lr = float(doc["train"]["params"]["LearningRate"])
        self.config = {**doc, "train": {**doc["train"], "params": {
            **doc["train"]["params"], **ctx.cell.get("train_params", {}), "TowerParams": tp}}}

    def build(self) -> None:
        super().build()
        params = self.config["train"]["params"]
        self.column_bins = ms.column_bins(self.mdir, self.schema).tolist()
        self.microbatch = int(params["MiniBatchs"])
        self.pack = int(params.get("RowsPerSequence", 1))
        self.block = int(self.tp.get("attention_block", 512))
        self.pad_id = ref.special_ids(self.column_bins)["PAD"]
        used = self.pack * (len(self.column_bins) + 1)
        self.seq = -(-used // self.block) * self.block          # a packed sequence's positions

    def saved_tower(self, mdir: str) -> dict:
        return load_model(os.path.join(mdir, "models", "model0.tower"))[1]

    def cfg(self, fault=None) -> dict:
        return {**self.tp, **(fault or {})}

    def packed(self, ids, rows_per_sequence=None):
        return ref.pack(ids, np.ones(len(ids), np.float32), rows_per_sequence or self.pack,
                        self.block, self.pad_id)


# ---------------------------------------------------------- (b) one step
def one_step(ctx, cell: AfmoeCell):
    """(b): a one-step job on the timed plane's shape — as many rows, so the
    step program is the timed one — with all but one microbatch (one packed
    sequence of the timed length) given to validation, one epoch and a
    checkpoint after it (its departures), at the configuration's learning
    rate.  Returns (the job's files, the reference's side) for
    :func:`judge_step`, or None."""
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
        biases = [k for k in names if k.endswith(".bias")]         # no gradient reaches them
        want = {"names": [k for k in names if k not in biases], "bias_names": biases, "before": before, "loss": loss,
                "grads": ref.flatten(grads), "tokens": tokens, "batch": ids}
        judge_step(ctx, cell, got, want)
    return got, want


def judge_step(ctx, cell: AfmoeCell, got: dict, want: dict) -> None:
    """``train_tower.judge_step`` over every array a gradient reaches, then the
    selection bias: after the step it equals the reference's rule's, exactly,
    on every expert whose reference count is more than ``bias_margin_tokens``
    from the mean; Adam must have left it alone (m = v = 0)."""
    tt.judge_step(ctx, cell, got, want)
    coeff = float(cell.tp.get("load_balance_coeff", 0.001))
    margin = float(ctx.cell["correct"]["bias_margin_tokens"])
    wrong = sure = 0
    moments = 0.0
    for name, tokens in zip(want["bias_names"], want["tokens"]):
        m, v, after = got["state"](name)
        expect = ref.bias_after(want["before"][name], tokens, coeff)
        far = np.abs(tokens - tokens.mean()) > margin
        wrong += int(np.sum(after[far] != expect[far]))
        sure += int(far.sum())
        moments = max(moments, float(np.abs(m).max()), float(np.abs(v).max()))
    ctx.say(f"selection bias: {wrong} of {sure} entries (of {sum(len(t) for t in want['tokens'])}; "
            f"the others lie within {margin:.0f} tokens of the mean) differ from the reference's "
            f"rule; Adam's moments of it at most {moments:.3g}")
    ctx.check("step.bias_vs_reference", wrong == 0 and sure > 0 and moments == 0.0,
              f"{wrong} of {sure} sure entries differ, Adam's moments {moments:.3g}")


# ------------------------------------------------------------ (a) forward
def forward(ctx, cell: AfmoeCell, mdir: str):
    """(a): a job's saved tower, scored by ``eval`` on the sample's rows
    (one row a sequence), against the reference's tag-logit difference for
    the same weights.  The tower is the one-step job's: after a whole job
    (48 Adam steps, each moving the head's 2 x 2,048 tag weights by 1e-4 with
    the gradient's sign) every score lies under 6 of 1000, where ``eval``'s
    three decimals are coarser than the differences judged.  Returns (the
    weights, the reference's differences)."""
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
FAULTS = {"every_layer_full": {"all_full": True}, "window_on_full_layer": {"window_on_full": True},
          "rotary_on_full_layer": {"rotary_on_full": True}, "gate_left_out": {"no_gate": True},
          "post_norms_left_out": {"no_post_norms": True}, "shared_expert_left_out": {"no_shared": True}}
CONTROL_ARRAY = 2 ** 23
SEEN_BY_EVAL = ("rotary_on_full_layer", "gate_left_out", "post_norms_left_out",
                "shared_expert_left_out")       # one row a sequence: no window, no packing


def controls(ctx, cell: AfmoeCell, step, fwd) -> None:
    """What the limits are held against, each judged as a run's own files
    are: a trainer's files after one step as the reference would have left
    them, and the reference's own scores of the saved tower — computed one
    precision lower, with dropped pairs, and with each of ``FAULTS``; the step
    alone with the rows not packed (each its own sequence), on half the
    microbatch, with the bias never moved and with the state left unchanged.
    The step's judge sees the arrays of up to ``CONTROL_ARRAY`` elements."""
    (got, want), (params, scores) = step, fwd
    ids = want["batch"]
    before = ref.nest(want["before"])
    # a control is judged on the arrays of up to CONTROL_ARRAY elements (attention, norms, routers,
    # the shared expert: 170 M of the 705 M): fewer arrays can only pass more easily, and judging
    # every array of every control on the host takes a quarter of an hour a seed
    want = {**want, "names": [k for k in want["names"] if want["before"][k].size <= CONTROL_ARRAY]}
    coeff = float(cell.tp.get("load_balance_coeff", 0.001))

    def files(lower=False, rows=len(ids), fault=None, move_bias=True):
        loss, grads, tokens = ref.loss_and_grads(before, *cell.packed(ids[:rows], min(rows, cell.pack)), cell.pad_id,
                                                 cell.cfg(fault), cell.lo, lower=lower)
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
    for name, fault in FAULTS.items():
        tt._refused(ctx, name, step_of(fault=fault),
                    *([forward_of(fault=fault)] if name in SEEN_BY_EVAL else []))
    tt._refused(ctx, "rows_not_packed", step_of(fault={"segment": len(cell.column_bins) + 1}))
    tt._refused(ctx, "half_batch", step_of(rows=len(ids) // 2))
    tt._refused(ctx, "bias_never_moved", step_of(move_bias=False))
    tt._refused(ctx, "state_unchanged", lambda sub: judge_step(sub, cell, {
        "loss": got["loss"], "state": lambda k: (zeros(k), zeros(k), want["before"][k])}, want))


# ------------------------------------------------------------ the window
COUNTERS = ("moe_pairs_max_expert", "moe_pairs_mean_expert", "moe_rows_computed", "dropped_pairs",
            "positions", "attn_key_blocks", "attn_key_blocks_dense", "pad_positions",
            "sequence_positions", "router_bias_absmax")


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
            f"of which {c['pad_positions']:.0f} PAD, key blocks visited {c['attn_key_blocks']:.0f} of "
            f"{c['attn_key_blocks_dense']:.0f} a full sweep, max |selection bias| "
            f"{c['router_bias_absmax']:.4f}, pairs a held expert max/mean "
            f"{c['moe_pairs_max_expert'] / c['moe_pairs_mean_expert']:.3f}, {pairs:.0f} pairs a layer "
            f"a step, dropped {c['dropped_pairs']:.0f}")
    ctx.counters["params"] = {"tower": "afmoe", "cfg": cell.tp, "seq": cell.seq,
                              "sequences": cell.microbatch // cell.pack, "pairs_per_layer": pairs}
    ctx.counters["op_scopes"] = tt._op_scopes(cell.mdir)


def _finish(ctx, cell: AfmoeCell, win: dict) -> dict:
    if ctx.trace:
        counters(ctx, cell)
    return jobs.finish(ctx, win, {"train_rate": (win["train_rate"], "rows.iters/s"),
                                  "setup_s": (win["setup_s"], "s")})


def run(ctx, t_start: float) -> dict:
    cell = AfmoeCell(ctx)
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
