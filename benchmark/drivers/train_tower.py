"""Tower training cells: jobs of ``numTrainEpochs`` epochs through ``cli train``
with ``algorithm: TENSORFLOW``, ``Tower: sdar_moe`` on the binned plane;
``correct`` holds what the CLI wrote — the saved tower's scores, the trainer
state after one step, the progress lines — to the plain reference.

The configuration's file holds config.json's keys at its top level; the
driver hands them to the program as ``train#params.TowerParams`` (with the
share: ``expert_parallel_size`` / ``_index`` from ``deployment``, and the
assumed ``block_length``).  ``--rehearse`` overlays the cell's toy
``tower_params`` and ``train_params`` (a width of 64 learns nothing in eight
steps at the configuration's learning rate).

Units.  With f32 parameters and the TPU's default matmul operands every
product's two operands are rounded to bfloat16 (relative step 2^-8, so a
rounding error of standard deviation 2^-9/sqrt(3) each): a matmul's output
carries a relative error of about EPS = sqrt(2) 2^-9 / sqrt(3) = 1.6e-3 of
its own scale.  A layer puts six matmuls in sequence on the residual path
(q/k/v, scores, values, output; gate/up, down) and the head one more, errors
adding in quadrature: UNIT = EPS sqrt(6 L + 1), 8e-3 at L = 4.

Limits (the cell's ``correct`` block; ``PERF.md`` gives the two readings
behind each).  Operand rounding flips the 8th/9th expert of a few (token,
layer) pairs in a hundred, so the distances of forward, loss and gradient to
the reference are the same whether parameters are kept in f32 or in
bfloat16: their limits sit between what sound runs read and what planted
faults read (half the microbatch, dropped pairs).  What tells the precisions
apart is the parameters' change after one step, which flips do not move:
Adam's first step moves every element by about the learning rate, 1e-4,
where bfloat16 parameters at 0.02 are 1.2e-4 apart.  ``--check-seeds`` puts
each control through the same limits (:func:`controls`): the reference one
precision lower, half the microbatch, dropped pairs, a state left unchanged —
each has to come out as not correct, and a control that passes fails the check.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

from .. import jobs, modelset as ms
from ..reference import sdar_moe as ref

EPS = math.sqrt(2.0) * 2.0 ** -9 / math.sqrt(3.0)
CONFIG_KEYS = ("attention_bias", "decoder_sparse_step", "head_dim", "hidden_act", "hidden_size",
               "intermediate_size", "max_position_embeddings", "max_window_layers",
               "mlp_only_layers", "model_type", "moe_intermediate_size", "norm_topk_prob",
               "num_attention_heads", "num_experts", "num_experts_per_tok", "num_hidden_layers",
               "num_key_value_heads", "rms_norm_eps", "rope_scaling", "rope_theta",
               "sliding_window", "tie_word_embeddings", "use_sliding_window", "vocab_size")
SURE = 1.0      # the update is compared where |reference gradient| >= SURE x its array's rms


class TowerCell(jobs.TrainCell):
    def __init__(self, ctx):
        super().__init__(ctx)
        doc = self.config
        tp = {k: doc[k] for k in CONFIG_KEYS if k in doc}
        tp.update(block_length=doc["block_length"],
                  expert_parallel_size=doc["deployment"]["expert_parallel_size"],
                  expert_parallel_index=doc["deployment"]["expert_parallel_index"])
        tp.update(ctx.cell.get("tower_params", {}))             # --rehearse: toy widths
        self.tp = tp
        self.block = int(tp["block_length"])
        self.lo = int(tp["num_experts"]) * int(tp["expert_parallel_index"])
        self.unit = EPS * math.sqrt(6 * int(tp["num_hidden_layers"]) + 1)
        self.step_lr = float(doc["train"]["params"]["LearningRate"])
        self.config = {**doc, "train": {**doc["train"], "params": {
            **doc["train"]["params"], **ctx.cell.get("train_params", {}), "TowerParams": tp}}}

    def build(self) -> None:
        super().build()
        self.column_bins = ms.column_bins(self.mdir, self.schema).tolist()
        self.microbatch = int(self.config["train"]["params"]["MiniBatchs"])
        self.seq = -(-len(self.column_bins) // self.block) * self.block + self.block

    def saved_tower(self, mdir: str) -> dict:
        """The tower file's arrays, nested as the reference takes them."""
        from shifu_tpu.models.tower_sdar import load_model          # the public loader
        return load_model(os.path.join(mdir, "models", "model0.tower"))[1]

    def reference(self, fn, *args, fault=None, **kw):
        """``ref.fn(..., cfg, lo, column_bins, block)`` with the cell's share."""
        return fn(*args, {**self.tp, **(fault or {})}, self.lo, self.column_bins, self.block, **kw)


def _get(params: dict, name: str):
    return params["layers"][name[7:]] if name.startswith("layers.") else params[name]


def _rel(a, b) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64)) /
                 max(np.linalg.norm(np.asarray(b, np.float64)), 1e-30))


def _spread(units: np.ndarray) -> str:
    p50, p90, p99 = np.percentile(units, [50, 90, 99])
    return (f"median {p50:.3f}, 90th percentile {p90:.3f}, 99th {p99:.3f}, worst row "
            f"{units.max():.3f}, rms {np.sqrt(np.mean(units ** 2)):.3f}")


# ---------------------------------------------------------- (b) one step
def one_step(ctx, cell: TowerCell):
    """(b): a one-step job on the timed plane's shape — as many rows, so the
    step program is the timed one — with all but one microbatch given to
    validation, one epoch and a checkpoint after it (its departures), at the
    configuration's learning rate.  Returns (the job's files, the
    reference's side) for :func:`judge_step`, or None."""
    with ctx.part("correct"):
        rate = 1.0 - cell.microbatch / int(ctx.cell["correct"]["sample_job_rows"])
        data = cell.check_set(params={"CheckpointInterval": 1, "LearningRate": cell.step_lr},
                              validSetRate=rate, numTrainEpochs=1)
        cell.job(cell.cdir)
        lines = ms.progress_lines(cell.cdir)
        if not ctx.check("step.epochs", len(lines) == 1, f"{len(lines)} progress lines"):
            return None
        # the parameter names in the order jax flattens the tree (sorted keys)
        files = np.load(os.path.join(cell.cdir, "models", "model0.tower")).files
        names = sorted(k for k in files if "." not in k and k != "__spec__") + \
            sorted(k for k in files if k.startswith("layers."))
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
        # the reference's side: the seed's split, order, noise and initial
        # parameters restated; its own loss, gradient and Adam step
        train, _ = ref.split_rows(len(data["y"]), rate, 0)
        if not ctx.check("step.rows", len(train) == cell.microbatch, f"{len(train)} training rows"):
            return None
        rows = train[ref.epoch_order(0, 0, len(train))]
        x0 = ref.rows_to_ids(data["bins"][rows], data["y"][rows], cell.column_bins, cell.block)
        t, masked = ref.noise(0, 0, 0, len(rows), x0.shape[1], cell.block)
        before = ref.init_params(0, cell.tp)
        loss, grads = cell.reference(ref.loss_and_grads, before, x0, t, masked)
        want = {"names": names, "before": before, "loss": loss, "grads": grads,
                "batch": (x0, t, masked)}
        judge_step(ctx, cell, got, want)
    return got, want


def judge_step(ctx, cell: TowerCell, got: dict, want: dict) -> None:
    """A trainer's files after one step against the reference.  ``got``:
    the first progress line's loss and ``state(name)`` -> (m, v, the parameter
    after).  Four numbers, each the worst array's: the loss; the gradient
    m / (1 - b1); Adam's v as |g| = sqrt(v / (1 - b2)); and the parameters'
    change against the reference's own Adam step from the reference's own
    initial parameters, over the elements whose reference gradient is at
    least ``SURE`` x its array's rms (Adam's first step is lr x the
    gradient's sign: elsewhere it hangs on the gradient's last digits) — 1
    is what a state left unchanged reads."""
    spec_c = ctx.cell["correct"]
    grad, second, update = {}, {}, {}
    for name in want["names"]:
        m, v, after = got["state"](name)
        g, before = _get(want["grads"], name), _get(want["before"], name)
        grad[name] = _rel(m / (1.0 - ref.ADAM_B1), g)
        second[name] = _rel(np.sqrt(v / (1.0 - ref.ADAM_B2)), np.abs(g))
        sure = np.abs(g) >= SURE * np.sqrt(np.mean(np.square(g, dtype=np.float64)))
        step = (ref.adam_first_step(before, g, cell.step_lr)[2] - before)[sure]
        update[name] = _rel((after - before)[sure], step)
    worst = lambda d: max((x, k) for k, x in d.items())
    by_array = lambda d, scale: ", ".join(f"{k} {x / scale:.3g}" for k, x in d.items())
    ctx.say(f"one step: loss {got['loss']:.6f} against the reference's {want['loss']:.6f}; "
            f"unit {cell.unit:.3e}; gradient in units by array: {by_array(grad, cell.unit)}; "
            f"second moment worst {worst(second)[0] / cell.unit:.2f} units ({worst(second)[1]}); "
            f"parameters' change by array: {by_array(update, 1.0)}")
    ctx.margin("step.loss_vs_reference", abs(got["loss"] - want["loss"]) / abs(want["loss"]),
               float(spec_c["loss_units"]) * cell.unit)
    ctx.margin("step.gradient_vs_reference", worst(grad)[0],
               float(spec_c["gradient_units"]) * cell.unit, worst(grad)[1])
    ctx.margin("step.second_moment_vs_reference", worst(second)[0],
               float(spec_c["gradient_units"]) * cell.unit, worst(second)[1])
    ctx.margin("step.update_vs_reference", worst(update)[0], float(spec_c["update_limit"]),
               worst(update)[1])


# ------------------------------------------------------------ (a) forward
def forward(ctx, cell: TowerCell, mdir: str):
    """(a): the job's saved tower, scored by ``eval`` on the sample's rows,
    against the reference's tag-logit difference for the same weights.
    Returns (the weights, the reference's differences) for the controls."""
    with ctx.part("correct"):
        p = np.clip(cell.eval_step(mdir) / 1000.0, 1e-6, 1.0 - 1e-6)
        params = cell.saved_tower(mdir)
        want = cell.reference(ref.tag_logit_difference, params, cell.head["bins"])
        if ctx.check("forward.rows", len(p) == len(want), f"{len(p)} scores"):
            judge_forward(ctx, cell, np.log(p / (1.0 - p)), want, params, decimals=True)
    return params, want


def judge_forward(ctx, cell: TowerCell, got, want, params, decimals: bool = False) -> None:
    """Tag-logit differences against the reference's, in units of UNIT x
    |head[TAG1] - head[TAG0]| (the scale a unit-RMS hidden state gives that
    difference).  Two limits, on the 90th and the 99th percentile over the
    rows: they hold the bulk to operand rounding, where a wrong mask, weight
    or dispatch moves every row.  The worst row is printed and not judged:
    an expert flipped at the tag's own position moves that row's logits by a
    whole expert's output, on some seeds as far as the faults do (``PERF.md``
    has the readings).  ``decimals``: ``eval`` keeps three decimals of
    0..1000, half a unit of the last place of the probability, carried to
    the logit."""
    spec_c = ctx.cell["correct"]
    sp = ref.special_ids(cell.column_bins)
    one = cell.unit * float(np.linalg.norm(params["head"][:, sp["TAG1"]] -
                                           params["head"][:, sp["TAG0"]]))
    pw = 1.0 / (1.0 + np.exp(-want))
    rounding = 0.5e-6 / (pw * (1.0 - pw)) if decimals else 0.0
    units = np.maximum(np.abs(got - want) - rounding, 0.0) / one
    ctx.say(f"forward: tag-logit difference against the reference over {len(want)} rows, in "
            f"units of {one:.3e}: {_spread(units)}; scores {1000 * pw.min():.1f}..{1000 * pw.max():.1f}")
    for q in (90, 99):
        ctx.margin(f"forward.p{q}_vs_reference", float(np.percentile(units, q)),
                   float(spec_c[f"forward_p{q}_units"]))


# ----------------------------------------------------------- (c) learning
def learning(ctx, cell: TowerCell, lines=None) -> None:
    """(c): a job's training loss falls from its first epoch to its last, to
    under ``loss_ratio_limit`` of it (a state left unchanged reads 1)."""
    lines = cell.baseline if lines is None else lines
    if len(lines) >= 2:
        ctx.margin("learn.train_loss_falls", lines[-1][0] / lines[0][0],
                   float(ctx.cell["correct"]["loss_ratio_limit"]),
                   f"(training loss {lines[0][0]} -> {lines[-1][0]})")


# --------------------------------------------------------------- controls
DROPPED = {"capacity_factor": 1.25}     # a dispatch with the usual capacity drops the pairs past it


def _refused(ctx, name: str, *judges) -> None:
    """Put one control through the cell's limits on a context of its own: it
    has to come out as not correct, by one limit at least."""
    sub = type(ctx)(ctx.cell, ctx.seed, ctx.seconds, False, ctx.rehearse)
    readings, inner = {}, sub.margin

    def margin(what, value, tolerance, detail=""):
        readings[what] = f"{value:.3g} of {tolerance:.3g}" + (" REFUSED" if abs(value) > tolerance else "")
        return inner(what, value, tolerance, detail)
    sub.say, sub.margin = (lambda msg: None), margin
    for judge in judges:
        judge(sub)
    ctx.say(f"CONTROL {name}: " + ("not correct" if sub.problems else "PASSED EVERY LIMIT") +
            "; reading of limit: " + json.dumps(readings))
    ctx.check(f"control.{name}", bool(sub.problems), "came out as correct")


def controls(ctx, cell: TowerCell, step, fwd) -> None:
    """What the limits are held against, each judged as a run's own files
    are: a trainer's files after one step as the reference would have left
    them, and the reference's own scores of the saved tower — computed one
    precision lower, and with dropped pairs; the step alone on half the
    microbatch, and with the state left unchanged."""
    (got, want), (params, scores) = step, fwd
    x0, t, masked = want["batch"]
    half = len(x0) // 2

    def files(lower=False, rows=len(x0), fault=None):
        loss, grads = cell.reference(ref.loss_and_grads, want["before"], x0[:rows], t[:rows],
                                     masked[:rows], fault=fault, lower=lower)
        return {"loss": loss, "state": lambda k: ref.adam_first_step(
            _get(want["before"], k), _get(grads, k), cell.step_lr, lower)}

    def step_of(**kw):
        return lambda sub: judge_step(sub, cell, files(**kw), want)

    def forward_of(**kw):
        return lambda sub: judge_forward(sub, cell, cell.reference(
            ref.tag_logit_difference, params, cell.head["bins"], **kw), scores, params)
    zeros = lambda k: np.zeros_like(_get(want["before"], k))
    _refused(ctx, "lower_precision", step_of(lower=True), forward_of(lower=True))
    _refused(ctx, "dropped_pairs", step_of(fault=DROPPED), forward_of(fault=DROPPED))
    _refused(ctx, "half_batch", step_of(rows=half))
    _refused(ctx, "state_unchanged", lambda sub: judge_step(sub, cell, {
        "loss": got["loss"], "state": lambda k: (zeros(k), zeros(k), _get(want["before"], k))}, want))


def unchanged_job(ctx, cell: TowerCell) -> None:
    """(c)'s control: the timed job at a learning rate that moves nothing."""
    ms.set_train(cell.mdir, params={"LearningRate": 1e-12})
    cell.job()
    lines = ms.progress_lines(cell.mdir)
    _refused(ctx, "unchanged_job", lambda sub: learning(sub, cell, lines))


# ------------------------------------------------------------ the window
def counters(ctx, cell: TowerCell) -> None:
    """What the traced jobs' telemetry counted, for the readers."""
    get = lambda name: ms.telemetry_counter(cell.mdir, name)
    c = {k: get("tower." + k) for k in ("moe_pairs_max_expert", "moe_pairs_mean_expert",
                                        "dropped_pairs", "masked_positions", "positions")}
    if not c["positions"]:
        return                                  # a program without these counters
    ctx.counters.update({"tower." + k: v for k, v in c.items()})
    ctx.check("moe.dropped_pairs", c["dropped_pairs"] == 0, f"{c['dropped_pairs']:.0f} pairs dropped")
    steps = -(-round(cell.rows * (1.0 - float(cell.config["train"]["validSetRate"]))) // cell.microbatch)
    epochs = get("train.epochs")
    pairs = c["moe_pairs_mean_expert"] * int(cell.tp["num_experts"]) / max(steps * epochs, 1)
    ctx.say(f"counters: masked share {c['masked_positions'] / c['positions']:.4f}, pairs a held "
            f"expert max/mean {c['moe_pairs_max_expert'] / c['moe_pairs_mean_expert']:.3f}, "
            f"{pairs:.0f} pairs a layer a step, dropped {c['dropped_pairs']:.0f}")
    ctx.counters["params"] = {"cfg": cell.tp, "rows": cell.microbatch, "seq": cell.seq,
                              "block": cell.block, "pairs_per_layer": pairs}
    ctx.counters["op_scopes"] = _op_scopes(cell.mdir)


def _op_scopes(mdir: str):
    """The step program's scope -> HLO instruction names, as the program's
    ``op_scopes`` event recorded them (None: a program that records none)."""
    path = os.path.join(mdir, "telemetry", "trace.jsonl")
    found = None
    if os.path.isfile(path):
        with open(path) as f:
            for line in f:
                if '"op_scopes"' not in line:
                    continue
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
                if doc.get("name") == "op_scopes":
                    found = doc["attrs"]["scopes"]
    return found


def _finish(ctx, cell: TowerCell, win: dict) -> dict:
    if ctx.trace:
        counters(ctx, cell)
    return jobs.finish(ctx, win, {"train_rate": (win["train_rate"], "rows.iters/s"),
                                  "setup_s": (win["setup_s"], "s")})


def run(ctx, t_start: float) -> dict:
    cell = TowerCell(ctx)
    cell.build()
    one_step(ctx, cell)
    cell.full_planes()
    cell.warm_up()
    learning(ctx, cell)
    forward(ctx, cell, cell.mdir)
    return _finish(ctx, cell, cell.window(t_start))


def check_only(ctx, full_jobs: int = 0) -> None:
    """Set-up and ``correct`` alone, then every control through the same
    limits; with ``full_jobs`` also (c)'s control, one more full-size job."""
    cell = TowerCell(ctx)
    cell.build()
    step = one_step(ctx, cell)
    cell.full_planes()
    cell.warm_up()
    learning(ctx, cell)
    fwd = forward(ctx, cell, cell.mdir)
    if step:
        controls(ctx, cell, step, fwd)
    if full_jobs:
        unchanged_job(ctx, cell)
