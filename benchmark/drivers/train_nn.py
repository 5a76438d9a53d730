"""NN training cells: jobs of ``numTrainEpochs`` epochs through ``cli train``
on a normalised f32 plane; ``correct`` holds the written model's forward and
the job's learning to the plain reference."""

from __future__ import annotations

import os
import shutil

import numpy as np

from .. import jobs, modelset as ms
from ..reference import mlp as ref


def _weights(path: str):
    from shifu_tpu.models.nn import load_model             # the public loader
    spec, params = load_model(path)
    return spec, [(np.asarray(layer["w"]), np.asarray(layer["b"])) for layer in params]


def learning(ctx, cell: jobs.TrainCell) -> None:
    """(b): one job on the small sample.  It learns: its last *training*
    error is below its first (at these widths 40 epochs drive it down a
    thousandfold, so the margin is wide on every seed).  And its last
    validation error lies inside the band the *reference itself* shows over
    ``reference_runs`` trainings of the same sample (own init, split and
    shuffles): mean +- ``band_multiple`` x their spread.  Four runs estimate
    a spread poorly, so it is taken no smaller than ``band_floor_rel`` of the
    mean — the median relative spread the reference showed over the 32-seed
    sweep on the chip.  The validation curve *between* its ends is not
    judged: the job overfits, the curve turns round near epoch 10, and there
    a step more or less moves it by more than any band (the first sweep
    failed 2 seeds of 32 at epoch 11)."""
    spec_c = ctx.cell["correct"]
    with ctx.part("correct"):
        data = cell.check_set()
        cell.set_iterations(cell.cdir)
        cell.job(cell.cdir)
        lines = ms.progress_lines(cell.cdir)
        if not ctx.check("learn.epochs", len(lines) == cell.iters, f"{len(lines)} epochs"):
            return
        ctx.margin("learn.train_error_falls", lines[-1][0] / lines[0][0], 1.0,
                   f"(training error {lines[0][0]} -> {lines[-1][0]})")
        p = cell.config["train"]["params"]
        runs = ref.train_adam(data["x"], data["y"], list(p["NumHiddenNodes"]), cell.iters,
                              int(p["MiniBatchs"]), float(p["LearningRate"]),
                              float(cell.config["train"]["validSetRate"]),
                              int(spec_c["reference_runs"]), ctx.seed)
        mean = float(runs["last"].mean())
        spread = max(float(runs["last"].std(ddof=1)), float(spec_c["band_floor_rel"]) * mean)
        ctx.say(f"learning: program valid {lines[0][1]:.5f} -> {lines[-1][1]:.5f}, train "
                f"{lines[0][0]:.6f} -> {lines[-1][0]:.6f}; reference last valid "
                f"{np.round(runs['last'], 5).tolist()}")
        ctx.margin("learn.last_valid_vs_reference", abs(lines[-1][1] - mean),
                   float(spec_c["band_multiple"]) * spread)


def forward(ctx, cell: jobs.TrainCell) -> None:
    """(a): the warm-up job's written model, scored by ``eval`` on the
    sample's rows, against the reference forward of the same weights."""
    spec_c = ctx.cell["correct"]
    with ctx.part("correct"):
        got = cell.eval_step(cell.mdir) / 1000.0
        _, weights = _weights(os.path.join(cell.mdir, "models", "model0.nn"))
        want, sigma = ref.forward64(weights, cell.head["x"])
        if not ctx.check("forward.rows", len(got) == len(want), f"{len(got)} scores"):
            return
        # forward_sigmas is measured: over 32 seeds x 4,096 rows the chip's
        # worst row sat at 9.5 sigma of the model (median over seeds; 10.75 the
        # largest) where the NumPy emulation of bf16 operands sits at 4 — the
        # chip rounds about 2.3 times more than the model, on every seed alike.
        # eval keeps three decimals of 0..1000: half a unit of the last place
        tol = float(spec_c["forward_sigmas"]) * sigma + 0.5e-6
        worst = float(np.max(np.abs(got - want) / tol))
        ctx.say(f"forward: max |eval - reference| {np.abs(got - want).max():.3e}, "
                f"sigma median {np.median(sigma):.3e}, worst case at {worst:.3f} of its tolerance")
        ctx.margin("forward.eval_vs_reference", worst, 1.0)


def run(ctx, t_start: float) -> dict:
    cell = jobs.TrainCell(ctx)
    cell.build()
    learning(ctx, cell)
    cell.full_planes()
    cell.warm_up()
    forward(ctx, cell)
    win = cell.window(t_start)
    if ctx.trace:
        p = cell.config["train"]["params"]
        widths = [cell.table.width] + list(p["NumHiddenNodes"]) + [1]
        ctx.counters["params"] = {
            "macs_per_row": sum(a * b for a, b in zip(widths[:-1], widths[1:])),
            "train_rows": cell.rows * (1.0 - float(cell.config["train"]["validSetRate"]))}
    return jobs.finish(ctx, win, {"train_rate": (win["train_rate"], "rows.iters/s"),
                                  "setup_s": (win["setup_s"], "s")})


def check_only(ctx, full_jobs: int = 0) -> None:
    cell = jobs.TrainCell(ctx)
    cell.build()
    learning(ctx, cell)
    if full_jobs:
        cell.full_planes()
        cell.warm_up()
        forward(ctx, cell)
        for i in range(full_jobs - 1):
            cell.job()
            cell.same_as_baseline(f"job{i + 2}")
    else:
        # the forward check needs a written model: the small job's serves
        shutil.copytree(os.path.join(cell.cdir, "models"), os.path.join(cell.mdir, "models"),
                        dirs_exist_ok=True)
        forward(ctx, cell)
