"""GBT training cells: jobs of ``TreeNum`` trees through ``cli train`` on a
binned plane; ``correct`` holds the written forest to the plain reference."""

from __future__ import annotations

import json
import os

import numpy as np

from .. import jobs, modelset as ms
from ..reference import gbt as ref


def correct(ctx, cell: jobs.TrainCell) -> None:
    """The check's own small job (``validSetRate`` 0, ``FeatureSubsetStrategy``
    ALL — its two departures, so the reference knows which rows and columns a
    tree saw), its first tree against the reference node by node, and the
    whole forest walked by the reference against ``eval``'s scores."""
    from shifu_tpu.models.tree import load_model          # the public loader
    spec_c = ctx.cell["correct"]
    with ctx.part("correct"):
        data = cell.check_set(params={"FeatureSubsetStrategy": "ALL"}, validSetRate=0.0)
        cell.set_iterations(cell.cdir)
        cell.job(cell.cdir)
        spec, trees = load_model(os.path.join(cell.cdir, "models", "model0.gbt"))
        params = cell.config["train"]["expect_params"]
        ctx.check("forest.trees", len(trees) == cell.iters, f"{len(trees)} trees written")
        with open(os.path.join(cell.cdir, "ColumnConfig.json")) as f:
            by_num = {c["columnNum"]: c for c in json.load(f)}
        cat_mask = np.array([by_num[cn]["columnType"] == "C" for cn in spec.column_nums])
        y = data["y"].astype(np.float64)
        res = ref.check_first_tree(trees[0], data["bins"], y, cat_mask, spec.n_bins,
                                   float(params["MinInstancesPerNode"]),
                                   float(params["MinInfoGain"]), float(spec_c["hist_rel_band"]))
        ctx.say(f"first tree vs reference: {res}")
        ctx.check("tree.decisive_mismatch", res["mismatch"] == 0,
                  f"{res['mismatch']} decisive nodes differ in column or left rows")
        ctx.check("tree.leaf_disagree", res["leaf_disagree"] == 0,
                  f"{res['leaf_disagree']} leaves where a split was clearly worth it")
        # every node is judged (by regret), so the decisive share is not what
        # gives the check its coverage; with 64 fine bins the neighbouring
        # threshold is often inside the band, and about two thirds of the
        # nodes are decisive.  Under a quarter would mean a sample too small.
        if ctx.check("tree.internal", res["internal"] > 0, "the first tree has no split"):
            ctx.margin("tree.decisive_share", 0.25 * res["internal"] / max(res["decisive"], 1e-9), 1.0,
                       f"({res['decisive']} of {res['internal']} internal nodes decisive)")
        ctx.margin("tree.regret_over_band", res["worst_regret_over_band"], 1.0)
        ctx.margin("tree.leaf_value", res["worst_leaf_err"], float(spec_c["leaf_abs_tol"]))
        ctx.margin("tree.prior", abs(spec.init_score - res["prior"]), float(spec_c["leaf_abs_tol"]))
        # the whole forest, walked by the reference over the sample's rows as
        # the program binned them, gives eval's scores (0..1000, 3 decimals)
        got = cell.eval_step(cell.cdir)
        want = 1000.0 * ref.forest_score(trees, cell.head["bins"], spec.init_score,
                                         spec.learning_rate)
        if ctx.check("eval.rows", len(got) == len(want), f"{len(got)} scores for {len(want)} rows"):
            ctx.margin("eval.score_vs_reference_walk", float(np.abs(got - want).max()),
                       float(spec_c["score_tol"]))


def _prepare(ctx) -> jobs.TrainCell:
    cell = jobs.TrainCell(ctx)
    cell.build()
    correct(ctx, cell)
    return cell


def run(ctx, t_start: float) -> dict:
    cell = _prepare(ctx)
    cell.full_planes()
    cell.warm_up()
    win = cell.window(t_start)
    if ctx.trace:
        ctx.counters["params"] = cell.config["train"]["expect_params"]
        ctx.counters["rows_per_chip"] = cell.rows / int(ctx.cell["chips"])
        syncs = ms.telemetry_counter(cell.mdir, "train.host_syncs")
        if syncs:
            # the warm-up job ran with telemetry too: one more job's worth
            ctx.counters["train.host_syncs"] = syncs
            ctx.counters["telemetry_jobs"] = ctx.counters["jobs"] + 1
    return jobs.finish(ctx, win, {"train_rate": (win["train_rate"], "rows.iters/s"),
                                  "setup_s": (win["setup_s"], "s")})


def check_only(ctx, full_jobs: int = 0) -> None:
    cell = _prepare(ctx)
    if full_jobs:
        cell.full_planes()
        cell.warm_up()
        for i in range(full_jobs - 1):
            cell.job()
            cell.same_as_baseline(f"job{i + 2}")
