"""Eval stack tests — AUC parity cases (reference
``core/evaluation/AreaUnderCurveTest.java`` pattern) + end-to-end eval run."""

import csv
import json
import os

import numpy as np
import pytest

from shifu_tpu.eval.metrics import auc_trapezoid, evaluate_scores
from shifu_tpu.eval.scorer import Scorer, CaseScoreResult


def test_auc_perfect_classifier():
    scores = np.array([0.9, 0.8, 0.7, 0.2, 0.1])
    targets = np.array([1, 1, 1, 0, 0])
    res = evaluate_scores(scores, targets)
    assert res.areaUnderRoc == pytest.approx(1.0)


def test_auc_random_is_half():
    rng = np.random.default_rng(0)
    scores = rng.random(20000)
    targets = (rng.random(20000) < 0.3).astype(float)
    res = evaluate_scores(scores, targets)
    assert res.areaUnderRoc == pytest.approx(0.5, abs=0.02)


def test_auc_matches_rank_statistic():
    """AUC == P(score_pos > score_neg) (Mann-Whitney), the textbook identity."""
    rng = np.random.default_rng(1)
    scores = rng.normal(size=500)
    targets = (rng.random(500) < 0.4).astype(float)
    scores[targets == 1] += 1.0
    res = evaluate_scores(scores, targets)
    pos = scores[targets == 1]
    neg = scores[targets == 0]
    mw = (pos[:, None] > neg[None, :]).mean() + \
        0.5 * (pos[:, None] == neg[None, :]).mean()
    assert res.areaUnderRoc == pytest.approx(mw, abs=1e-6)


def test_device_sweep_matches_host_exactly():
    """Device sweep (sort/cumsum/tie-scans in HBM, one packed fetch) must
    reproduce the host sweep's AUC/wAUC/PR-AUC exactly — ties included —
    and its downsampled curve points must lie ON the host curve."""
    from shifu_tpu.eval.metrics import evaluate_scores_device, sweep

    rng = np.random.default_rng(5)
    n = 5000
    # heavy ties: quantized scores
    scores = np.round(rng.normal(size=n), 2)
    targets = (rng.random(n) < 0.3).astype(float)
    scores[targets == 1] += 0.5
    weights = rng.random(n) + 0.5
    host = evaluate_scores(scores, targets, weights)
    import jax
    enable_x64 = getattr(jax, "enable_x64", None)
    if enable_x64 is None:                 # jax<0.5 spells it experimental
        from jax.experimental import enable_x64
    with enable_x64():            # exactness check at f64 (TPU runs f32)
        curves, dev = evaluate_scores_device(scores, targets, weights)
    assert dev.areaUnderRoc == pytest.approx(host.areaUnderRoc, abs=1e-12)
    assert dev.weightedAuc == pytest.approx(host.weightedAuc, abs=1e-12)
    assert dev.areaUnderPr == pytest.approx(host.areaUnderPr, abs=1e-12)
    # default (f32, the TPU precision) stays within float tolerance
    _, dev32 = evaluate_scores_device(scores, targets, weights)
    assert dev32.areaUnderRoc == pytest.approx(host.areaUnderRoc, abs=2e-4)
    assert dev.recordCount == host.recordCount
    assert dev.posCount == pytest.approx(host.posCount)
    # every downsampled point must be an exact host tie-group end
    hc = sweep(scores, targets, weights)
    host_pts = {(round(t, 9), tp, fp)
                for t, tp, fp in zip(hc.thresholds, hc.tp, hc.fp)}
    for t, tp, fp in zip(curves.thresholds, curves.tp, curves.fp):
        assert (round(t, 9), tp, fp) in host_pts


def test_device_sweep_small_and_degenerate():
    from shifu_tpu.eval.metrics import evaluate_scores_device

    # n < points path + all-one-class degenerate
    scores = np.array([0.9, 0.8, 0.8, 0.1])
    targets = np.array([1.0, 1.0, 0.0, 0.0])
    _, res = evaluate_scores_device(scores, targets)
    host = evaluate_scores(scores, targets)
    assert res.areaUnderRoc == pytest.approx(host.areaUnderRoc, abs=1e-12)
    _, degen = evaluate_scores_device(scores, np.ones(4))
    assert np.isnan(degen.areaUnderRoc)


def test_score_device_matches_host_scorer():
    """score_device must agree with score() and feed sweep_device without
    leaving HBM (the device-resident eval plane)."""
    import jax
    import jax.numpy as jnp
    from shifu_tpu.models.nn import (IndependentNNModel, NNModelSpec,
                                     init_params)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 16)).astype(np.float32)
    spec = NNModelSpec(input_dim=16, hidden_nodes=[8, 4],
                       activations=["relu", "relu"], output_dim=1)
    models = [IndependentNNModel(spec, init_params(jax.random.PRNGKey(i),
                                                   spec))
              for i in range(3)]
    sc = Scorer(models)
    host = sc.score(x)
    raw_d, mean_d = sc.score_device(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(raw_d), host.scores,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mean_d), host.mean,
                               rtol=1e-5, atol=1e-5)


def test_weighted_auc_reweights():
    scores = np.array([0.9, 0.8, 0.3, 0.2])
    targets = np.array([1.0, 0.0, 1.0, 0.0])
    unweighted = evaluate_scores(scores, targets)
    # weight the high-score pair heavily -> weighted AUC improves
    weighted = evaluate_scores(scores, targets,
                               np.array([10.0, 0.1, 0.1, 10.0]))
    assert weighted.weightedAuc > unweighted.areaUnderRoc


def test_bucket_points_monotone():
    rng = np.random.default_rng(2)
    scores = rng.random(5000)
    targets = (scores + rng.normal(0, 0.3, 5000) > 0.6).astype(float)
    res = evaluate_scores(scores, targets, buckets=10)
    assert len(res.points) == 10
    recalls = [p.recall for p in res.points]
    actions = [p.actionRate for p in res.points]
    assert recalls == sorted(recalls)
    assert actions == sorted(actions)
    assert res.points[-1].recall == pytest.approx(1.0)
    # threshold column is descending in score
    ths = [p.binLowestScore for p in res.points]
    assert ths == sorted(ths, reverse=True)


def test_degenerate_single_class():
    res = evaluate_scores(np.array([0.5, 0.6]), np.array([1.0, 1.0]))
    assert np.isnan(res.areaUnderRoc)


class _ConstModel:
    def __init__(self, v):
        self.v = v

    def compute(self, x):
        return np.full((len(x), 1), self.v)


def test_scorer_aggregates_and_scale():
    sc = Scorer([_ConstModel(0.2), _ConstModel(0.6)])
    res = sc.score(np.zeros((3, 4)))
    assert res.scores.shape == (3, 2)
    np.testing.assert_allclose(res.mean, 400.0)
    np.testing.assert_allclose(res.max, 600.0)
    np.testing.assert_allclose(res.min, 200.0)
    assert res.select("model1")[0] == 600.0


def test_eval_pipeline_end_to_end(prepared_set):
    model_set = prepared_set          # init/stats/norm ran in the template
    from shifu_tpu.pipeline.train import TrainProcessor
    from shifu_tpu.pipeline.evaluate import EvalProcessor

    assert TrainProcessor(model_set, params={}).run() == 0
    assert EvalProcessor(model_set, params={"run_eval": ""}).run() == 0

    eval_dir = os.path.join(model_set, "evals", "Eval1")
    perf = json.load(open(os.path.join(eval_dir, "EvalPerformance.json")))
    # the model learned something real: AUC well above chance on train data
    assert perf["areaUnderRoc"] > 0.7
    assert perf["recordCount"] == 4000
    assert len(perf["performance"]) == 10

    with open(os.path.join(eval_dir, "EvalScore")) as f:
        rows = list(csv.reader(f, delimiter="|"))
    assert len(rows) == 4001  # header + all records
    assert rows[0][:3] == ["tag", "weight", "mean"]

    assert os.path.isfile(os.path.join(eval_dir, "EvalConfusionMatrix"))
    assert os.path.isfile(os.path.join(eval_dir, "gainchart.csv"))


def test_eval_crud(prepared_set):
    model_set = prepared_set          # init ran in the template
    from shifu_tpu.pipeline.evaluate import EvalProcessor
    assert EvalProcessor(model_set, params={"new_eval": "EvalX"}).run() == 0
    from shifu_tpu.config import ModelConfig
    mc = ModelConfig.load(os.path.join(model_set, "ModelConfig.json"))
    assert any(e.name == "EvalX" for e in mc.evals)
    assert EvalProcessor(model_set, params={"delete_eval": "EvalX"}).run() == 0
    mc = ModelConfig.load(os.path.join(model_set, "ModelConfig.json"))
    assert not any(e.name == "EvalX" for e in mc.evals)
    assert EvalProcessor(model_set, params={"delete_eval": "nope"}).run() == 1


def test_posttrain_bin_avg_scores(prepared_set):
    model_set = prepared_set          # init/stats/norm ran in the template
    from shifu_tpu.pipeline.train import TrainProcessor
    from shifu_tpu.pipeline.posttrain import PostTrainProcessor
    from shifu_tpu.config import load_column_configs

    assert TrainProcessor(model_set, params={}).run() == 0
    assert PostTrainProcessor(model_set, params={}).run() == 0
    ccs = load_column_configs(os.path.join(model_set, "ColumnConfig.json"))
    scored = [c for c in ccs if c.columnBinning.binAvgScore]
    assert scored, "no binAvgScore written"
    fi_path = os.path.join(model_set, "posttrain", "featureImportance.csv")
    assert os.path.isfile(fi_path)
    lines = open(fi_path).read().strip().splitlines()
    assert len(lines) >= 3
    # ranked descending
    vals = [float(l.split("\t")[1]) for l in lines]
    assert vals == sorted(vals, reverse=True)
