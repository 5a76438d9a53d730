"""AUC-parity oracle against the reference's own trained golden models.

The measured baselines (BASELINE.md "Measured baselines") come from scoring
the reference's shipped model artifacts on its shipped eval data:

- NN bag: ``example/cancer-judgement/ModelStore/ModelSet1/models/*.nn``
  (Encog EG text, reference ``core/alg/NNTrainer.java`` output) -> AUC
  0.998528 on EvalSet1.
- GBT: ``example/readablespec/model0.gbt`` (``BinaryDTSerializer.java``
  v4 gzip, cancer-judgement columns) -> AUC 0.940076 on the same rows.

These tests pin (a) the importers keep reproducing those numbers and (b) our
own trainers reach reference AUC within ±0.005 on the same data — the parity
gate BASELINE.json's north star requires.
"""

import os

import numpy as np
import pytest

REF = "/root/reference/src/test/resources/example/cancer-judgement"
MODELSET = f"{REF}/ModelStore/ModelSet1"
GBT_GOLDEN = "/root/reference/src/test/resources/example/readablespec/model0.gbt"

# measured by the baseline script that last lived at commit 3da7e39
# (tools/, removed in PR 29 with the harness that read its constants)
REFERENCE_NN_AUC = 0.998528
REFERENCE_GBT_AUC = 0.940076
AUC_TOL = 0.005

pytestmark = pytest.mark.skipif(
    not os.path.isdir(REF), reason="reference example data not mounted")


def _cancer(split):
    from shifu_tpu.models.reference_import import load_reference_psv
    cols = load_reference_psv(f"{REF}/DataStore/{split}/part-00",
                              f"{REF}/DataStore/{split}/.pig_header")
    target = (cols["diagnosis"] == "M").astype(np.float32)
    return cols, target


def _normalized(cols, ccs):
    from shifu_tpu.models.reference_import import zscore_matrix
    return zscore_matrix(cols, ccs)


@pytest.fixture(scope="module")
def ccs():
    from shifu_tpu.config.column_config import load_column_configs
    return load_column_configs(f"{MODELSET}/ColumnConfig.json")


@pytest.fixture(scope="module")
def eval_data(ccs):
    cols, target = _cancer("EvalSet1")
    z, raw = _normalized(cols, ccs)
    return z, raw, target


@pytest.fixture(scope="module")
def train_data(ccs):
    cols, target = _cancer("DataSet1")
    z, raw = _normalized(cols, ccs)
    return z, raw, target


def _auc(scores, target):
    from shifu_tpu.eval.metrics import evaluate_scores
    return float(evaluate_scores(np.asarray(scores, np.float32),
                                 target).areaUnderRoc)


def test_reference_nn_golden_auc(eval_data):
    """Importer + our forward reproduce the recorded reference NN AUC."""
    from shifu_tpu.models.nn import IndependentNNModel
    from shifu_tpu.models.reference_import import load_encog_nn

    z, _, target = eval_data
    scores = np.zeros(len(target))
    n_models = 0
    for i in range(8):
        path = f"{MODELSET}/models/model{i}.nn"
        if not os.path.exists(path):
            break
        spec, params = load_encog_nn(path)
        assert spec.input_dim == 30 and spec.hidden_nodes == [45, 45]
        scores += IndependentNNModel(spec, params).compute(z)[:, 0]
        n_models += 1
    assert n_models == 5
    assert abs(_auc(scores / n_models, target) - REFERENCE_NN_AUC) < 2e-3


def test_reference_gbt_golden_auc(eval_data):
    """Importer + faithful node walk reproduce the recorded GBT AUC."""
    from shifu_tpu.models.reference_import import load_reference_tree

    _, raw, target = eval_data
    model = load_reference_tree(GBT_GOLDEN)
    assert model.algorithm == "GBT" and len(model.trees) == 100
    assert abs(_auc(model.compute(raw), target) - REFERENCE_GBT_AUC) < 2e-3


def test_our_nn_reaches_reference_auc(ccs, train_data, eval_data):
    """Our meshed NN ensemble trained with the reference ModelSet1 recipe
    (5 bags, 2x45 sigmoid, 100 epochs) matches reference AUC within tol."""
    from shifu_tpu.models import nn as nn_model
    from shifu_tpu.train.nn_trainer import TrainSettings, train_ensemble
    from shifu_tpu.train.sampling import member_masks

    z_tr, _, y_tr = train_data
    z_ev, _, y_ev = eval_data
    bags = 5
    train_w, valid_w = member_masks(len(y_tr), bags, valid_rate=0.1,
                                    sample_rate=1.0, replacement=True,
                                    targets=y_tr, seed=0)
    spec = nn_model.NNModelSpec(input_dim=z_tr.shape[1],
                                hidden_nodes=[45, 45],
                                activations=["sigmoid", "sigmoid"],
                                loss="squared")
    res = train_ensemble(z_tr, y_tr, train_w, valid_w, spec,
                         TrainSettings(optimizer="ADAM", learning_rate=0.01,
                                       epochs=100, seed=0))
    scores = np.zeros(len(y_ev))
    for params in res.params:
        scores += np.asarray(
            nn_model.forward(params, spec, z_ev))[:, 0]
    auc = _auc(scores / bags, y_ev)
    assert auc >= REFERENCE_NN_AUC - AUC_TOL, f"our NN AUC {auc}"


def test_our_gbt_reaches_reference_auc(ccs, train_data, eval_data):
    """Our jitted GBT on equal-population bins beats/matches the reference
    golden forest's AUC within tol."""
    from shifu_tpu.models.tree import IndependentTreeModel, TreeModelSpec
    from shifu_tpu.train.dt_trainer import DTSettings, train_gbt

    _, raw_tr, y_tr = train_data
    _, raw_ev, y_ev = eval_data
    cols = sorted(raw_tr)
    n_bins = 32
    edges = {}
    for c in cols:
        qs = np.quantile(raw_tr[c], np.linspace(0, 1, n_bins)[1:-1])
        edges[c] = np.unique(qs)

    def binned(raw):
        return np.stack([np.searchsorted(edges[c], raw[c]).astype(np.int32)
                         for c in cols], axis=1)

    bins_tr, bins_ev = binned(raw_tr), binned(raw_ev)
    res = train_gbt(bins_tr, y_tr, np.ones(len(y_tr), np.float32), n_bins,
                    np.zeros(len(cols), bool),
                    DTSettings(n_trees=100, depth=4, loss="log",
                               learning_rate=0.05, valid_rate=0.1, seed=0))
    spec = TreeModelSpec(n_trees=len(res.trees), depth=4, n_bins=n_bins,
                         **res.spec_kwargs)
    scores = IndependentTreeModel(spec, res.trees).compute(bins_ev)[:, 0]
    auc = _auc(scores, y_ev)
    assert auc >= REFERENCE_GBT_AUC - AUC_TOL, f"our GBT AUC {auc}"
