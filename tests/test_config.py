"""Config substrate tests: JSON round-trip + reference-contract compatibility."""

import json
import os
import sys
from dataclasses import dataclass
from typing import List, Optional

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "helpers"))
import jsonbean_reference as reference  # noqa: E402

from shifu_tpu.config import (Algorithm, ColumnBinning, ColumnConfig,
                              ColumnFlag, ColumnStats, ColumnType,
                              ModelBasicConf, ModelConfig, ModelTrainConf,
                              NormType, build_initial_column_configs,
                              jsonbean, load_column_configs,
                              save_column_configs)
from shifu_tpu.config.jsonbean import parse_enum
from shifu_tpu.config.validator import ModelStep, ValidationError, probe

REFERENCE_STYLE_MODEL_CONFIG = {
    "basic": {"name": "cancer-judgement", "author": "", "description": None,
              "runMode": "local", "customPaths": None},
    "dataSet": {"source": "LOCAL", "dataPath": "./data", "dataDelimiter": "|",
                "headerPath": "./data/.pig_header", "headerDelimiter": "|",
                "filterExpressions": "", "weightColumnName": "column_3",
                "targetColumnName": "diagnosis", "posTags": ["M"], "negTags": ["B"],
                "metaColumnNameFile": None, "categoricalColumnNameFile": None},
    "stats": {"maxNumBin": 10, "binningMethod": "EqualPositive", "sampleRate": 1.0,
              "sampleNegOnly": False},
    "varSelect": {"forceEnable": True, "filterEnable": True, "filterNum": 200,
                  "filterBy": "KS",
                  "params": {"worker_sample_rate": 0.5}},
    "normalize": {"stdDevCutOff": 4.0, "sampleRate": 1.0, "sampleNegOnly": False},
    "train": {"baggingNum": 5, "baggingWithReplacement": True,
              "baggingSampleRate": 1.0, "validSetRate": 0.1, "trainOnDisk": False,
              "numTrainEpochs": 100, "algorithm": "NN",
              "params": {"NumHiddenLayers": 2, "ActivationFunc": ["Sigmoid", "Sigmoid"],
                         "NumHiddenNodes": [45, 45], "LearningRate": 0.1,
                         "Propagation": "Q"}},
    "evals": [{"name": "EvalA",
               "dataSet": {"source": "LOCAL", "dataPath": "./evaldata",
                           "dataDelimiter": "|"},
               "performanceBucketNum": 10, "performanceScoreSelector": "mean"}],
}


def test_model_config_loads_reference_style_json():
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    assert mc.basic.name == "cancer-judgement"
    assert mc.dataSet.posTags == ["M"] and mc.dataSet.negTags == ["B"]
    assert mc.train.algorithm == Algorithm.NN
    assert mc.train.params["NumHiddenNodes"] == [45, 45]
    assert mc.stats.binningMethod.name == "EqualPositive"
    assert len(mc.evals) == 1 and mc.evals[0].name == "EvalA"


def test_model_config_round_trip(tmp_path):
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    p = str(tmp_path / "ModelConfig.json")
    mc.save(p)
    mc2 = ModelConfig.load(p)
    assert mc2.to_dict()["dataSet"]["targetColumnName"] == "diagnosis"
    assert mc2.train.params == mc.train.params
    assert mc2.normalize.normType == NormType.ZSCALE  # default preserved


def test_unknown_keys_survive_round_trip(tmp_path):
    d = dict(REFERENCE_STYLE_MODEL_CONFIG)
    d["someFutureSection"] = {"a": 1}
    mc = ModelConfig.from_dict(d)
    p = str(tmp_path / "m.json")
    mc.save(p)
    with open(p) as f:
        out = json.load(f)
    assert out["someFutureSection"] == {"a": 1}


def test_enum_parse_case_insensitive():
    assert parse_enum(NormType, "zscale") == NormType.ZSCALE
    assert parse_enum(Algorithm, "gbt") == Algorithm.GBT
    with pytest.raises(ValueError):
        parse_enum(Algorithm, "nope")


def test_column_config_init_and_round_trip(tmp_path):
    header = ["id", "amount", "country", "tag", "w"]
    ccs = build_initial_column_configs(header, target="tag",
                                      meta_cols=["id"], categorical_cols=["country"],
                                      weight_col="w")
    assert ccs[0].columnFlag == ColumnFlag.Meta
    assert ccs[2].columnType == ColumnType.C
    assert ccs[3].is_target() and ccs[4].is_weight()
    ccs[1].columnStats.mean = 3.5
    ccs[1].columnBinning.binBoundary = [float("-inf"), 1.0, 2.0]
    p = str(tmp_path / "ColumnConfig.json")
    save_column_configs(ccs, p)
    back = load_column_configs(p)
    assert back[1].columnStats.mean == 3.5
    assert back[1].columnBinning.binBoundary[1] == 1.0
    assert back[3].columnFlag == ColumnFlag.Target


@dataclass
class _NoExtra:
    rate: float = 0.0
    counts: Optional[List[int]] = None


def _columns_of(kind):
    def columns(request):
        mdir = request.getfixturevalue("_prepared_template")
        with open(os.path.join(mdir, "ColumnConfig.json")) as f:
            cols = [c for c in json.load(f) if c["columnType"] == kind]
        key = "binCategory" if kind == "C" else "binBoundary"
        assert any(c["columnBinning"][key] for c in cols)
        return ColumnConfig, cols
    return columns


# name -> (request -> (class, the JSON dicts to convert))
FROM_DICT_CASES = {
    "columns_numeric": _columns_of("N"),
    "columns_categorical": _columns_of("C"),
    "model_config": lambda r: (ModelConfig, [REFERENCE_STYLE_MODEL_CONFIG]),
    "bool_into_float": lambda r: (ColumnStats, [
        {"mean": True, "max": False, "min": 3, "ks": "0.5"}]),
    "integral_float_into_int": lambda r: (ColumnStats, [
        {"totalCount": 3.0, "missingCount": 2.5, "distinctCount": 7}]),
    "string_into_bool": lambda r: (ColumnConfig, [
        {"finalSelect": "yes"}, {"finalSelect": " TRUE "},
        {"finalSelect": "no"}, {"finalSelect": 1}]),
    "enum_by_lower_case": lambda r: (ModelBasicConf, [
        {"runMode": "mapred"}, {"runMode": " Dist "}]),
    "enum_member": lambda r: (ColumnConfig, [
        {"columnType": ColumnType.H, "columnFlag": ColumnFlag.Meta}]),
    "none_optional_list": lambda r: (ColumnBinning, [
        {"length": 0, "binBoundary": None, "binCategory": None}]),
    "none_nested": lambda r: (ColumnConfig, [
        {"columnStats": None, "columnBinning": None}]),
    "unknown_keys": lambda r: (ColumnConfig, [
        {"columnNum": 1, "futureKey": {"a": [1]},
         "columnStats": {"newStat": 1.5, "mean": 2}}]),
    "unknown_keys_dropped": lambda r: (_NoExtra, [
        {"rate": 1, "counts": [1.0, 2.5, None], "futureKey": 3}]),
    "key_named_extra": lambda r: (ColumnConfig, [
        {"extra": {"x": 1}, "columnNum": 2}]),
    "non_list_under_list": lambda r: (ColumnBinning, [
        {"binCategory": "abc", "binCountPos": {"1": 2}}]),
}


@pytest.mark.parametrize("case", FROM_DICT_CASES)
def test_from_dict_matches_per_value_reference(case, request):
    """The planned ``from_dict`` builds the objects the per-value one did:
    equal, equal as dicts, and with the same types inside (``repr`` tells
    1 from 1.0 from True)."""
    cls, dicts = FROM_DICT_CASES[case](request)
    got = [jsonbean.from_dict(cls, d) for d in dicts]
    want = [reference.from_dict(cls, d) for d in dicts]
    assert got == want
    assert [jsonbean.to_dict(o) for o in got] == \
        [jsonbean.to_dict(o) for o in want]
    assert repr(got) == repr(want)


def test_from_dict_copies_lists_and_dicts():
    """An object shares no list or dict with the parsed JSON it came from."""
    d = {"sampleValues": ["a", "b"],
         "columnBinning": {"binBoundary": [1.0, 2.0], "binCountPos": [3, 4],
                           "binCategory": ["x"]}}
    cc = jsonbean.from_dict(ColumnConfig, d)
    train = jsonbean.from_dict(ModelTrainConf, {"params": {"a": 1}})
    d["sampleValues"].append("c")
    for v in d["columnBinning"].values():
        v.clear()
    assert cc.sampleValues == ["a", "b"]
    assert cc.columnBinning.binBoundary == [1.0, 2.0]
    assert cc.columnBinning.binCountPos == [3, 4]
    assert cc.columnBinning.binCategory == ["x"]
    assert train.params == {"a": 1}


def test_column_configs_second_load_builds_no_plan(tmp_path, monkeypatch):
    """The first load of a process builds the three classes' plans; a
    second builds none and gives equal objects."""
    monkeypatch.setattr(jsonbean, "_PLANS", {})
    ccs = build_initial_column_configs(["a", "tag"], target="tag")
    ccs[0].columnStats.mean = 0.5
    ccs[0].columnBinning.binBoundary = [float("-inf"), 1.0]
    ccs[0].columnBinning.binCountPos = [1, 2, 0]
    p = str(tmp_path / "ColumnConfig.json")
    save_column_configs(ccs, p)
    first = load_column_configs(p)
    built = jsonbean.plans_built()
    second = load_column_configs(p)
    assert (built, jsonbean.plans_built()) == (3, 3)
    assert first == second == ccs


def test_validator_catches_problems():
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    probe(mc, ModelStep.TRAIN)  # valid
    mc.train.baggingNum = 0
    mc.train.validSetRate = 1.5
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.TRAIN)
    assert len(e.value.problems) == 2


def test_nn_param_consistency_validated():
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    mc.train.params["NumHiddenLayers"] = 3  # mismatch with 2 nodes/act lists
    with pytest.raises(ValidationError):
        probe(mc, ModelStep.TRAIN)


def test_out_of_order_steps_fail_with_coded_hint(model_set):
    """norm/train before stats/norm fail with ERROR_STEP_PRECONDITION and a
    'run X first' hint, not a deep traceback (verify-skill gotcha)."""
    import pytest
    from shifu_tpu.config.errors import ErrorCode, ShifuError
    from shifu_tpu.pipeline.create import InitProcessor
    from shifu_tpu.pipeline.norm import NormalizeProcessor
    from shifu_tpu.pipeline.stats import StatsProcessor
    from shifu_tpu.pipeline.train import TrainProcessor

    assert InitProcessor(model_set).run() == 0
    with pytest.raises(ShifuError) as ei:
        NormalizeProcessor(model_set, params={}).run()
    assert ei.value.error_code is ErrorCode.ERROR_STEP_PRECONDITION
    assert "stats" in str(ei.value)
    assert StatsProcessor(model_set, params={}).run() == 0
    with pytest.raises(ShifuError) as ei:
        TrainProcessor(model_set, params={}).run()
    assert "norm" in str(ei.value)
    assert NormalizeProcessor(model_set, params={}).run() == 0
    assert TrainProcessor(model_set, params={}).run() == 0


def test_profile_json_written(model_set):
    """Per-step wall-clock + per-phase timers land in tmp/profile.json
    (SURVEY §5 tracing/profiling)."""
    from shifu_tpu.pipeline.create import InitProcessor
    from shifu_tpu.pipeline.norm import NormalizeProcessor
    from shifu_tpu.pipeline.stats import StatsProcessor
    from shifu_tpu.pipeline.train import TrainProcessor

    assert InitProcessor(model_set).run() == 0
    assert StatsProcessor(model_set, params={}).run() == 0
    assert NormalizeProcessor(model_set, params={}).run() == 0
    assert TrainProcessor(model_set, params={}).run() == 0
    prof = json.load(open(os.path.join(model_set, "tmp", "profile.json")))
    assert prof["STATS"]["total_s"] > 0
    # the default stats plane is the fused one-pass sweep (moments +
    # histograms in one streamed read)
    assert "fused_sweep" in prof["STATS"]["phases_s"]
    assert "train" in prof["TRAIN"]["phases_s"]
    assert "load_data" in prof["TRAIN"]["phases_s"]


def test_probe_cross_list_column_conflicts(tmp_path):
    """Reference ModelInspector.checkColumnConf (:213-262): target vs
    meta/force lists, and pairwise list overlaps under forceEnable."""
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    meta_f = tmp_path / "meta.names"
    meta_f.write_text("diagnosis\ntxid\n")       # target in meta!
    frm = tmp_path / "rm.names"
    frm.write_text("txid\namount\n")             # txid also in meta
    mc.dataSet.metaColumnNameFile = str(meta_f)
    mc.varSelect.forceRemoveColumnNameFile = str(frm)
    mc.varSelect.forceEnable = True
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.STATS, str(tmp_path))
    text = "\n".join(e.value.problems)
    assert "target column must not be a meta column" in text
    assert "meta" in text and "forceRemove" in text


def test_probe_force_file_must_exist(tmp_path):
    """Reference ModelInspector.checkVarSelect (:316-357)."""
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    mc.varSelect.forceEnable = True
    mc.varSelect.forceSelectColumnNameFile = "no/such/file.names"
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.VARSELECT, str(tmp_path))
    assert any("does not exist" in p for p in e.value.problems)


def test_probe_stats_multiclass_binning_rules():
    """Reference ModelInspector.checkStatsConf (:263-305)."""
    from shifu_tpu.config.model_config import (BinningAlgorithm,
                                               BinningMethod)
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    mc.dataSet.posTags = ["a", "b", "c"]          # multi-class
    mc.dataSet.negTags = []
    mc.stats.binningMethod = BinningMethod.EqualPositive
    mc.stats.binningAlgorithm = BinningAlgorithm.MunroPat
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.STATS)
    text = "\n".join(e.value.problems)
    assert "EqualPositive" in text
    assert "SPDTI" in text


def test_probe_init_missing_datapath_flagged(tmp_path):
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    mc.dataSet.dataPath = "/no/such/data.csv"
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.INIT, str(tmp_path))
    assert any("does not exist" in p for p in e.value.problems)


def test_probe_init_missing_header_flagged(tmp_path):
    """Reference checkRawData probes headerPath too (:366-369)."""
    data = tmp_path / "d.csv"
    data.write_text("a|b\n1|2\n")
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    mc.dataSet.dataPath = str(data)
    mc.dataSet.headerPath = "/no/such/header"
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.INIT, str(tmp_path))
    assert any("headerPath" in p for p in e.value.problems)


def test_probe_stats_name_files_must_exist(tmp_path):
    """Reference probe() at STATS verifies meta/categorical name files
    (:121-131)."""
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    mc.dataSet.metaColumnNameFile = "no/meta.names"
    mc.dataSet.categoricalColumnNameFile = "no/cat.names"
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.STATS, str(tmp_path))
    text = "\n".join(e.value.problems)
    assert "metaColumnNameFile" in text
    assert "categoricalColumnNameFile" in text


def test_probe_post_correlation_metric_se_pairing():
    """Reference checkVarSelect :335-343."""
    from shifu_tpu.config.model_config import FilterBy
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    mc.varSelect.filterBy = FilterBy.KS
    mc.varSelect.postCorrelationMetric = "SE"
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.VARSELECT)
    assert any("postCorrelationMetric" in p for p in e.value.problems)
    mc.varSelect.filterBy = FilterBy.SE
    probe(mc, ModelStep.VARSELECT)               # both SE: valid


def test_probe_train_multiclass_cross_checks():
    """Reference checkTrainSetting :513-534: OVA algorithm restriction and
    NATIVE-RF impurity restriction."""
    from shifu_tpu.config.model_config import (Algorithm,
                                               MultipleClassification)
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    mc.dataSet.posTags = ["a", "b", "c"]
    mc.dataSet.negTags = []
    mc.train.algorithm = Algorithm.WDL
    mc.train.multiClassifyMethod = MultipleClassification.ONEVSALL
    mc.train.params = {}
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.TRAIN)
    assert any("one vs all" in p for p in e.value.problems)
    mc.train.algorithm = Algorithm.RF
    mc.train.multiClassifyMethod = MultipleClassification.NATIVE
    mc.train.params = {"Impurity": "variance"}
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.TRAIN)
    assert any("entropy/gini" in p for p in e.value.problems)


def test_probe_hinge_requires_svm():
    from shifu_tpu.config.model_config import Algorithm
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    mc.train.algorithm = Algorithm.NN
    mc.train.params = {"Loss": "hinge"}
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.TRAIN)
    assert any("SVM" in p for p in e.value.problems)


def test_probe_eval_semantic_checks(tmp_path):
    """Reference probe() EVAL loop: per-set data existence +
    scoreMetaColumnNameFile + bucket sanity."""
    mc = ModelConfig.from_dict(REFERENCE_STYLE_MODEL_CONFIG)
    ev = mc.evals[0]
    ev.dataSet.dataPath = "/no/such/eval.csv"
    ev.scoreMetaColumnNameFile = "no/score.meta"
    ev.performanceBucketNum = 0
    with pytest.raises(ValidationError) as e:
        probe(mc, ModelStep.EVAL, str(tmp_path))
    text = "\n".join(e.value.problems)
    assert "dataPath does not exist" in text
    assert "scoreMetaColumnNameFile" in text
    assert "performanceBucketNum" in text
