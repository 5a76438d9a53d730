"""Aux pipeline steps: export (pmml/columnstats/woe/corr), smoke test,
encode, convert, combo — reference processors from SURVEY.md §2.1/2.7."""

import json
import os
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from shifu_tpu.config import ModelConfig


def _set_train_alg(mdir, alg=None, tree_params=None):
    if not alg:
        return
    from shifu_tpu.config.model_config import Algorithm
    mc_path = os.path.join(mdir, "ModelConfig.json")
    mc = ModelConfig.load(mc_path)
    mc.train.algorithm = Algorithm[alg]
    if tree_params:
        mc.train.params = tree_params
    mc.save(mc_path)


def _train_prepared(prepared_set, alg=None, tree_params=None):
    """Train on a prepared (post-norm) copy — init/stats/norm already ran
    in the session template; norm materializes both planes so any
    algorithm can train from it."""
    from shifu_tpu.pipeline.train import TrainProcessor
    _set_train_alg(prepared_set, alg, tree_params)
    assert TrainProcessor(prepared_set, params={}).run() == 0


def _run_pipeline(model_set, alg=None, tree_params=None):
    from shifu_tpu.pipeline.create import InitProcessor
    from shifu_tpu.pipeline.stats import StatsProcessor
    from shifu_tpu.pipeline.norm import NormalizeProcessor
    from shifu_tpu.pipeline.train import TrainProcessor
    assert InitProcessor(model_set).run() == 0
    assert StatsProcessor(model_set, params={}).run() == 0
    _set_train_alg(model_set, alg, tree_params)
    assert NormalizeProcessor(model_set, params={}).run() == 0
    assert TrainProcessor(model_set, params={}).run() == 0


NS = {"p": "http://www.dmg.org/PMML-4_2"}


def test_export_pmml_model_stats_and_concise(prepared_set):
    """Default export carries ModelStats with per-bin Extensions
    (reference ModelStatsCreator); `export -c` trims them
    (ShifuCLI.java:366 IS_CONCISE)."""
    model_set = prepared_set
    from shifu_tpu.pipeline.export import ExportProcessor
    from shifu_tpu.pipeline.train import TrainProcessor
    assert TrainProcessor(model_set, params={}).run() == 0
    for concise, want_ext in ((False, True), (True, False)):
        assert ExportProcessor(model_set, params={
            "type": "pmml", "concise": concise}).run() == 0
        f = [x for x in os.listdir(os.path.join(model_set, "export"))
             if x.endswith(".pmml")][0]
        doc = ET.parse(os.path.join(model_set, "export", f))
        body = ET.tostring(doc.getroot(), encoding="unicode")
        assert "ModelStats" in body and "UnivariateStats" in body
        assert ("BinCountPos" in body) == want_ext


def test_init_model_fills_algorithm_defaults(model_set):
    """`shifu init -model` fills the reference's per-algorithm default
    train#params (BasicModelProcessor.java:404-500) and is idempotent."""
    import json

    from shifu_tpu.pipeline.create import check_algorithm_param
    mc_path = os.path.join(model_set, "ModelConfig.json")
    with open(mc_path) as f:
        mc = json.load(f)
    mc["train"]["algorithm"] = "RF"
    mc["train"]["params"] = {}
    with open(mc_path, "w") as f:
        json.dump(mc, f)
    assert check_algorithm_param(model_set) == 0
    with open(mc_path) as f:
        mc = json.load(f)
    assert mc["train"]["params"]["MaxDepth"] == 14
    assert mc["train"]["params"]["Impurity"] == "entropy"
    mc["train"]["params"]["MaxDepth"] = 5        # user edit survives re-run
    with open(mc_path, "w") as f:
        json.dump(mc, f)
    assert check_algorithm_param(model_set) == 0
    with open(mc_path) as f:
        assert json.load(f)["train"]["params"]["MaxDepth"] == 5


def test_init_model_defaults_pass_train_validation():
    """Whatever `init -model` writes, `train` must accept: the GBT
    defaults once carried a DropoutRate the validator rejects (1051)."""
    from shifu_tpu.config.meta import validate_train_params
    from shifu_tpu.config.model_config import Algorithm
    from shifu_tpu.pipeline.create import _ALG_DEFAULT_PARAMS
    for alg, (_, defaults) in _ALG_DEFAULT_PARAMS.items():
        assert validate_train_params(defaults, Algorithm[alg]) == [], alg


def test_export_pmml_nn(prepared_set):
    model_set = prepared_set
    from shifu_tpu.pipeline.export import ExportProcessor
    _train_prepared(model_set)
    assert ExportProcessor(model_set, params={"type": "pmml"}).run() == 0
    pmml_files = [f for f in os.listdir(os.path.join(model_set, "export"))
                  if f.endswith(".pmml")]
    assert pmml_files
    doc = ET.parse(os.path.join(model_set, "export", pmml_files[0]))
    root = doc.getroot()
    assert root.find("p:DataDictionary", NS) is not None
    nn = root.find("p:NeuralNetwork", NS)
    assert nn is not None
    layers = nn.findall("p:NeuralLayer", NS)
    assert len(layers) == 2               # 1 hidden + output
    # every neuron in layer0 has one Con per input
    inputs = nn.find("p:NeuralInputs", NS)
    n_in = int(inputs.get("numberOfInputs"))
    neuron0 = layers[0].find("p:Neuron", NS)
    assert len(neuron0.findall("p:Con", NS)) == n_in


def test_export_pmml_nn_onehot(model_set):
    """One-hot-expanding norms export (VERDICT r3 missing item 6): every
    categorical bin becomes an indicator DerivedField, the net inputs bind
    to the flat expanded feature list, and the indicator tables one-hot
    exactly (row out=1 only for the bin's own category)."""
    from shifu_tpu.config.model_config import NormType
    from shifu_tpu.pipeline.export import ExportProcessor

    mc_path = os.path.join(model_set, "ModelConfig.json")
    mc = ModelConfig.load(mc_path)
    mc.normalize.normType = NormType.ZSCALE_ONEHOT
    # txn_id must be meta here: a onehot norm would expand the id column
    # into ~4000 indicator inputs (real configs flag id-like columns; the
    # unflagged fixture is fine for non-expanding norms)
    meta = os.path.join(model_set, "meta.names")
    with open(meta, "w") as f:
        f.write("txn_id\n")
    mc.dataSet.metaColumnNameFile = meta
    mc.save(mc_path)
    _run_pipeline(model_set)
    assert ExportProcessor(model_set, params={"type": "pmml"}).run() == 0
    pmml_files = [f for f in os.listdir(os.path.join(model_set, "export"))
                  if f.endswith(".pmml")]
    doc = ET.parse(os.path.join(model_set, "export", pmml_files[0]))
    nn = doc.getroot().find("p:NeuralNetwork", NS)
    lt = nn.find("p:LocalTransformations", NS)
    defined = {df.get("name") for df in lt.findall("p:DerivedField", NS)}
    # onehot indicator fields carry 0/1 MapValues defaults
    onehot_fields = {
        df.get("name") for df in lt.findall("p:DerivedField", NS)
        if (df.find("p:MapValues", NS) is not None
            and df.find("p:MapValues", NS).get("defaultValue") in ("0", "1"))}
    assert onehot_fields                     # categorical bins expanded
    inputs = nn.find("p:NeuralInputs", NS)
    refs = [ni.find("p:DerivedField/p:FieldRef", NS).get("field")
            for ni in inputs.findall("p:NeuralInput", NS)]
    assert int(inputs.get("numberOfInputs")) == len(refs) == len(defined)
    assert set(refs) == defined              # every input resolves
    # indicator semantics: in each onehot MapValues exactly one row is 1
    # per bin field (except the missing feature whose rows are all 0)
    for df in lt.findall("p:DerivedField", NS):
        if df.get("name") not in onehot_fields:
            continue
        mv = df.find("p:MapValues", NS)
        outs = [r.find("p:out", NS).text
                for r in mv.findall("p:InlineTable/p:row", NS)]
        if mv.get("defaultValue") == "1":    # the missing-bin indicator
            assert all(o == "0" for o in outs)
        else:
            assert outs.count("1") == 1


def test_pmml_numeric_onehot_discretize_indicators():
    """Plain NormType.ONEHOT expands NUMERIC columns too: each bin becomes
    a Discretize indicator over its interval (not an empty MapValues —
    round-4 review finding)."""
    from shifu_tpu.config import ColumnConfig
    from shifu_tpu.config.model_config import NormType
    from shifu_tpu.export.pmml import _local_transformations

    mc = ModelConfig()
    mc.normalize.normType = NormType.ONEHOT
    cc = ColumnConfig(columnNum=0, columnName="amount")
    cc.columnType = cc.columnType.__class__.N
    cc.columnBinning.binBoundary = [float("-inf"), 1.0, 5.0]
    cc.columnBinning.binCountNeg = [1, 1, 1]
    cc.columnBinning.binCountPos = [1, 1, 1]
    parent = ET.Element("x")
    names = _local_transformations(parent, [cc], mc)
    assert len(names) == 4                   # 3 bins + missing indicator
    dfs = parent.find("LocalTransformations").findall("DerivedField")
    assert len(dfs) == 4
    for j, df in enumerate(dfs):
        disc = df.find("Discretize")
        assert disc is not None              # numeric -> Discretize
        if j < 3:
            assert disc.get("mapMissingTo") == "0"
            b = disc.find("DiscretizeBin")
            assert b is not None and b.get("binValue") == "1"
        else:                                # the missing indicator
            assert disc.get("mapMissingTo") == "1"
            assert disc.find("DiscretizeBin") is None


def test_categorical_accumulator_nan_rows_fold_into_missing():
    """factorize codes NaN as -1; such rows must land in the missing slot,
    not crash bincount (round-4 review finding)."""
    import pandas as pd
    from shifu_tpu.ops.binning import CategoricalAccumulator

    vals = pd.Series(["a", None, "b", float("nan")], dtype=str) \
        .str.strip().to_numpy()
    acc = CategoricalAccumulator()
    acc.update("c", vals, np.array([True, True, True, True]),
               np.array([1.0, 0.0, 1.0, 0.0]), np.ones(4), stripped=True)
    cats, counts, n_distinct, n_missing = acc.finalize("c")
    assert set(cats) == {"a", "b"}
    assert counts[-1][0] + counts[-1][1] == 2   # both NaN rows -> missing


def test_export_pmml_tree(prepared_set):
    model_set = prepared_set
    from shifu_tpu.pipeline.export import ExportProcessor
    _train_prepared(model_set, alg="GBT",
                    tree_params={"TreeNum": 3, "MaxDepth": 3, "Loss": "log"})
    assert ExportProcessor(model_set, params={"type": "pmml"}).run() == 0
    pmml_files = [f for f in os.listdir(os.path.join(model_set, "export"))
                  if f.endswith(".pmml")]
    doc = ET.parse(os.path.join(model_set, "export", pmml_files[0]))
    mm = doc.getroot().find("p:MiningModel", NS)
    assert mm is not None
    segs = mm.find("p:Segmentation", NS)
    assert segs.get("multipleModelMethod") == "sum"
    # 3 tree segments + the GBT init-score constant segment
    assert len(segs.findall("p:Segment", NS)) == 4
    assert segs.find("p:Segment[@id='init']", NS) is not None
    # every bin(col) split field is defined in LocalTransformations
    lt = mm.find("p:LocalTransformations", NS)
    defined = {df.get("name") for df in lt.findall("p:DerivedField", NS)}
    used = {p.get("field") for p in mm.iter(f"{{{NS['p']}}}SimpleSetPredicate")}
    assert used <= defined and used
    # log loss -> logistic link output
    out = mm.find("p:Output", NS)
    assert out is not None and len(out.findall("p:OutputField", NS)) == 2


def test_export_columnstats_and_woe(prepared_set):
    model_set = prepared_set          # init/stats ran in the template
    from shifu_tpu.pipeline.export import ExportProcessor
    assert ExportProcessor(model_set, params={"type": "columnstats"}).run() == 0
    stats_csv = os.path.join(model_set, "export", "columnstats.csv")
    lines = open(stats_csv).read().splitlines()
    assert len(lines) > 5 and lines[0].startswith("columnNum,")
    assert ExportProcessor(model_set, params={"type": "woemapping"}).run() == 0
    woe_csv = os.path.join(model_set, "export", "woemapping.csv")
    assert "MISSING" in open(woe_csv).read()


def test_smoke_test_ok_and_one_sided(model_set, tmp_path):
    from shifu_tpu.pipeline.create import InitProcessor
    from shifu_tpu.pipeline.smoke import SmokeTestProcessor
    assert InitProcessor(model_set).run() == 0
    assert SmokeTestProcessor(model_set, params={}).run() == 0
    # break the tags -> smoke must fail
    mc_path = os.path.join(model_set, "ModelConfig.json")
    mc = ModelConfig.load(mc_path)
    mc.dataSet.posTags = ["never-matches"]
    mc.save(mc_path)
    assert SmokeTestProcessor(model_set, params={}).run() == 1


def test_encode_leaf_indices(prepared_set):
    model_set = prepared_set
    from shifu_tpu.pipeline.encode import EncodeProcessor
    _train_prepared(model_set, alg="RF",
                    tree_params={"TreeNum": 4, "MaxDepth": 3})
    assert EncodeProcessor(model_set, params={}).run() == 0
    enc = os.path.join(model_set, "tmp", "EncodedData")
    lines = open(enc).read().splitlines()
    assert lines[0] == "target|tree0|tree1|tree2|tree3"
    assert len(lines) == 4001
    # leaf ids are valid node indices for depth-3 trees (< 15)
    vals = np.array([r.split("|")[1:] for r in lines[1:]], dtype=int)
    assert vals.max() < 15


def test_convert_roundtrip(prepared_set):
    model_set = prepared_set
    from shifu_tpu.pipeline.convert import run_convert
    from shifu_tpu.models import load_any
    from shifu_tpu.data.shards import Shards
    _train_prepared(model_set)
    models_dir = os.path.join(model_set, "models")
    orig = load_any(os.path.join(models_dir, "model0.nn"))
    data = Shards.open(os.path.join(model_set, "tmp", "NormalizedData")).load_all()
    want = orig.compute(data["x"][:100])
    assert run_convert(model_set, {"tozipb": True}) == 0
    jpath = os.path.join(models_dir, "model0.nn.json")
    assert os.path.isfile(jpath)
    os.remove(os.path.join(models_dir, "model0.nn"))
    os.rename(jpath, os.path.join(models_dir, "model0.nn.json"))
    assert run_convert(model_set, {"tob": True}) == 0
    got = load_any(os.path.join(models_dir, "model0.nn")).compute(data["x"][:100])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_combo_ensemble(model_set):
    from shifu_tpu.pipeline.create import InitProcessor
    from shifu_tpu.pipeline.stats import StatsProcessor
    from shifu_tpu.pipeline.combo import run_combo
    assert InitProcessor(model_set).run() == 0
    assert StatsProcessor(model_set, params={}).run() == 0
    mc_path = os.path.join(model_set, "ModelConfig.json")
    mc = ModelConfig.load(mc_path)
    mc.train.numTrainEpochs = 10
    mc.train.params = {"TreeNum": 5, "MaxDepth": 3, "NumHiddenNodes": [8],
                       "ActivationFunc": ["tanh"], "Loss": "log",
                       "LearningRate": 0.1}
    mc.save(mc_path)
    assert run_combo(model_set, "new", "LR:GBT") == 0
    assert run_combo(model_set, "run", None) == 0
    assert os.path.isfile(os.path.join(model_set, "combo_0_LR", "models",
                                       "model0.lr"))
    assert os.path.isfile(os.path.join(model_set, "combo_1_GBT", "models",
                                       "model0.gbt"))
    assert run_combo(model_set, "eval", None) == 0
    doc = json.load(open(os.path.join(model_set, "ComboEval.Eval1.json")))
    assert doc["areaUnderRoc"] > 0.7
    assert len(doc["memberAuc"]) == 2


def test_analysis_fi_command(prepared_set):
    model_set = prepared_set
    """`analysis -fi model.gbt` writes a ranked .fi file (reference
    ShifuCLI.analysisModelFi)."""
    from shifu_tpu.cli import main as cli_main

    _train_prepared(model_set, alg="GBT",
                    tree_params={"TreeNum": 5, "MaxDepth": 3,
                                 "Loss": "log"})
    mp = os.path.join(model_set, "models", "model0.gbt")
    assert cli_main(["--dir", model_set, "analysis", "-fi", mp]) == 0
    lines = open(mp + ".fi").read().strip().split("\n")
    assert len(lines) >= 4
    name, v = lines[0].split("\t")
    assert float(v) > 0
    # names come from the model spec's feature list (txn_id is a candidate
    # in this fixture — no meta file — and its unique-id pos-rate leak
    # makes it the top splitter, as conftest documents)
    from shifu_tpu.models import tree as tree_model
    spec, _ = tree_model.load_model(mp)
    assert name in spec.feature_names
    assert len(lines) == len(spec.feature_names)


def test_error_codes_surface():
    """Coded errors (reference ShifuErrorCode taxonomy): remote sources,
    missing inputs, missing models."""
    import pytest
    from shifu_tpu.config.errors import ErrorCode, ShifuError
    from shifu_tpu.data.reader import resolve_data_files
    from shifu_tpu.eval.scorer import Scorer

    with pytest.raises(ShifuError) as ei:
        resolve_data_files("hdfs://nn/data/train")
    assert ei.value.error_code is ErrorCode.ERROR_REMOTE_SOURCE
    assert "1007" in str(ei.value)
    with pytest.raises(ShifuError) as ei:
        resolve_data_files("/nonexistent/glob*")
    assert ei.value.error_code is ErrorCode.ERROR_INPUT_NOT_FOUND
    with pytest.raises(ShifuError) as ei:
        Scorer.from_dir("/nonexistent/models")
    assert ei.value.error_code is ErrorCode.ERROR_MODEL_FILE_NOT_FOUND


def test_parquet_source_end_to_end(model_set, tmp_path):
    """A parquet dataPath flows through the same pipeline (reference
    NNParquetWorker/GuaguaParquetMapReduceClient role)."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq
    from shifu_tpu.config import ModelConfig
    from shifu_tpu.pipeline.create import InitProcessor
    from shifu_tpu.pipeline.evaluate import EvalProcessor
    from shifu_tpu.pipeline.norm import NormalizeProcessor
    from shifu_tpu.pipeline.stats import StatsProcessor
    from shifu_tpu.pipeline.train import TrainProcessor

    mcp = os.path.join(model_set, "ModelConfig.json")
    mc = ModelConfig.load(mcp)
    df = pd.read_csv(mc.dataSet.dataPath, sep="|", dtype=str,
                     keep_default_na=False)
    pdir = tmp_path / "pq"
    pdir.mkdir()
    # typed columns: parquet carries real floats + nulls
    out = pd.DataFrame({
        "amount": pd.to_numeric(df["amount"], errors="coerce"),
        "velocity": pd.to_numeric(df["velocity"], errors="coerce"),
        "age_days": pd.to_numeric(df["age_days"], errors="coerce"),
        "country": df["country"], "channel": df["channel"],
        "tag": df["tag"]})
    pq.write_table(pa.Table.from_pandas(out), str(pdir / "part-0.parquet"))
    mc.dataSet.dataPath = str(pdir)
    mc.dataSet.weightColumnName = None
    mc.train.numTrainEpochs = 15
    mc.train.params = {"NumHiddenNodes": [8], "ActivationFunc": ["tanh"],
                       "Propagation": "ADAM", "LearningRate": 0.05}
    mc.evals[0].dataSet.dataPath = str(pdir)
    mc.save(mcp)
    assert InitProcessor(model_set).run() == 0
    assert StatsProcessor(model_set, params={}).run() == 0
    assert NormalizeProcessor(model_set, params={}).run() == 0
    assert TrainProcessor(model_set, params={}).run() == 0
    assert EvalProcessor(model_set, params={"run_eval": "Eval1"}).run() == 0
    perf = json.load(open(os.path.join(model_set, "evals", "Eval1",
                                       "EvalPerformance.json")))
    assert perf["areaUnderRoc"] > 0.7


def test_grid_config_file(model_set):
    """train.gridConfigFile: one explicit trial per line, key:value;...
    (GridSearch.java:119-153); trials validate against the meta schema."""
    from shifu_tpu.config import ModelConfig
    from shifu_tpu.config.validator import ValidationError
    from shifu_tpu.pipeline.create import InitProcessor
    from shifu_tpu.pipeline.norm import NormalizeProcessor
    from shifu_tpu.pipeline.stats import StatsProcessor
    from shifu_tpu.pipeline.train import TrainProcessor

    gcf = os.path.join(model_set, "grid.conf")
    open(gcf, "w").write(
        "Propagation:ADAM;LearningRate:0.05\n"
        "Propagation:ADAM;LearningRate:0.2\n"
        "Propagation:ADAM;LearningRate:0.1;RegularizedConstant:0.001\n")
    mcp = os.path.join(model_set, "ModelConfig.json")
    mc = ModelConfig.load(mcp)
    mc.train.numTrainEpochs = 8
    mc.train.params = {"NumHiddenNodes": [8], "ActivationFunc": ["tanh"]}
    mc.train.gridConfigFile = "grid.conf"
    mc.save(mcp)
    assert InitProcessor(model_set).run() == 0
    assert StatsProcessor(model_set, params={}).run() == 0
    assert NormalizeProcessor(model_set, params={}).run() == 0
    assert TrainProcessor(model_set, params={}).run() == 0
    report = json.load(open(os.path.join(model_set, "tmp",
                                         "grid_search.json")))
    assert len(report) == 3
    # a typo in the file must fail probe-style, before training
    open(gcf, "w").write("Propagation:ADAM;LearningRat:0.05\n"
                         "Propagation:ADAM;LearningRate:0.2\n")
    import pytest
    with pytest.raises(ValidationError, match="LearningRate"):
        TrainProcessor(model_set, params={}).run()


def test_combo_resume_skips_trained(model_set):
    from shifu_tpu.pipeline.create import InitProcessor
    from shifu_tpu.pipeline.stats import StatsProcessor
    from shifu_tpu.pipeline.combo import run_combo
    assert InitProcessor(model_set).run() == 0
    assert StatsProcessor(model_set, params={}).run() == 0
    mc_path = os.path.join(model_set, "ModelConfig.json")
    mc = ModelConfig.load(mc_path)
    mc.train.numTrainEpochs = 5
    mc.train.params = {"NumHiddenNodes": [6], "ActivationFunc": ["tanh"],
                       "LearningRate": 0.1}
    mc.save(mc_path)
    assert run_combo(model_set, "new", "LR:NN") == 0
    assert run_combo(model_set, "run", None) == 0
    m0 = os.path.join(model_set, "combo_0_LR", "models", "model0.lr")
    t0 = os.path.getmtime(m0)
    assert run_combo(model_set, "run", None, resume=True) == 0
    assert os.path.getmtime(m0) == t0          # untouched: skipped


def test_encode_ref_model(prepared_set, tmp_path):
    """`encode -ref <dir>`: leaf-encode with ANOTHER model set's tree
    model (reference ModelDataEncodeProcessor ENCODE_REF_MODEL)."""
    import shutil
    model_set = prepared_set
    from shifu_tpu.pipeline.encode import EncodeProcessor
    _train_prepared(model_set, alg="RF",
                    tree_params={"TreeNum": 3, "MaxDepth": 3})
    # champion set = a copy holding the trained model; the working set's
    # own models are deleted so only -ref can supply one
    champ = str(tmp_path / "champion")
    shutil.copytree(model_set, champ)
    shutil.rmtree(os.path.join(model_set, "models"))
    assert EncodeProcessor(model_set, params={}).run() == 1
    assert EncodeProcessor(model_set,
                           params={"ref_model": champ}).run() == 0
    # a per-column binning mismatch must be rejected loudly (silent
    # garbage leaf ids otherwise)
    import json as _json
    ref_cc = os.path.join(champ, "ColumnConfig.json")
    cc = _json.load(open(ref_cc))
    for c in cc:
        if (c.get("columnBinning") or {}).get("binBoundary"):
            c["columnBinning"]["binBoundary"] = \
                c["columnBinning"]["binBoundary"][:-1]
            break
    _json.dump(cc, open(ref_cc, "w"))
    assert EncodeProcessor(model_set,
                           params={"ref_model": champ}).run() == 1
    assert EncodeProcessor(model_set,
                           params={"ref_model": "/nonexistent"}).run() == 1
    enc = os.path.join(model_set, "tmp", "EncodedData")
    lines = open(enc).read().splitlines()
    assert lines[0] == "target|tree0|tree1|tree2"
    assert len(lines) == 4001


def test_eval_score_sorted_and_nosort(prepared_set):
    """`eval -score` writes the score file sorted by mean score
    (reference sorts unless -nosort); -nosort keeps input order."""
    model_set = prepared_set
    from shifu_tpu.pipeline.evaluate import EvalProcessor
    _train_prepared(model_set)

    def means(path):
        rows = open(path).read().splitlines()[1:]
        return [float(r.split("|")[2]) for r in rows]

    assert EvalProcessor(model_set, params={"score": ""}).run() == 0
    hits = []
    for root, _, files in os.walk(model_set):
        for f in files:
            if f.startswith("EvalScore"):
                hits.append(os.path.join(root, f))
    assert hits
    sorted_means = means(hits[0])
    assert sorted_means == sorted(sorted_means, reverse=True)
    assert EvalProcessor(model_set,
                         params={"score": "", "nosort": True}).run() == 0
    unsorted_means = means(hits[0])
    assert unsorted_means != sorted_means     # input order preserved
    assert sorted(unsorted_means, reverse=True) == sorted_means
