"""Test rig: force an 8-device virtual CPU mesh before jax initializes.

The reference only unit-tests master/worker math separately (SURVEY.md §4);
here every distributed code path runs for real on a virtual multi-device mesh.
"""

import os

# Unit tests run on the virtual 8-device host mesh, deterministically, in
# x64 — whatever platform the environment names (the chip is exercised by
# chip_smoke.py, never by this suite).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "true")

# Persistent XLA compilation cache: the suite is compile-bound (e2e
# pipeline tests trace dozens of executables); re-runs on the same
# machine skip those compiles entirely (measured -31% on test_wdl.py).
# An outside JAX_COMPILATION_CACHE_DIR is respected; subprocess children
# (CLI, elastic controllers) inherit whichever directory is in force.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      "/tmp/shifu_tpu_jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _no_programs_held_across_tests():
    """A trainer's jitted programs outlive its job (``compile_cache.PROGRAMS``):
    not its test, or a test's monkeypatched constant would be traced into the
    next test's programs."""
    yield
    from shifu_tpu import compile_cache
    compile_cache.PROGRAMS.clear()


@pytest.fixture(scope="session")
def fraud_csv(tmp_path_factory):
    """Synthetic fraud-style dataset: mixed numeric/categorical, missing
    values, a weight column, '|' delimited like the reference's tutorial
    data.  ONE generator serves the suite and the tutorial
    (``examples/make_fraud_data.py``) so they can never drift — the
    golden-parity pins ride on this exact byte stream."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "make_fraud_data",
        os.path.join(os.path.dirname(__file__), "..", "examples",
                     "make_fraud_data.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    d = tmp_path_factory.mktemp("fraud")
    src = mod.make(str(d), n=4000)
    path = os.path.join(str(d), "part-000.csv")
    os.rename(src, path)
    return path


def _scaffold_model_set(base_dir: str, fraud_csv: str) -> str:
    from shifu_tpu.config import ModelConfig
    from shifu_tpu.pipeline.create import create_new_model

    mdir = create_new_model("fraudtest", base_dir=base_dir)
    mc = ModelConfig.load(os.path.join(mdir, "ModelConfig.json"))
    mc.dataSet.dataPath = fraud_csv
    mc.dataSet.dataDelimiter = "|"
    mc.dataSet.targetColumnName = "tag"
    mc.dataSet.posTags = ["bad"]
    mc.dataSet.negTags = ["good"]
    mc.dataSet.weightColumnName = "weight"
    mc.dataSet.metaColumnNameFile = None
    mc.train.baggingNum = 1
    mc.train.numTrainEpochs = 30
    mc.evals[0].dataSet.dataPath = fraud_csv
    mc.evals[0].dataSet.dataDelimiter = "|"
    mc.save(os.path.join(mdir, "ModelConfig.json"))
    return mdir


@pytest.fixture
def model_set(tmp_path, fraud_csv):
    """A scaffolded model set over the synthetic fraud data, ready for init."""
    return _scaffold_model_set(str(tmp_path), fraud_csv)


@pytest.fixture(scope="session")
def _prepared_template(tmp_path_factory, fraud_csv):
    """init+stats+norm run ONCE on the default config (norm materializes
    both the norm and clean/binned planes, so any algorithm can train from
    a copy) — the suite's pipeline-mechanics tests were each re-running
    these three identical steps."""
    from shifu_tpu.pipeline.create import InitProcessor
    from shifu_tpu.pipeline.norm import NormalizeProcessor
    from shifu_tpu.pipeline.stats import StatsProcessor

    mdir = _scaffold_model_set(
        str(tmp_path_factory.mktemp("prepared")), fraud_csv)
    assert InitProcessor(mdir).run() == 0
    assert StatsProcessor(mdir, params={}).run() == 0
    assert NormalizeProcessor(mdir, params={}).run() == 0
    return mdir


@pytest.fixture
def prepared_set(_prepared_template, tmp_path):
    """A fresh per-test copy of the prepared (post-norm) model set.  Use
    when the test does not change dataSet/stats/normalize config; set
    train config + run TrainProcessor directly."""
    import shutil
    dst = str(tmp_path / "fraudtest")
    shutil.copytree(_prepared_template, dst)
    return dst
