"""The resident NN trainer's programs outlive the job
(``compile_cache.PROGRAMS``, ``train/nn_trainer.nn_programs_key``): the second
job of a process with an equal key builds nothing and trains to the bit as a
job on fresh programs does; each setting the programs read is in the key; the
row count is not, and nothing of a job stays alive in the holder.  Toy widths,
on the CPU's 8-device mesh.
"""

import gc
import os
import shutil
import sys
import weakref

import numpy as np
import pytest

import jax

from benchmark.run import _CompileCounter
from shifu_tpu import compile_cache, obs
from shifu_tpu.models import nn as nn_model
from shifu_tpu.train.nn_trainer import TrainSettings, train_ensemble
from shifu_tpu.train.sampling import member_masks

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "helpers"))
from reachable import reachable_arrays  # noqa: E402

ROWS, D, BAGS, MB = 256, 6, 2, 64       # 4 minibatches an epoch on ensemble 2 x data 4
SPEC = nn_model.NNModelSpec(input_dim=D, hidden_nodes=[8, 4],
                            activations=["tanh", "relu"], loss="log")
COMPILES = _CompileCounter()            # the benchmark's own count: what `job_rebuilds` reads


@pytest.fixture(scope="module", autouse=True)
def _count_builds():
    COMPILES.install()


@pytest.fixture(autouse=True)
def _fresh():                           # conftest.py empties the holder after every test
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()
    obs.set_enabled(False)


def _plane(rows):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(rows, D)).astype(np.float32)
    y = (rng.random(rows) < 1 / (1 + np.exp(-2 * x[:, 0]))).astype(np.float32)
    tw, vw = member_masks(rows, BAGS, valid_rate=0.25, sample_rate=0.9, replacement=False,
                          targets=y, seed=0)
    return x, y, tw, vw


def _job(rows=ROWS, mb=MB, epochs=3, hypers=False, y_members=False, **settings):
    """One resident ``train_ensemble`` job of two members; returns (result,
    programs built inside it)."""
    x, y, tw, vw = _plane(rows)
    kw = dict(optimizer="ADAM", learning_rate=0.05, epochs=epochs, batch_size=mb, seed=3)
    st = TrainSettings(**{**kw, **settings})
    before = COMPILES.built
    res = train_ensemble(
        x, y, tw, vw, SPEC, st,
        member_hypers={"lr_scale": np.array([1.0, 0.5]), "l2": np.array([0.0, 1e-3]),
                       "l1": np.zeros(BAGS), "dropout": np.array([0.0, 0.1])}
        if hypers else None,
        y_members=np.stack([y, 1.0 - y]) if y_members else None)
    return res, COMPILES.built - before


def _bits(res):
    return [np.asarray(a).tobytes() for a in jax.tree_util.tree_leaves(res.params)]


def _same(a, b):
    assert a.history == b.history and a.epochs_run == b.epochs_run
    assert np.array_equal(a.valid_errors, b.valid_errors)
    assert np.array_equal(a.train_errors, b.train_errors)
    assert _bits(a) == _bits(b)


def _init_spans():
    return [r["attrs"] for r in obs.pending_records()
            if r.get("kind") == "span" and r.get("name") == "nn.init"]


# ------------------------------------------------- the second job builds nothing
@pytest.mark.parametrize("telemetry", [False, True], ids=["plain", "telemetry"])
@pytest.mark.parametrize("mb", [MB, 0], ids=["epoch_steps", "full_batch"])
def test_second_job_builds_nothing_and_trains_to_the_bit(mb, telemetry):
    obs.set_enabled(telemetry)
    first, built_first = _job(mb=mb)
    held = compile_cache.PROGRAMS._held
    second, built_second = _job(mb=mb)
    assert built_first >= 2 and built_second == 0
    assert compile_cache.PROGRAMS._held is held and len(held) == 3
    if telemetry:
        assert [a["programs_built"] for a in _init_spans()] == [3, 0]
        assert obs.counter("nn.programs_reused").value == 1
    compile_cache.PROGRAMS.clear()
    fresh, built_fresh = _job(mb=mb)
    assert built_fresh >= 2
    assert len(first.history) == 3
    _same(first, second)
    _same(first, fresh)
    assert not reachable_arrays(compile_cache.PROGRAMS._held)


# ------------------------------------------------------- the key
CHANGES = {                             # key field -> (the first job's, the second job's)
    "l2": ({}, dict(l2=1e-3)),
    "l1": ({}, dict(l1=1e-3)),
    "dropout_rate": (dict(dropout_rate=0.1), dict(dropout_rate=0.2)),
    "dropout_gate": ({}, dict(dropout_rate=0.1)),
    "optimizer": ({}, dict(optimizer="RMSPROP")),
    "learning_rate": ({}, dict(learning_rate=0.02)),
    "precision_bf16": (dict(precision="f32"), dict(precision="bf16")),
    "precision_mixed": (dict(precision="f32"), dict(precision="mixed")),
    "precision_bf16_mixed": (dict(precision="bf16"), dict(precision="mixed")),
    "fixed_layers": ({}, dict(fixed_layers=(1,))),
    "member_hypers": ({}, dict(hypers=True)),
    "y_members": ({}, dict(y_members=True)),
    "telemetry": ({}, {}),              # switched on between the jobs
}


@pytest.mark.parametrize("what", sorted(CHANGES))
def test_a_changed_key_builds_anew_and_drops_the_old_programs(what):
    first, second = CHANGES[what]
    _, built = _job(epochs=1, **first)
    assert built >= 2
    old = [weakref.ref(p) for p in compile_cache.PROGRAMS._held]
    obs.set_enabled(what == "telemetry")
    _, built = _job(epochs=1, **second)
    assert built >= 2
    if what == "telemetry":
        assert [a["programs_built"] for a in _init_spans()] == [3]
        assert obs.counter("nn.programs_reused").value == 0
    held = compile_cache.PROGRAMS._held
    assert len(held) == 3 and not any(r() in held for r in old)
    del held
    gc.collect()
    assert [r() for r in old] == [None, None, None]          # one entry: the old one is gone
    _, built = _job(epochs=1, **second)
    assert built == 0                                        # and the new one is held


def test_another_row_count_reuses_the_held_programs():
    """Rows and minibatches are shapes and static arguments: jit traces the
    held callables again, the holder keeps them."""
    obs.set_enabled(True)
    _, built = _job(epochs=1)
    held = compile_cache.PROGRAMS._held
    _, built_rows = _job(rows=ROWS + 128, epochs=1)
    assert built >= 2 and built_rows >= 1
    assert compile_cache.PROGRAMS._held is held
    assert [a["programs_built"] for a in _init_spans()] == [3, 0]
    assert obs.counter("nn.programs_reused").value == 1
    _, built = _job(epochs=1)
    assert built == 0 and compile_cache.PROGRAMS._held is held


def test_a_finished_job_leaves_no_array_alive(monkeypatch):
    """The holder keeps callables: the hyper rows, the plane and the state a
    job put on the device die with the job."""
    put, dead = jax.device_put, []

    def recording_put(*args, **kwargs):
        out = put(*args, **kwargs)
        dead.extend(weakref.ref(a) for a in jax.tree_util.tree_leaves(out)
                    if isinstance(a, jax.Array))
        return out
    monkeypatch.setattr(jax, "device_put", recording_put)
    res, _ = _job(epochs=2, hypers=True)
    monkeypatch.undo()
    assert len(dead) >= 6                   # params, state, x, y, weights, hyper rows
    del res
    gc.collect()
    assert [r for r in dead if r() is not None] == []
    assert compile_cache.PROGRAMS._held is not None
    assert not reachable_arrays(compile_cache.PROGRAMS._held)


def test_the_key_names_what_the_closures_read():
    from shifu_tpu.parallel import mesh as meshlib
    from shifu_tpu.train.nn_trainer import nn_programs_key
    mesh = meshlib.device_mesh(n_ensemble=BAGS)
    st = TrainSettings(optimizer="adam", learning_rate=0.05, opt_kwargs={"beta2": 0.99})

    def key(spec=SPEC, settings=st, precision="f32", uniform=True, dropout=False, y_axis=None,
            mesh=mesh):
        return nn_programs_key(spec, settings, precision, uniform, dropout, y_axis, mesh)
    base = key()
    hash(base)
    assert base == key(spec=nn_model.NNModelSpec.from_json(SPEC.to_json()),
                       settings=TrainSettings(optimizer="ADAM", learning_rate=0.05,
                                              opt_kwargs={"beta2": 0.99}, seed=9, epochs=7,
                                              batch_size=128, checkpoint_dir="elsewhere"))
    others = [key(settings=TrainSettings(optimizer="ADAM", learning_rate=0.05)),   # opt_kwargs
              key(spec=nn_model.NNModelSpec(input_dim=D, hidden_nodes=[8, 5],
                                            activations=["tanh", "relu"], loss="log")),
              key(spec=nn_model.NNModelSpec(input_dim=D, hidden_nodes=[8, 4],
                                            activations=["tanh", "relu"], loss="squared")),
              key(settings=TrainSettings(optimizer="adam", learning_rate=0.05,
                                         opt_kwargs={"beta2": 0.99}, matmul_precision="bfloat16")),
              key(settings=TrainSettings(optimizer="adam", learning_rate=0.05,
                                         opt_kwargs={"beta2": 0.99}, fixed_layers=(1,),
                                         fixed_bias=True)),
              key(mesh=meshlib.device_mesh(n_ensemble=1))]
    assert all(o != base for o in others)
    # stacked trials carry their regularisers in the hyper rows: not in the key
    assert key(uniform=False) == key(
        uniform=False, settings=TrainSettings(optimizer="adam", learning_rate=0.05,
                                              opt_kwargs={"beta2": 0.99}, l2=0.1))


# --------------------------------------- resume and stacked grid trials held
def test_resumed_job_continues_bit_exactly_on_held_programs(tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    whole, _ = _job(epochs=4)
    compile_cache.PROGRAMS.clear()
    _job(epochs=2, checkpoint_dir=ckpt_dir, checkpoint_every=2)
    held = compile_cache.PROGRAMS._held
    shutil.copytree(ckpt_dir, ckpt_dir + "-fresh")  # a resumed job checkpoints its last epoch
    resumed, built = _job(epochs=4, checkpoint_dir=ckpt_dir, checkpoint_every=2, resume=True)
    assert built == 0 and compile_cache.PROGRAMS._held is held
    compile_cache.PROGRAMS.clear()
    resumed_fresh, built_fresh = _job(epochs=4, checkpoint_dir=ckpt_dir + "-fresh",
                                      checkpoint_every=2, resume=True)
    assert built_fresh >= 2
    assert resumed.history == whole.history[2:] and resumed.epochs_run == 4
    _same(resumed, resumed_fresh)
    assert _bits(resumed) == _bits(whole)


def test_stacked_grid_trials_twice_in_one_process():
    first, built_first = _job(hypers=True)
    second, built_second = _job(hypers=True)
    assert built_first >= 2 and built_second == 0
    compile_cache.PROGRAMS.clear()
    fresh, _ = _job(hypers=True)
    _same(first, second)
    _same(first, fresh)
