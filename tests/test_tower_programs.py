"""The tower trainer's three programs outlive the job
(``compile_cache.PROGRAMS``, ``train/tower_trainer.programs_key``): the second
job of a process with an equal key builds nothing and trains to the bit as a
job on fresh programs does; anything a program depends on is in the key; one
entry is held, of callables only.  Toy widths, on the CPU: the towers' own test
files' ``TowerParams``.
"""

import gc
import os
import shutil
import sys
import weakref

import numpy as np
import pytest

import jax

import test_tower_afmoe as afmoe_t
import test_tower_deepseek_v3 as deepseek_t
import test_tower_lfm2 as lfm2_t
import test_tower_nemotron_h as nemotron_t
import test_tower_sdar as sdar_t
from benchmark.run import _CompileCounter
from shifu_tpu import compile_cache, obs
from shifu_tpu.train import tower_trainer as tt
from shifu_tpu.train.nn_trainer import TrainSettings

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "helpers"))
from reachable import reachable_arrays  # noqa: E402

ROWS, MB, VALID = 32, 8, 0.25           # 24 training rows: 3 steps and 1 validation step an epoch
TOWERS = {                              # name -> (toy spec, its columns' bins, RowsPerSequence)
    "sdar_moe": (sdar_t._spec, sdar_t.COL_BINS, 1),
    "nemotron_h": (nemotron_t._spec, nemotron_t.COL_BINS, 1),
    "afmoe": (afmoe_t._spec, afmoe_t.COL_BINS, afmoe_t.R),
    "lfm2_moe": (lfm2_t._spec, lfm2_t.COL_BINS, lfm2_t.R),
    "deepseek_v3": (deepseek_t._spec, deepseek_t.COL_BINS, deepseek_t.R),
}
COMPILES = _CompileCounter()            # the benchmark's own count: what `*_job_rebuilds` reads


@pytest.fixture(scope="module", autouse=True)
def _count_builds():
    COMPILES.install()


@pytest.fixture(autouse=True)
def _fresh():                           # conftest.py empties the holder after every test
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()
    obs.set_enabled(False)


def _plane(name, rows=ROWS, seed=0):
    rng = np.random.default_rng(seed)
    bins = np.stack([rng.integers(0, b + 1, rows) for b in TOWERS[name][1]], 1).astype(np.uint8)
    return bins, (rng.random(rows) < 0.5).astype(np.float32), np.ones(rows, np.float32)


def _job(name, rows=ROWS, mb=MB, lr=1e-3, rps=None, spec_over=None, epochs=2, **settings):
    """One ``train_tower`` job; returns (result, programs built inside it)."""
    make_spec, _, toy_rps = TOWERS[name]
    st = TrainSettings(optimizer="ADAM", learning_rate=lr, epochs=epochs, batch_size=mb, seed=3,
                       **settings)
    before = COMPILES.built
    res = tt.train_tower(*_plane(name, rows), make_spec(**(spec_over or {})), st, VALID,
                         rows_per_sequence=toy_rps if rps is None else rps)
    return res, COMPILES.built - before


def _bits(res):
    return [np.asarray(a).tobytes() for a in jax.tree_util.tree_leaves(res.params)]


def _init_spans():
    return [r["attrs"] for r in obs.pending_records()
            if r.get("kind") == "span" and r.get("name") == "tower.init"]


# ------------------------------------------------- the second job builds nothing
@pytest.mark.parametrize("telemetry", [False, True], ids=["plain", "telemetry"])
@pytest.mark.parametrize("name", sorted(TOWERS))
def test_second_job_builds_nothing_and_trains_to_the_bit(name, telemetry):
    obs.set_enabled(telemetry)
    first, built_first = _job(name)
    held = compile_cache.PROGRAMS._held
    second, built_second = _job(name)
    assert built_first >= 3 and built_second == 0
    assert compile_cache.PROGRAMS._held is held and len(held) == 3
    if telemetry:
        assert [a["programs_built"] for a in _init_spans()] == [3, 0]
        assert obs.counter("tower.programs_reused").value == 1
        scopes = [r["attrs"]["scopes"] for r in obs.pending_records()
                  if r.get("name") == "op_scopes"]
        assert len(scopes) == 2 and scopes[0] == scopes[1]          # the held step's HLO
        assert sum(bool(v) for v in scopes[1].values()) >= 3
    compile_cache.PROGRAMS.clear()
    fresh, built_fresh = _job(name)
    assert built_fresh >= 3
    assert first.history == second.history == fresh.history and len(first.history) == 2
    assert _bits(first) == _bits(second) == _bits(fresh)
    assert not reachable_arrays(compile_cache.PROGRAMS._held)


# ------------------------------------------------------- the key and the bound
CHANGES = {                             # what changed -> (tower, the second job's difference)
    "learning_rate": ("sdar_moe", dict(lr=2e-3)),
    "tower_params_width": ("sdar_moe", dict(spec_over={"moe_intermediate_size": 40})),
    "rows": ("sdar_moe", dict(rows=ROWS + 8)),
    "microbatch": ("sdar_moe", dict(mb=2 * MB)),
    "rows_per_sequence": ("afmoe", dict(rps=afmoe_t.R // 2)),
    "telemetry": ("sdar_moe", {}),      # switched on between the jobs
}


@pytest.mark.parametrize("what", sorted(CHANGES))
def test_a_changed_key_builds_three_and_drops_the_old_programs(what):
    name, change = CHANGES[what]
    _, built = _job(name, epochs=1)
    assert built >= 3
    old = [weakref.ref(p) for p in compile_cache.PROGRAMS._held]
    obs.set_enabled(what == "telemetry")
    _, built = _job(name, epochs=1, **change)
    assert built >= 3
    if what == "telemetry":
        assert [a["programs_built"] for a in _init_spans()] == [3]
        assert obs.counter("tower.programs_reused").value == 0
    held = compile_cache.PROGRAMS._held
    assert len(held) == 3 and not any(r() in held for r in old)
    del held
    gc.collect()
    assert [r() for r in old] == [None, None, None]          # one entry: the old one is gone
    _, built = _job(name, epochs=1, **change)
    assert built == 0                                        # and the new one is held


def test_the_key_names_what_the_closures_read():
    spec = sdar_t._spec()
    st = TrainSettings(optimizer="adam", learning_rate=1e-3, opt_kwargs={"beta2": 0.99})
    key = tt.programs_key(spec, st, MB, 1, (ROWS, spec.seq_len))
    hash(key)
    assert key == tt.programs_key(sdar_t._spec(), st, MB, 1, (ROWS, spec.seq_len))
    for other in (tt.programs_key(spec, TrainSettings(optimizer="ADAM", learning_rate=1e-3), MB, 1,
                                  (ROWS, spec.seq_len)),                    # opt_kwargs
                  tt.programs_key(spec, TrainSettings(optimizer="SGD", learning_rate=1e-3,
                                                      opt_kwargs={"beta2": 0.99}), MB, 1,
                                  (ROWS, spec.seq_len)),                    # the rule
                  tt.programs_key(sdar_t._spec(rank=1), st, MB, 1, (ROWS, spec.seq_len)),
                  tt.programs_key(nemotron_t._spec(), st, MB, 1, (ROWS, spec.seq_len))):
        assert other != key


def test_holder_keeps_one_entry_and_builds_only_on_a_miss():
    holder = compile_cache.ProgramHolder()
    made = []

    def build(tag):
        return lambda: made.append(tag) or (tag,)
    assert holder.programs(("a", 1), build("a")) == (("a",), False)
    assert holder.programs(("a", 1), build("never")) == (("a",), True)
    assert holder.programs(("b", 1), build("b")) == (("b",), False)
    assert holder.programs(("a", 1), build("a again")) == (("a again",), False)     # not kept
    assert made == ["a", "b", "a again"]
    holder.clear()
    assert holder.programs(("a", 1), build("after clear")) == (("after clear",), False)

    def fails():
        raise RuntimeError("no program")
    with pytest.raises(RuntimeError):
        holder.programs(("c", 1), fails)
    assert holder.programs(("a", 1), build("after a failed build")) == \
        (("after a failed build",), False)                  # a failed build holds nothing


# ------------------------------------------------ resume runs on held programs
def test_resumed_job_continues_bit_exactly_on_held_programs(tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    whole, _ = _job("sdar_moe", epochs=3)
    compile_cache.PROGRAMS.clear()
    _job("sdar_moe", epochs=1, checkpoint_dir=ckpt_dir, checkpoint_every=1)
    held = compile_cache.PROGRAMS._held
    assert [f for f in os.listdir(ckpt_dir) if f.startswith("ckpt-1")]
    resumed, built = _job("sdar_moe", epochs=3, checkpoint_dir=ckpt_dir, checkpoint_every=1,
                          resume=True)
    assert built == 0 and compile_cache.PROGRAMS._held is held
    assert resumed.epochs_run == 3 and resumed.history == whole.history[1:]
    assert _bits(resumed) == _bits(whole)
    shutil.rmtree(ckpt_dir)
