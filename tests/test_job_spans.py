"""What is left of a tower job has names (PR 35): ``tower.save``'s four child
spans with the bytes each moved, nothing at all with telemetry off, every
scope of a tower's ``SCOPES`` in its step with the catch-all ``tower/trunk``
taking only what no sub-layer scope claims, and the names read being this
source's.  Toy widths, on the CPU: the towers' own test files' ``TowerParams``
and ``tests/test_tower_programs.py``'s planes.
"""

import glob
import json
import os
import re

import pytest

import jax
import jax.numpy as jnp

import test_tower_afmoe as afmoe_t
import test_tower_nemotron_h as nemotron_t
import test_tower_sdar as sdar_t
from test_tower_programs import MB, ROWS, TOWERS
from shifu_tpu import obs
from shifu_tpu.models import towers
from shifu_tpu.obs import tracer
from shifu_tpu.obs.costs import op_scopes
from shifu_tpu.train import tower_trainer as tt
from shifu_tpu.train.optimizers import make_optimizer


@pytest.fixture(autouse=True)
def _fresh():
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()
    obs.set_enabled(False)


# ------------------------------------------- what is left of a job has names
CLI_SETS = {"sdar_moe": sdar_t._tower_set, "nemotron_h": nemotron_t._tower_set,
            "afmoe": afmoe_t._tower_set}
SAVE_SPANS = ("tower.save.clear", "tower.save.fetch", "tower.save.write", "tower.save.commit")


def _job_records(mdir, kind):
    with open(os.path.join(mdir, "telemetry", "trace.jsonl")) as f:
        return [r for r in map(json.loads, f) if r["kind"] == kind]


@pytest.mark.parametrize("name", sorted(CLI_SETS))
def test_the_save_is_spanned_from_the_clearing_to_the_rename(name, prepared_set):
    """One code path for the three towers: ``tower.save``'s four children, in
    order, with the bytes each moved; the second job of the set clears what
    the first wrote."""
    from shifu_tpu.cli import main
    CLI_SETS[name](prepared_set, epochs=1)
    model = os.path.join(prepared_set, "models", "model0.tower")
    cleared = []
    for _ in range(2):
        obs.reset_for_tests()
        obs.set_enabled(None)           # the flag is what turns it on
        assert main(["--dir", prepared_set, "train", "--telemetry"]) == 0
        spans = _job_records(prepared_set, "span")
        (scopes,) = [r["attrs"]["scopes"] for r in _job_records(prepared_set, "event")
                     if r["name"] == "op_scopes"]
        assert list(scopes) == list(towers.module(name).SCOPES) and all(scopes.values())
        (save,) = [s for s in spans if s["name"] == "tower.save"]
        kids = sorted((s for s in spans if s["parent"] == save["id"]), key=lambda s: s["ts"])
        assert tuple(s["name"] for s in kids) == SAVE_SPANS
        assert sum(s["dur_s"] for s in kids) <= save["dur_s"]
        attrs = {s["name"]: s["attrs"] for s in kids}
        _, params = towers.load_model(model)
        leaves = jax.tree_util.tree_leaves(params)
        assert attrs["tower.save.fetch"] == {"bytes": sum(a.nbytes for a in leaves)}
        assert attrs["tower.save.write"] == {"bytes": os.path.getsize(model)} == save["attrs"]
        assert attrs["tower.save.commit"] == {}
        cleared.append(attrs["tower.save.clear"]["bytes"])
        assert not glob.glob(model + ".tmp*")
        os.remove(os.path.join(prepared_set, "telemetry", "trace.jsonl"))
    assert cleared == [0, os.path.getsize(model)]


def test_telemetry_off_the_job_opens_no_span_and_writes_no_annotation(prepared_set, tmp_path):
    """The same job with telemetry off, inside a profiler session: the null
    span everywhere, nothing recorded, no ``shifu:`` annotation in the file."""
    from jax.profiler import ProfileData
    from shifu_tpu.cli import main
    sdar_t._tower_set(prepared_set, epochs=1)
    obs.reset_for_tests()
    obs.set_enabled(False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        assert main(["--dir", prepared_set, "train"]) == 0
        assert obs.span("tower.save.fetch") is obs.span("setup.columns")      # the null span
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path / "trace"), "**", "*.xplane.pb"), recursive=True)
    assert not [ev.name for plane in ProfileData.from_file(path).planes for line in plane.lines
                for ev in line.events if ev.name.startswith(tracer.ANNOTATION_PREFIX)]
    assert obs.pending_records() == []
    assert not os.path.exists(os.path.join(prepared_set, "telemetry", "trace.jsonl"))
    assert os.path.getsize(os.path.join(prepared_set, "models", "model0.tower")) > 0


# --------------------------------------------------- the step's scopes by name
@pytest.fixture()
def no_compile_cache():
    """jax keys its persistent cache without the ops' names: an entry an
    older source left there would be handed back under that source's scopes
    (``obs.costs`` keys what telemetry builds with them; a plain ``lower()
    .compile()`` here must not read the cache)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _toy_step_text(name):
    make_spec, _, rps = TOWERS[name]
    spec = make_spec()
    opt = make_optimizer("ADAM", 1e-3)
    tower = towers.module(spec.tower)
    state = jax.eval_shape(lambda k: (lambda p: (p, opt.init(p)))(tower.init_params(k, spec)),
                           jax.random.PRNGKey(0))
    acc = jax.eval_shape(lambda: tt._zero_acc(spec))
    arg = jax.ShapeDtypeStruct
    step, _ = tt.build_programs(spec, opt, MB, rps)
    text = step.lower(*state, acc, arg((ROWS, spec.seq_len), jnp.int32), arg((ROWS,), jnp.float32),
                      arg((MB,), jnp.int32), arg((2,), jnp.uint32), arg((4,), jnp.int32),
                      arg((), jnp.int32), arg((), jnp.int32)).compile().as_text()
    return tower, text


@pytest.mark.parametrize("name", sorted(TOWERS))
def test_every_scope_finds_ops_and_the_catch_all_takes_only_the_rest(name, no_compile_cache):
    """``tower/trunk`` lies around the layer loop, so every sub-layer's op
    name holds it too: it is listed after every scope that occurs inside it
    (``op_scopes`` gives an op to the first scope its name holds) and before
    ``tower/opt``, and no op traced under a sub-layer's scope is given to it."""
    tower, text = _toy_step_text(name)
    scopes = tower.SCOPES
    assert scopes[-1] == "tower/opt" and len(set(scopes)) == len(scopes)
    inner = [s for s in scopes if s.startswith(("tower/attn", "tower/moe", "tower/ssm", "tower/mlp",
                                                "tower/mtp"))]
    assert inner and all(scopes.index(s) < scopes.index("tower/trunk") for s in inner)
    table = op_scopes(text, scopes)
    assert set(table) == set(scopes) and all(table[s] for s in scopes), \
        {s: len(v) for s, v in table.items()}
    op_name = dict(re.findall(r'%([\w.\-]+) = .*metadata=\{op_name="([^"]*)"', text))
    for inst in table["tower/trunk"]:
        assert "tower/trunk" in op_name[inst] and not any(s in op_name[inst] for s in inner), \
            (inst, op_name[inst])
    nested = [n for n, o in op_name.items() if "tower/trunk" in o and any(s in o for s in inner)]
    assert nested and not set(nested) & set(table["tower/trunk"])       # they exist, and went inward


def test_what_telemetry_builds_is_not_an_older_sources_executable(tmp_path):
    """Two sources that differ in a scope's name alone: jax's persistent cache
    keys them alike and hands the second the first's executable, names and
    all; ``obs.costed_jit`` keys what it builds with the names, so
    ``hlo_text`` (what ``op_scopes`` reads) is this source's."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()
    try:
        def source(scope):
            def f(x):
                with jax.named_scope(scope):
                    return jnp.sin(x) * 2.0
            return f
        x = jnp.ones(8)
        obs.set_enabled(True)
        for scope in ("tower/older", "tower/newer"):
            program = obs.costed_jit("t.renamed", source(scope))
            program(x)
            assert scope in program.hlo_text()
        assert "tower/older" not in program.hlo_text()
        plain = [jax.jit(source(scope)).lower(x).compile().as_text()
                 for scope in ("tower/older", "tower/newer")]
        assert "tower/older" in plain[1] and "tower/newer" not in plain[1]      # jax's own key
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        cc.reset_cache()
