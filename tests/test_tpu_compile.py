"""Compile-only checks at real widths for a described (not attached) TPU v5e:
the TPU's own compiler, run here without the chip, refuses what the chip would
refuse — a program that does not fit the device's memory first of all.  Kept
in ONE file: the worker that runs it loads the TPU's library, and keeps it.
Nothing here runs on a device; no number here is a measurement.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 15.75 * 2 ** 30           # what a v5e chip gives a program


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache_no_x64():
    """A compile for a described chip cannot be read back from the
    persistent cache, and the suite's x64 is not what the chip runs."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_nemotron_train_step_fits_one_v5e_chip(one_chip, no_cache_no_x64):
    """The cell ``nemotron-train``'s step program — 8 rows of 433 positions,
    the 838 M-parameter share with its Adam state donated — compiles for one
    v5e chip and its live bytes stay under the chip's memory."""
    from benchmark.drivers.train_nemotron import tower_params
    from shifu_tpu.models import tower_nemotron_h as tw
    from shifu_tpu.train import tower_trainer as tt
    from shifu_tpu.train.optimizers import make_optimizer
    with open(os.path.join(ROOT, "benchmark", "configs", "nemotron3-super-tp8-ep64.json")) as f:
        doc = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic", "retrain-320x433-2epochs.json")) as f:
        rows = json.load(f)["rows"]
    mb = doc["train"]["params"]["MiniBatchs"]
    bins = [doc["stats"]["maxNumBin"]] * 383 + [64] * 49          # the most ids the table can need
    spec = tw.spec_from_params(tower_params(doc), list(range(432)), bins, [""] * 432)
    assert (mb, spec.seq_len, spec.n_ids) == (8, 433, 15828)
    opt = make_optimizer("ADAM", doc["train"]["params"]["LearningRate"])
    state = jax.eval_shape(lambda k: (lambda p: (p, opt.init(p)))(tw.init_params(k, spec)),
                           jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(state[0])) == 838_249_968
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params, opt_state = jax.tree_util.tree_map(on_chip, state)
    acc = jax.tree_util.tree_map(on_chip, jax.eval_shape(lambda: tt._zero_acc(spec)))
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    step, _ = tt.build_programs(spec, opt, mb)
    compiled = step.lower(params, opt_state, acc, arg((rows, 433), jnp.int32),
                          arg((rows,), jnp.float32), arg((mb,), jnp.int32), arg((2,), jnp.uint32),
                          arg((4,), jnp.int32), arg((), jnp.int32), arg((), jnp.int32)).compile()
    ma = compiled.memory_analysis()
    live = ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes + \
        ma.temp_size_in_bytes
    assert ma.alias_size_in_bytes > 10.0e9            # 12 bytes a parameter updated in place
    assert live + ma.generated_code_size_in_bytes < HBM_BYTES, (live, ma)
