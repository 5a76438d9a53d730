"""Compile-only checks at real widths for a described (not attached) TPU v5e:
the TPU's own compiler, run here without the chip, refuses what the chip would
refuse — a program that does not fit the device's memory first of all.  Kept
in ONE file: the worker that runs it loads the TPU's library, and keeps it.
Nothing here runs on a device; no number here is a measurement.
"""

import json
import os
import re

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


def _doc(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def _spec(tw, tower_params, doc):
    bins = [doc["stats"]["maxNumBin"]] * 383 + [64] * 49          # the most ids the table can need
    return tw.spec_from_params(tower_params, list(range(432)), bins, [""] * 432)


def _compiled_step(tw, spec, doc, rows, one_chip):
    """(the cell's step program compiled for the described chip, its
    parameter count): state and accumulators donated, shapes only."""
    from shifu_tpu.train import tower_trainer as tt
    from shifu_tpu.train.optimizers import make_optimizer
    mb = doc["train"]["params"]["MiniBatchs"]
    opt = make_optimizer("ADAM", doc["train"]["params"]["LearningRate"])
    state = jax.eval_shape(lambda k: (lambda p: (p, opt.init(p)))(tw.init_params(k, spec)),
                           jax.random.PRNGKey(0))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params, opt_state = jax.tree_util.tree_map(on_chip, state)
    acc = jax.tree_util.tree_map(on_chip, jax.eval_shape(lambda: tt._zero_acc(spec)))
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    step, _ = tt.build_programs(spec, opt, mb, doc["train"]["params"].get("RowsPerSequence", 1))
    compiled = step.lower(params, opt_state, acc, arg((rows, spec.seq_len), jnp.int32),
                          arg((rows,), jnp.float32), arg((mb,), jnp.int32), arg((2,), jnp.uint32),
                          arg((4,), jnp.int32), arg((), jnp.int32), arg((), jnp.int32)).compile()
    return compiled, sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(state[0]))


def _live_bytes(compiled) -> float:
    ma = compiled.memory_analysis()
    return ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes + \
        ma.temp_size_in_bytes + ma.generated_code_size_in_bytes


_COMPUTATION = re.compile(r'^(ENTRY )?%([\w.\-]+) \(.*\{\s*$')
_CALLED = re.compile(r'(?:body|condition|to_apply|branch_computations|true_computation|'
                     r'false_computation)=\{?((?:%[\w.\-]+(?:, )?)+)\}?')
_NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast", "after-all"}
UNNAMED_FUSIONS = 0.08                # of a step's fusions, the share the compiler made without an op_name


def _device_instructions(text):
    """[(name, opcode, op_name or None)] of what a device trace can show of a
    compiled step: the instructions of ENTRY and of the computations its
    ``while`` / ``conditional`` / ``call`` instructions reach (a fusion's own
    computation is inside the fusion), parameters, constants and tuples left out."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    out, seen, todo = [], set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps.get(comp, ()):
            m = re.match(r'^\s*(?:ROOT )?%([\w.\-]+) = (.*)$', line)
            op = m and re.search(r'\s([a-z][\w\-]*)\(', " " + m.group(2))
            if not op:
                continue
            if op.group(1) in ("while", "conditional", "call"):
                todo.extend(n.lstrip("%") for found in _CALLED.findall(line)
                            for n in found.split(", "))
            elif op.group(1) not in _NO_WORK:
                named = re.search(r'metadata=\{op_name="([^"]*)"', line)
                out.append((m.group(1), op.group(1), named.group(1) if named else None))
    return out


def _assert_the_scopes_reach_what_a_scope_can(text, scopes):
    """Of the step's device-visible instructions, every one that carries an
    ``op_name`` lies in a scope of ``scopes`` (XLA's ``ragged-dot`` kernels
    are found by name); what no scope takes is the compiler's own — layout
    copies, async copy and slice pairs, ``AllocateBuffer`` / ``ConcatBitcast``
    custom calls and a few fusions, none with an ``op_name`` a
    ``jax.named_scope`` could have reached — and of the fusions that is under
    ``UNNAMED_FUSIONS``.  (By count those are 47–72 % of the instructions, so
    no limit by count over all of them can be stated; by device time they are
    what PERF.md section 5 gives as outside the scopes.)"""
    from shifu_tpu.obs.costs import op_scopes
    taken = {n for names in op_scopes(text, scopes).values() for n in names}
    insts = _device_instructions(text)
    assert len(insts) > 1000
    stray = [(n, name) for n, op, name in insts            # a copy of an argument bears the argument's name
             if name is not None and op != "copy" and n not in taken and not n.startswith("ragged-dot")]
    assert not stray, stray[:8]
    work = [(n, op, name) for n, op, name in insts if op in ("fusion", "custom-call", "dot", "convolution")]
    assert all(name is None for n, _, name in work if n not in taken and not n.startswith("ragged-dot"))
    fusions = [name for _, op, name in work if op == "fusion"]
    assert sum(name is None for name in fusions) < UNNAMED_FUSIONS * len(fusions), \
        (sum(name is None for name in fusions), len(fusions))


def test_nemotron_train_step_fits_one_v5e_chip(one_chip, no_cache_no_x64):
    """The cell ``nemotron-train``'s step program — 8 rows of 433 positions,
    the 838 M-parameter share with its Adam state donated — compiles for one
    v5e chip, its live bytes stay under the chip's memory, and the held
    experts' results never go back to a (token, choice) layout."""
    from benchmark.drivers.train_nemotron import tower_params
    from shifu_tpu.models import tower_nemotron_h as tw
    doc = _doc("configs", "nemotron3-super-tp8-ep64.json")
    spec = _spec(tw, tower_params(doc), doc)
    assert (doc["train"]["params"]["MiniBatchs"], spec.seq_len, spec.n_ids) == (8, 433, 15828)
    compiled, n_params = _compiled_step(
        tw, spec, doc, _doc("traffic", "retrain-320x433-2epochs.json")["rows"], one_chip)
    assert n_params == 838_249_968
    assert compiled.memory_analysis().alias_size_in_bytes > 10.0e9   # 12 bytes a parameter updated in place
    assert _live_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()
    assert "[3456,22,1024]" not in compiled.as_text()
    _assert_the_scopes_reach_what_a_scope_can(compiled.as_text(), tw.SCOPES)


def _on_the_chips_branch(monkeypatch):
    """The code asks ``jax.default_backend()``, which is the CPU here: steer
    it to the chip's branch (bf16 MXU operands, the kernels compiled by Mosaic)."""
    from shifu_tpu.ops import attention
    monkeypatch.setattr(attention, "mxu_operand_dtype", lambda like: jnp.bfloat16)
    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")


def test_sdar_train_step_fits_one_v5e_chip(one_chip, no_cache_no_x64, monkeypatch):
    """The cell ``sdar-train``'s step program — 16 rows of ``[x_t ; x_0]``,
    872 positions each (1,024 for the attention kernels: 512 a half), the
    456 M-parameter share with its Adam state donated — likewise: it fits, no
    ``[tokens, choices, hidden]`` array is built, the attention is the three
    kernels under ``tower/attn`` and no score array exists outside them."""
    from benchmark.drivers.train_tower import CONFIG_KEYS
    from shifu_tpu.models import tower_sdar as tw
    from shifu_tpu.obs.costs import op_scopes
    _on_the_chips_branch(monkeypatch)
    doc = _doc("configs", "sdar-30b-a3b-ep8.json")
    tp = {**{k: doc[k] for k in CONFIG_KEYS if k in doc}, "block_length": doc["block_length"],
          **{k: doc["deployment"][k] for k in ("expert_parallel_size", "expert_parallel_index")}}
    spec = _spec(tw, tp, doc)
    assert (doc["train"]["params"]["MiniBatchs"], spec.seq_len) == (16, 436)
    compiled, n_params = _compiled_step(
        tw, spec, doc, _doc("traffic", "retrain-640x436-2epochs.json")["rows"], one_chip)
    assert n_params == 456_346_624
    assert compiled.memory_analysis().alias_size_in_bytes > 5.4e9
    # 14.08 GB; the dense scores' step, compiled on this branch, held 13.76 (PR 36: the kernels'
    # residuals, q in bf16 and the f32 output, wait through the experts' backward pass)
    assert _live_bytes(compiled) < 14.2e9, compiled.memory_analysis()
    text = compiled.as_text()
    assert "[13952,8,2048]" not in text
    assert not re.search(r"f32\[[\d,]*(872,436|436,872|872,872|1024,1024)\]", text)   # no scores in HBM
    calls = sorted(n.split(".")[0] for n in op_scopes(text, tw.SCOPES)["tower/attn"]
                   if n.startswith("blocked_attention"))
    # in the layers' scan: the forward, the forward again for the backward, dq, dk/dv
    assert calls == ["blocked_attention_dkv", "blocked_attention_dq"] + ["blocked_attention_fwd"] * 2, calls
    _assert_the_scopes_reach_what_a_scope_can(text, tw.SCOPES)


def test_trinity_train_step_fits_one_v5e_chip(one_chip, no_cache_no_x64, monkeypatch):
    """The cell ``trinity-train``'s step program — 18 rows packed into one
    sequence of 8,192 positions, the 705 M-parameter share with its Adam state
    donated, the attention kernels compiled by Mosaic at their real shape —
    fits, holds no ``[heads, S, S]`` scores, and every attention kernel keeps
    its scope."""
    from benchmark.drivers.train_afmoe import tower_params
    from shifu_tpu.models import tower_afmoe as tw
    from shifu_tpu.obs.costs import op_scopes
    _on_the_chips_branch(monkeypatch)
    doc = _doc("configs", "trinity-mini-ep8.json")
    spec = _spec(tw, tower_params(doc), doc)
    assert (doc["train"]["params"]["MiniBatchs"], doc["train"]["params"]["RowsPerSequence"],
            spec.seq_len, spec.n_ids) == (18, 18, 433, 17360)
    compiled, n_params = _compiled_step(
        tw, spec, doc, _doc("traffic", "retrain-540x433-pack18-2epochs.json")["rows"], one_chip)
    assert n_params == 705_474_304
    assert compiled.memory_analysis().alias_size_in_bytes > 8.4e9   # 12 bytes a parameter updated in place
    assert _live_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()
    text = compiled.as_text()
    assert "8192,8192]" not in text
    scopes = op_scopes(text, tw.SCOPES)
    calls = lambda scope: [n for n in scopes[scope] if n.startswith("blocked_attention")]
    assert len(calls("tower/attn/window")) == 4 * 4 and len(calls("tower/attn/full")) == 4, \
        {k: len(v) for k, v in scopes.items()}
    _assert_the_scopes_reach_what_a_scope_can(text, tw.SCOPES)


def test_lfm2_train_step_fits_one_v5e_chip(one_chip, no_cache_no_x64, monkeypatch):
    """The cell ``lfm2-train``'s step program — 18 rows packed into one
    sequence of 8,192 positions (two sequences, 36 rows, count 14.56 GB live:
    over the 14.5 GB the cell allows itself), the 486 M-parameter share with
    its Adam state donated, the attention kernels compiled by Mosaic at
    ``head_dim`` 64 (each head padded to 128 lanes) — fits, holds no ``[heads,
    S, S]`` scores, keeps its four attention kernel calls under
    ``tower/attn/full`` and the convolution's gates and taps under
    ``tower/conv/mix``."""
    from benchmark.drivers.train_afmoe import tower_params
    from shifu_tpu.models import tower_lfm2 as tw
    from shifu_tpu.obs.costs import op_scopes
    _on_the_chips_branch(monkeypatch)
    doc = _doc("configs", "lfm2-24b-a2b-ep8.json")
    spec = _spec(tw, tower_params(doc), doc)
    assert (doc["train"]["params"]["MiniBatchs"], doc["train"]["params"]["RowsPerSequence"],
            spec.seq_len, spec.n_ids, spec.head_dim) == (18, 18, 433, 7402, 64)
    compiled, n_params = _compiled_step(
        tw, spec, doc, _doc("traffic", "retrain-540x433-pack18-2epochs.json")["rows"], one_chip)
    assert n_params == 486_062_464
    assert compiled.memory_analysis().alias_size_in_bytes > 5.8e9   # 12 bytes a parameter updated in place
    live = _live_bytes(compiled)
    print(f"lfm2-train step: {live / 1e9:.2f} GB live", compiled.memory_analysis())
    assert live < 11.6e9, compiled.memory_analysis()       # 11.47 GB
    text = compiled.as_text()
    # [8192, 8192]: the head's logits over the 8,192-row vocabulary slice; never one a head
    lead = {m for m in re.findall(r"\[((?:\d+,)*)8192,8192\]", text)}
    assert all(np.prod([int(x) for x in m.split(",") if x]) == 1 for m in lead), lead
    scopes = op_scopes(text, tw.SCOPES)
    calls = sorted(n.split(".")[0] for n in scopes["tower/attn/full"] if n.startswith("blocked_attention"))
    assert calls == ["blocked_attention_dkv", "blocked_attention_dq"] + ["blocked_attention_fwd"] * 2, calls
    assert scopes["tower/conv/mix"] and scopes["tower/conv/proj"]
    _assert_the_scopes_reach_what_a_scope_can(text, tw.SCOPES)


def test_moonlight_train_step_fits_one_v5e_chip(one_chip, no_cache_no_x64, monkeypatch):
    """The cell ``moonlight-train``'s step program — 18 rows packed into one
    sequence of 8,192 positions, the 568 M-parameter share with its Adam
    state donated, latent attention on the kernels compiled by Mosaic with q
    and k at 256 lanes (192 real channels) and v at its own 128 — fits with
    room under the 14.5 GB the cell allows itself, holds no ``[heads, S, S]``
    scores, keeps its twenty attention kernel calls (forward, forward again,
    ``dq``, ``dk``/``dv`` a layer) under ``tower/attn/full`` with v's and the
    output's lanes at 16 x 128, and every instruction with an ``op_name`` lies
    in a scope."""
    from benchmark.drivers.train_afmoe import tower_params
    from shifu_tpu.models import tower_deepseek_v3 as tw
    from shifu_tpu.obs.costs import op_scopes
    _on_the_chips_branch(monkeypatch)
    doc = _doc("configs", "moonlight-16b-a3b-ep8.json")
    spec = _spec(tw, tower_params(doc), doc)
    assert (doc["train"]["params"]["MiniBatchs"], doc["train"]["params"]["RowsPerSequence"],
            spec.seq_len, spec.n_ids, spec.qk_head_dim, spec.v_head_dim) == (18, 18, 433, 17360, 192, 128)
    compiled, n_params = _compiled_step(
        tw, spec, doc, _doc("traffic", "retrain-540x433-pack18-2epochs.json")["rows"], one_chip)
    assert n_params == 568_484_608
    assert compiled.memory_analysis().alias_size_in_bytes > 6.8e9   # 12 bytes a parameter updated in place
    live = _live_bytes(compiled)
    print(f"moonlight-train step: {live / 1e9:.2f} GB live", compiled.memory_analysis())
    assert live < 10.6e9, compiled.memory_analysis()       # 10.39 GB
    text = compiled.as_text()
    assert "8192,8192]" not in text
    scopes = op_scopes(text, tw.SCOPES)
    calls = sorted(n.split(".")[0] for n in scopes["tower/attn/full"] if n.startswith("blocked_attention"))
    assert calls == (["blocked_attention_dkv"] * 5 + ["blocked_attention_dq"] * 5 +
                     ["blocked_attention_fwd"] * 10), calls
    outputs = re.findall(r"%(blocked_attention_(?:fwd|dkv)\.\d+) = \((f32\[[\d,]+\])", text)
    assert outputs and all(shape == "f32[1,8192,2048]" for name, shape in outputs
                           if name.startswith("blocked_attention_fwd"))
    assert all(re.search(rf"%{name} = \(f32\[1,8192,4096\]\{{[^}}]*\}}, f32\[1,8192,2048\]", text)
               for name, _ in outputs if name.startswith("blocked_attention_dkv"))
    assert scopes["tower/attn/latent"] and scopes["tower/attn/proj"] and scopes["tower/moe/shared"]
    _assert_the_scopes_reach_what_a_scope_can(text, tw.SCOPES)
