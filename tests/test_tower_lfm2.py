"""The ``lfm2_moe`` tower (``algorithm: TENSORFLOW``, ``train#params.Tower``)
against its plain reference, ``benchmark/reference/lfm2_moe.py``: seeded
weights, toy size (hidden 64; 4 query heads of 16 on 2 key-value heads; 8 rows
of 8 tokens packed into a sequence of 64 = 4 attention blocks of 16; 1 dense +
4 layers — conv, full attention, conv, conv, conv as the cut has them —; 3
taps; 8 experts top-2 of width 24, 4 held by each of 2 ranks; 97 ids), on the
CPU with the attention kernels interpreted.
"""

import os
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import lfm2_moe as ref
from shifu_tpu import faults, obs
from shifu_tpu.config import ModelConfig, environment
from shifu_tpu.config.errors import ShifuError
from shifu_tpu.models import tower_lfm2 as tw
from shifu_tpu.models import towers
from shifu_tpu.ops import moe
from shifu_tpu.train import tower_trainer as tt
from shifu_tpu.train.optimizers import make_optimizer

COL_BINS = [10, 11, 9, 12, 10, 11, 10]              # 7 columns + the tag: 8 positions a row
R, BLOCK = 8, 16                                     # 8 rows a sequence of 64 positions
TOY = dict(model_type="lfm2_moe", hidden_size=64, num_hidden_layers=5, num_dense_layers=1,
           layer_types=["conv", "full_attention", "conv", "conv", "conv"], num_attention_heads=4,
           num_key_value_heads=2, intermediate_size=96, moe_intermediate_size=24, num_experts=4,
           expert_parallel_size=2, expert_parallel_index=0, num_experts_per_tok=2, conv_L_cache=3,
           conv_bias=False, use_expert_bias=True, norm_eps=1e-5, norm_topk_prob=True,
           routed_scaling_factor=1.0, rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
           vocab_size=97, max_position_embeddings=64, attention_block=BLOCK)
LR = 1e-3


def _spec(rank=0, **over):
    return tw.spec_from_params({**TOY, "expert_parallel_index": rank, **over},
                               list(range(7)), COL_BINS, [f"c{i}" for i in range(7)])


LEAVES = sorted(tw.param_shapes(_spec()))
PAD = _spec().special("PAD")


def _rows(n=16, seed=0):
    rng = np.random.default_rng(seed)
    bins = np.stack([rng.integers(0, b + 1, n) for b in COL_BINS], 1).astype(np.uint8)
    return bins, (rng.random(n) < 0.5).astype(np.float32), (1.0 + rng.random(n)).astype(np.float32)


def _params(spec, seed=1):
    """Seeded weights with every array off its initial value — norm weights
    off 1, the selection bias off 0 (by less than the scores spread: the
    routing follows the input) — so that each one's part shows."""
    p = tw.init_params(jax.random.PRNGKey(seed), spec)
    k = jax.random.PRNGKey(seed + 100)
    flat = towers.flat_names(p)
    return towers.nest_names({
        name: flat[name] + {"bias": 0.03, "conv_w": 0.3}.get(name.rsplit(".", 1)[-1], 0.05) * jax.random.normal(
            jax.random.fold_in(k, i), flat[name].shape, jnp.float32)
        for i, name in enumerate(sorted(flat))})


def _np(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


@pytest.fixture(scope="module")
def case():
    """One microbatch of two packed sequences through the program (loss,
    gradients and one optimizer step of the trainer's own program) and
    through the reference."""
    out = {}
    bins, y, w = _rows()
    for rank in (0, 1):
        spec = _spec(rank)
        params = _params(spec)
        ids = towers.tokenize(spec, bins, y)
        seqs, pos_w = towers.pack_rows(jnp.asarray(ids), jnp.asarray(w), R, BLOCK, PAD)
        fn = jax.jit(jax.value_and_grad(
            lambda p: tw.causal_loss(p, spec, seqs, pos_w, PAD), has_aux=True))
        (loss, aux), grads = fn(params)
        want_ids, want_w = ref.pack(ids, w, R, BLOCK, PAD)
        want_loss, want, tokens = ref.loss_and_grads(_np(params), want_ids, want_w, PAD, TOY,
                                                     spec.expert_lo)
        out[rank] = dict(spec=spec, params=params, bins=bins, ids=ids, seqs=np.asarray(seqs),
                         pos_w=np.asarray(pos_w), want_ids=want_ids, want_w=want_w,
                         loss=float(loss), aux=_np(aux), grads=towers.flat_names(_np(grads)),
                         want_loss=want_loss, want=ref.flatten(want), tokens=tokens)
    # the trainer's step on rank 0's microbatch: Adam's first step, then the bias's rule
    c = out[0]
    opt = make_optimizer("ADAM", LR)
    step, _ = tt.build_programs(c["spec"], opt, len(c["ids"]), R)
    before = jax.tree_util.tree_map(jnp.array, c["params"])
    specials = jnp.asarray([c["spec"].special(n) for n in towers.SPECIALS], jnp.int32)
    after, opt_state, acc = step(before, opt.init(before), tt._zero_acc(c["spec"]),
                                 jnp.asarray(c["ids"]), jnp.asarray(w),
                                 jnp.arange(len(c["ids"]), dtype=jnp.int32),
                                 jax.random.PRNGKey(0), specials, jnp.int32(0), jnp.int32(0))
    c.update(after=towers.flat_names(_np(after)), acc=_np(acc),
             m=towers.flat_names(_np(opt_state["m"])), v=towers.flat_names(_np(opt_state["v"])))
    return out


def test_initial_parameters_are_the_references_to_the_bit():
    spec = _spec()
    mine = towers.flat_names(_np(tw.init_params(jax.random.PRNGKey(3), spec)))
    theirs = ref.flatten(ref.init_params(3, TOY))
    assert sorted(mine) == sorted(theirs) == LEAVES
    for name in LEAVES:
        assert mine[name].tobytes() == np.asarray(theirs[name], np.float32).tobytes(), name
    taps = mine["blocks.00.conv_w"]
    assert taps.shape == (3, 64) and 0.5 < np.abs(taps).max() <= 3 ** -0.5       # U(+-1/sqrt(3))
    assert spec.head_dim == 16 and tw.sequence_block(spec) == BLOCK


# ----------------------------------------------- against the plain reference
@pytest.mark.parametrize("rank", [0, 1])
def test_forward_logits_match_the_reference(case, rank):
    c = case[rank]
    h, _ = tw.trunk(c["params"], c["spec"], jnp.asarray(c["seqs"]))
    got = tw._rms(h, c["params"]["norm_f"], 1e-5) @ c["params"]["head"]
    want = ref.forward_logits(_np(c["params"]), c["want_ids"], TOY, c["spec"].expert_lo)
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("rank", [0, 1])
def test_loss_and_counters_match_the_reference(case, rank):
    c = case[rank]
    assert abs(c["loss"] - c["want_loss"]) < 1e-5 * c["want_loss"]
    aux = c["aux"]
    targets = (c["want_ids"][:, 1:] != PAD) * c["want_w"][:, 1:]
    assert abs(float(aux["positions"]) - targets.sum()) < 1e-3
    assert (aux["tokens"] == c["tokens"]).all() and aux["tokens"].sum() == 4 * 2 * 64 * 2
    assert (aux["pairs"] == c["tokens"][:, c["spec"].expert_lo:c["spec"].expert_lo + 4]).all()
    assert not aux["dropped"].any()
    assert aux["pad_positions"] == 0 and aux["sequence_positions"] == 128
    # one full layer, 4 heads: 1 + 2 + 3 + 4 key blocks a head
    assert aux["attn_key_blocks"] == aux["attn_key_blocks_dense"] == 2 * 4 * 10


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("rank", [0, 1])
def test_gradient_matches_the_reference(case, rank, leaf):
    got, want = case[rank]["grads"][leaf], case[rank]["want"][leaf]
    if leaf.endswith(".bias"):
        assert not got.any() and not want.any()                 # it enters the choice only
        return
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, atol=3e-5 * np.abs(want).max())


@pytest.mark.parametrize("leaf", LEAVES)
def test_one_adam_step_and_the_moved_bias_match_the_references(case, leaf):
    c = case[0]
    before = np.asarray(towers.flat_names(c["params"])[leaf])
    if leaf.endswith(".bias"):
        layer = [n for n in LEAVES if n.endswith(".bias")].index(leaf)
        tokens = c["tokens"][layer]
        assert not c["m"][leaf].any() and not c["v"][leaf].any()    # Adam left it alone
        assert c["after"][leaf].tobytes() == ref.bias_after(before, tokens, 0.001).tobytes()
        moved = c["after"][leaf] - before
        assert (np.sign(moved) == np.sign(tokens.mean() - tokens)).all() and moved.any()
        return
    m, v, after = ref.adam_first_step(before, c["want"][leaf], LR)
    np.testing.assert_allclose(c["m"][leaf], m, atol=3e-6 * np.abs(m).max())
    sure = np.abs(c["want"][leaf]) >= np.sqrt(np.mean(np.square(c["want"][leaf])))
    np.testing.assert_allclose((c["after"][leaf] - before)[sure], (after - before)[sure], rtol=2e-2)


def test_the_step_counts_what_the_loss_does_and_moves_the_largest_bias(case):
    c = case[0]
    assert abs(c["acc"]["loss_sum"] / c["acc"]["positions"] - c["loss"]) < 1e-5
    assert (c["acc"]["pairs"] == c["aux"]["pairs"]).all()
    biases = lambda flat: np.stack([np.asarray(flat[n]) for n in LEAVES if n.endswith(".bias")])
    moved = np.abs(biases(c["after"])).max() - np.abs(biases(towers.flat_names(c["params"]))).max()
    assert abs(c["acc"]["router_bias_absmax"] - moved) < 1e-6


# ---------------------------------------------- the convolution, the share
@pytest.mark.parametrize("op", ["conv", "attention"])
def test_a_changed_later_position_leaves_every_earlier_output_bit_equal(case, op):
    """Causal to the bit: the operator's outputs before position 40 do not
    move when the input from 40 on does; those from 40 on do."""
    c = case[0]
    layer = c["params"]["blocks"]["00" if op == "conv" else "01"]
    fn = jax.jit(lambda a: (tw._conv if op == "conv" else tw._attention)(layer, a, c["spec"]))
    a = jax.random.normal(jax.random.PRNGKey(7), (2, 64, 64), jnp.float32)
    b = a.at[:, 40:].add(jax.random.normal(jax.random.PRNGKey(8), (2, 24, 64), jnp.float32))
    before, after = np.asarray(fn(a)), np.asarray(fn(b))
    assert before[:, :40].tobytes() == after[:, :40].tobytes()
    assert (np.abs(before[:, 40:] - after[:, 40:]).max(axis=-1) > 1e-3).all()


def test_the_convolution_reaches_across_a_packed_rows_start_and_not_before_the_sequence():
    """Position t reads t - 2 .. t: the first positions of row 2 (8, 9) see
    the end of row 1, position 10 no longer does; position 0's two missing
    taps are zeros, not the sequence's other end."""
    rng = np.random.default_rng(3)
    u, w = rng.normal(size=(1, 16, 4)).astype(np.float32), rng.normal(size=(3, 4)).astype(np.float32)
    out = np.asarray(towers.causal_conv(jnp.asarray(u), jnp.asarray(w)))
    np.testing.assert_allclose(out[0, 0], w[2] * u[0, 0], rtol=1e-6)
    np.testing.assert_allclose(out[0, 1], w[1] * u[0, 0] + w[2] * u[0, 1], rtol=1e-6)
    np.testing.assert_allclose(out[0, 8], w[0] * u[0, 6] + w[1] * u[0, 7] + w[2] * u[0, 8], rtol=1e-6)
    moved = u.copy()
    moved[0, 7] += 1.0                                    # row 1's last token
    again = np.asarray(towers.causal_conv(jnp.asarray(moved), jnp.asarray(w)))
    assert (again[0, 8] != out[0, 8]).all() and (again[0, 9] != out[0, 9]).all()
    assert again[0, 10:].tobytes() == out[0, 10:].tobytes()
    # the whole operator against the reference's explicit taps, and its reversed-taps control
    cfg = {**TOY, "hidden_size": 4}
    layer = {"conv_in": rng.normal(size=(4, 12)).astype(np.float32), "conv_w": w,
             "conv_out": rng.normal(size=(4, 4)).astype(np.float32)}
    keep = ref.knobs_for(cfg, 16)["keep"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(tw._conv(layer, jnp.asarray(u), SimpleNamespace(hidden_size=4)))
        want, rev = (np.asarray(ref.short_conv(layer, jnp.asarray(u), cfg, keep, r)) for r in (0.0, 1.0))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.abs(rev - want).max() > 1e-2


def test_the_two_expert_shares_of_a_moe_layer_add_up_to_the_uncut_layer():
    """The held experts' parts of both ranks against the uncut reference (8
    experts on one rank)."""
    rng = np.random.default_rng(5)
    d, f, e = 64, 24, 8
    whole = {"router": rng.normal(0, 0.5, (d, e)), "bias": rng.normal(0, 0.3, e),
             "we_gate_up": rng.normal(0, 0.1, (e, d, 2 * f)), "we_down": rng.normal(0, 0.1, (e, f, d))}
    whole = {k: v.astype(np.float32) for k, v in whole.items()}
    m = rng.normal(0, 1, (2, 32, d)).astype(np.float32)
    uncut = {**TOY, "num_experts": e, "expert_parallel_size": 1}
    with jax.default_matmul_precision("highest"):
        want, tokens = ref.moe_ffn(whole, jnp.asarray(m), uncut, 0)
    total = np.zeros_like(m)
    for rank in (0, 1):
        part = {**whole, "we_gate_up": whole["we_gate_up"][4 * rank:4 * rank + 4],
                "we_down": whole["we_down"][4 * rank:4 * rank + 4]}
        y, counters = tw._moe(jax.tree_util.tree_map(jnp.asarray, part), jnp.asarray(m), _spec(rank))
        assert (np.asarray(counters["tokens"]) == np.asarray(tokens)).all()     # all 8, on every rank
        assert float(np.abs(y).max()) > 0.01
        total += np.asarray(y)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_the_selection_bias_rule_is_one_function_of_the_two_towers():
    tokens = np.asarray([3.0, 9.0, 6.0, 6.0], np.float32)
    bias = np.asarray([0.1, -0.2, 0.0, 0.3], np.float32)
    got = np.asarray(moe.bias_step(jnp.asarray(bias), jnp.asarray(tokens), 0.001))
    assert got.tobytes() == ref.bias_after(bias, tokens, 0.001).tobytes()
    from shifu_tpu.models import tower_afmoe
    assert tw.after_step is tower_afmoe.after_step


# ---------------------------------------------------------- scores, the file
def test_eval_score_is_one_row_a_sequence_and_survives_the_file(case, tmp_path):
    c = case[1]
    model = towers.IndependentTowerModel(c["spec"], c["params"])
    got = model.compute(c["bins"])[:, 0]
    d = ref.tag_logit_difference(_np(c["params"]), c["bins"], TOY, c["spec"].expert_lo, COL_BINS)
    np.testing.assert_allclose(got, 1.0 / (1.0 + np.exp(-d)), atol=1e-6)
    path = str(tmp_path / "model0.tower")
    assert towers.save_model(path, c["spec"], _np(c["params"])) == os.path.getsize(path)
    from shifu_tpu.models import load_any, spec_kind
    assert spec_kind(path) == "tower"
    again = load_any(path)
    assert again.spec == c["spec"] and again.spec.tower == "lfm2_moe"
    assert sorted(towers.flat_names(again.params)) == LEAVES
    assert again.compute(c["bins"]).tobytes() == model.compute(c["bins"]).tobytes()


# ------------------------------------------------------- config, declarations
@pytest.mark.parametrize("over,message", [
    (dict(model_type="lfm2"), "model_type must be 'lfm2_moe'"),
    (dict(conv_bias=True), "conv_bias must be False"),
    (dict(use_expert_bias=False), "use_expert_bias must be True"),
    (dict(layer_types=["conv"] * 4 + ["sliding_attention"]), r"layer_types holds \['sliding_attention'\]"),
    (dict(layer_types=["conv"] * 4), "layer_types has 4 entries, num_hidden_layers is 5"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}), "rope_type 'default'"),
    (dict(foo=1), "unknown TowerParams key 'foo'"),
    (dict(head_dim=64), "unknown TowerParams key 'head_dim'"),
    (dict(load_balance_coeff=0.01), "unknown TowerParams key 'load_balance_coeff'"),
    (dict(num_dense_layers=5), "num_dense_layers 5 leaves no MoE layer"),
    (dict(num_experts_per_tok=9), "exceeds the router's 8 experts"),
    (dict(expert_parallel_index=2), "expert_parallel_index 2 is not a rank of 2"),
    (dict(num_attention_heads=3, num_key_value_heads=3), "even head_dim"),
    (dict(conv_L_cache=0), "conv_L_cache 0 leaves the convolution no tap"),
    (dict(vocab_size=80), "84 token ids .* slice holds 80"),
    (dict(max_position_embeddings=7), "a row is 8 positions"),
])
def test_tower_params_refusals(over, message):
    with pytest.raises(ShifuError, match=message) as e:
        _spec(**over)
    assert "[" in str(e.value)                                 # a coded error


def test_the_tower_is_found_by_name_and_the_rules_know_it():
    from shifu_tpu.config.meta import validate_train_params
    from shifu_tpu.config.model_config import Algorithm
    assert towers.module("lfm2_moe") is tw
    ok = {"Tower": "lfm2_moe", "TowerParams": dict(TOY), "MiniBatchs": 16, "RowsPerSequence": 8}
    assert validate_train_params(ok, Algorithm.TENSORFLOW) == []
    missing = {k: v for k, v in TOY.items() if k != "conv_L_cache"}
    with pytest.raises(ShifuError, match="TowerParams.conv_L_cache is required"):
        tw.spec_from_params(missing, list(range(7)), COL_BINS, [])
    assert tw.SCOPES[-1] == "tower/opt" and callable(tw.after_step)
    for name in tw.OBS_COUNTERS.values():
        assert obs.manifest.is_declared(name), name
    assert set(tw.OBS_COUNTERS) <= set(tw.counter_shapes(_spec()))
    spec = _spec()
    assert (spec.num_experts, spec.experts_held, spec.moe_layers, spec.rope_theta) == (8, 4, 4, 1e6)


# ------------------------------------------------------------------- the CLI
CLI = {**TOY, "num_hidden_layers": 2, "layer_types": ["conv", "full_attention"],
       "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 4200,
       "max_position_embeddings": 256, "attention_block": 64}
# the set's rows are 8 positions: 30 a sequence = 240 + 16 PAD = 4 blocks (the interpreter's cost is a grid step's)


def _tower_set(mdir, epochs=3, **params):
    mc = ModelConfig.load(os.path.join(mdir, "ModelConfig.json"))
    mc.train.algorithm = "TENSORFLOW"
    mc.train.numTrainEpochs = epochs
    mc.train.params = {"Tower": "lfm2_moe", "MiniBatchs": 510, "RowsPerSequence": 30,
                       "LearningRate": 0.003, "Propagation": "ADAM", "TowerParams": dict(CLI),
                       **params}
    mc.save(os.path.join(mdir, "ModelConfig.json"))


@pytest.fixture(autouse=True)
def _clean():
    environment.reset_for_tests()
    faults.reset_for_tests()
    yield
    environment.reset_for_tests()
    faults.reset_for_tests()
    obs.set_enabled(False)


def _load(mdir):
    return towers.load_model(os.path.join(mdir, "models", "model0.tower"))


def _progress(mdir):
    with open(os.path.join(mdir, "tmp", "train.progress")) as f:
        return f.read().strip().splitlines()


def test_cli_train_writes_a_tower_and_eval_scores_it_as_the_reference(prepared_set):
    from shifu_tpu.cli import main
    from shifu_tpu.data.shards import Shards
    _tower_set(prepared_set)
    assert main(["--dir", prepared_set, "train"]) == 0
    spec, params = _load(prepared_set)
    assert spec.tower == "lfm2_moe" and spec.n_features == len(spec.column_bins)
    assert np.asarray(params["blocks"]["01"]["bias"]).any()
    lines = _progress(prepared_set)
    assert len(lines) == 3 and lines[0].startswith("Tower Epoch #1 Train Error: ")
    first, last = (float(l.split("Train Error: ")[1].split()[0]) for l in (lines[0], lines[-1]))
    assert last < first
    assert main(["--dir", prepared_set, "eval", "-run"]) == 0
    with open(os.path.join(prepared_set, "evals", "Eval1", "EvalScore")) as f:
        col = f.readline().strip().split("|").index("mean")
        got = np.sort([float(line.split("|")[col]) for line in f])
    bins = Shards.open(os.path.join(prepared_set, "tmp", "CleanedData")).load_all()["bins"]
    d = ref.tag_logit_difference(params, bins, CLI, spec.expert_lo, spec.column_bins, 256)
    want = np.sort(1000.0 / (1.0 + np.exp(-d)))
    assert len(got) == len(want)
    np.testing.assert_allclose(got, want, atol=2e-3)


def test_cli_killed_job_resumes_bit_exactly_with_the_bias_in_the_checkpoint(prepared_set):
    from shifu_tpu.pipeline.train import TrainProcessor
    control = prepared_set + "_ctl"
    shutil.copytree(prepared_set, control)
    for d in (prepared_set, control):
        _tower_set(d, epochs=2, CheckpointInterval=1)
    assert TrainProcessor(control, params={}).run() == 0

    environment.set_property("shifu.faults", "train:epoch=2:ioerror")     # before its checkpoint
    faults.reset_for_tests()
    with pytest.raises(faults.InjectedFault):
        TrainProcessor(prepared_set, params={}).run()
    environment.set_property("shifu.faults", "")
    faults.reset_for_tests()
    assert TrainProcessor(prepared_set, params={}).run() == 0     # torn journal: resumes

    want, got = (towers.flat_names(_load(d)[1]) for d in (control, prepared_set))
    for name in want:
        assert got[name].tobytes() == want[name].tobytes(), name
    assert want["blocks.01.bias"].any()
    assert _progress(prepared_set)[-1] == _progress(control)[-1]
    assert len(_progress(prepared_set)) == 1                      # epoch 2 again
