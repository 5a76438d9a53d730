"""The ``afmoe`` tower (``algorithm: TENSORFLOW``, ``train#params.Tower``)
against its plain reference, ``benchmark/reference/afmoe.py``: seeded weights,
toy size (hidden 64; 4 query heads of 16 on 2 key-value heads; a window of one
16-position block; 8 rows of 8 tokens packed into a sequence of 64 = 4 windows;
1 dense + 4 layers, the last one full; 8 experts top-2 of width 24 — 4 held by
each of 2 ranks —, a shared expert; 97 ids), on the CPU with the attention
kernels interpreted.
"""

import json
import os
import shutil
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import afmoe as ref
from shifu_tpu import faults, obs
from shifu_tpu.config import ModelConfig, environment
from shifu_tpu.config.errors import ShifuError
from shifu_tpu.models import tower_afmoe as tw
from shifu_tpu.models import towers
from shifu_tpu.train import tower_trainer as tt
from shifu_tpu.train.optimizers import make_optimizer

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "helpers"))
from dense_attention import allowed  # noqa: E402

COL_BINS = [10, 11, 9, 12, 10, 11, 10]              # 7 columns + the tag: 8 positions a row
R, BLOCK = 8, 16                                     # 8 rows a sequence of 64 positions
TOY = dict(model_type="afmoe", hidden_size=64, num_hidden_layers=5, num_dense_layers=1,
           layer_types=["sliding_attention"] * 4 + ["full_attention"], num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, sliding_window=16, intermediate_size=96,
           moe_intermediate_size=24, num_experts=4, expert_parallel_size=2,
           expert_parallel_index=0, num_experts_per_tok=2, vocab_size=97,
           max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000, route_norm=True,
           route_scale=2.826, load_balance_coeff=0.001, mup_enabled=True, attention_block=BLOCK,
           score_func="sigmoid", hidden_act="silu", n_group=1, topk_group=1, num_shared_experts=1,
           rope_scaling=None, tie_word_embeddings=False, global_attn_every_n_layers=4,
           use_grouped_mm=True, num_expert_groups=1, num_limited_groups=1)
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
    off 1, the selection bias off 0 — so that each one's part shows."""
    p = tw.init_params(jax.random.PRNGKey(seed), spec)
    k = jax.random.PRNGKey(seed + 100)
    flat = towers.flat_names(p)
    return towers.nest_names({
        name: flat[name] + (0.3 if name.endswith(".bias") else 0.05) * jax.random.normal(
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


# ------------------------------------------------- tokens, packing, the seed
def test_rows_are_packed_end_to_end_and_padded_to_whole_blocks():
    spec = _spec()
    bins, y, w = _rows(6)
    ids = towers.tokenize(spec, bins, y)
    seqs, pos_w = towers.pack_rows(jnp.asarray(ids), jnp.asarray(w), 3, BLOCK, PAD)
    assert seqs.shape == pos_w.shape == (2, 32)                 # 3 x 8 = 24 tokens + 8 PAD
    want_ids, want_w = ref.pack(ids, w, 3, BLOCK, PAD)
    assert (np.asarray(seqs) == want_ids).all() and (np.asarray(pos_w) == want_w).all()
    assert (np.asarray(seqs)[0, :24] == ids[:3].reshape(-1)).all()
    assert (np.asarray(seqs)[:, 24:] == PAD).all() and not np.asarray(pos_w)[:, 24:].any()
    assert (np.asarray(pos_w)[1, 8:16] == w[4]).all()           # a position weighs what its row does
    assert towers.pack_plan(spec, 6, 3, BLOCK) == {"rows": 6, "sequences": 2, "positions": 32,
                                                   "pad_positions": 8}
    assert tw.sequence_block(spec) == BLOCK
    with pytest.raises(ShifuError, match="MiniBatchs 7 is not whole sequences of RowsPerSequence 3"):
        towers.pack_plan(spec, 7, 3, BLOCK)
    with pytest.raises(ShifuError, match="RowsPerSequence 9 x 8 positions a row exceeds "
                                         "max_position_embeddings 64"):
        towers.pack_plan(spec, 18, 9, BLOCK)


def test_initial_parameters_are_the_references_to_the_bit():
    spec = _spec()
    mine = towers.flat_names(_np(tw.init_params(jax.random.PRNGKey(3), spec)))
    theirs = ref.flatten(ref.init_params(3, TOY))
    assert sorted(mine) == sorted(theirs) == LEAVES
    for name in LEAVES:
        assert mine[name].tobytes() == np.asarray(theirs[name], np.float32).tobytes(), name
    assert towers.n_params(tw.init_params(jax.random.PRNGKey(0), spec)) == \
        sum(int(np.prod(s)) for s in ref.param_shapes(TOY).values())


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
    # per head and layer: a window layer's 1 + 2 + 2 + 2 key blocks, the full layer's 1 + 2 + 3 + 4
    assert aux["attn_key_blocks"] == 2 * 4 * (4 * 7 + 10)
    assert aux["attn_key_blocks_dense"] == 2 * 4 * 5 * 10


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


# ------------------------------------------------------ the share, the masks
def test_the_two_expert_shares_of_a_moe_layer_add_up_to_the_uncut_layer():
    """The held experts' parts of both ranks, the shared expert counted once,
    against the uncut reference (8 experts on one rank)."""
    rng = np.random.default_rng(5)
    d, f, e = 64, 24, 8
    whole = {"router": rng.normal(0, 0.5, (d, e)), "bias": rng.normal(0, 0.3, e),
             "ws_gate_up": rng.normal(0, 0.1, (d, 2 * f)), "ws_down": rng.normal(0, 0.1, (f, d)),
             "we_gate_up": rng.normal(0, 0.1, (e, d, 2 * f)), "we_down": rng.normal(0, 0.1, (e, f, d))}
    whole = {k: v.astype(np.float32) for k, v in whole.items()}
    m = rng.normal(0, 1, (2, 32, d)).astype(np.float32)
    uncut = {**TOY, "num_experts": e, "expert_parallel_size": 1}
    with jax.default_matmul_precision("highest"):
        want, tokens = ref.moe_ffn(whole, jnp.asarray(m), uncut, 0)
        shared = ref._swiglu(jnp.asarray(m), whole["ws_gate_up"], whole["ws_down"])
    total = np.zeros_like(m)
    for rank in (0, 1):
        spec = _spec(rank)
        part = {**whole, "we_gate_up": whole["we_gate_up"][4 * rank:4 * rank + 4],
                "we_down": whole["we_down"][4 * rank:4 * rank + 4]}
        y, counters = tw._moe(jax.tree_util.tree_map(jnp.asarray, part), jnp.asarray(m), spec)
        assert (np.asarray(counters["tokens"]) == np.asarray(tokens)).all()     # all 8, on every rank
        total += np.asarray(y) - np.asarray(shared)
    np.testing.assert_allclose(total + np.asarray(shared), want, atol=2e-5)
    assert float(np.abs(total).max()) > 0.01


def test_a_packed_rows_logits_are_the_unpacked_rows_until_a_later_row_looks_back(case):
    """With a window as long as the sequence, the first row of a packed
    sequence sees what it sees alone; the second row does not."""
    spec = _spec(sliding_window=64)
    params, ids = case[0]["params"], case[0]["ids"]
    logits = lambda seqs: tw._rms(tw.trunk(params, spec, seqs)[0], params["norm_f"], 1e-5) \
        @ params["head"]
    packed, _ = towers.pack_rows(jnp.asarray(ids[:8]), jnp.ones(8), R, BLOCK, PAD)
    alone, _ = towers.pack_rows(jnp.asarray(ids[:8]), jnp.ones(8), 1, BLOCK, PAD)
    got, want = np.asarray(logits(packed))[0], np.asarray(logits(alone))
    np.testing.assert_allclose(got[:8], want[0, :8], atol=2e-4)
    assert np.abs(got[8:16] - want[1, :8]).max() > 1e-2


def test_positions_past_the_window_differ_between_a_window_and_a_full_layer(case):
    c = case[0]
    both = {}
    for name, types in (("window", ["sliding_attention"] * 5), ("mixed", TOY["layer_types"])):
        spec = _spec(layer_types=types)
        both[name] = np.asarray(tw.trunk(c["params"], spec, jnp.asarray(c["seqs"]))[0])
        want = ref.trunk(_np(c["params"]), jnp.asarray(c["want_ids"]), {**TOY, "layer_types": types},
                         0)[0]
        np.testing.assert_allclose(both[name], want, atol=3e-4)
    # position 0 sees itself alone, unrotated, in either kind of layer
    np.testing.assert_allclose(both["window"][:, 0], both["mixed"][:, 0], atol=1e-5)
    assert np.abs(both["window"][:, 16:] - both["mixed"][:, 16:]).max() > 1e-2
    assert (allowed(64, 16).sum(1)[16:] == 16).all()            # a full window behind each of them


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
    assert again.spec == c["spec"] and again.spec.tower == "afmoe"
    assert sorted(towers.flat_names(again.params)) == LEAVES
    assert again.compute(c["bins"]).tobytes() == model.compute(c["bins"]).tobytes()


# ------------------------------------------------------- config, declarations
@pytest.mark.parametrize("over,message", [
    (dict(model_type="sdar_moe"), "model_type must be 'afmoe'"),
    (dict(score_func="softmax"), "score_func must be 'sigmoid'"),
    (dict(hidden_act="gelu"), "hidden_act must be 'silu'"),
    (dict(n_group=8), "n_group must be 1"),
    (dict(topk_group=4), "topk_group must be 1"),
    (dict(num_shared_experts=2), "num_shared_experts must be 1"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling must be None"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings must be False"),
    (dict(foo=1), "unknown TowerParams key 'foo'"),
    (dict(layer_types=["sliding_attention"] * 4), "layer_types has 4 entries, num_hidden_layers is 5"),
    (dict(layer_types=["sliding_attention"] * 4 + ["linear_attention"]), "layer_types holds"),
    (dict(sliding_window=24), "sliding_window 24 is not whole attention blocks of 16"),
    (dict(num_dense_layers=5), "num_dense_layers 5 leaves no MoE layer"),
    (dict(num_experts_per_tok=9), "exceeds the router's 8 experts"),
    (dict(expert_parallel_index=2), "expert_parallel_index 2 is not a rank of 2"),
    (dict(num_attention_heads=3), "multiple of num_key_value_heads"),
    (dict(vocab_size=80), "84 token ids .* slice holds 80"),
    (dict(max_position_embeddings=7), "a row is 8 positions"),
])
def test_tower_params_refusals(over, message):
    with pytest.raises(ShifuError, match=message) as e:
        _spec(**over)
    assert "[" in str(e.value)                                 # a coded error


def test_tower_params_problems_come_in_one_error_and_required_keys_are_named():
    with pytest.raises(ShifuError) as e:
        _spec(n_group=2, foo=1, score_func="softmax")
    msg = str(e.value)
    assert "n_group must be 1" in msg and "unknown TowerParams key 'foo'" in msg \
        and "score_func must be 'sigmoid'" in msg
    missing = {k: v for k, v in TOY.items() if k != "sliding_window"}
    with pytest.raises(ShifuError, match="TowerParams.sliding_window is required"):
        tw.spec_from_params(missing, list(range(7)), COL_BINS, [])
    spec = _spec()
    assert (spec.num_experts, spec.experts_held, spec.moe_layers) == (8, 4, 4)
    assert [spec.window_of(i) for i in range(5)] == [16, 16, 16, 16, None]


def test_the_tower_is_found_by_name_and_the_rules_know_it():
    from shifu_tpu.config.meta import validate_train_params
    from shifu_tpu.config.model_config import Algorithm
    assert towers.module("afmoe") is tw
    ok = {"Tower": "afmoe", "TowerParams": dict(TOY), "MiniBatchs": 16, "RowsPerSequence": 8}
    assert validate_train_params(ok, Algorithm.TENSORFLOW) == []
    assert validate_train_params({**ok, "RowsPerSequence": 0}, Algorithm.TENSORFLOW)
    assert tw.SCOPES[-1] == "tower/opt" and callable(tw.after_step)
    for name in tw.OBS_COUNTERS.values():
        assert obs.manifest.is_declared(name), name
    assert obs.manifest.is_declared_span("tower.pack")
    assert set(tw.OBS_COUNTERS) <= set(tw.counter_shapes(_spec()))


@pytest.mark.parametrize("name", ["sdar_moe", "nemotron_h"])
def test_the_other_towers_refuse_several_rows_a_sequence(name):
    """Their masks and recurrences end with the row: a coded error, before
    anything is built."""
    from types import SimpleNamespace
    spec = SimpleNamespace(tower=name, n_ids=10, seq_len=8)
    settings = SimpleNamespace(precision="", seed=0, batch_size=4)
    with pytest.raises(ShifuError, match=f"RowsPerSequence 2: the {name} tower takes one row"):
        tt.train_tower(np.zeros((8, 7), np.uint8), np.zeros(8), np.ones(8), spec, settings, 0.25,
                       rows_per_sequence=2)
    assert not hasattr(towers.module(name), "sequence_block")


# ------------------------------------------------------------------- the CLI
CLI = {**TOY, "num_hidden_layers": 2, "layer_types": ["sliding_attention", "full_attention"],
       "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 4200,
       "max_position_embeddings": 256, "sliding_window": 64, "attention_block": 64}
# the set's rows are 8 positions: 30 a sequence = 240 + 16 PAD = 4 blocks (the interpreter's cost is a grid step's)


def _tower_set(mdir, epochs=3, **params):
    mc = ModelConfig.load(os.path.join(mdir, "ModelConfig.json"))
    mc.train.algorithm = "TENSORFLOW"
    mc.train.numTrainEpochs = epochs
    mc.train.params = {"Tower": "afmoe", "MiniBatchs": 510, "RowsPerSequence": 30,
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
    assert spec.tower == "afmoe" and spec.n_features == len(spec.column_bins)
    assert any(np.asarray(p["bias"]).any() for p in params["blocks"].values() if "bias" in p)
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
        _tower_set(d, epochs=3, CheckpointInterval=1)
    assert TrainProcessor(control, params={}).run() == 0

    environment.set_property("shifu.faults", "train:epoch=2:ioerror")
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
    assert len(_progress(prepared_set)) == 2                      # epochs 2 and 3 again


def test_telemetry_counts_the_towers_own_counters_and_the_pack_span(prepared_set):
    from shifu_tpu.cli import main
    _tower_set(prepared_set, epochs=1)
    assert main(["--dir", prepared_set, "train", "--telemetry"]) == 0
    found, scopes, pack = {}, None, None
    with open(os.path.join(prepared_set, "telemetry", "trace.jsonl")) as f:
        for line in f:
            doc = json.loads(line)
            if str(doc.get("name", "")).startswith("tower.") and "value" in doc:
                found[doc["name"]] = found.get(doc["name"], 0.0) + float(doc["value"])
            if doc.get("name") == "op_scopes":
                scopes = doc["attrs"]["scopes"]
            if doc.get("name") == "tower.pack":
                pack = doc["attrs"]
    assert found["tower.dropped_pairs"] == 0 and found["tower.positions"] > 0
    assert 0 < found["tower.attn_key_blocks"] < found["tower.attn_key_blocks_dense"]
    assert 0 < found["tower.pad_positions"] < found["tower.sequence_positions"]
    assert 0 < found["tower.router_bias_absmax"] <= 0.001 * 7 + 1e-9       # 7 steps an epoch
    assert found["tower.moe_pairs_max_expert"] >= found["tower.moe_pairs_mean_expert"] > 0
    assert pack == {"rows": 510, "sequences": 17, "positions": 256, "pad_positions": 16}
    assert set(scopes) == set(tw.SCOPES)
    for scope in tw.SCOPES:
        assert scopes[scope], scope


def test_cli_refuses_a_microbatch_that_is_no_whole_sequences(prepared_set, capsys):
    from shifu_tpu.cli import main
    _tower_set(prepared_set, MiniBatchs=512)
    assert main(["--dir", prepared_set, "train"]) == 1
    assert "MiniBatchs 512 is not whole sequences of RowsPerSequence 30" in capsys.readouterr().err
