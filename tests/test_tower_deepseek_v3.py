"""The ``deepseek_v3`` tower (``algorithm: TENSORFLOW``, ``train#params.Tower``)
against its plain reference, ``benchmark/reference/deepseek_v3.py``: seeded
weights, toy size (hidden 64; 4 heads of q/k 16 + 8 rotary channels against
v 16 from a 32-wide latent; 8 rows of 8 tokens packed into a sequence of 64 =
4 attention blocks of 16; 1 dense + 2 MoE layers; 8 experts top-3 of width 24
beside two shared, 4 held by each of 2 ranks; a balance loss weighted 0.05 so
that its gradient shows; 97 ids), on the CPU with the attention kernels
interpreted.
"""

import os
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import deepseek_v3 as ref
from shifu_tpu import faults, obs
from shifu_tpu.config import ModelConfig, environment
from shifu_tpu.config.errors import ShifuError
from shifu_tpu.models import tower_deepseek_v3 as tw
from shifu_tpu.models import towers
from shifu_tpu.train import tower_trainer as tt
from shifu_tpu.train.optimizers import make_optimizer

COL_BINS = [10, 11, 9, 12, 10, 11, 10]              # 7 columns + the tag: 8 positions a row
R, BLOCK = 8, 16                                     # 8 rows a sequence of 64 positions
TOY = dict(model_type="deepseek_v3", hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1,
           num_attention_heads=4, num_key_value_heads=4, q_lora_rank=None, kv_lora_rank=32,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
           moe_intermediate_size=24, n_shared_experts=2, n_routed_experts=4, expert_parallel_size=2,
           expert_parallel_index=0, num_experts_per_tok=3, norm_topk_prob=True,
           routed_scaling_factor=2.446, scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
           topk_group=1, seq_aux=True, aux_loss_alpha=0.05, rms_norm_eps=1e-5, rope_theta=50000,
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
        name: flat[name] + {"bias": 0.03, "router": 0.3}.get(name.rsplit(".", 1)[-1], 0.05) *
        jax.random.normal(jax.random.fold_in(k, i), flat[name].shape, jnp.float32)
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
        want_loss, want, tokens, balance = ref.loss_and_grads(_np(params), want_ids, want_w, PAD,
                                                               TOY, spec.expert_lo)
        out[rank] = dict(spec=spec, params=params, bins=bins, ids=ids, seqs=np.asarray(seqs),
                         pos_w=np.asarray(pos_w), want_ids=want_ids, want_w=want_w,
                         loss=float(loss), aux=_np(aux), grads=towers.flat_names(_np(grads)),
                         want_loss=want_loss, want=ref.flatten(want), tokens=tokens, balance=balance)
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
    assert mine["blocks.01.w_ukv"].shape == (32, 4 * (16 + 16)) and mine["blocks.01.wq"].shape == (64, 4 * 24)
    assert mine["blocks.01.ws_gate_up"].shape == (64, 2 * 48)                  # two shared experts as one
    assert spec.qk_head_dim == 24 and tw.sequence_block(spec) == BLOCK


# ----------------------------------------------- against the plain reference
@pytest.mark.parametrize("rank", [0, 1])
def test_forward_logits_match_the_reference(case, rank):
    c = case[rank]
    h, _ = tw.trunk(c["params"], c["spec"], jnp.asarray(c["seqs"]))
    got = tw._rms(h, c["params"]["norm_f"], 1e-5) @ c["params"]["head"]
    want = ref.forward_logits(_np(c["params"]), c["want_ids"], TOY, c["spec"].expert_lo)
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("rank", [0, 1])
def test_loss_with_the_balance_loss_and_counters_match_the_reference(case, rank):
    c = case[rank]
    assert abs(c["loss"] - c["want_loss"]) < 1e-5 * c["want_loss"]
    aux = c["aux"]
    # sum_e f_e P_e is 1 a layer when the load is even: two sequences, two MoE layers
    assert abs(aux["balance_loss_sum"] - c["balance"]) < 1e-5 * c["balance"]
    assert 4.0 < c["balance"] < 6.0
    targets = (c["want_ids"][:, 1:] != PAD) * c["want_w"][:, 1:]
    assert abs(float(aux["positions"]) - targets.sum()) < 1e-3
    assert abs(aux["loss_sum"] / aux["positions"] - c["loss"]) < 1e-6 * c["loss"]
    assert (aux["tokens"] == c["tokens"]).all() and aux["tokens"].sum() == 2 * 3 * 64 * 2
    assert (aux["pairs"] == c["tokens"][:, c["spec"].expert_lo:c["spec"].expert_lo + 4]).all()
    assert not aux["dropped"].any()
    assert aux["pad_positions"] == 0 and aux["sequence_positions"] == 128
    # three full layers, 4 heads: 1 + 2 + 3 + 4 key blocks a head
    assert aux["attn_key_blocks"] == aux["attn_key_blocks_dense"] == 2 * 4 * 3 * 10


def test_the_balance_loss_is_what_alpha_adds_to_the_loss(case):
    """The loss at alpha less the loss at 0 is alpha x the sequences' mean
    balance sum; the reference's control without it reads the same difference."""
    c = case[0]
    spec0 = _spec(aux_loss_alpha=0.0)
    loss0, aux0 = tw.causal_loss(c["params"], spec0, jnp.asarray(c["seqs"]), jnp.asarray(c["pos_w"]), PAD)
    np.testing.assert_allclose(c["loss"] - float(loss0), 0.05 * c["balance"] / 2, rtol=1e-4)
    assert aux0["balance_loss_sum"] == pytest.approx(c["balance"], rel=1e-6)
    off, _, _, _ = ref.loss_and_grads(_np(c["params"]), c["want_ids"], c["want_w"], PAD,
                                      {**TOY, "no_balance": True}, 0)
    np.testing.assert_allclose(c["want_loss"] - off, 0.05 * c["balance"] / 2, rtol=1e-4)


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
    assert abs(c["acc"]["balance_loss_sum"] - c["balance"]) < 1e-5 * c["balance"]
    assert (c["acc"]["pairs"] == c["aux"]["pairs"]).all()
    biases = lambda flat: np.stack([np.asarray(flat[n]) for n in LEAVES if n.endswith(".bias")])
    moved = np.abs(biases(c["after"])).max() - np.abs(biases(towers.flat_names(c["params"]))).max()
    assert abs(c["acc"]["router_bias_absmax"] - moved) < 1e-6


# ------------------------------------------------ latent attention, the share
def test_latent_attention_matches_a_dense_per_head_statement_and_is_causal(case):
    """The program's MLA — q and k built in the kernels' layout, v at its own
    width, the shared rotary key put beside every head's k_N — against the
    reference's per-head statement, in which every head is handed its own
    copy of k_R and the pairs are turned in place; and causal to the bit."""
    c = case[0]
    layer = _np(c["params"]["blocks"]["01"])
    a = jax.random.normal(jax.random.PRNGKey(7), (2, 64, 64), jnp.float32)
    got = np.asarray(jax.jit(lambda a: tw._attention(layer, a, c["spec"]))(a))
    kn = ref.knobs_for(TOY, 64)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.mla(layer, a, TOY, jnp.asarray(kn["mask"]), kn["cos"], kn["sin"],
                                  kn["scale"], kn["kv_norm"]))
        # each control moves the output: the scale of the nope channels alone, the latent unnormed
        for fault in ({"scale_nope": True}, {"no_kv_norm": True}):
            k2 = ref.knobs_for({**TOY, **fault}, 64)
            off = np.asarray(ref.mla(layer, a, TOY, jnp.asarray(k2["mask"]), k2["cos"], k2["sin"],
                                     k2["scale"], k2["kv_norm"]))
            assert np.abs(off - want).max() > 1e-2 * np.abs(want).max(), fault
    np.testing.assert_allclose(got, want, atol=1e-5 * np.abs(want).max())
    b = a.at[:, 40:].add(jax.random.normal(jax.random.PRNGKey(8), (2, 24, 64), jnp.float32))
    again = np.asarray(jax.jit(lambda a: tw._attention(layer, a, c["spec"]))(b))
    assert got[:, :40].tobytes() == again[:, :40].tobytes()
    assert (np.abs(got[:, 40:] - again[:, 40:]).max(axis=-1) > 1e-4).all()


def test_the_shares_of_eight_ranks_add_up_to_the_uncut_layer():
    """A dense layer and a MoE layer, 16 experts over 8 ranks of 2: each
    rank's output less what every rank computes alike (attention, the norms,
    the shared experts, the dense layer: the same tower with its routed
    experts' down-projections at 0), summed over the ranks, with that common
    part once, is the uncut reference's (16 experts on one rank)."""
    cfg = {**TOY, "num_hidden_layers": 2, "n_routed_experts": 16, "expert_parallel_size": 1,
           "num_experts_per_tok": 4}
    whole = ref.init_params(11, cfg)
    rng = np.random.default_rng(11)
    whole["blocks"]["01"]["router"] = rng.normal(0, 0.5, (64, 16)).astype(np.float32)
    whole["blocks"]["01"]["bias"] = rng.normal(0, 0.1, 16).astype(np.float32)
    ids = rng.integers(0, 80, (2, 32))
    with jax.default_matmul_precision("highest"):
        want, tokens, _ = ref.trunk(ref.nest(ref.flatten(whole)), jnp.asarray(ids), cfg, 0)

    def rank_out(rank, zero_routed=False):
        spec = tw.spec_from_params({**cfg, "n_routed_experts": 2, "expert_parallel_size": 8,
                                    "expert_parallel_index": rank, "attention_block": 16},
                                   list(range(7)), COL_BINS, [""] * 7)
        blocks = {k: dict(v) for k, v in whole["blocks"].items()}
        moe = blocks["01"]
        moe["we_gate_up"] = moe["we_gate_up"][2 * rank:2 * rank + 2]
        moe["we_down"] = moe["we_down"][2 * rank:2 * rank + 2] * (0.0 if zero_routed else 1.0)
        with jax.default_matmul_precision("highest"):
            h, found = tw.trunk(jax.tree_util.tree_map(jnp.asarray, {**whole, "blocks": blocks}),
                                spec, jnp.asarray(ids))
        assert (np.asarray(found[0]["tokens"]) == np.asarray(tokens[0])).all()   # all 16, on every rank
        return np.asarray(h)
    common = rank_out(0, zero_routed=True)
    parts = [rank_out(r) - common for r in range(8)]
    assert all(np.abs(p).max() > 1e-3 for p in parts)
    np.testing.assert_allclose(common + sum(parts), want, atol=2e-5 * np.abs(want).max())


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
    assert again.spec == c["spec"] and again.spec.tower == "deepseek_v3"
    assert sorted(towers.flat_names(again.params)) == LEAVES
    assert again.compute(c["bins"]).tobytes() == model.compute(c["bins"]).tobytes()


# ------------------------------------------------------- config, declarations
@pytest.mark.parametrize("over,message", [
    (dict(model_type="deepseek_v2"), "model_type must be 'deepseek_v3'"),
    (dict(q_lora_rank=1536), "q_lora_rank must be None"),
    (dict(scoring_func="softmax"), "scoring_func must be 'sigmoid'"),
    (dict(topk_method="greedy"), "topk_method must be 'noaux_tc'"),
    (dict(n_group=8), "n_group must be 1"),
    (dict(seq_aux=False), "seq_aux must be True"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling must be None"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers must be 0"),
    (dict(foo=1), "unknown TowerParams key 'foo'"),
    (dict(head_dim=64), "unknown TowerParams key 'head_dim'"),
    (dict(first_k_dense_replace=3), "first_k_dense_replace 3 leaves no MoE layer"),
    (dict(num_experts_per_tok=9), "exceeds the router's 8 experts"),
    (dict(expert_parallel_index=2), "expert_parallel_index 2 is not a rank of 2"),
    (dict(num_key_value_heads=2), "num_key_value_heads 2 must equal num_attention_heads 4"),
    (dict(qk_rope_head_dim=7), "qk_rope_head_dim 7 must be even"),
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
    assert towers.module("deepseek_v3") is tw
    ok = {"Tower": "deepseek_v3", "TowerParams": dict(TOY), "MiniBatchs": 16, "RowsPerSequence": 8}
    assert validate_train_params(ok, Algorithm.TENSORFLOW) == []
    missing = {k: v for k, v in TOY.items() if k != "kv_lora_rank"}
    with pytest.raises(ShifuError, match="TowerParams.kv_lora_rank is required"):
        tw.spec_from_params(missing, list(range(7)), COL_BINS, [])
    assert tw.SCOPES[-1] == "tower/opt" and callable(tw.after_step)
    for name in tw.OBS_COUNTERS.values():
        assert obs.manifest.is_declared(name), name
    assert set(tw.OBS_COUNTERS) <= set(tw.counter_shapes(_spec()))
    spec = _spec()
    assert (spec.num_experts, spec.experts_held, spec.moe_layers, spec.rope_theta,
            spec.aux_loss_alpha) == (8, 4, 2, 50000.0, 0.05)
    assert _spec(**{"aux_loss_alpha": 1e-4}).aux_loss_alpha == 1e-4
    assert tw.spec_from_params({k: v for k, v in TOY.items() if k != "aux_loss_alpha"},
                               list(range(7)), COL_BINS, []).aux_loss_alpha == 1e-4


# ------------------------------------------------------------------- the CLI
CLI = {**TOY, "num_hidden_layers": 2, "num_attention_heads": 2, "num_key_value_heads": 2,
       "vocab_size": 4200, "max_position_embeddings": 256, "attention_block": 64}
# the set's rows are 8 positions: 30 a sequence = 240 + 16 PAD = 4 blocks (the interpreter's cost is a grid step's)


def _tower_set(mdir, epochs=3, **params):
    mc = ModelConfig.load(os.path.join(mdir, "ModelConfig.json"))
    mc.train.algorithm = "TENSORFLOW"
    mc.train.numTrainEpochs = epochs
    mc.train.params = {"Tower": "deepseek_v3", "MiniBatchs": 510, "RowsPerSequence": 30,
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
    assert spec.tower == "deepseek_v3" and spec.n_features == len(spec.column_bins)
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
