"""The ``sdar_moe`` tower (``algorithm: TENSORFLOW``, ``train#params.Tower``)
against its plain reference, ``benchmark/reference/sdar_moe.py``: seeded
weights, toy size (hidden 64, 4/2 heads of 16, 8 experts top-2 of width 32 —
4 held by each of 2 ranks —, 2 layers, 97 ids, B = 4, S = 12), on the CPU.
"""

import json
import os
import shutil
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import sdar_moe as ref
from shifu_tpu import faults, obs
from shifu_tpu.config import ModelConfig, environment
from shifu_tpu.config.errors import ShifuError
from shifu_tpu.models import tower_sdar as tw
from shifu_tpu.models import towers
from shifu_tpu.ops import moe

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "helpers"))
import moe_loads  # noqa: E402

COL_BINS = [10, 11, 9, 12, 10, 11, 10, 10]          # 91 ids + 4 specials = 95 <= 97
TOY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, moe_intermediate_size=32, num_experts=4, expert_parallel_size=2,
           expert_parallel_index=0, num_experts_per_tok=2, vocab_size=97,
           max_position_embeddings=12, block_length=4, model_type="sdar_moe",
           rms_norm_eps=1e-6, rope_theta=1000000, norm_topk_prob=True)
LEAVES = ["embed", "final_norm", "head"] + ["layers." + k for k in (
    "k_norm", "ln1", "ln2", "q_norm", "router", "w_down", "w_gate_up", "wk", "wo", "wq", "wv")]


def _spec(rank=0, **over):
    return tw.spec_from_params({**TOY, "expert_parallel_index": rank, **over},
                               list(range(8)), COL_BINS, [f"c{i}" for i in range(8)])


def _rows(n=6, seed=0):
    rng = np.random.default_rng(seed)
    bins = np.stack([rng.integers(0, b + 1, n) for b in COL_BINS], 1).astype(np.uint8)
    return bins, (rng.random(n) < 0.5).astype(np.float32)


def _params(spec, seed=1):
    """Seeded weights with every norm weight off 1, so their gradients differ."""
    p = tw.init_params(jax.random.PRNGKey(seed), spec)
    k = jax.random.PRNGKey(seed + 100)
    bump = lambda a, i: a + 0.1 * jax.random.normal(jax.random.fold_in(k, i), a.shape, a.dtype)
    p["final_norm"] = bump(p["final_norm"], 0)
    for i, name in enumerate(("ln1", "ln2", "q_norm", "k_norm")):
        p["layers"][name] = bump(p["layers"][name], 1 + i)
    return p


def _np(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def _get(tree, name):
    return tree["layers"][name[7:]] if name.startswith("layers.") else tree[name]


@pytest.fixture(scope="module")
def case():
    """One microbatch through the program and through the reference."""
    out = {}
    for rank in (0, 1):
        spec = _spec(rank)
        params = _params(spec)
        bins, y = _rows()
        ids = towers.tokenize(spec, bins, y)
        t, masked = tw.noise(jax.random.PRNGKey(5), len(y), spec)
        row_w = jnp.ones(len(y), jnp.float32)
        fn = jax.jit(jax.value_and_grad(
            lambda p: tw.diffusion_loss(p, spec, jnp.asarray(ids), t, masked, row_w,
                                        spec.special("MASK"), spec.special("PAD")), has_aux=True))
        (loss, aux), grads = fn(params)
        want_loss, want = ref.loss_and_grads(_np(params), ids, t, masked, TOY, spec.expert_lo,
                                             COL_BINS, 4, rows_per_block=2)
        out[rank] = dict(spec=spec, params=params, bins=bins, ids=ids, t=t, masked=masked,
                         loss=float(loss), aux=_np(aux), grads=_np(grads),
                         want_loss=want_loss, want=want)
    return out


# ------------------------------------------------------------ tokens, masks
def test_tokeniser_ids_match_the_reference_and_its_layout():
    spec = _spec()
    bins, y = _rows(5)
    ids = towers.tokenize(spec, bins, y)
    assert ids.shape == (5, 12) and ids.dtype == np.int32
    assert (ids == ref.rows_to_ids(bins, y, COL_BINS, 4)).all()
    off = np.concatenate([[0], np.cumsum(np.asarray(COL_BINS) + 1)[:-1]])
    assert (ids[:, :8] == bins + off).all()
    assert (ids[:, 8] == np.where(y > 0.5, spec.special("TAG1"), spec.special("TAG0"))).all()
    assert (ids[:, 9:] == spec.special("PAD")).all()
    assert [spec.special(n) for n in towers.SPECIALS] == [91, 92, 93, 94] and spec.n_ids == 95


def test_more_ids_than_the_slice_holds_is_an_error_never_a_clamp():
    with pytest.raises(ShifuError, match="95 token ids .* slice holds 94"):
        _spec(vocab_size=94)
    spec = _spec()
    bins, y = _rows(3)
    bins[1, 2] = COL_BINS[2] + 1                    # past the column's missing bin
    with pytest.raises(ShifuError, match="holds bin 10"):
        towers.tokenize(spec, bins, y)


def test_feature_tokens_pad_to_whole_blocks():
    spec = tw.spec_from_params({**TOY, "max_position_embeddings": 16}, list(range(6)),
                               COL_BINS[:6], [f"c{i}" for i in range(6)])
    assert (spec.feature_len, spec.seq_len) == (8, 12)
    ids = towers.tokenize(spec, _rows(2)[0][:, :6], np.array([1.0, 0.0]))
    assert (ids[:, 6:8] == spec.special("PAD")).all() and ids[0, 8] == spec.special("TAG1")


@pytest.mark.parametrize("s,block", [(12, 4), (8, 2), (9, 3)])
def test_block_mask_against_two_loops(s, block):
    want = np.zeros((2 * s, 2 * s), bool)
    for q in range(2 * s):
        for k in range(2 * s):
            q_noised, k_noised = q < s, k < s
            qb, kb = (q % s) // block, (k % s) // block
            if q_noised:
                want[q, k] = (k_noised and kb == qb) or (not k_noised and kb < qb)
            else:
                want[q, k] = (not k_noised) and kb <= qb
    assert (tw.block_mask(s, block) == want).all()
    assert (ref.block_mask(s, block) == want).all()
    ev = np.array([[k // block <= q // block for k in range(s)] for q in range(s)])
    assert (tw.eval_mask(s, block) == ev).all() and (ref.eval_mask(s, block) == ev).all()


# -------------------------------------------- forward, loss, every gradient
@pytest.mark.parametrize("mask", ["split", "dense"])
@pytest.mark.parametrize("rank", [0, 1])
def test_forward_logits_match_the_reference(case, rank, mask):
    """Both masks through the attention kernels: ``split``, what training runs
    (``[x_t ; x_0]`` under the block-diffusion mask), and ``dense``, ``eval``'s
    (one half, block-causal)."""
    c = case[rank]
    spec, s = c["spec"], c["spec"].seq_len
    xt = jnp.where(c["masked"], spec.special("MASK"), jnp.asarray(c["ids"]))
    if mask == "split":
        h, _ = tw.hidden(c["params"], spec, jnp.concatenate([xt, jnp.asarray(c["ids"])], 1),
                         jnp.concatenate([jnp.arange(s), jnp.arange(s)]), tw.block_mask(s, 4))
        got = np.asarray(h[:, :s] @ c["params"]["head"])
        want = ref.forward_logits(_np(c["params"]), c["ids"], c["masked"], TOY, spec.expert_lo,
                                  COL_BINS, 4)
    else:
        h, _ = tw.hidden(c["params"], spec, xt, jnp.arange(s), tw.eval_mask(s, 4))
        got = np.asarray(h @ c["params"]["head"])
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.hidden(c["params"], xt, jnp.arange(s), ref.eval_mask(s, 4), TOY,
                                         spec.expert_lo) @ c["params"]["head"])
    assert got.shape == want.shape == (6, 12, 97)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("rank", [0, 1])
def test_loss_matches_the_reference(case, rank):
    c = case[rank]
    assert c["loss"] == pytest.approx(c["want_loss"], rel=1e-6)
    assert c["aux"]["positions"] == 6 * 9 and (c["aux"]["dropped"] == 0).all()
    use = np.asarray(c["masked"]) & (c["ids"] != c["spec"].special("PAD"))
    assert c["aux"]["masked"] == use.sum()


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("rank", [0, 1])
def test_gradient_matches_the_reference(case, rank, leaf):
    got, want = _get(case[rank]["grads"], leaf), _get(case[rank]["want"], leaf)
    assert got.shape == want.shape and got.dtype == np.float32
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


def test_eval_score_is_one_denoising_step_of_the_tag_block(case):
    c = case[1]
    got = towers.IndependentTowerModel(c["spec"], c["params"]).compute(c["bins"])[:, 0]
    d = ref.tag_logit_difference(_np(c["params"]), c["bins"], TOY, c["spec"].expert_lo, COL_BINS, 4)
    np.testing.assert_allclose(got, 1.0 / (1.0 + np.exp(-d)), atol=1e-6)


def test_a_mask_the_kernels_cannot_describe_is_refused():
    spec = _spec()
    comb = (np.arange(12)[:, None] - np.arange(12)[None, :]) % 3 == 0    # every third key: four runs a query
    with pytest.raises(ValueError, match="separate runs of keys"):
        tw.hidden(_params(spec), spec, jnp.zeros((2, 12), jnp.int32), jnp.arange(12), comb)
    with pytest.raises(ValueError, match="not over whole halves of 12"):
        tw.attention_plan(12, np.ones((18, 18), bool))


@pytest.mark.parametrize("s,block,half", [(12, 32, 16), (20, 64, 32), (436, 512, 512), (600, 512, 1024)])
def test_attention_plan_pads_each_half_to_whole_blocks(s, block, half):
    """The padded description allows exactly the mask's pairs, moved to the
    padded positions: a pad key lies in nobody's intervals, a pad query has none."""
    from shifu_tpu.ops import attention
    plan = tw.attention_plan(s, tw.block_mask(s, 4))
    assert (plan.block, plan.half, plan.parts, plan.mask.seq) == (block, half, 2, 2 * half)
    real = np.concatenate([np.arange(s), half + np.arange(s)])
    dense = plan.mask.dense()
    assert (dense[np.ix_(real, real)] == tw.block_mask(s, 4)).all()
    assert dense.sum() == tw.block_mask(s, 4).sum()
    x = jnp.arange(2 * 2 * s * 3, dtype=jnp.float32).reshape(2, 2 * s, 3) + 1.0
    padded = np.asarray(plan.pad(x))
    assert padded.shape == (2, 2 * half, 3) and (padded[:, real] == np.asarray(x)).all()
    assert padded.sum() == float(x.sum()) and (np.asarray(plan.unpad(plan.pad(x))) == np.asarray(x)).all()
    got = plan.counters(each=3)
    assert got["attn_pad_positions"] == 2 * (half - s)
    assert got["attn_key_blocks"] == 3 * attention.visited_key_blocks(2 * half, block, mask=plan.mask)
    assert got["attn_key_blocks_dense"] == 3 * (2 * half // block) ** 2
    if s == 436:                                            # the cell: 3 of 4 block pairs a head, 152 of 1,024 positions
        assert (got["attn_key_blocks"], got["attn_key_blocks_dense"], got["attn_pad_positions"]) == (9, 12, 152)
    one = tw.attention_plan(s, tw.eval_mask(s, 4))
    assert (one.parts, one.mask.seq) == (1, half) and one.mask.dense().sum() == tw.eval_mask(s, 4).sum()


def test_the_attention_counters_are_declared_and_read_the_schedule_and_the_pad(case):
    """``aux`` counts, for the rows that count: heads x layers x the
    schedule's visits, x every block pair, and the positions padded."""
    from shifu_tpu.obs.manifest import MANIFEST
    from shifu_tpu.ops import attention
    c = case[0]
    spec, plan = c["spec"], tw.attention_plan(12, tw.block_mask(12, 4))
    assert set(tw.ATTN_COUNTERS) <= set(tw.OBS_COUNTERS) <= set(tw.counter_shapes(spec))
    for name in tw.ATTN_COUNTERS.values():
        assert MANIFEST[name][0] == "counter", name
    visits = attention.visited_key_blocks(32, 32, mask=plan.mask)
    assert (plan.block, visits) == (32, 1)                 # a toy sequence is one block; the cell's 3 of 4: the test above
    assert c["aux"]["attn_key_blocks"] == 6 * 4 * 2 * visits          # rows x heads x layers
    assert c["aux"]["attn_key_blocks_dense"] == 6 * 4 * 2 * 1
    assert c["aux"]["attn_pad_positions"] == 6 * 2 * 4
    # a padding row (weight 0) counts for nothing
    ids = jnp.asarray(c["ids"])
    _, aux = tw.diffusion_loss(c["params"], spec, ids, c["t"], c["masked"],
                               jnp.asarray([1, 1, 0, 1, 0, 0], jnp.float32),
                               spec.special("MASK"), spec.special("PAD"))
    assert aux["attn_key_blocks"] == 3 * 4 * 2 * visits and aux["attn_pad_positions"] == 3 * 8


# ------------------------------------------------------------------ the share
def test_the_shares_add_up_to_the_uncut_layer():
    """The partial MoE outputs of all ranks sum to the reference's layer with
    every expert held."""
    spec = _spec()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    router = jnp.asarray(0.3 * rng.normal(size=(64, 8)), jnp.float32)
    w_gu = jnp.asarray(0.1 * rng.normal(size=(8, 64, 64)), jnp.float32)
    w_d = jnp.asarray(0.1 * rng.normal(size=(8, 32, 64)), jnp.float32)
    weights, experts = moe.route(x, router, 2)
    total = 0.0
    for rank in (0, 1):
        sl = slice(4 * rank, 4 * rank + 4)
        part, counters = moe.held_experts_ffn(x, weights, experts, w_gu[sl], w_d[sl], lo=4 * rank)
        want = ref.moe_layer({"router": router, "w_gate_up": w_gu[sl], "w_down": w_d[sl]}, x,
                             TOY, 4 * rank)
        np.testing.assert_allclose(part, want, atol=1e-5)
        assert int(counters["dropped"]) == 0
        total = total + part
    whole = ref.moe_layer({"router": router, "w_gate_up": w_gu, "w_down": w_d}, x,
                          {**TOY, "num_experts": 8}, 0)
    np.testing.assert_allclose(total, whole, atol=1e-5)
    assert spec.num_experts == 8 and spec.experts_held == 4


def test_no_pair_dropped_when_every_token_picks_one_expert():
    """The worst imbalance: the router sends every token to expert 2 first."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(np.abs(rng.normal(size=(48, 64))) + 0.1, jnp.float32)
    router = np.zeros((64, 8), np.float32)
    router[:, 2] = 1.0                                  # x > 0: expert 2 wins everywhere
    router[:, 5] = 0.5
    w_gu = jnp.asarray(0.1 * rng.normal(size=(4, 64, 64)), jnp.float32)
    w_d = jnp.asarray(0.1 * rng.normal(size=(4, 32, 64)), jnp.float32)
    weights, experts = moe.route(x, jnp.asarray(router), 2)
    assert (np.asarray(experts[:, 0]) == 2).all()
    y, counters = moe.held_experts_ffn(x, weights, experts, w_gu, w_d, lo=0)
    assert np.asarray(counters["pairs"]).tolist() == [0, 0, 48, 0]
    assert int(counters["dropped"]) == 0
    want = ref.moe_layer({"router": jnp.asarray(router), "w_gate_up": w_gu, "w_down": w_d}, x, TOY, 0)
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_dropped_counts_the_pairs_outside_their_experts_group():
    """The counter is measured: group sizes clipped to a capacity (what a
    dispatch that drops does) leave the later experts' rows outside their
    own groups.  Four tokens, two choices, four held: two chunks of 4 rows."""
    local = jnp.asarray([[0, 1], [0, 2], [0, 1], [1, 4]])          # 4 = an absent expert
    chosen = jnp.sort(jnp.where(local < 4, local, 4).reshape(-1))  # the buffer's rows, by expert
    sizes = jnp.bincount(chosen, length=5)[:4]
    assert sizes.tolist() == [3, 3, 1, 0]
    assert moe.chunk_sizes(sizes, 2, 4).tolist() == [[3, 1, 0, 0], [0, 2, 1, 0]]
    assert int(moe.covered_pairs(chosen, moe.chunk_sizes(sizes, 2, 4))) == 7
    # expert 0 capped at 2 rows: its third pair falls into expert 1's group,
    # and every later group starts one row early
    capped = sizes.at[0].set(2)
    assert int(moe.covered_pairs(chosen, moe.chunk_sizes(capped, 2, 4))) == 2 + 2 + 0


@pytest.mark.parametrize("load", moe_loads.LOADS)
def test_chunk_walk_matches_the_reference_layer_and_its_gradients(load, monkeypatch):
    """16 tokens, top-2 of 8, experts 0 .. 3 held: a buffer of two chunks of
    16 rows (no rounding up to the kernel's row tile at this size).  Output
    and the gradients of x, the router and both matrices against the plain
    layer's autodiff; the walk runs the chunks the routed pairs fill, no more."""
    monkeypatch.setattr(moe, "ROW_TILE", 1)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    p = {"router": jnp.asarray(moe_loads.router_to(x, moe_loads.picks(load, 2, 8), 8)),
         "w_gate_up": jnp.asarray(0.3 * rng.normal(size=(4, 32, 24)), jnp.float32),
         "w_down": jnp.asarray(0.3 * rng.normal(size=(4, 12, 32)), jnp.float32)}
    g = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)

    def mine(x, p):
        weights, experts = moe.route(x, p["router"], 2)
        y, counters = moe.held_experts_ffn(x, weights, experts, p["w_gate_up"], p["w_down"], lo=0)
        return jnp.sum(y * g), (y, counters)
    (_, (y, counters)), got = jax.value_and_grad(mine, argnums=(0, 1), has_aux=True)(x, p)
    want = jax.grad(lambda x, p: jnp.sum(ref.moe_layer(p, x, TOY, 0) * g), argnums=(0, 1))(x, p)
    n_here = moe_loads.routed(load, 2)
    assert int(counters["pairs"].sum()) == n_here and int(counters["dropped"]) == 0
    assert int(counters["rows"]) == -(-n_here // 16) * 16
    np.testing.assert_allclose(y, ref.moe_layer(p, x, TOY, 0), atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(float(jnp.abs(b).max()), 1.0))
        assert n_here or not np.asarray(a).any()


def test_bf16_operands_stay_near_f32_and_leave_f32(monkeypatch):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    router = jnp.asarray(0.3 * rng.normal(size=(64, 8)), jnp.float32)
    w_gu = jnp.asarray(0.1 * rng.normal(size=(4, 64, 64)), jnp.float32)
    w_d = jnp.asarray(0.1 * rng.normal(size=(4, 32, 64)), jnp.float32)
    weights, experts = moe.route(x, router, 2)
    f = lambda x, a, b: jnp.sum(moe.held_experts_ffn(x, weights, experts, a, b, 0)[0] ** 2)
    want = jax.grad(f, argnums=(0, 1, 2))(x, w_gu, w_d)
    monkeypatch.setattr(moe, "mxu_operand_dtype", lambda like: jnp.bfloat16)   # the TPU's
    got = jax.grad(f, argnums=(0, 1, 2))(x, w_gu, w_d)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32
        assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 3e-2


# ------------------------------------------------------- config, declarations
def test_tower_params_are_checked_in_one_coded_error():
    with pytest.raises(ShifuError) as e:
        _spec(attention_bias=True, foo=1, hidden_act="gelu")
    msg = str(e.value)
    assert "attention_bias must be False" in msg and "unknown TowerParams key 'foo'" in msg \
        and "hidden_act must be 'silu'" in msg
    missing = {k: v for k, v in TOY.items() if k != "head_dim"}
    with pytest.raises(ShifuError, match="TowerParams.head_dim is required"):
        tw.spec_from_params(missing, list(range(8)), COL_BINS, [])


def test_meta_rules_know_the_slot():
    from shifu_tpu.config.meta import (TF_ONLY_PARAMS, TRAIN_PARAM_RULES,
                                       validate_train_params)
    from shifu_tpu.config.model_config import Algorithm
    assert "Tower" in TRAIN_PARAM_RULES and "TowerParams" in TRAIN_PARAM_RULES
    assert "Tower" not in TF_ONLY_PARAMS and "NumPS" in TF_ONLY_PARAMS
    ok = {"Tower": "sdar_moe", "TowerParams": dict(TOY), "MiniBatchs": 16}
    assert validate_train_params(ok, Algorithm.TENSORFLOW) == []
    assert any("does not apply" in p for p in validate_train_params(ok, Algorithm.NN))
    assert validate_train_params({"Tower": "resnet"}, Algorithm.TENSORFLOW)
    assert validate_train_params({"TowerParams": [1]}, Algorithm.TENSORFLOW)


@pytest.mark.parametrize("name", ["tower.tokenize", "tower.init", "tower.epoch",
                                  "tower.epoch.dispatch", "tower.epoch.fetch",
                                  "tower.epoch.checkpoint", "tower.save"])
def test_tower_spans_are_declared(name):
    assert obs.manifest.is_declared_span(name) and obs.manifest.SPANS[name].strip()


def test_op_scopes_reads_named_scopes_from_the_compiled_program():
    from shifu_tpu.obs.costs import op_scopes

    def f(a, b):
        with jax.named_scope("tower/attn"):
            c = jnp.tanh(a @ b)
        with jax.named_scope("tower/opt"):
            return jnp.sum(jnp.exp(c))
    x = jnp.ones((8, 8), jnp.float32)
    table = op_scopes(jax.jit(jax.grad(f)).lower(x, x).compile().as_text(), tw.SCOPES)
    assert table["tower/attn"] and table["tower/opt"] and not table["tower/head"]
    assert not set(table["tower/attn"]) & set(table["tower/opt"])


# ------------------------------------------------------------------- the CLI
# the attention kernels run in the interpreter, a sequence a call, and its cost is a grid
# step's: two query heads on one key-value head, the set's 4,000 rows one block each
CLI = {**TOY, "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 4200}


def _tower_set(mdir, epochs=3, **params):
    mc = ModelConfig.load(os.path.join(mdir, "ModelConfig.json"))
    mc.train.algorithm = "TENSORFLOW"
    mc.train.numTrainEpochs = epochs
    mc.train.params = {"Tower": "sdar_moe", "MiniBatchs": 512, "LearningRate": 0.003,
                       "Propagation": "ADAM",
                       "TowerParams": {**CLI, "max_position_embeddings": 64},
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
    assert spec.tower == "sdar_moe" and spec.n_features == len(spec.column_bins)
    lines = _progress(prepared_set)
    assert len(lines) == 3 and lines[0].startswith("Tower Epoch #1 Train Error: ")
    first, last = (float(l.split("Train Error: ")[1].split()[0]) for l in (lines[0], lines[-1]))
    assert last < first
    assert main(["--dir", prepared_set, "eval", "-run"]) == 0
    with open(os.path.join(prepared_set, "evals", "Eval1", "EvalScore")) as f:
        col = f.readline().strip().split("|").index("mean")
        got = np.sort([float(line.split("|")[col]) for line in f])
    bins = Shards.open(os.path.join(prepared_set, "tmp", "CleanedData")).load_all()["bins"]
    d = ref.tag_logit_difference(params, bins, CLI, spec.expert_lo, spec.column_bins, 4, 256)
    want = np.sort(1000.0 / (1.0 + np.exp(-d)))
    assert len(got) == len(want)
    np.testing.assert_allclose(got, want, atol=2e-3)


def test_a_job_counts_the_attention_counters_and_the_report_shows_them(prepared_set, capsys):
    from shifu_tpu.cli import main
    _tower_set(prepared_set, epochs=1)
    assert main(["--dir", prepared_set, "train", "--telemetry"]) == 0
    found = {}
    with open(os.path.join(prepared_set, "telemetry", "trace.jsonl")) as f:
        for doc in map(json.loads, f):
            if str(doc.get("name", "")).startswith("tower.attn_") and "value" in doc:
                found[doc["name"]] = found.get(doc["name"], 0.0) + float(doc["value"])
    # 3,200 training rows of 12 positions: one block of 32 a sequence, 2 heads x 2 layers; 2 x 4 pad positions a row
    assert found == {"tower.attn_key_blocks": 3200 * 4.0, "tower.attn_key_blocks_dense": 3200 * 4.0,
                     "tower.attn_pad_positions": 3200 * 8.0}
    capsys.readouterr()
    assert main(["--dir", prepared_set, "analysis", "--telemetry"]) == 0
    shown = capsys.readouterr().out
    assert all(name in shown for name in found), shown


def test_cli_killed_job_resumes_bit_exactly(prepared_set):
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

    _, want = _load(control)
    _, got = _load(prepared_set)
    for name in LEAVES:
        assert _get(got, name).tobytes() == _get(want, name).tobytes(), name
    assert _progress(prepared_set)[-1] == _progress(control)[-1]
    assert len(_progress(prepared_set)) == 2                      # epochs 2 and 3 again


def test_tensorflow_without_tower_still_trains_the_mlp(prepared_set):
    from shifu_tpu.cli import main
    mc = ModelConfig.load(os.path.join(prepared_set, "ModelConfig.json"))
    mc.train.algorithm = "TENSORFLOW"
    mc.train.numTrainEpochs = 3
    mc.train.params = {"NumHiddenNodes": [8], "ActivationFunc": ["relu"], "NumHiddenLayers": 1}
    mc.save(os.path.join(prepared_set, "ModelConfig.json"))
    assert main(["--dir", prepared_set, "train"]) == 0
    assert os.listdir(os.path.join(prepared_set, "models")) == ["model0.nn"]


@pytest.mark.parametrize("step", [["export"], ["export", "-t", "spec"], ["serve", "--selfcheck", "2"],
                                  ["combo", "new", "-alg", "NN:GBT"]])
def test_cli_steps_without_a_tower_path_refuse_with_a_coded_error(prepared_set, step, capsys):
    from shifu_tpu.cli import main
    _tower_set(prepared_set)
    assert main(["--dir", prepared_set] + step) == 1
    err = capsys.readouterr().err
    assert "[1052]" in err and f"`{step[0]}` cannot take a tower" in err


def test_varselect_wrapper_refuses_a_tower(prepared_set):
    from shifu_tpu.pipeline.varselect import VarSelectProcessor
    _tower_set(prepared_set)
    mc = ModelConfig.load(os.path.join(prepared_set, "ModelConfig.json"))
    mc.varSelect.filterEnable = True
    mc.varSelect.filterBy = "SE"
    mc.save(os.path.join(prepared_set, "ModelConfig.json"))
    with pytest.raises(ShifuError, match=r"`varselect -wrapper` cannot take a tower"):
        VarSelectProcessor(prepared_set, params={}).run()
