"""The ``nemotron_h`` tower (``algorithm: TENSORFLOW``, ``train#params.Tower``)
against its plain reference, ``benchmark/reference/nemotron_h.py``: seeded
weights, toy size (hidden 64; 4 Mamba heads of 8 in 2 groups, state 16, chunks
of 3; 4/2 attention heads of 16; 8 experts top-3 of width 24 in a latent space
of 32 — 4 held by each of 2 ranks —, a shared expert of 48; pattern ``EM*`` and
an MTP module ``*E``; 97 ids, 9 positions), on the CPU.
"""

import os
import shutil
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import nemotron_h as ref
from shifu_tpu import faults, obs
from shifu_tpu.config import ModelConfig, environment
from shifu_tpu.config.errors import ShifuError
from shifu_tpu.models import tower_nemotron_h as tw
from shifu_tpu.models import towers
from shifu_tpu.ops import moe
from shifu_tpu.train import tower_trainer as tt
from shifu_tpu.train.optimizers import make_optimizer

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "helpers"))
import moe_loads  # noqa: E402

COL_BINS = [10, 11, 9, 12, 10, 11, 10, 10]          # 91 ids + 4 specials = 95 <= 97
TOY = dict(model_type="nemotron_h", hidden_size=64, num_hidden_layers=3,
           hybrid_override_pattern="EM*", num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
           conv_kernel=4, chunk_size=3, n_routed_experts=4, expert_parallel_size=2,
           expert_parallel_index=0, num_experts_per_tok=3, moe_intermediate_size=24,
           moe_latent_size=32, moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5,
           norm_topk_prob=True, vocab_size=97, max_position_embeddings=9, layer_norm_epsilon=1e-5,
           num_nextn_predict_layers=1, mtp_hybrid_override_pattern="*E",
           tensor_parallel_size=2, tensor_parallel_index=0)
LR = 1e-3


def _spec(rank=0, **over):
    return tw.spec_from_params({**TOY, "expert_parallel_index": rank, **over},
                               list(range(8)), COL_BINS, [f"c{i}" for i in range(8)])


LEAVES = sorted(tw.param_shapes(_spec()))


def _rows(n=6, seed=0):
    rng = np.random.default_rng(seed)
    bins = np.stack([rng.integers(0, b + 1, n) for b in COL_BINS], 1).astype(np.uint8)
    return bins, (rng.random(n) < 0.5).astype(np.float32)


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
    """One microbatch through the program (loss, gradients and one optimizer
    step of the trainer's own program) and through the reference."""
    out = {}
    for rank in (0, 1):
        spec = _spec(rank)
        params = _params(spec)
        bins, y = _rows()
        ids = towers.tokenize(spec, bins, y)
        row_w = jnp.ones(len(y), jnp.float32)
        fn = jax.jit(jax.value_and_grad(
            lambda p: tw.causal_loss(p, spec, jnp.asarray(ids), row_w), has_aux=True))
        (loss, aux), grads = fn(params)
        want_loss, want = ref.loss_and_grads(_np(params), ids, TOY, spec.expert_lo,
                                             rows_per_block=2)
        out[rank] = dict(spec=spec, params=params, bins=bins, ids=ids, loss=float(loss),
                         aux=_np(aux), grads=towers.flat_names(_np(grads)),
                         want_loss=want_loss, want=ref.flatten(want))
    # the trainer's step on rank 0's microbatch: Adam's first step
    c = out[0]
    opt = make_optimizer("ADAM", LR)
    step, _ = tt.build_programs(c["spec"], opt, len(c["ids"]))
    before = jax.tree_util.tree_map(jnp.array, c["params"])
    specials = jnp.asarray([c["spec"].special(n) for n in towers.SPECIALS], jnp.int32)
    after, opt_state, acc = step(before, opt.init(before), tt._zero_acc(c["spec"]),
                                 jnp.asarray(c["ids"]), jnp.ones(len(c["ids"]), jnp.float32),
                                 jnp.arange(len(c["ids"]), dtype=jnp.int32),
                                 jax.random.PRNGKey(0), specials, jnp.int32(0), jnp.int32(0))
    c.update(after=towers.flat_names(_np(after)), acc=_np(acc),
             m=towers.flat_names(_np(opt_state["m"])), v=towers.flat_names(_np(opt_state["v"])))
    return out


# --------------------------------------------------- tokens, init, the seed
def test_tokeniser_is_one_token_a_column_then_the_tag():
    spec = _spec()
    bins, y = _rows(5)
    ids = towers.tokenize(spec, bins, y)
    assert ids.shape == (5, 9) and (spec.feature_len, spec.seq_len, spec.block_length) == (8, 9, 1)
    assert (ids == ref.rows_to_ids(bins, y, COL_BINS)).all()
    assert (ids[:, 8] == np.where(y > 0.5, spec.special("TAG1"), spec.special("TAG0"))).all()
    with pytest.raises(ShifuError, match="95 token ids .* slice holds 94"):
        _spec(vocab_size=94)
    with pytest.raises(ShifuError, match="a row is 9 positions"):
        _spec(max_position_embeddings=8)


def test_initial_parameters_are_the_references_to_the_bit():
    spec = _spec()
    got = towers.flat_names(_np(tw.init_params(jax.random.PRNGKey(7), spec)))
    want = ref.flatten(ref.init_params(7, TOY))
    assert sorted(got) == sorted(want) == LEAVES
    for name in LEAVES:
        assert got[name].dtype == np.float32 and got[name].tobytes() == want[name].tobytes(), name
    blk = "blocks.01."
    assert (got[blk + "D"] == 1).all() and (got["blocks.00.bias"] == 0).all()
    a = np.exp(got[blk + "A_log"])
    assert ((a >= 1) & (a <= 16)).all()
    dt = np.log1p(np.exp(got[blk + "dt_bias"]))              # softplus
    assert ((dt >= 1e-3 - 1e-6) & (dt <= 0.1 + 1e-6)).all()
    assert np.abs(got[blk + "conv_w"]).max() <= 0.5 and got[blk + "norm"].tolist() == [1.0] * 64
    assert towers.n_params(tw.init_params(jax.random.PRNGKey(7), spec)) == \
        sum(int(np.prod(s)) for s in tw.param_shapes(spec).values())


# -------------------------------------------- forward, loss, every gradient
@pytest.mark.parametrize("rank", [0, 1])
def test_forward_logits_match_the_reference(case, rank):
    c = case[rank]
    spec, p, ids = c["spec"], c["params"], jnp.asarray(c["ids"])
    h, _ = tw.trunk(p, spec, ids[:, :-1])
    main = tw._rms(h, p["norm_f"], 1e-5) @ p["head"]
    hm, _ = tw._mtp_hidden(p, spec, h[:, :-1], ids[:, 1:-1])
    want_main, want_mtp = ref.forward_logits(_np(p), c["ids"], TOY, spec.expert_lo)
    assert main.shape == want_main.shape == (6, 8, 97) and want_mtp.shape == (6, 7, 97)
    np.testing.assert_allclose(main, want_main, atol=2e-5)
    np.testing.assert_allclose(hm @ p["head"], want_mtp, atol=2e-5)


@pytest.mark.parametrize("rank", [0, 1])
def test_loss_and_counters_match_the_reference(case, rank):
    c = case[rank]
    assert c["loss"] == pytest.approx(c["want_loss"], rel=1e-6)
    aux = c["aux"]
    assert aux["positions"] == 6 * 8 and (aux["dropped"] == 0).all()
    assert aux["loss_sum"] / aux["positions"] == pytest.approx(c["want_loss"], rel=1e-6)
    assert aux["ssm_chunks"] == 6 * 1 * 3                    # rows x M layers x ceil(8 / 3)
    assert aux["pairs"].shape == (2, 4)                      # the trunk's E layer and the MTP's
    # the MTP term is in the loss: the reference without it reads lower by 0.1 x its mean
    without, _ = ref.loss_and_grads(_np(c["params"]), c["ids"], {**TOY, "no_mtp": True},
                                    c["spec"].expert_lo, rows_per_block=6)
    assert c["loss"] - without == pytest.approx(0.1 * aux["mtp_loss_sum"] / (6 * 7), rel=1e-5)


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("rank", [0, 1])
def test_gradient_matches_the_reference(case, rank, leaf):
    got, want = case[rank]["grads"][leaf], case[rank]["want"][leaf]
    assert got.shape == want.shape and got.dtype == np.float32
    if leaf.endswith(".bias"):
        assert not got.any() and not want.any()             # the selection bias takes no gradient
        return
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("leaf", LEAVES)
def test_one_adam_step_matches_the_references(case, leaf):
    """The trainer's own step program: Adam's moments are the reference's
    gradient's, and the parameters move by the reference's first step where
    that gradient is sure of its sign."""
    c = case[0]
    g, before = c["want"][leaf], np.asarray(towers.flat_names(_np(c["params"]))[leaf])
    m, v, after = ref.adam_first_step(before, g, LR)
    scale = max(np.abs(g).max(), 1e-30)
    np.testing.assert_allclose(c["m"][leaf], m, atol=2e-5 * (1 - ref.ADAM_B1) * scale)
    np.testing.assert_allclose(c["v"][leaf], v, atol=1e-4 * (1 - ref.ADAM_B2) * scale ** 2)
    sure = np.abs(g) > 1e-3 * scale
    np.testing.assert_allclose((c["after"][leaf] - before)[sure], (after - before)[sure],
                               atol=0.02 * LR)
    assert not sure.any() or np.abs(c["after"][leaf] - before).max() > 0.5 * LR
    assert c["acc"]["loss_sum"] / c["acc"]["positions"] == pytest.approx(c["want_loss"], rel=1e-6)


# -------------------------------------------------------------- the recurrence
@pytest.mark.parametrize("t,chunk", [(11, 4), (8, 3), (7, 8), (12, 4)])
def test_chunked_scan_is_the_sequential_recurrence(t, chunk):
    """Lengths that are no multiple of the chunk, one shorter than a chunk,
    and a whole number of chunks."""
    rng = np.random.default_rng(t)
    n, h, p, g, ns = 2, 4, 5, 2, 6
    x = jnp.asarray(rng.normal(size=(n, t, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.9, size=(n, t, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, size=h), jnp.float32)
    b = jnp.asarray(rng.normal(size=(n, t, g, ns)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(n, t, g, ns)), jnp.float32)
    got = tw.ssd_chunked(x, dt, a, b, c, chunk)
    s = np.zeros((n, h, p, ns))
    want = np.zeros((n, t, h, p))
    bh, ch = np.repeat(np.asarray(b), h // g, 2), np.repeat(np.asarray(c), h // g, 2)
    for i in range(t):
        s = np.exp(np.asarray(dt)[:, i] * np.asarray(a))[..., None, None] * s + \
            (np.asarray(dt)[:, i, :, None] * np.asarray(x)[:, i])[..., None] * bh[:, i, :, None, :]
        want[:, i] = np.einsum("nhps,nhs->nhp", s, ch[:, i])
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


def test_a_state_reset_at_chunk_boundaries_is_told_apart(case):
    """The reference's control: the recurrent state zeroed every chunk moves
    the Mamba layer's output (what a chunked scan that loses its carry does)."""
    c = case[0]
    p = _np(c["params"])["blocks"]["01"]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 8, 64)), jnp.float32)
    sound = ref.mamba_mixer(p, x, TOY)
    reset = ref.mamba_mixer(p, x, {**TOY, "reset_state_every": 3})
    np.testing.assert_allclose(tw._mamba(c["params"]["blocks"]["01"], x, c["spec"]), sound, atol=1e-5)
    np.testing.assert_allclose(reset[:, :3], sound[:, :3], atol=1e-6)      # the first chunk is whole
    assert np.abs(np.asarray(reset[:, 3:] - sound[:, 3:])).max() > 1e-3


# ------------------------------------------------------------------ the shares
def _uncut_mamba(rng, d=32, h=16, p=4, g=8, ns=8, k=4):
    di = h * p
    return {"w_in": 0.3 * rng.normal(size=(d, 2 * di + 2 * g * ns + h)),
            "conv_w": rng.uniform(-0.5, 0.5, size=(k, di + 2 * g * ns)),
            "conv_b": rng.uniform(-0.5, 0.5, size=di + 2 * g * ns),
            "dt_bias": rng.normal(size=h), "A_log": np.log(rng.uniform(1, 16, size=h)),
            "D": rng.normal(size=h), "w_norm": 1 + 0.1 * rng.normal(size=di),
            "w_out": 0.3 * rng.normal(size=(di, d))}


def test_the_eight_head_shares_of_a_mamba_layer_add_up_to_the_uncut_layer():
    """Rank r of 8 holds heads 2r, 2r+1 of 16 and B/C group r of 8 (its
    norm group is its own): the partial sums add up to the whole layer."""
    rng = np.random.default_rng(11)
    d, h, p, g, ns, ranks = 32, 16, 4, 8, 8, 8
    di, hl, dl = h * p, h // ranks, h * p // ranks
    full = {k: np.asarray(v, np.float32) for k, v in _uncut_mamba(rng).items()}
    cfg = dict(mamba_num_heads=h, mamba_head_dim=p, n_groups=g, ssm_state_size=ns, conv_kernel=4,
               layer_norm_epsilon=1e-5)
    x = jnp.asarray(rng.normal(size=(3, 10, d)), jnp.float32)
    whole = ref.mamba_mixer(full, x, cfg)
    spec = _spec(mamba_num_heads=hl, mamba_head_dim=p, n_groups=1, ssm_state_size=ns, chunk_size=4,
                 hidden_size=d, tensor_parallel_size=ranks)
    total = 0.0
    for r in range(ranks):
        ch = np.r_[r * dl:(r + 1) * dl]                                     # its x / z channels
        bc = lambda base: base + np.r_[r * ns:(r + 1) * ns]                  # its B (or C) group
        conv = np.concatenate([ch, bc(di), bc(di + g * ns)])
        cols = np.concatenate([ch, di + conv, 2 * di + 2 * g * ns + np.r_[r * hl:(r + 1) * hl]])
        heads = slice(r * hl, (r + 1) * hl)
        part = {"w_in": full["w_in"][:, cols], "conv_w": full["conv_w"][:, conv],
                "conv_b": full["conv_b"][conv], "dt_bias": full["dt_bias"][heads],
                "A_log": full["A_log"][heads], "D": full["D"][heads],
                "w_norm": full["w_norm"][ch], "w_out": full["w_out"][ch]}
        total = total + tw._mamba(jax.tree_util.tree_map(jnp.asarray, part), x, spec)
    np.testing.assert_allclose(total, whole, atol=2e-5 * np.abs(whole).max())


def test_the_eight_head_shares_of_an_attention_layer_add_up_to_the_uncut_layer():
    """Rank r of 8 holds query head r of 8 and key-value head r // 4 of 2 (a
    key-value head is replicated over the four ranks whose queries use it)."""
    rng = np.random.default_rng(12)
    d, h, kv, hd, ranks = 32, 8, 2, 8, 8
    full = {k: np.asarray(0.3 * rng.normal(size=s), np.float32) for k, s in
            (("wq", (d, h * hd)), ("wk", (d, kv * hd)), ("wv", (d, kv * hd)), ("wo", (h * hd, d)))}
    cfg = dict(num_attention_heads=h, num_key_value_heads=kv, head_dim=hd)
    x = jnp.asarray(rng.normal(size=(3, 10, d)), jnp.float32)
    whole = ref.attention_mixer(full, x, cfg)
    spec = _spec(num_attention_heads=1, num_key_value_heads=1, head_dim=hd, hidden_size=d,
                 tensor_parallel_size=ranks)
    total = 0.0
    for r in range(ranks):
        q, k = slice(r * hd, (r + 1) * hd), slice((r // 4) * hd, (r // 4 + 1) * hd)
        part = {"wq": full["wq"][:, q], "wk": full["wk"][:, k], "wv": full["wv"][:, k],
                "wo": full["wo"][q]}
        total = total + tw._attention(jax.tree_util.tree_map(jnp.asarray, part), x, spec)
    np.testing.assert_allclose(total, whole, atol=2e-5 * np.abs(whole).max())


def test_the_64_expert_shares_of_a_latent_moe_layer_add_up_to_the_uncut_layer():
    """128 experts top-22 over 64 ranks of 2: the ranks' routed parts, summed
    in the latent space, with the router, both latent projections and the
    shared expert counted once, are the reference's layer with every expert
    held; one rank's whole layer is the reference's with that share."""
    rng = np.random.default_rng(13)
    d, lat, f, fs, e, held, k = 32, 16, 12, 24, 128, 2, 22
    p = {"router": 0.5 * rng.normal(size=(d, e)), "bias": 0.2 * rng.normal(size=e),
         "w_lat1": 0.3 * rng.normal(size=(d, lat)), "w_lat2": 0.3 * rng.normal(size=(lat, d)),
         "w_up": 0.3 * rng.normal(size=(e, lat, f)), "w_down": 0.3 * rng.normal(size=(e, f, lat)),
         "ws_up": 0.3 * rng.normal(size=(d, fs)), "ws_down": 0.3 * rng.normal(size=(fs, d))}
    p = {name: jnp.asarray(a, jnp.float32) for name, a in p.items()}
    cfg = dict(num_experts_per_tok=k, routed_scaling_factor=5, norm_topk_prob=True)
    x = jnp.asarray(rng.normal(size=(40, d)), jnp.float32)
    whole = ref.moe_mixer(p, x, cfg, 0)
    weights, experts = moe.route(x, p["router"], k, True, bias=p["bias"], scale=5.0)
    part = jax.jit(lambda lo, up, down: moe.held_experts_ffn(x @ p["w_lat1"], weights, experts,
                                                             up, down, lo, act="relu2"))
    routed, pairs = 0.0, 0
    for r in range(e // held):
        sl = slice(held * r, held * (r + 1))
        y, counters = part(jnp.int32(held * r), p["w_up"][sl], p["w_down"][sl])
        assert int(counters["dropped"]) == 0
        routed, pairs = routed + y, pairs + int(counters["pairs"].sum())
    assert pairs == 40 * k                                    # every pair lands on exactly one rank
    total = routed @ p["w_lat2"] + jnp.square(jax.nn.relu(x @ p["ws_up"])) @ p["ws_down"]
    np.testing.assert_allclose(total, whole, atol=2e-5 * np.abs(whole).max())
    spec = _spec(hidden_size=d, n_routed_experts=held, expert_parallel_size=e // held,
                 expert_parallel_index=3, num_experts_per_tok=k, moe_latent_size=lat,
                 moe_intermediate_size=f, moe_shared_expert_intermediate_size=fs,
                 routed_scaling_factor=5)
    mine = {**p, "w_up": p["w_up"][6:8], "w_down": p["w_down"][6:8]}
    got, _ = tw._latent_moe(mine, x[None], spec)
    np.testing.assert_allclose(got[0], ref.moe_mixer(mine, x, cfg, 6), atol=2e-5 * np.abs(whole).max())


def test_top_22_routing_selects_by_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(14)
    x = rng.normal(size=(30, 16)).astype(np.float32)
    w = (0.5 * rng.normal(size=(16, 64))).astype(np.float32)
    bias = (0.5 * rng.normal(size=64)).astype(np.float32)
    weights, experts = moe.route(jnp.asarray(x), jnp.asarray(w), 22, True, bias=jnp.asarray(bias),
                                 scale=5.0)
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ w)))
    by_bias = np.argsort(-(s + bias), axis=1, kind="stable")[:, :22]
    by_score = np.argsort(-s, axis=1, kind="stable")[:, :22]
    assert (np.sort(np.asarray(experts), 1) == np.sort(by_bias, 1)).all()
    assert (np.sort(by_bias, 1) != np.sort(by_score, 1)).any()     # the bias changed a choice
    chosen = np.take_along_axis(s, np.asarray(experts), 1)
    np.testing.assert_allclose(weights, 5.0 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    # the bias takes no gradient; the scores do
    f = lambda w_, b_: jnp.sum(moe.route(jnp.asarray(x), w_, 22, True, bias=b_, scale=5.0)[0] ** 2)
    gw, gb = jax.grad(f, argnums=(0, 1))(jnp.asarray(w), jnp.asarray(bias))
    assert not np.asarray(gb).any() and np.asarray(gw).any()


def test_no_pair_dropped_when_every_token_chooses_every_held_expert(monkeypatch):
    """k = 6 over 16 experts with 4 held, all four among every token's
    choices: every chunk of the walk runs, here four of 64 rows (48 tokens
    rounded up to a row tile of 32), three of them full."""
    monkeypatch.setattr(moe, "ROW_TILE", 32)
    rng = np.random.default_rng(15)
    x = jnp.asarray(rng.normal(size=(48, 16)), jnp.float32)
    bias = np.zeros(16, np.float32)
    bias[:4] = 10.0                                           # held experts win the selection
    router = jnp.asarray(0.3 * rng.normal(size=(16, 16)), jnp.float32)
    w_up = jnp.asarray(0.3 * rng.normal(size=(4, 16, 12)), jnp.float32)
    w_down = jnp.asarray(0.3 * rng.normal(size=(4, 12, 16)), jnp.float32)
    weights, experts = moe.route(x, router, 6, True, bias=jnp.asarray(bias), scale=2.0)
    assert (np.sort(np.asarray(experts), 1)[:, :4] == np.arange(4)).all()
    y, counters = moe.held_experts_ffn(x, weights, experts, w_up, w_down, 0, act="relu2")
    assert np.asarray(counters["pairs"]).tolist() == [48] * 4 and int(counters["dropped"]) == 0
    assert int(counters["rows"]) == 3 * 64
    eye = jnp.eye(16, dtype=jnp.float32)
    zero = jnp.zeros((16, 1), jnp.float32)
    want = ref.moe_mixer({"router": router, "bias": jnp.asarray(bias), "w_lat1": eye, "w_lat2": eye,
                          "w_up": w_up, "w_down": w_down, "ws_up": zero, "ws_down": zero.T}, x,
                         dict(num_experts_per_tok=6, routed_scaling_factor=2.0), 0)
    np.testing.assert_allclose(y, want, atol=1e-5)
    # and its gradients flow through the same buffer
    g = jax.grad(lambda a: jnp.sum(moe.held_experts_ffn(x, weights, experts, a, w_down, 0,
                                                        act="relu2")[0] ** 2))(w_up)
    assert np.isfinite(np.asarray(g)).all() and np.asarray(g).any()


@pytest.mark.parametrize("load", moe_loads.LOADS)
def test_chunk_walk_matches_the_reference_mixer_and_its_gradients(load, monkeypatch):
    """16 tokens, top-6 of 16 with a selection bias, experts 0 .. 3 held,
    relu^2: a buffer of four chunks of 16 rows (k > held; no rounding up to
    the kernel's row tile at this size).  Output and the gradients of x, the
    router and both matrices against the plain mixer's autodiff; the walk
    runs the chunks the routed pairs fill, no more."""
    monkeypatch.setattr(moe, "ROW_TILE", 1)
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    eye, zero = jnp.eye(32, dtype=jnp.float32), jnp.zeros((32, 1), jnp.float32)
    p = {"router": jnp.asarray(moe_loads.router_to(x, moe_loads.picks(load, 6, 16), 16)),
         "w_up": jnp.asarray(0.3 * rng.normal(size=(4, 32, 12)), jnp.float32),
         "w_down": jnp.asarray(0.3 * rng.normal(size=(4, 12, 32)), jnp.float32)}
    fixed = {"bias": jnp.asarray(0.05 * rng.normal(size=16), jnp.float32), "w_lat1": eye,
             "w_lat2": eye, "ws_up": zero, "ws_down": zero.T}
    cfg = dict(num_experts_per_tok=6, routed_scaling_factor=2.0)
    g = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)

    def mine(x, p):
        weights, experts = moe.route(x, p["router"], 6, True, bias=fixed["bias"], scale=2.0)
        y, counters = moe.held_experts_ffn(x, weights, experts, p["w_up"], p["w_down"], 0,
                                           act="relu2")
        return jnp.sum(y * g), (y, counters)
    theirs = lambda x, p: ref.moe_mixer({**p, **fixed}, x, cfg, 0)
    (_, (y, counters)), got = jax.value_and_grad(mine, argnums=(0, 1), has_aux=True)(x, p)
    want = jax.grad(lambda x, p: jnp.sum(theirs(x, p) * g), argnums=(0, 1))(x, p)
    n_here = moe_loads.routed(load, 6)
    assert int(counters["pairs"].sum()) == n_here and int(counters["dropped"]) == 0
    assert int(counters["rows"]) == -(-n_here // 16) * 16
    np.testing.assert_allclose(y, theirs(x, p), atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(float(jnp.abs(b).max()), 1.0))
        assert n_here or not np.asarray(a).any()


# ---------------------------------------------------------- scores, the file
def test_eval_score_is_one_causal_forward_and_survives_the_file(case, tmp_path):
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
    assert again.spec == c["spec"] and again.spec.tower == "nemotron_h"
    assert sorted(towers.flat_names(again.params)) == LEAVES
    assert again.compute(c["bins"]).tobytes() == model.compute(c["bins"]).tobytes()


# ------------------------------------------------------- config, declarations
@pytest.mark.parametrize("over,message", [
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act must be 'relu2'"),
    (dict(mamba_hidden_act="gelu"), "mamba_hidden_act must be 'silu'"),
    (dict(n_group=8), "n_group must be 1"),
    (dict(topk_group=4), "topk_group must be 1"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings must be False"),
    (dict(use_bias=True), "use_bias must be False"),
    (dict(model_type="sdar_moe"), "model_type must be 'nemotron_h'"),
    (dict(foo=1), "unknown TowerParams key 'foo'"),
    (dict(hybrid_override_pattern="EM-"), "hybrid_override_pattern holds '-'"),
    (dict(hybrid_override_pattern="EM"), "hybrid_override_pattern has 2 layers"),
    (dict(mtp_hybrid_override_pattern="ME"), "holds no Mamba-2 layer"),
    (dict(num_nextn_predict_layers=0), "num_nextn_predict_layers is 0 or 1"),
    (dict(num_experts_per_tok=9), "exceeds the router's 8 experts"),
    (dict(expert_parallel_index=2), "expert_parallel_index 2 is not a rank of 2"),
    (dict(tensor_parallel_index=5), "tensor_parallel_index 5 is not a rank of 2"),
    (dict(n_groups=3), "mamba_num_heads must be a multiple of n_groups"),
    (dict(expand=2), "expand 2 x hidden_size is not"),
])
def test_tower_params_refusals(over, message):
    with pytest.raises(ShifuError, match=message.replace("(", r"\(")) as e:
        _spec(**over)
    assert "[" in str(e.value)                                 # a coded error


def test_tower_params_problems_come_in_one_error_and_required_keys_are_named():
    with pytest.raises(ShifuError) as e:
        _spec(use_bias=True, foo=1, mlp_hidden_act="silu")
    msg = str(e.value)
    assert "use_bias must be False" in msg and "unknown TowerParams key 'foo'" in msg \
        and "mlp_hidden_act must be 'relu2'" in msg
    missing = {k: v for k, v in TOY.items() if k != "moe_latent_size"}
    with pytest.raises(ShifuError, match="TowerParams.moe_latent_size is required"):
        tw.spec_from_params(missing, list(range(8)), COL_BINS, [])
    assert _spec(expand=1).mamba_num_heads == 4               # 1 x 64 = 4 x 8 x 2 ranks


def test_towers_are_found_by_name_and_the_rules_know_both():
    from shifu_tpu.config.meta import validate_train_params
    from shifu_tpu.config.model_config import Algorithm
    from shifu_tpu.models import tower_sdar
    assert towers.module("nemotron_h") is tw and towers.module("sdar_moe") is tower_sdar
    with pytest.raises(ShifuError, match="'resnet' is not one of"):
        towers.module("resnet")
    ok = {"Tower": "nemotron_h", "TowerParams": dict(TOY), "MiniBatchs": 8}
    assert validate_train_params(ok, Algorithm.TENSORFLOW) == []
    assert validate_train_params({"Tower": "resnet"}, Algorithm.TENSORFLOW)
    for mod in (tw, tower_sdar):
        assert mod.SCOPES[-1] == "tower/opt" and callable(mod.train_loss) and callable(mod.tag_logits)
        for name in mod.OBS_COUNTERS.values():
            assert obs.manifest.is_declared(name), name


# ------------------------------------------------------------------- the CLI
def _tower_set(mdir, epochs=3, **params):
    mc = ModelConfig.load(os.path.join(mdir, "ModelConfig.json"))
    mc.train.algorithm = "TENSORFLOW"
    mc.train.numTrainEpochs = epochs
    mc.train.params = {"Tower": "nemotron_h", "MiniBatchs": 512, "LearningRate": 0.003,
                       "Propagation": "ADAM",
                       "TowerParams": {**TOY, "vocab_size": 4200, "max_position_embeddings": 64},
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
    assert spec.tower == "nemotron_h" and spec.n_features == len(spec.column_bins)
    lines = _progress(prepared_set)
    assert len(lines) == 3 and lines[0].startswith("Tower Epoch #1 Train Error: ")
    first, last = (float(l.split("Train Error: ")[1].split()[0]) for l in (lines[0], lines[-1]))
    assert last < first
    assert main(["--dir", prepared_set, "eval", "-run"]) == 0
    with open(os.path.join(prepared_set, "evals", "Eval1", "EvalScore")) as f:
        col = f.readline().strip().split("|").index("mean")
        got = np.sort([float(line.split("|")[col]) for line in f])
    bins = Shards.open(os.path.join(prepared_set, "tmp", "CleanedData")).load_all()["bins"]
    cfg = {**TOY, "vocab_size": 4200}
    d = ref.tag_logit_difference(params, bins, cfg, spec.expert_lo, spec.column_bins, 256)
    want = np.sort(1000.0 / (1.0 + np.exp(-d)))
    assert len(got) == len(want)
    np.testing.assert_allclose(got, want, atol=2e-3)


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

    want, got = (towers.flat_names(_load(d)[1]) for d in (control, prepared_set))
    for name in LEAVES:
        assert got[name].tobytes() == want[name].tobytes(), name
    assert _progress(prepared_set)[-1] == _progress(control)[-1]
    assert len(_progress(prepared_set)) == 2                      # epochs 2 and 3 again


def test_telemetry_counts_the_towers_own_counters(prepared_set):
    from shifu_tpu.cli import main
    _tower_set(prepared_set, epochs=1)
    assert main(["--dir", prepared_set, "train", "--telemetry"]) == 0
    found, scopes = {}, None
    import json
    with open(os.path.join(prepared_set, "telemetry", "trace.jsonl")) as f:
        for line in f:
            doc = json.loads(line)
            if str(doc.get("name", "")).startswith("tower.") and "value" in doc:
                found[doc["name"]] = found.get(doc["name"], 0.0) + float(doc["value"])
            if doc.get("name") == "op_scopes":
                scopes = doc["attrs"]["scopes"]
    assert found["tower.dropped_pairs"] == 0 and found["tower.positions"] > 0
    assert found["tower.mtp_loss_sum"] > 0 and found["tower.ssm_chunks"] > 0
    assert found["tower.moe_pairs_max_expert"] >= found["tower.moe_pairs_mean_expert"] > 0
    assert set(scopes) == set(tw.SCOPES)
    assert tw.SCOPES[0] == "tower/mtp" and tw.SCOPES.index("tower/trunk") == len(tw.SCOPES) - 3
    for scope in tw.SCOPES:             # the catch-all and the step's own among them
        assert scopes[scope], scope


@pytest.mark.parametrize("step", [["export"], ["export", "-t", "spec"], ["serve", "--selfcheck", "2"],
                                  ["combo", "new", "-alg", "NN:GBT"]])
def test_cli_steps_without_a_tower_path_refuse_with_a_coded_error(prepared_set, step, capsys):
    from shifu_tpu.cli import main
    _tower_set(prepared_set)
    assert main(["--dir", prepared_set] + step) == 1
    err = capsys.readouterr().err
    assert "[1052]" in err and f"`{step[0]}` cannot take a tower" in err and "nemotron_h" in err
