"""The ``deepseek_v3`` tower: multi-head latent attention (MLA), a leading
dense SwiGLU layer, then sigmoid-routed experts beside an ungated shared
SwiGLU, a selection bias that moves and a sequence-wise balance loss; trained
as a causal next-token model over rows packed into sequences (``algorithm:
TENSORFLOW``, ``train#params.Tower: "deepseek_v3"``,
``train#params.RowsPerSequence``).

Architecture as config.json of moonshotai/Moonlight-16B-A3B states it and
DeepSeek-V3 (arXiv:2412.19437, section 2.1) completes it (no bias anywhere;
``q_lora_rank`` null: q has no down-projection)::

    a   = RMSNorm_in(h)
    q_i = a W_Q,i                        [q_N,i (qk_nope) ; q_R,i (qk_rope)]
    [c ; k_R'] = a W_DKV                 [kv_lora_rank ; qk_rope]
    c   = RMSNorm_kv(c)
    k_R = RoPE(k_R')                     one rotary key a position, shared by every head
    [k_N,i ; v_i] = c W_UKV,i            [qk_nope ; v_head_dim] a head
    q_i = [q_N,i ; RoPE(q_R,i)]   k_i = [k_N,i ; k_R]
    o_i = softmax_{j <= i}(q_i k_i^T / sqrt(qk_nope + qk_rope)) v_i
    h   = h + [o_1 .. o_H] W_O
    m   = RMSNorm_post(h)
    f   = SwiGLU(m)                                                  (layer < first_k_dense_replace)
        | SwiGLU_shared(m) + sum_{e in top-k of (s + b)} g_e SwiGLU_e(m),
          s = sigmoid(m W_r),  g = routed_scaling_factor x s[chosen] / sum s[chosen]
    h   = h + f
    logits = RMSNorm_final(h) W_head
    L_bal = aux_loss_alpha x sum_e f_e P_e   a packed sequence and a MoE layer, over its
          T positions that are not PAD:  f_e = E / (k T) #{t: e chosen},  P_e = 1/T sum_t s_e,t / sum_j s_j,t

RoPE rotates pairs of interleaved channels (2i, 2i + 1), as DeepSeek-V3's
published modelling code does: the channels are read even ones first, then
rotated half against half, so q_R and k_R leave in that order (the score
does not depend on it).  The shared experts (``n_shared_experts`` of
``moe_intermediate_size``) run as one SwiGLU of their summed width.

MLA's q and k leave their projections already in the kernels' lane layout
(:func:`..ops.attention.lane_width`: ``[q_N | q_R | zeros]`` a head, one
write each, the shared k_R put beside every head's k_N in the same pass);
``ops/attention.blocked_attention`` takes v at its own width and scales by
``1 / sqrt(qk_nope + qk_rope)``.  The experts are ``ops/moe.py``; the router's
full scores for the balance loss come from the same product
(``moe.route_scores``).  The rank computes its *share*: ``n_routed_experts``
experts from ``expert_lo`` on (one of ``expert_parallel_size`` ranks; the
router keeps every expert's output and the bias every expert's entry), a
slice of the vocabulary; attention, the shared experts, the router and the
norms are whole on every rank.

The selection bias, the packing, the next-token loss and the score are
``afmoe``'s (:mod:`.tower_afmoe`: ``after_step`` — ``ops/moe.bias_step``'s
rule once a step —, ``packed_loss``, ``row_tag_logits``); the balance loss is
added to the next-token loss, averaged over the microbatch's sequences.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from ..config.errors import ErrorCode, ShifuError
from ..ops import attention, moe
from .tower_afmoe import _rms, _swiglu, after_step, packed_loss, row_tag_logits  # noqa: F401
from .towers import SPECIALS, RowTokens, nest_names

# the step's named scopes, most specific first: device ops carry them
# ``tower/trunk`` is the catch-all around the layer loop: after every scope that
# occurs inside it (the first scope an op's name contains takes the op)
SCOPES = ("tower/attn/latent", "tower/attn/full", "tower/attn/proj", "tower/mlp",
          "tower/moe/route", "tower/moe/experts", "tower/moe/shared", "tower/head", "tower/input",
          "tower/embed", "tower/trunk", "tower/acc", "tower/opt")
OBS_COUNTERS = {"attn_key_blocks": "tower.attn_key_blocks",
                "attn_key_blocks_dense": "tower.attn_key_blocks_dense",
                "pad_positions": "tower.pad_positions",
                "sequence_positions": "tower.sequence_positions",
                "router_bias_absmax": "tower.router_bias_absmax",
                "balance_loss_sum": "tower.moe_balance_loss_sum"}

# TowerParams: config.json's keys.  Read: the shapes.  Checked: the keys whose
# other values would be another architecture.  The rest says nothing here.
_READ = ("hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
         "num_key_value_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "intermediate_size", "moe_intermediate_size", "n_shared_experts",
         "n_routed_experts", "num_experts_per_tok", "vocab_size", "max_position_embeddings")
_DEFAULTS = {"rms_norm_eps": 1e-6, "rope_theta": 10000.0, "norm_topk_prob": True,
             "routed_scaling_factor": 1.0, "aux_loss_alpha": 1e-4, "attention_block": attention.BLOCK,
             "expert_parallel_size": 1, "expert_parallel_index": 0}
_MUST_BE = {"model_type": "deepseek_v3", "hidden_act": "silu", "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
            "q_lora_rank": None, "attention_bias": False, "seq_aux": True, "rope_scaling": None,
            "num_nextn_predict_layers": 0, "tie_word_embeddings": False}
_INERT = ("ep_size",)               # the deployment's own: expert_parallel_size says it here


@dataclass
class TowerSpec(RowTokens):
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int      # the leading dense layers
    num_attention_heads: int
    kv_lora_rank: int               # the compressed key-value's width
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int          # the dense layers' width
    moe_intermediate_size: int      # an expert's
    n_shared_experts: int
    num_experts: int                # the router's width: ALL routed experts
    experts_held: int               # this rank's
    expert_lo: int                  # its first
    num_experts_per_tok: int
    vocab_size: int                 # this rank's slice
    max_position_embeddings: int
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    aux_loss_alpha: float = 1e-4          # the balance loss's weight
    load_balance_coeff: float = 0.001     # the bias rule's step: config.json names no rate
    attention_block: int = attention.BLOCK
    column_nums: List[int] = field(default_factory=list)
    column_bins: List[int] = field(default_factory=list)   # value bins a column
    feature_names: List[str] = field(default_factory=list)
    tower: str = "deepseek_v3"
    kind: str = "tower"

    block_length = 1                # one token a column, then the tag

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


def spec_from_params(tower_params: Dict[str, Any], column_nums: List[int],
                     column_bins: List[int], feature_names: List[str]) -> TowerSpec:
    """``train#params.TowerParams`` (config.json's keys, and the share) ->
    spec; every problem named in one coded error."""
    p = dict(tower_params or {})
    problems = [f"TowerParams.{k} is required" for k in _READ if k not in p]
    for k, want in _MUST_BE.items():
        if k in p and p[k] != want:
            problems.append(f"TowerParams.{k} must be {want!r}, got {p[k]!r}")
    known = set(_READ) | set(_DEFAULTS) | set(_MUST_BE) | set(_INERT)
    problems += [f"unknown TowerParams key {k!r}" for k in sorted(set(p) - known)]
    if problems:
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION, "; ".join(problems))
    p = {**_DEFAULTS, **p}
    held, size, index = (int(p[k]) for k in ("n_routed_experts", "expert_parallel_size",
                                             "expert_parallel_index"))
    ints = [k for k in _READ if k not in ("n_routed_experts", "num_key_value_heads")]
    spec = TowerSpec(
        **{k: int(p[k]) for k in ints}, num_experts=held * size, experts_held=held,
        expert_lo=held * index,
        **{k: float(p[k]) for k in ("rms_norm_eps", "rope_theta", "routed_scaling_factor",
                                    "aux_loss_alpha")},
        norm_topk_prob=bool(p["norm_topk_prob"]), attention_block=int(p["attention_block"]),
        column_nums=list(column_nums), column_bins=[int(b) for b in column_bins],
        feature_names=list(feature_names))
    if not 0 <= index < size:
        problems.append(f"expert_parallel_index {index} is not a rank of {size}")
    if not 0 <= spec.first_k_dense_replace < spec.num_hidden_layers:
        problems.append(f"first_k_dense_replace {spec.first_k_dense_replace} leaves no MoE layer "
                        f"of {spec.num_hidden_layers}")
    if spec.num_experts_per_tok > spec.num_experts:
        problems.append(f"num_experts_per_tok {spec.num_experts_per_tok} exceeds the "
                        f"router's {spec.num_experts} experts")
    if int(p["num_key_value_heads"]) != spec.num_attention_heads:
        problems.append(f"num_key_value_heads {p['num_key_value_heads']} must equal "
                        f"num_attention_heads {spec.num_attention_heads}: under latent attention "
                        "every head has its own k and v")
    if spec.qk_rope_head_dim % 2:
        problems.append(f"qk_rope_head_dim {spec.qk_rope_head_dim} must be even (rotated in pairs)")
    problems += spec.token_problems()
    if problems:
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION, "; ".join(problems))
    return spec


def sequence_block(spec: TowerSpec) -> int:
    """Sequences are padded to whole blocks of this many positions."""
    return spec.attention_block


# ---------------------------------------------------------------- parameters
def _layer_shapes(layer: int, spec: TowerSpec) -> Dict[str, tuple]:
    d, h, r = spec.hidden_size, spec.num_attention_heads, spec.kv_lora_rank
    out = {"norm_in": (d,), "norm_post": (d,), "wq": (d, h * spec.qk_head_dim),
           "w_dkv": (d, r + spec.qk_rope_head_dim), "norm_kv": (r,),
           "w_ukv": (r, h * (spec.qk_nope_head_dim + spec.v_head_dim)),
           "wo": (h * spec.v_head_dim, d)}
    if layer < spec.first_k_dense_replace:
        f = spec.intermediate_size
        return {**out, "w_gate_up": (d, 2 * f), "w_down": (f, d)}
    f, held = spec.moe_intermediate_size, spec.experts_held
    shared = spec.n_shared_experts * f
    return {**out, "router": (d, spec.num_experts), "bias": (spec.num_experts,),
            "ws_gate_up": (d, 2 * shared), "ws_down": (shared, d),
            "we_gate_up": (held, d, 2 * f), "we_down": (held, f, d)}


def param_shapes(spec: TowerSpec) -> Dict[str, tuple]:
    """Flat name -> shape: ``blocks.<nn>.<array>`` a layer."""
    d, v = spec.hidden_size, spec.vocab_size
    out = {"embed": (v, d), "head": (d, v), "norm_f": (d,)}
    for i in range(spec.num_hidden_layers):
        out.update({f"blocks.{i:02d}.{k}": s for k, s in _layer_shapes(i, spec).items()})
    return out


def _draw(key, name: str, shape):
    leaf = name.rsplit(".", 1)[-1]
    if leaf.startswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if leaf == "bias":
        return jnp.zeros(shape, jnp.float32)
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


def init_params(key, spec: TowerSpec) -> Dict[str, Any]:
    """Array ``i`` of the flat names in sorted order is drawn from
    ``fold_in(key, i)``: normal(0, 0.02) matrices, unit norm weights, a zero
    selection bias."""
    shapes = param_shapes(spec)
    return nest_names({name: _draw(jax.random.fold_in(key, i), name, shapes[name])
                       for i, name in enumerate(sorted(shapes))})


# -------------------------------------------------------------------- layers
def _rotary(seq: int, channels: int, theta: float):
    """(cos, sin) [seq, channels / 2]: pair i turns by position x theta^(-2i / channels)."""
    inv = 1.0 / (theta ** (jnp.arange(0, channels, 2, dtype=jnp.float32) / channels))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rotate_pairs(x, cos, sin):
    """x [n, S, ..., c] rotated pair by pair, (x_2i, x_2i+1) by the angle of
    pair i: the even channels' results first, then the odd ones'."""
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin], -1)


def _attention(p, a, spec: TowerSpec):
    n, s, _ = a.shape
    h, nope, rope, dv = (spec.num_attention_heads, spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                         spec.v_head_dim)
    qk = nope + rope
    width = attention.lane_width(qk)               # the kernels' channels a head: zeros after qk
    with jax.named_scope("tower/attn/latent"):
        cos, sin = _rotary(s, rope, spec.rope_theta)
        zeros = [jnp.zeros((n, s, h, width - qk), jnp.float32)] if width > qk else []
        ckr = a @ p["w_dkv"]
        c = _rms(ckr[..., :spec.kv_lora_rank], p["norm_kv"], spec.rms_norm_eps)
        kv = (c @ p["w_ukv"]).reshape(n, s, h, nope + dv)
        k_r = _rotate_pairs(ckr[..., spec.kv_lora_rank:], cos, sin)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, :, None], (n, s, h, rope)),
                             *zeros], -1)
        v = kv[..., nope:]
    with jax.named_scope("tower/attn/proj"):
        q = (a @ p["wq"]).reshape(n, s, h, qk)
        q = jnp.concatenate([q[..., :nope], _rotate_pairs(q[..., nope:], cos, sin), *zeros], -1)
    with jax.named_scope("tower/attn/full"):
        o = attention.blocked_attention(q[:, :, :, None], k, v, None, spec.attention_block,
                                        qk_dim=qk)
    with jax.named_scope("tower/attn/proj"):
        return o.reshape(n, s, h * dv) @ p["wo"]


def _balance(scores, experts, live, spec: TowerSpec):
    """[n]: each sequence's sum_e f_e P_e over its ``live`` positions
    (``live`` [n, S] bool; scores [n S, E], experts [n S, k])."""
    n, s = live.shape
    e, k = spec.num_experts, spec.num_experts_per_tok
    on = live.reshape(n, s, 1).astype(jnp.float32)
    chosen = jnp.sum(jax.nn.one_hot(experts, e, dtype=jnp.float32), axis=1).reshape(n, s, e)
    share = (scores / jnp.sum(scores, axis=-1, keepdims=True)).reshape(n, s, e)
    t = jnp.maximum(jnp.sum(on, axis=1), 1.0)                      # [n, 1]
    f = jnp.sum(chosen * on, axis=1) * (e / k) / t
    big_p = jnp.sum(share * on, axis=1) / t
    return jnp.sum(f * big_p, axis=-1)


def _moe(p, m, live, spec: TowerSpec):
    """(the held experts' part of the layer's feed-forward output with the
    shared experts', counters with ``tokens`` [E]: the positions whose top-k
    holds each expert, held or not, and ``balance`` [n]: the sequences' sum_e
    f_e P_e, or None without ``live``)."""
    n, s, d = m.shape
    x = m.reshape(n * s, d)
    with jax.named_scope("tower/moe/route"):
        weights, experts, scores = moe.route_scores(
            x, p["router"], spec.num_experts_per_tok, spec.norm_topk_prob, bias=p["bias"],
            scale=spec.routed_scaling_factor)
        tokens = jnp.zeros(spec.num_experts, jnp.float32).at[experts.reshape(-1)].add(1.0)
        balance = None if live is None else _balance(scores, experts, live, spec)
    with jax.named_scope("tower/moe/experts"):
        y, counters = moe.held_experts_ffn(x, weights, experts, p["we_gate_up"], p["we_down"],
                                           spec.expert_lo, act="swiglu")
    with jax.named_scope("tower/moe/shared"):
        y = y + _swiglu(x, p["ws_gate_up"], p["ws_down"])
    return y.reshape(n, s, d), {**counters, "tokens": tokens, "balance": balance}


def trunk(params, spec: TowerSpec, ids, live=None):
    """ids [n, S] (S whole attention blocks) -> (the last layer's output
    [n, S, D] before ``norm_f``, [the MoE layers' counters]); ``live`` [n, S]:
    the positions the balance loss counts (None: no balance loss).  Each layer
    is recomputed in the backward pass."""
    eps = spec.rms_norm_eps

    def layer(i):
        def fn(h, p, live):
            h = h + _attention(p, _rms(h, p["norm_in"], eps), spec)
            m = _rms(h, p["norm_post"], eps)
            if i < spec.first_k_dense_replace:
                with jax.named_scope("tower/mlp"):
                    f, counters = _swiglu(m, p["w_gate_up"], p["w_down"]), None
            else:
                f, counters = _moe(p, m, live, spec)
            return h + f, counters
        return jax.checkpoint(fn)
    with jax.named_scope("tower/embed"):
        h = params["embed"][ids]
    found = []
    with jax.named_scope("tower/trunk"):
        for i, name in enumerate(sorted(params["blocks"])):
            h, counters = layer(i)(h, params["blocks"][name], live)
            if counters is not None:
                found.append(counters)
    return h, found


def causal_loss(params, spec: TowerSpec, ids, w, pad_id):
    """The microbatch's loss.  ids [n, S] packed sequences (S whole attention
    blocks), w [n, S] each position's row's weight (0: ``PAD``, or a padding
    row).  Position i's target is id_{i+1} where that is not ``PAD``.
    Returns (loss, aux): ``afmoe``'s head, loss and counters, and the balance
    loss — ``aux_loss_alpha`` x the MoE layers' sum_e f_e P_e, averaged over
    the sequences with a position that counts — added to the loss;
    ``balance_loss_sum`` is the sum over the layers and those sequences,
    unscaled."""
    live = (ids != pad_id) & (w > 0)
    h, found = trunk(params, spec, ids, live)
    # every attention layer is full causal: what it visits is a full sweep
    blocks = spec.num_attention_heads * spec.num_hidden_layers * \
        attention.visited_key_blocks(ids.shape[1], spec.attention_block)
    loss, aux = packed_loss(params, h, found, ids, w, pad_id, spec.rms_norm_eps, (blocks, blocks))
    with jax.named_scope("tower/moe/route"):
        balance = jnp.sum(sum(c["balance"] for c in found))
        sequences = jnp.maximum(jnp.sum(jnp.any(live, axis=1)).astype(jnp.float32), 1.0)
        extra = spec.aux_loss_alpha * balance / sequences
    with jax.named_scope("tower/head"):
        return loss + extra, {**aux, "loss_sum": aux["loss_sum"] + extra * aux["positions"],
                              "balance_loss_sum": balance}


def train_loss(params, spec: TowerSpec, ids, w, key, specials):
    """The trainer's loss of one microbatch of packed sequences
    (``towers.pack_rows``); nothing is drawn: ``key`` goes unused."""
    with jax.named_scope("tower/input"):
        pad_id = specials[SPECIALS.index("PAD")]
    return causal_loss(params, spec, ids, w, pad_id)


def counter_shapes(spec: TowerSpec) -> Dict[str, tuple]:
    """``aux``'s counters beside ``loss_sum`` and ``positions`` (``tokens``
    is the step's own: ``after_step`` reads it, nothing adds it up)."""
    return {**{k: () for k in OBS_COUNTERS}, "pairs": (spec.moe_layers, spec.experts_held),
            "rows": (spec.moe_layers,), "dropped": (spec.moe_layers,)}


def tag_logits(params, spec: TowerSpec, feature_ids, tag0_id, mask_id):
    """One causal forward over one row a sequence.  feature_ids [n, C] ->
    [n, 2] logits of (TAG0, TAG1) at the last feature token."""
    return row_tag_logits(trunk, params, spec, feature_ids, tag0_id, spec.rms_norm_eps)
