"""The ``lfm2_moe`` tower: gated short convolutions and full causal attention
mixed after ``layer_types``, leading dense SwiGLU layers, then sigmoid-routed
experts with a selection bias that moves; trained as a causal next-token model
over rows packed into sequences (``algorithm: TENSORFLOW``,
``train#params.Tower: "lfm2_moe"``, ``train#params.RowsPerSequence``).

Architecture as config.json of LiquidAI/LFM2-24B-A2B states it and its
published modelling code completes it (no bias anywhere; ``head_dim`` =
hidden_size / num_attention_heads)::

    h0 = Embed[ids]
    a  = RMSNorm_op(h)
    conv:            [B | C | x] = a W_in   u = B * x
                     c_t = sum_{j < L} w_j * u_{t-L+1+j}   (u before the sequence = 0; L = conv_L_cache)
                     o = (C * c) W_out
    full_attention:  q = RMSNorm_q(a Wq)   k = RMSNorm_k(a Wk)   v = a Wv   (per head)
                     rotate_half RoPE on q, k;  o = softmax(q k^T / sqrt(head_dim)) v over keys j <= i;  o Wo
    h  = h + o
    m  = RMSNorm_ffn(h)
    f  = SwiGLU(m)                                              (layer < num_dense_layers)
       | sum_{e in top-k of (s + b)} w_e SwiGLU_e(m),   s = sigmoid(m Wr),  w = s[chosen] / sum s[chosen]
    h  = h + f
    logits = RMSNorm_final(h) W_head

The convolution is ``towers.causal_conv`` (the one ``nemotron_h``'s mixer
runs); it looks back across a packed row's start as the causal mask does.
Attention is ``ops/attention.blocked_attention`` (a sequence is padded with
``PAD`` to whole blocks of ``attention_block`` positions), the experts
``ops/moe.py``.  The rank computes its *share*: ``num_experts`` experts from
``expert_lo`` on (one of ``expert_parallel_size`` ranks; the router keeps every
expert's output and the bias every expert's entry), a slice of the vocabulary.
The published code divides the chosen scores by their sum + 1e-6; ``moe.route``
adds nothing (under 1e-5 relative at four sigmoid scores).

The selection bias, the packing, the loss and the score are ``afmoe``'s
(:mod:`.tower_afmoe`: ``after_step`` — ``ops/moe.bias_step``'s rule once a
step —, ``packed_loss``, ``row_tag_logits``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from ..config.errors import ErrorCode, ShifuError
from ..ops import attention, moe
from .tower_afmoe import (_rms, _rotary, _rotate, _swiglu, after_step,  # noqa: F401  (the same rules)
                          packed_loss, row_tag_logits)
from .towers import SPECIALS, RowTokens, causal_conv, nest_names

# the step's named scopes, most specific first: device ops carry them
# ``tower/trunk`` is the catch-all around the layer loop: after every scope that
# occurs inside it (the first scope an op's name contains takes the op)
SCOPES = ("tower/conv/mix", "tower/conv/proj", "tower/attn/full", "tower/attn/proj", "tower/mlp",
          "tower/moe/route", "tower/moe/experts", "tower/head", "tower/input", "tower/embed",
          "tower/trunk", "tower/acc", "tower/opt")
OBS_COUNTERS = {"attn_key_blocks": "tower.attn_key_blocks",
                "attn_key_blocks_dense": "tower.attn_key_blocks_dense",
                "pad_positions": "tower.pad_positions",
                "sequence_positions": "tower.sequence_positions",
                "router_bias_absmax": "tower.router_bias_absmax"}
KINDS = ("conv", "full_attention")

# TowerParams: config.json's keys.  Read: the shapes.  Checked: the keys whose
# other values would be another architecture.
_READ = ("hidden_size", "num_hidden_layers", "num_dense_layers", "layer_types",
         "num_attention_heads", "num_key_value_heads", "intermediate_size",
         "moe_intermediate_size", "num_experts", "num_experts_per_tok", "conv_L_cache",
         "rope_parameters", "vocab_size", "max_position_embeddings")
_DEFAULTS = {"norm_eps": 1e-5, "norm_topk_prob": True, "routed_scaling_factor": 1.0,
             "attention_block": attention.BLOCK, "expert_parallel_size": 1, "expert_parallel_index": 0}
_MUST_BE = {"model_type": "lfm2_moe", "conv_bias": False, "use_expert_bias": True}


@dataclass
class TowerSpec(RowTokens):
    hidden_size: int
    num_hidden_layers: int
    num_dense_layers: int
    layer_types: List[str]
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int          # the dense layers' width
    moe_intermediate_size: int      # an expert's
    num_experts: int                # the router's width: ALL experts
    experts_held: int               # this rank's
    expert_lo: int                  # its first
    num_experts_per_tok: int
    conv_L_cache: int               # the convolution's taps
    vocab_size: int                 # this rank's slice
    max_position_embeddings: int
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    load_balance_coeff: float = 0.001     # the bias rule's step: config.json names no rate
    attention_block: int = attention.BLOCK
    column_nums: List[int] = field(default_factory=list)
    column_bins: List[int] = field(default_factory=list)   # value bins a column
    feature_names: List[str] = field(default_factory=list)
    tower: str = "lfm2_moe"
    kind: str = "tower"

    block_length = 1                # one token a column, then the tag

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers


def spec_from_params(tower_params: Dict[str, Any], column_nums: List[int],
                     column_bins: List[int], feature_names: List[str]) -> TowerSpec:
    """``train#params.TowerParams`` (config.json's keys, and the share) ->
    spec; every problem named in one coded error."""
    p = dict(tower_params or {})
    problems = [f"TowerParams.{k} is required" for k in _READ if k not in p]
    for k, want in _MUST_BE.items():
        if k in p and p[k] != want:
            problems.append(f"TowerParams.{k} must be {want!r}, got {p[k]!r}")
    known = set(_READ) | set(_DEFAULTS) | set(_MUST_BE)
    problems += [f"unknown TowerParams key {k!r}" for k in sorted(set(p) - known)]
    bad = sorted(set(map(str, p.get("layer_types") or ())) - set(KINDS))
    if bad:
        problems.append(f"TowerParams.layer_types holds {bad}: a layer is one of {list(KINDS)}")
    rope = p.get("rope_parameters")
    if "rope_parameters" in p and not (isinstance(rope, dict) and "rope_theta" in rope and
                                       rope.get("rope_type", "default") == "default"):
        problems.append(f"TowerParams.rope_parameters must give rope_theta with rope_type "
                        f"'default', got {rope!r}")
    if problems:
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION, "; ".join(problems))
    p = {**_DEFAULTS, **p}
    held, size, index = (int(p[k]) for k in ("num_experts", "expert_parallel_size",
                                             "expert_parallel_index"))
    ints = [k for k in _READ if k not in ("layer_types", "num_experts", "rope_parameters")]
    spec = TowerSpec(
        **{k: int(p[k]) for k in ints}, layer_types=[str(t) for t in p["layer_types"]],
        num_experts=held * size, experts_held=held, expert_lo=held * index,
        rope_theta=float(rope["rope_theta"]),
        **{k: float(p[k]) for k in ("norm_eps", "routed_scaling_factor")},
        norm_topk_prob=bool(p["norm_topk_prob"]), attention_block=int(p["attention_block"]),
        column_nums=list(column_nums), column_bins=[int(b) for b in column_bins],
        feature_names=list(feature_names))
    if not 0 <= index < size:
        problems.append(f"expert_parallel_index {index} is not a rank of {size}")
    if len(spec.layer_types) != spec.num_hidden_layers:
        problems.append(f"layer_types has {len(spec.layer_types)} entries, num_hidden_layers "
                        f"is {spec.num_hidden_layers}")
    if not 0 <= spec.num_dense_layers < spec.num_hidden_layers:
        problems.append(f"num_dense_layers {spec.num_dense_layers} leaves no MoE layer of "
                        f"{spec.num_hidden_layers}")
    if spec.num_experts_per_tok > spec.num_experts:
        problems.append(f"num_experts_per_tok {spec.num_experts_per_tok} exceeds the "
                        f"router's {spec.num_experts} experts")
    if spec.num_attention_heads % spec.num_key_value_heads:
        problems.append("num_attention_heads must be a multiple of num_key_value_heads")
    if spec.hidden_size % spec.num_attention_heads or spec.head_dim % 2:
        problems.append(f"hidden_size {spec.hidden_size} must be an even head_dim a head of "
                        f"{spec.num_attention_heads} (rotate_half)")
    if spec.conv_L_cache < 1:
        problems.append(f"conv_L_cache {spec.conv_L_cache} leaves the convolution no tap")
    problems += spec.token_problems()
    if problems:
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION, "; ".join(problems))
    return spec


def sequence_block(spec: TowerSpec) -> int:
    """Sequences are padded to whole blocks of this many positions."""
    return spec.attention_block


# ---------------------------------------------------------------- parameters
def _layer_shapes(layer: int, spec: TowerSpec) -> Dict[str, tuple]:
    d, hd = spec.hidden_size, spec.head_dim
    h, kv = spec.num_attention_heads, spec.num_key_value_heads
    out = {"norm_op": (d,), "norm_ffn": (d,)}
    if spec.layer_types[layer] == KINDS[0]:
        out.update(conv_in=(d, 3 * d), conv_w=(spec.conv_L_cache, d), conv_out=(d, d))
    else:
        out.update(norm_q=(hd,), norm_k=(hd,), wq=(d, h * hd), wk=(d, kv * hd), wv=(d, kv * hd),
                   wo=(h * hd, d))
    if layer < spec.num_dense_layers:
        f = spec.intermediate_size
        return {**out, "w_gate_up": (d, 2 * f), "w_down": (f, d)}
    f, held = spec.moe_intermediate_size, spec.experts_held
    return {**out, "router": (d, spec.num_experts), "bias": (spec.num_experts,),
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
    if leaf == "conv_w":                    # a depthwise Conv1d's default: fan-in = the taps
        bound = 1.0 / shape[0] ** 0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


def init_params(key, spec: TowerSpec) -> Dict[str, Any]:
    """Array ``i`` of the flat names in sorted order is drawn from
    ``fold_in(key, i)``: normal(0, 0.02) matrices, the taps U(+-1/sqrt(L)),
    unit norm weights, a zero selection bias."""
    shapes = param_shapes(spec)
    return nest_names({name: _draw(jax.random.fold_in(key, i), name, shapes[name])
                       for i, name in enumerate(sorted(shapes))})


# -------------------------------------------------------------------- layers
def _conv(p, a, spec: TowerSpec):
    d = spec.hidden_size
    with jax.named_scope("tower/conv/proj"):
        bcx = a @ p["conv_in"]
    with jax.named_scope("tower/conv/mix"):
        y = bcx[..., d:2 * d] * causal_conv(bcx[..., :d] * bcx[..., 2 * d:], p["conv_w"])
    with jax.named_scope("tower/conv/proj"):
        return y @ p["conv_out"]


def _attention(p, a, spec: TowerSpec):
    n, s, _ = a.shape
    h, kv, hd = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim
    eps = spec.norm_eps
    with jax.named_scope("tower/attn/proj"):
        q = _rms((a @ p["wq"]).reshape(n, s, kv, h // kv, hd), p["norm_q"], eps)
        k = _rms((a @ p["wk"]).reshape(n, s, kv, hd), p["norm_k"], eps)
        v = (a @ p["wv"]).reshape(n, s, kv, hd)
        tables = _rotary(s, hd, spec.rope_theta)
        q, k = _rotate(q, *tables), _rotate(k, *tables)
    with jax.named_scope("tower/attn/full"):
        o = attention.blocked_attention(q, k, v, None, spec.attention_block)
    with jax.named_scope("tower/attn/proj"):
        return o.reshape(n, s, h * hd) @ p["wo"]


def _moe(p, m, spec: TowerSpec):
    """(the held experts' part of the layer's output, counters with
    ``tokens`` [E]: the positions whose top-k holds each expert, held or not)."""
    n, s, d = m.shape
    x = m.reshape(n * s, d)
    with jax.named_scope("tower/moe/route"):
        weights, experts = moe.route(x, p["router"], spec.num_experts_per_tok, spec.norm_topk_prob,
                                     bias=p["bias"], scale=spec.routed_scaling_factor)
        tokens = jnp.zeros(spec.num_experts, jnp.float32).at[experts.reshape(-1)].add(1.0)
    with jax.named_scope("tower/moe/experts"):
        y, counters = moe.held_experts_ffn(x, weights, experts, p["we_gate_up"], p["we_down"],
                                           spec.expert_lo, act="swiglu")
    return y.reshape(n, s, d), {**counters, "tokens": tokens}


def trunk(params, spec: TowerSpec, ids):
    """ids [n, S] (S whole attention blocks) -> (the last layer's output
    [n, S, D] before ``norm_f``, [the MoE layers' counters]); each layer is
    recomputed in the backward pass."""
    eps = spec.norm_eps

    def layer(i):
        op = _conv if spec.layer_types[i] == KINDS[0] else _attention

        def fn(h, p):
            h = h + op(p, _rms(h, p["norm_op"], eps), spec)
            m = _rms(h, p["norm_ffn"], eps)
            if i < spec.num_dense_layers:
                with jax.named_scope("tower/mlp"):
                    f, counters = _swiglu(m, p["w_gate_up"], p["w_down"]), None
            else:
                f, counters = _moe(p, m, spec)
            return h + f, counters
        return jax.checkpoint(fn)
    with jax.named_scope("tower/embed"):
        h = params["embed"][ids]
    found = []
    with jax.named_scope("tower/trunk"):
        for i, name in enumerate(sorted(params["blocks"])):
            h, counters = layer(i)(h, params["blocks"][name])
            if counters is not None:
                found.append(counters)
    return h, found


def causal_loss(params, spec: TowerSpec, ids, w, pad_id):
    """The microbatch's loss.  ids [n, S] packed sequences (S whole attention
    blocks), w [n, S] each position's row's weight (0: ``PAD``, or a padding
    row).  Position i's target is id_{i+1} where that is not ``PAD``.
    Returns (loss, aux): ``afmoe``'s head, loss and counters."""
    h, found = trunk(params, spec, ids)
    # every attention layer is full causal: what it visits is a full sweep
    blocks = spec.num_attention_heads * spec.layer_types.count(KINDS[1]) * \
        attention.visited_key_blocks(ids.shape[1], spec.attention_block)
    return packed_loss(params, h, found, ids, w, pad_id, spec.norm_eps, (blocks, blocks))


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
    return row_tag_logits(trunk, params, spec, feature_ids, tag0_id, spec.norm_eps)
