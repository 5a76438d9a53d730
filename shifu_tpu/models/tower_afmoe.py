"""The ``afmoe`` tower: window and full causal attention mixed after
``layer_types``, a sigmoid gate on the attention output, a norm before and
after every sub-layer, leading dense SwiGLU layers, then sigmoid-routed
experts with one shared expert and a selection bias that moves; trained as a
causal next-token model over rows packed into sequences (``algorithm:
TENSORFLOW``, ``train#params.Tower: "afmoe"``, ``train#params.RowsPerSequence``).

Architecture as config.json of arcee-ai/Trinity-Mini states it and its
published modelling code completes it (no bias anywhere)::

    h0 = Embed[ids] sqrt(d)                                     (mup_enabled)
    a  = RMSNorm_in(h)
    q  = RMSNorm_q(a Wq)   k = RMSNorm_k(a Wk)   v = a Wv       (per head, over head_dim)
    sliding_attention: rotate_half RoPE on q, k; key j allowed when 0 <= i - j < sliding_window
    full_attention:    no rotary;                key j allowed when j <= i
    o  = softmax(q k^T / sqrt(head_dim)) v  *  sigmoid(a Wg)
    h  = h + RMSNorm_post_attn(o Wo)
    m  = RMSNorm_pre_mlp(h)
    f  = SwiGLU(m)                                              (layer < num_dense_layers)
       | SwiGLU_shared(m) + sum_{e in top-k of (s + b)} w_e SwiGLU_e(m)
         s = sigmoid(m Wr),  w = s[chosen] / sum s[chosen] x route_scale
    h  = h + RMSNorm_post_mlp(f)
    logits = RMSNorm_final(h) W_head

Attention is ``ops/attention.blocked_attention`` (a sequence is padded with
``PAD`` to whole blocks of ``attention_block`` positions), the experts
``ops/moe.py``.  The rank computes its *share*: ``num_experts`` experts from
``expert_lo`` on (one of ``expert_parallel_size`` ranks; the router keeps every
expert's output and the bias every expert's entry), a slice of the vocabulary.

The selection bias ``b`` enters the choice only, takes no gradient (so Adam's
update of it is exactly zero), and :func:`after_step` moves it once a step:
``b <- b + load_balance_coeff x sign(mean(n) - n_e)`` with ``n_e`` the step's
positions whose top-k holds expert e, all experts, held or not (the
auxiliary-loss-free rule of DeepSeek-V3, arXiv:2412.19437, not centred).

A row of the binned plane is ``[f_0 .. f_{C-1}, TAG_y]`` (:mod:`.towers`);
``towers.pack_rows`` lays ``RowsPerSequence`` of them end to end.  Loss: the
cross-entropy of ``id_{i+1}`` at every position ``i`` whose successor is not
``PAD``, weighted by the successor's row's weight, over the weighted count.
Score: one row a sequence, ``p = sigmoid(logit_TAG1 - logit_TAG0)`` at the
last feature token.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from ..config.errors import ErrorCode, ShifuError
from ..ops import attention, moe
from .towers import SPECIALS, RowTokens, nest_names, pad_to_block

# the step's named scopes, most specific first: device ops carry them
# ``tower/trunk`` is the catch-all around the layer loop: after every scope that
# occurs inside it (the first scope an op's name contains takes the op)
SCOPES = ("tower/attn/window", "tower/attn/full", "tower/attn/proj", "tower/mlp",
          "tower/moe/route", "tower/moe/experts", "tower/moe/shared", "tower/head", "tower/input",
          "tower/embed", "tower/trunk", "tower/acc", "tower/opt")
OBS_COUNTERS = {"attn_key_blocks": "tower.attn_key_blocks",
                "attn_key_blocks_dense": "tower.attn_key_blocks_dense",
                "pad_positions": "tower.pad_positions",
                "sequence_positions": "tower.sequence_positions",
                "router_bias_absmax": "tower.router_bias_absmax"}
KINDS = ("sliding_attention", "full_attention")

# TowerParams: config.json's keys.  Read: the shapes.  Checked: the keys whose
# other values would be another architecture.  The rest says nothing here.
_READ = ("hidden_size", "num_hidden_layers", "num_dense_layers", "layer_types",
         "num_attention_heads", "num_key_value_heads", "head_dim", "sliding_window",
         "intermediate_size", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
         "vocab_size", "max_position_embeddings")
_DEFAULTS = {"rms_norm_eps": 1e-5, "rope_theta": 10000.0, "route_norm": True, "route_scale": 1.0,
             "load_balance_coeff": 0.001, "mup_enabled": False, "attention_block": attention.BLOCK,
             "expert_parallel_size": 1, "expert_parallel_index": 0}
_MUST_BE = {"model_type": "afmoe", "hidden_act": "silu", "score_func": "sigmoid", "n_group": 1,
            "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
            "num_shared_experts": 1, "rope_scaling": None, "tie_word_embeddings": False}
_INERT = ("global_attn_every_n_layers", "use_grouped_mm")


@dataclass
class TowerSpec(RowTokens):
    hidden_size: int
    num_hidden_layers: int
    num_dense_layers: int
    layer_types: List[str]
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    intermediate_size: int          # the dense layers' width
    moe_intermediate_size: int      # an expert's, and the shared expert's
    num_experts: int                # the router's width: ALL experts
    experts_held: int               # this rank's
    expert_lo: int                  # its first
    num_experts_per_tok: int
    vocab_size: int                 # this rank's slice
    max_position_embeddings: int
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    route_norm: bool = True
    route_scale: float = 1.0
    load_balance_coeff: float = 0.001
    mup_enabled: bool = False
    attention_block: int = attention.BLOCK
    column_nums: List[int] = field(default_factory=list)
    column_bins: List[int] = field(default_factory=list)   # value bins a column
    feature_names: List[str] = field(default_factory=list)
    tower: str = "afmoe"
    kind: str = "tower"

    block_length = 1                # one token a column, then the tag

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    def window_of(self, layer: int):
        """The layer's window in keys (None: full causal)."""
        return self.sliding_window if self.layer_types[layer] == KINDS[0] else None


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
    bad = sorted(set(map(str, p.get("layer_types") or ())) - set(KINDS))
    if bad:
        problems.append(f"TowerParams.layer_types holds {bad}: a layer is one of {list(KINDS)}")
    if problems:
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION, "; ".join(problems))
    p = {**_DEFAULTS, **p}
    held, size, index = (int(p[k]) for k in ("num_experts", "expert_parallel_size",
                                             "expert_parallel_index"))
    ints = [k for k in _READ if k not in ("layer_types", "num_experts")]
    spec = TowerSpec(
        **{k: int(p[k]) for k in ints}, layer_types=[str(t) for t in p["layer_types"]],
        num_experts=held * size, experts_held=held, expert_lo=held * index,
        **{k: float(p[k]) for k in ("rms_norm_eps", "rope_theta", "route_scale",
                                    "load_balance_coeff")},
        route_norm=bool(p["route_norm"]), mup_enabled=bool(p["mup_enabled"]),
        attention_block=int(p["attention_block"]),
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
    if spec.sliding_window % spec.attention_block:
        problems.append(f"sliding_window {spec.sliding_window} is not whole attention blocks "
                        f"of {spec.attention_block} positions")
    if spec.num_experts_per_tok > spec.num_experts:
        problems.append(f"num_experts_per_tok {spec.num_experts_per_tok} exceeds the "
                        f"router's {spec.num_experts} experts")
    if spec.num_attention_heads % spec.num_key_value_heads:
        problems.append("num_attention_heads must be a multiple of num_key_value_heads")
    if spec.head_dim % 2:
        problems.append("head_dim must be even (rotate_half)")
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
    out = {"norm_in": (d,), "norm_post_attn": (d,), "norm_pre_mlp": (d,), "norm_post_mlp": (d,),
           "norm_q": (hd,), "norm_k": (hd,), "wq": (d, h * hd), "wk": (d, kv * hd),
           "wv": (d, kv * hd), "wg": (d, h * hd), "wo": (h * hd, d)}
    if layer < spec.num_dense_layers:
        f = spec.intermediate_size
        return {**out, "w_gate_up": (d, 2 * f), "w_down": (f, d)}
    f, held = spec.moe_intermediate_size, spec.experts_held
    return {**out, "router": (d, spec.num_experts), "bias": (spec.num_experts,),
            "ws_gate_up": (d, 2 * f), "ws_down": (f, d),
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
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotary(seq: int, hd: int, theta: float):
    """(cos, sin) [seq, hd] of rotate_half RoPE at positions 0 .. seq - 1."""
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.cos(ang)] * 2, -1), jnp.concatenate([jnp.sin(ang)] * 2, -1)


def _rotate(x, cos, sin):
    """x [n, S, ..., hd] rotated by the tables [S, hd]."""
    hd = x.shape[-1]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (hd,)
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos.reshape(shape) + half * sin.reshape(shape)


def _swiglu(x, w_gate_up, w_down):
    gu = x @ w_gate_up
    f = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ w_down


def _attention(p, a, spec: TowerSpec, window):
    n, s, _ = a.shape
    h, kv, hd = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim
    eps = spec.rms_norm_eps
    with jax.named_scope("tower/attn/proj"):
        q = _rms((a @ p["wq"]).reshape(n, s, kv, h // kv, hd), p["norm_q"], eps)
        k = _rms((a @ p["wk"]).reshape(n, s, kv, hd), p["norm_k"], eps)
        v = (a @ p["wv"]).reshape(n, s, kv, hd)
        if window is not None:
            tables = _rotary(s, hd, spec.rope_theta)
            q, k = _rotate(q, *tables), _rotate(k, *tables)
    with jax.named_scope("tower/attn/window" if window is not None else "tower/attn/full"):
        o = attention.blocked_attention(q, k, v, window, spec.attention_block)
    with jax.named_scope("tower/attn/proj"):
        return (o.reshape(n, s, h * hd) * jax.nn.sigmoid(a @ p["wg"])) @ p["wo"]


def _moe(p, m, spec: TowerSpec):
    """(the layer's feed-forward output, counters with ``tokens`` [E]: the
    positions whose top-k holds each expert, held or not)."""
    n, s, d = m.shape
    x = m.reshape(n * s, d)
    with jax.named_scope("tower/moe/route"):
        weights, experts = moe.route(x, p["router"], spec.num_experts_per_tok, spec.route_norm,
                                     bias=p["bias"], scale=spec.route_scale)
        tokens = jnp.zeros(spec.num_experts, jnp.float32).at[experts.reshape(-1)].add(1.0)
    with jax.named_scope("tower/moe/experts"):
        y, counters = moe.held_experts_ffn(x, weights, experts, p["we_gate_up"], p["we_down"],
                                           spec.expert_lo, act="swiglu")
    with jax.named_scope("tower/moe/shared"):
        y = y + _swiglu(x, p["ws_gate_up"], p["ws_down"])
    return y.reshape(n, s, d), {**counters, "tokens": tokens}


def trunk(params, spec: TowerSpec, ids):
    """ids [n, S] (S whole attention blocks) -> (the last layer's output
    [n, S, D] before ``norm_f``, [the MoE layers' counters]); each layer is
    recomputed in the backward pass."""
    eps = spec.rms_norm_eps

    def layer(i):
        def fn(h, p):
            a = _rms(h, p["norm_in"], eps)
            h = h + _rms(_attention(p, a, spec, spec.window_of(i)), p["norm_post_attn"], eps)
            m = _rms(h, p["norm_pre_mlp"], eps)
            if i < spec.num_dense_layers:
                with jax.named_scope("tower/mlp"):
                    f, counters = _swiglu(m, p["w_gate_up"], p["w_down"]), None
            else:
                f, counters = _moe(p, m, spec)
            return h + _rms(f, p["norm_post_mlp"], eps), counters
        return jax.checkpoint(fn)
    with jax.named_scope("tower/embed"):
        h = params["embed"][ids]
        if spec.mup_enabled:
            h = h * jnp.float32(spec.hidden_size ** 0.5)
    found = []
    with jax.named_scope("tower/trunk"):
        for i, name in enumerate(sorted(params["blocks"])):
            h, counters = layer(i)(h, params["blocks"][name])
            if counters is not None:
                found.append(counters)
    return h, found


def _key_blocks(spec: TowerSpec, seq: int):
    """(key blocks a sequence's attention visits over the layers, and what a
    full causal sweep of every layer would): counts the code holds."""
    visit = lambda w: attention.visited_key_blocks(seq, spec.attention_block, w)
    heads = spec.num_attention_heads
    return (heads * sum(visit(spec.window_of(i)) for i in range(spec.num_hidden_layers)),
            heads * spec.num_hidden_layers * visit(None))


def causal_loss(params, spec: TowerSpec, ids, w, pad_id):
    """The microbatch's loss.  ids [n, S] packed sequences (S whole attention
    blocks), w [n, S] each position's row's weight (0: ``PAD``, or a padding
    row).  Position i's target is id_{i+1} where that is not ``PAD``.
    Returns (loss, aux)."""
    h, found = trunk(params, spec, ids)
    return packed_loss(params, h, found, ids, w, pad_id, spec.rms_norm_eps,
                       _key_blocks(spec, ids.shape[1]))


def packed_loss(params, h, found, ids, w, pad_id, eps, key_blocks):
    """:func:`causal_loss` from the trunk's output ``h`` and its MoE layers'
    counters ``found`` on: the final norm, the head, the loss and ``aux``
    (``key_blocks``: a sequence's visited and full-sweep key blocks)."""
    with jax.named_scope("tower/head"):
        logits = (_rms(h, params["norm_f"], eps) @ params["head"]).astype(jnp.float32)
        targets = jnp.roll(ids, -1, axis=1)                 # the last position's counts for nothing
        ce = jax.nn.logsumexp(logits, axis=-1) - \
            jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        tw = jnp.where(targets != pad_id, jnp.roll(w, -1, axis=1), 0.0).at[:, -1].set(0.0)
        loss_sum, count = jnp.sum(ce * tw), jnp.sum(tw)
        counts = jnp.any(w > 0, axis=1, keepdims=True)                      # sequences that count
        live = jnp.sum(counts).astype(jnp.float32)
        visited, dense = key_blocks
        aux = {"loss_sum": loss_sum, "positions": count,
               "attn_key_blocks": live * visited, "attn_key_blocks_dense": live * dense,
               "pad_positions": jnp.sum((ids == pad_id) & counts).astype(jnp.float32),
               "sequence_positions": live * ids.shape[1],
               "router_bias_absmax": jnp.float32(0.0),
               **{k: jnp.stack([c[k] for c in found]) for k in ("pairs", "rows", "dropped", "tokens")}}
        return loss_sum / jnp.maximum(count, 1.0), aux


def train_loss(params, spec: TowerSpec, ids, w, key, specials):
    """The trainer's loss of one microbatch of packed sequences
    (``towers.pack_rows``); nothing is drawn: ``key`` goes unused."""
    with jax.named_scope("tower/input"):
        pad_id = specials[SPECIALS.index("PAD")]
    return causal_loss(params, spec, ids, w, pad_id)


def _bias_absmax(params):
    return jnp.max(jnp.stack([jnp.max(jnp.abs(p["bias"])) for p in params["blocks"].values()
                              if "bias" in p] or [jnp.float32(0.0)]))


def after_step(params, aux, spec: TowerSpec):
    """The selection bias's rule (``ops/moe.bias_step``), once an optimizer
    step: every MoE layer's ``b <- b + load_balance_coeff x sign(mean(n) -
    n_e)`` from the step's counts.  Returns (params, aux):
    ``router_bias_absmax`` is how far max |b| moved, so that the steps' sum is
    the largest |b| since a zero start."""
    before = _bias_absmax(params)
    blocks = dict(params["blocks"])
    moe_names = [name for name in sorted(blocks) if "bias" in blocks[name]]
    for name, n_e in zip(moe_names, aux["tokens"]):
        blocks[name] = {**blocks[name],
                        "bias": moe.bias_step(blocks[name]["bias"], n_e, spec.load_balance_coeff)}
    params = {**params, "blocks": blocks}
    return params, {**aux, "router_bias_absmax": _bias_absmax(params) - before}


def counter_shapes(spec: TowerSpec) -> Dict[str, tuple]:
    """``aux``'s counters beside ``loss_sum`` and ``positions`` (``tokens``
    is the step's own: :func:`after_step` reads it, nothing adds it up)."""
    return {**{k: () for k in OBS_COUNTERS}, "pairs": (spec.moe_layers, spec.experts_held),
            "rows": (spec.moe_layers,), "dropped": (spec.moe_layers,)}


def tag_logits(params, spec: TowerSpec, feature_ids, tag0_id, mask_id):
    """One causal forward over one row a sequence.  feature_ids [n, C] ->
    [n, 2] logits of (TAG0, TAG1) at the last feature token."""
    return row_tag_logits(trunk, params, spec, feature_ids, tag0_id, spec.rms_norm_eps)


def row_tag_logits(trunk_of, params, spec, feature_ids, tag0_id, eps):
    """:func:`tag_logits` through the trunk ``trunk_of(params, spec, ids)``."""
    c = feature_ids.shape[1]
    pad_id = tag0_id + (SPECIALS.index("PAD") - SPECIALS.index("TAG0"))
    h, _ = trunk_of(params, spec, pad_to_block(feature_ids, spec.attention_block, pad_id))
    with jax.named_scope("tower/head"):
        last = _rms(h[:, c - 1], params["norm_f"], eps)
        two = jax.lax.dynamic_slice_in_dim(params["head"], tag0_id, 2, axis=1)
        return (last @ two).astype(jnp.float32)
