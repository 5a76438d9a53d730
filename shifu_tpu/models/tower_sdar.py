"""The ``sdar_moe`` tower: a block-diffusion mixture-of-experts transformer
over tokenised rows (``algorithm: TENSORFLOW``, ``train#params.Tower``).

Architecture as config.json of JetLM/SDAR-30B-A3B-Chat states it (no
biases): ``h <- h + Attn(RMSNorm(h))``, ``h <- h + MoE(RMSNorm(h))``, a final
RMSNorm and an untied head.  Attn is grouped-query attention with an RMSNorm
over each head on q and on k and rotate-half RoPE; MoE a softmax router over
all experts, the top-k renormalised, SwiGLU experts.  The rank computes its
*share*: ``experts_held`` experts from ``expert_lo`` on (``ops/moe.py``).

A row of the binned plane is a sequence: one token a column (id = the
column's offset + its bin), padded with ``PAD`` to whole blocks, then one
block ``[TAG_y, PAD, ...]``.  Training is block diffusion (Arriola et al.,
ICLR 2025): the input is ``[x_t ; x_0]`` under :func:`block_mask`, the loss
the 1/t-weighted cross-entropy of the masked positions.  Scoring is one
denoising step of the last block: the tag's token masked, every feature
clean, score = p(tag = 1) over the tag's two ids.

The tokeniser, the ``.tower`` file, ``eval``'s scorer and the refusals are
every tower's: :mod:`shifu_tpu.models.towers`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..config.errors import ErrorCode, ShifuError
from ..ops import moe
from .towers import RowTokens, load_model  # noqa: F401  (load_model: benchmark/drivers/train_tower.py)

# the step's named scopes, most specific first: device ops carry them
# ``tower/trunk`` is the catch-all around the layer loop: after every scope that
# occurs inside it (the first scope an op's name contains takes the op)
SCOPES = ("tower/attn", "tower/moe/route", "tower/moe/experts", "tower/head", "tower/input",
          "tower/embed", "tower/trunk", "tower/acc", "tower/opt")
OBS_COUNTERS = {"masked": "tower.masked_positions"}
T_MIN = 1e-3                        # per block t ~ U(T_MIN, 1]
ATTN_ROWS = 4                       # rows whose f32 scores are alive at once
_NEG = float(np.finfo(np.float32).min)

# TowerParams: config.json's keys.  Read: the shapes.  Checked: the keys whose
# other values would be another architecture.  The rest of config.json
# (intermediate_size, max_window_layers, ...) says nothing here and is accepted.
_READ = ("hidden_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "head_dim", "moe_intermediate_size",
         "num_experts", "num_experts_per_tok", "vocab_size",
         "max_position_embeddings")
_DEFAULTS = {"norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
             "block_length": 4, "expert_parallel_size": 1,
             "expert_parallel_index": 0}
_MUST_BE = {"model_type": "sdar_moe", "hidden_act": "silu",
            "attention_bias": False, "tie_word_embeddings": False,
            "decoder_sparse_step": 1, "mlp_only_layers": [],
            "rope_scaling": None, "use_sliding_window": False,
            "sliding_window": None}
_INERT = ("intermediate_size", "max_window_layers")


@dataclass
class TowerSpec(RowTokens):
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int                # the router's width: ALL experts
    experts_held: int               # this rank's
    expert_lo: int                  # its first
    num_experts_per_tok: int
    vocab_size: int                 # this rank's slice
    max_position_embeddings: int
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    block_length: int = 4
    column_nums: List[int] = field(default_factory=list)
    column_bins: List[int] = field(default_factory=list)   # value bins a column
    feature_names: List[str] = field(default_factory=list)
    tower: str = "sdar_moe"
    kind: str = "tower"


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
    held, size, index = (int(p[k]) for k in ("num_experts", "expert_parallel_size",
                                             "expert_parallel_index"))
    spec = TowerSpec(
        **{k: int(p[k]) for k in _READ if k != "num_experts"},
        num_experts=held * size, experts_held=held, expert_lo=held * index,
        norm_topk_prob=bool(p["norm_topk_prob"]), rms_norm_eps=float(p["rms_norm_eps"]),
        rope_theta=float(p["rope_theta"]), block_length=int(p["block_length"]),
        column_nums=list(column_nums), column_bins=[int(b) for b in column_bins],
        feature_names=list(feature_names))
    if not 0 <= index < size:
        problems.append(f"expert_parallel_index {index} is not a rank of {size}")
    if spec.num_experts_per_tok > spec.num_experts:
        problems.append(f"num_experts_per_tok {spec.num_experts_per_tok} exceeds the "
                        f"router's {spec.num_experts} experts")
    if spec.num_attention_heads % spec.num_key_value_heads:
        problems.append("num_attention_heads must be a multiple of num_key_value_heads")
    if spec.head_dim % 2:
        problems.append("head_dim must be even (rotate-half)")
    problems += spec.token_problems()
    if problems:
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION, "; ".join(problems))
    return spec


# ----------------------------------------------------------------- the masks
def block_mask(s: int, block: int) -> np.ndarray:
    """[2S, 2S] bool over ``[x_t ; x_0]``: True = the query (row) sees the key
    (column).  A noised query sees noised keys of its own block and clean
    keys of earlier blocks; a clean query sees clean keys of its own and
    earlier blocks."""
    blk = np.arange(s) // block
    q, k = blk[:, None], blk[None, :]
    out = np.zeros((2 * s, 2 * s), bool)
    out[:s, :s] = q == k
    out[:s, s:] = k < q
    out[s:, s:] = k <= q
    return out


def eval_mask(s: int, block: int) -> np.ndarray:
    """[S, S] bool: block-causal, bidirectional inside a block."""
    blk = np.arange(s) // block
    return blk[None, :] <= blk[:, None]


# ---------------------------------------------------------------- parameters
def init_params(key, spec: TowerSpec) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, unit norms; layers stacked on a leading axis."""
    d, hd, f = spec.hidden_size, spec.head_dim, spec.moe_intermediate_size
    n, e = spec.num_hidden_layers, spec.experts_held
    shapes = {"wq": (n, d, spec.num_attention_heads * hd),
              "wk": (n, d, spec.num_key_value_heads * hd),
              "wv": (n, d, spec.num_key_value_heads * hd),
              "wo": (n, spec.num_attention_heads * hd, d),
              "router": (n, d, spec.num_experts),
              "w_gate_up": (n, e, d, 2 * f), "w_down": (n, e, f, d)}
    keys = jax.random.split(key, len(shapes) + 2)
    normal = lambda k, shape: 0.02 * jax.random.normal(k, shape, jnp.float32)
    layers = {name: normal(k, shape) for k, (name, shape) in zip(keys, shapes.items())}
    layers.update(ln1=jnp.ones((n, d), jnp.float32), ln2=jnp.ones((n, d), jnp.float32),
                  q_norm=jnp.ones((n, hd), jnp.float32), k_norm=jnp.ones((n, hd), jnp.float32))
    return {"embed": normal(keys[-2], (spec.vocab_size, d)), "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32), "head": normal(keys[-1], (d, spec.vocab_size))}


# ------------------------------------------------------------------- forward
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [n, T, heads, hd]; rotate-half over the whole head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attend(q, k, v, mask):
    """q [c, T, KV, R, hd], k/v [c, T, KV, hd] -> [c, T, KV, R, hd]; scores and
    softmax in f32."""
    scores = jnp.einsum("cqgrd,ckgd->cgrqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(mask, scores, _NEG), axis=-1)
    return jnp.einsum("cgrqk,ckgd->cqgrd", probs, v, preferred_element_type=jnp.float32)


def _attend_split(q, k, v, mask, s, block):
    """:func:`_attend` for ``[x_t ; x_0]``, where no query sees a noised key
    outside its own block: every query against the clean keys (the mask's
    right half) and, beside it, each noised query against its block's noised
    keys — half the score pairs of the dense product.  One softmax over both
    parts, in f32."""
    c, _, g, r, d = q.shape
    nb, scale = s // block, 1.0 / math.sqrt(d)
    clean = jnp.einsum("cqgrd,ckgd->cgrqk", q, k[:, s:],
                       preferred_element_type=jnp.float32) * scale
    clean = jnp.where(mask[:, s:], clean, _NEG)                        # [c,g,r,2S,S]
    own = jnp.einsum("cnqgrd,cnkgd->cgrnqk", q[:, :s].reshape(c, nb, block, g, r, d),
                     k[:, :s].reshape(c, nb, block, g, d),
                     preferred_element_type=jnp.float32) * scale       # [c,g,r,nb,B,B]
    own = jnp.concatenate([own.reshape(c, g, r, s, block),
                           jnp.full((c, g, r, s, block), _NEG, jnp.float32)], axis=3)
    top = jax.lax.stop_gradient(jnp.maximum(clean.max(-1), own.max(-1)))[..., None]
    e_clean, e_own = jnp.exp(clean - top), jnp.exp(own - top)
    z = e_clean.sum(-1) + e_own.sum(-1)                                # [c,g,r,2S]
    out = jnp.einsum("cgrqk,ckgd->cqgrd", e_clean, v[:, s:], preferred_element_type=jnp.float32)
    out_own = jnp.einsum("cgrnqk,cnkgd->cnqgrd", e_own[..., :s, :].reshape(c, g, r, nb, block, block),
                         v[:, :s].reshape(c, nb, block, g, d),
                         preferred_element_type=jnp.float32).reshape(c, s, g, r, d)
    out = out.at[:, :s].add(out_own)
    return out / jnp.moveaxis(z, 3, 1)[..., None]


def _attention(p, x, pos, mask, spec: TowerSpec, noised: int = 0):
    n, t, _ = x.shape
    h, kv, hd = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim
    q = (x @ p["wq"]).reshape(n, t, h, hd)
    k = (x @ p["wk"]).reshape(n, t, kv, hd)
    v = (x @ p["wv"]).reshape(n, t, kv, hd)
    q = _rope(_rms(q, p["q_norm"], spec.rms_norm_eps), pos, spec.rope_theta)
    k = _rope(_rms(k, p["k_norm"], spec.rms_norm_eps), pos, spec.rope_theta)
    # a few rows' scores at a time, recomputed in the backward pass
    c = max(d for d in range(1, ATTN_ROWS + 1) if n % d == 0)
    chunks = lambda a: a.reshape((n // c, c) + a.shape[1:])
    if noised:
        b = spec.block_length
        blk = np.arange(noised) // b
        if mask[noised:, :noised].any() or (mask[:noised, :noised] != (blk[:, None] == blk)).any():
            raise ValueError("the mask is not the block-diffusion mask of [x_t ; x_0]")
        attend = lambda qkv: _attend_split(*qkv, jnp.asarray(mask), noised, b)
    else:
        attend = lambda qkv: _attend(*qkv, jnp.asarray(mask))

    def core(qkv):
        with jax.named_scope("tower/attn"):      # again: under lax.map a checkpointed body names its ops from its own root
            return attend(qkv)
    out = jax.lax.map(jax.checkpoint(core),
                      (chunks(q.reshape(n, t, kv, h // kv, hd)), chunks(k), chunks(v)))
    return out.reshape(n, t, h * hd) @ p["wo"]


def _layer(spec: TowerSpec, pos, mask, noised, h, p):
    n, t, d = h.shape
    with jax.named_scope("tower/attn"):
        h = h + _attention(p, _rms(h, p["ln1"], spec.rms_norm_eps), pos, mask, spec, noised)
    x = _rms(h, p["ln2"], spec.rms_norm_eps).reshape(n * t, d)
    with jax.named_scope("tower/moe/route"):
        weights, experts = moe.route(x, p["router"], spec.num_experts_per_tok,
                                     spec.norm_topk_prob)
    with jax.named_scope("tower/moe/experts"):
        y, counters = moe.held_experts_ffn(x, weights, experts, p["w_gate_up"],
                                           p["w_down"], spec.expert_lo)
    return h + y.reshape(n, t, d), counters


def hidden(params, spec: TowerSpec, ids, pos, mask: np.ndarray,
           noised: int = 0) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """ids [n, T] -> (final-normed hidden [n, T, D], the layers' MoE counters
    stacked [L, ...]).  Each layer is recomputed in the backward pass.
    ``noised``: the first ``noised`` positions are the ``x_t`` of
    ``[x_t ; x_0]`` under :func:`block_mask` (attention then skips the score
    pairs that mask never allows)."""
    layer = jax.checkpoint(lambda h, p: _layer(spec, pos, mask, noised, h, p))
    with jax.named_scope("tower/embed"):
        h = params["embed"][ids]
    with jax.named_scope("tower/trunk"):
        h, counters = jax.lax.scan(layer, h, params["layers"])
        return _rms(h, params["final_norm"], spec.rms_norm_eps), counters


def diffusion_loss(params, spec: TowerSpec, x0, t, masked, row_w, mask_id, pad_id):
    """The microbatch's block-diffusion loss.  x0 [n, S] ids, t [n, S] the
    blocks' noise levels, masked [n, S] bool, row_w [n] row weights (0 = a
    padding row).  Returns (loss, aux): the sum over masked, non-PAD positions
    of (1/t) CE(logits_i, x_0,i) over the count of non-PAD positions.
    ``mask_id`` / ``pad_id`` come in as values, not constants: they follow the
    columns' bins, and a program must not be rebuilt for another table."""
    s = spec.seq_len
    with jax.named_scope("tower/input"):
        xt = jnp.where(masked, mask_id, x0)
        pos = jnp.concatenate([jnp.arange(s), jnp.arange(s)])
        ids = jnp.concatenate([xt, x0], axis=1)
    h, counters = hidden(params, spec, ids, pos, block_mask(s, spec.block_length), noised=s)
    with jax.named_scope("tower/head"):
        logits = (h[:, :s] @ params["head"]).astype(jnp.float32)
        ce = jax.nn.logsumexp(logits, axis=-1) - \
            jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0]
        real = (x0 != pad_id) * row_w[:, None]
        total = jnp.sum(jnp.where(masked, ce / t, 0.0) * real)
        count = jnp.sum(real)
        aux = {"loss_sum": total, "positions": count,
               "masked": jnp.sum(masked * real), **counters}
        return total / jnp.maximum(count, 1.0), aux


def noise(key, rows: int, spec: TowerSpec):
    """(t [rows, S], masked [rows, S]): per block t ~ U(T_MIN, 1], each
    position of the block masked with probability t."""
    kt, km = jax.random.split(key)
    b = spec.block_length
    t = jnp.repeat(jax.random.uniform(kt, (rows, spec.seq_len // b), jnp.float32,
                                      T_MIN, 1.0), b, axis=1)
    return t, jax.random.uniform(km, (rows, spec.seq_len), jnp.float32) < t


def train_loss(params, spec: TowerSpec, x0, row_w, key, specials):
    """The trainer's loss of one microbatch: :func:`diffusion_loss` under the
    noise drawn from the step's key.  ``specials``: the ids of
    :data:`.towers.SPECIALS`, as values."""
    with jax.named_scope("tower/input"):
        t, masked = noise(key, x0.shape[0], spec)
        mask_id, pad_id = specials[2], specials[3]
    return diffusion_loss(params, spec, x0, t, masked, row_w, mask_id, pad_id)


def counter_shapes(spec: TowerSpec) -> Dict[str, tuple]:
    """``aux``'s counters beside ``loss_sum`` and ``positions``."""
    return {"masked": (), "pairs": (spec.num_hidden_layers, spec.experts_held),
            "rows": (spec.num_hidden_layers,), "dropped": (spec.num_hidden_layers,)}


def tag_logits(params, spec: TowerSpec, feature_ids, tag0_id, mask_id):
    """One denoising step of the last block.  feature_ids [n, feature_len]
    clean -> [n, 2] logits of (TAG0, TAG1) at the tag's position."""
    s, b = spec.seq_len, spec.block_length
    ids = jnp.concatenate([feature_ids, jnp.full((feature_ids.shape[0], b), mask_id,
                                                 feature_ids.dtype)], 1)
    h, _ = hidden(params, spec, ids, jnp.arange(s), eval_mask(s, b))
    with jax.named_scope("tower/head"):
        two = jax.lax.dynamic_slice_in_dim(params["head"], tag0_id, 2, axis=1)
        return (h[:, spec.feature_len] @ two).astype(jnp.float32)
