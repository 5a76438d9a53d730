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

Attention is ``ops/attention.blocked_attention`` under either mask, handed
over as data (:func:`attention_plan`): each half of the sequence is padded
with zeros to whole blocks (:func:`attention_layout`), the pad keys lie in
nobody's intervals, and no ``[T, T]`` scores exist.

The tokeniser, the ``.tower`` file, ``eval``'s scorer and the refusals are
every tower's: :mod:`shifu_tpu.models.towers`.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..config.errors import ErrorCode, ShifuError
from ..ops import attention, moe
from .towers import RowTokens, load_model  # noqa: F401  (load_model: benchmark/drivers/train_tower.py)

# the step's named scopes, most specific first: device ops carry them
# ``tower/trunk`` is the catch-all around the layer loop: after every scope that
# occurs inside it (the first scope an op's name contains takes the op)
SCOPES = ("tower/attn", "tower/moe/route", "tower/moe/experts", "tower/head", "tower/input",
          "tower/embed", "tower/trunk", "tower/acc", "tower/opt")
ATTN_COUNTERS = {"attn_key_blocks": "tower.attn_key_blocks",
                 "attn_key_blocks_dense": "tower.attn_key_blocks_dense",
                 "attn_pad_positions": "tower.attn_pad_positions"}
OBS_COUNTERS = {"masked": "tower.masked_positions", **ATTN_COUNTERS}
T_MIN = 1e-3                        # per block t ~ U(T_MIN, 1]

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
    """x [n, T, heads, hd]; rotate-half over the whole head: ``x cos +
    [-x2, x1] sin``, each half written once (no rotated copy of x is made)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_layout(s: int, parts: int) -> Tuple[int, int]:
    """(half, block) for a sequence of ``parts`` halves of ``s`` positions:
    a half is padded to the power of two at or above ``s`` (beyond the
    kernels' ``attention.BLOCK`` to whole blocks of it), and the block is as
    long as that allows — the whole sequence while it fits one.
    On the chip (S = 436, PR 36) a half as one block of 512 beat two of 256 by
    a fifth, 3 block pairs of 4 against 8 of 16 a head: what a query block
    costs beside its visits (its q, output and statistics moved, 1.1 us)
    outweighs the scores a smaller block skips."""
    one = min(attention.BLOCK, max(8, 1 << (s - 1).bit_length()))
    half = -(-s // one) * one
    return half, min(attention.BLOCK, parts * half)


@dataclass(frozen=True, eq=False)
class AttentionPlan:
    """How a sequence of ``parts`` halves of ``s`` positions goes through the
    kernels: each half padded to ``half`` (whole blocks of ``block``), under
    ``mask`` over the padded positions."""
    s: int
    parts: int
    half: int
    block: int
    mask: attention.Mask

    def pad(self, a):
        """[n, parts x s, ...] -> [n, parts x half, ...], zeros behind each half."""
        s, zeros = self.s, jnp.zeros((a.shape[0], self.half - self.s) + a.shape[2:], a.dtype)
        return jnp.concatenate([piece for i in range(self.parts)
                                for piece in (a[:, i * s:(i + 1) * s], zeros)], axis=1)

    def unpad(self, a):
        return jnp.concatenate([a[:, i * self.half:i * self.half + self.s]
                                for i in range(self.parts)], axis=1)

    def counters(self, each: int) -> Dict[str, int]:
        """:data:`ATTN_COUNTERS` of one sequence: key blocks the kernels'
        forward visits over ``each`` heads and layers, what a sweep of every
        block pair would, positions padded."""
        return {"attn_key_blocks": each * attention.schedule(self.mask, self.block).visits,
                "attn_key_blocks_dense": each * (self.parts * self.half // self.block) ** 2,
                "attn_pad_positions": self.parts * (self.half - self.s)}


def attention_plan(s: int, mask: np.ndarray) -> AttentionPlan:
    """``mask`` [T, T] bool over T = 1 or 2 halves of ``s`` positions ->
    the padded layout and the mask's description over it (``ValueError`` for a
    mask the kernels' description cannot hold)."""
    t = mask.shape[0]
    if t % s or mask.shape != (t, t):
        raise ValueError(f"a mask of {list(mask.shape)} is not over whole halves of {s} positions")
    half, block = attention_layout(s, t // s)
    real = (np.arange(t) // s) * half + np.arange(t) % s
    padded = np.zeros((t // s * half,) * 2, bool)
    padded[np.ix_(real, real)] = mask
    return AttentionPlan(s, t // s, half, block, attention.mask_of(padded))


def _attention(p, x, pos, plan: AttentionPlan, spec: TowerSpec):
    n, t, _ = x.shape
    h, kv, hd = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim
    q = (x @ p["wq"]).reshape(n, t, h, hd)
    k = (x @ p["wk"]).reshape(n, t, kv, hd)
    v = (x @ p["wv"]).reshape(n, t, kv, hd)
    q = _rope(_rms(q, p["q_norm"], spec.rms_norm_eps), pos, spec.rope_theta)
    k = _rope(_rms(k, p["k_norm"], spec.rms_norm_eps), pos, spec.rope_theta)
    # zeros behind each half, after the norms and rotary: a pad key has no
    # weight, but 0 x NaN would still be NaN in the P.V product.  Padded as
    # [n, T, heads x hd], the kernels' own layout: grouping the heads is then free
    grouped = lambda a, *heads: plan.pad(a.reshape(n, t, -1)).reshape((n, plan.mask.seq) + heads + (hd,))
    out = attention.blocked_attention(grouped(q, kv, h // kv), grouped(k, kv), grouped(v, kv),
                                      block=plan.block, mask=plan.mask)
    return plan.unpad(out.reshape(n, plan.mask.seq, h * hd)) @ p["wo"]


def _layer(spec: TowerSpec, pos, plan, h, p):
    n, t, d = h.shape
    with jax.named_scope("tower/attn"):
        h = h + _attention(p, _rms(h, p["ln1"], spec.rms_norm_eps), pos, plan, spec)
    x = _rms(h, p["ln2"], spec.rms_norm_eps).reshape(n * t, d)
    with jax.named_scope("tower/moe/route"):
        weights, experts = moe.route(x, p["router"], spec.num_experts_per_tok,
                                     spec.norm_topk_prob)
    with jax.named_scope("tower/moe/experts"):
        y, counters = moe.held_experts_ffn(x, weights, experts, p["w_gate_up"],
                                           p["w_down"], spec.expert_lo)
    return h + y.reshape(n, t, d), counters


def hidden(params, spec: TowerSpec, ids, pos, mask: np.ndarray) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """ids [n, T] -> (final-normed hidden [n, T, D], counters: the layers' MoE
    counters stacked [L, ...] and :data:`ATTN_COUNTERS`, all layers' and heads'
    of one sequence).  ``mask`` [T, T] bool, True = the query sees the key:
    :func:`block_mask` over ``[x_t ; x_0]`` or :func:`eval_mask`.  Each layer
    is recomputed in the backward pass."""
    plan = attention_plan(spec.seq_len, mask)
    layer = jax.checkpoint(lambda h, p: _layer(spec, pos, plan, h, p))
    with jax.named_scope("tower/embed"):
        h = params["embed"][ids]
    with jax.named_scope("tower/trunk"):
        h, counters = jax.lax.scan(layer, h, params["layers"])
        counters.update(plan.counters(spec.num_hidden_layers * spec.num_attention_heads))
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
    h, counters = hidden(params, spec, ids, pos, block_mask(s, spec.block_length))
    with jax.named_scope("tower/head"):
        logits = (h[:, :s] @ params["head"]).astype(jnp.float32)
        ce = jax.nn.logsumexp(logits, axis=-1) - \
            jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0]
        real = (x0 != pad_id) * row_w[:, None]
        total = jnp.sum(jnp.where(masked, ce / t, 0.0) * real)
        count = jnp.sum(real)
        live = jnp.sum(row_w > 0).astype(jnp.float32)       # the rows that count
        aux = {"loss_sum": total, "positions": count, "masked": jnp.sum(masked * real),
               **counters, **{k: live * counters[k] for k in ATTN_COUNTERS}}
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
    return {**{k: () for k in OBS_COUNTERS}, "pairs": (spec.num_hidden_layers, spec.experts_held),
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
