"""The ``nemotron_h`` tower: Mamba-2 layers, causal attention, LatentMoE
feed-forwards and one multi-token-prediction module, trained as a causal
next-token model over tokenised rows (``algorithm: TENSORFLOW``,
``train#params.Tower: "nemotron_h"``).

Architecture as config.json of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
states it (no biases but the conv's): one mixer a layer after
``hybrid_override_pattern``, ``h <- h + Mixer_c(RMSNorm(h))``, a final RMSNorm
and an untied head.

- ``M`` Mamba-2: ``[z | xBC | dt] = x W_in``; a depthwise causal conv over
  ``xBC`` and silu; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t =
  S_t C_t + D x_t`` with ``S = 0`` before a row's first position, computed in
  chunks of ``chunk_size`` (:func:`ssd_chunked`: inside a chunk a masked
  chunk x chunk product, between chunks the carried state); a gated RMSNorm
  over each B/C group's channels; ``W_out``.
- ``*`` attention: causal grouped-query attention, softmax in f32, no rotary.
- ``E`` LatentMoE: sigmoid scores over all experts, the top-k of score +
  selection bias, the chosen scores renormalised and scaled; the experts (two
  matrices around relu^2) in a latent space between two shared projections;
  one shared expert on the hidden state itself (``ops/moe.py``).
- MTP (after Megatron-Core's ``MultiTokenPredictionLayer``): ``[RMSNorm(h_i) ;
  RMSNorm(Embed[id_{i+1}])] W_eh``, the layers of ``mtp_hybrid_override_pattern``,
  its own final norm, the shared embedding and head; it predicts ``id_{i+2}``.

The rank computes its *share*: ``mamba_num_heads`` heads with their ``n_groups``
B/C groups and ``num_attention_heads`` query heads with their key-value heads
(one of ``tensor_parallel_size`` ranks that share each mixer: ``W_out`` /
``W_o`` give the rank's partial sum), ``n_routed_experts`` experts from
``expert_lo`` on (one of ``expert_parallel_size``), a slice of the vocabulary.

A row of the binned plane is the sequence ``[f_0 .. f_{C-1}, TAG_y]``
(:mod:`.towers`).  Loss: the mean over ``i`` of ``CE(logits_i, id_{i+1})``
plus ``MTP_LOSS_SCALE`` x the mean of ``CE(mtp logits_i, id_{i+2})``; the trunk
runs over the C feature tokens (the tag is only ever a target).  Score: the
same causal forward, ``p = sigmoid(logit_TAG1 - logit_TAG0)`` at the last
feature token; no MTP.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from ..config.errors import ErrorCode, ShifuError
from ..ops import moe
from .towers import RowTokens, causal_conv, nest_names

MTP_LOSS_SCALE = 0.1                # Megatron-Core's default scaling of the MTP loss
# the step's named scopes, most specific first: device ops carry them (the
# MTP module's own layers are nested under its scope, which takes them)
# ``tower/mtp`` first: it takes its module's own layers and lookup; ``tower/trunk``
# is the catch-all around the layer loop, after every scope that occurs inside it
SCOPES = ("tower/mtp", "tower/ssm/proj", "tower/ssm/scan", "tower/attn", "tower/moe/route",
          "tower/moe/latent", "tower/moe/experts", "tower/moe/shared", "tower/head", "tower/input",
          "tower/embed", "tower/trunk", "tower/acc", "tower/opt")
OBS_COUNTERS = {"mtp_loss_sum": "tower.mtp_loss_sum", "ssm_chunks": "tower.ssm_chunks"}
_NEG = float(np.finfo(np.float32).min)

# TowerParams: config.json's keys.  Read: the shapes.  Checked: the keys whose
# other values would be another architecture.  The rest says nothing here.
_READ = ("hidden_size", "num_hidden_layers", "hybrid_override_pattern", "num_attention_heads",
         "num_key_value_heads", "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
         "ssm_state_size", "conv_kernel", "chunk_size", "n_routed_experts",
         "num_experts_per_tok", "moe_intermediate_size", "moe_latent_size",
         "moe_shared_expert_intermediate_size", "vocab_size", "max_position_embeddings")
_DEFAULTS = {"norm_topk_prob": True, "routed_scaling_factor": 1.0, "layer_norm_epsilon": 1e-5,
             "num_nextn_predict_layers": 0, "mtp_hybrid_override_pattern": "",
             "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
             "tensor_parallel_size": 1, "tensor_parallel_index": 0,
             "expert_parallel_size": 1, "expert_parallel_index": 0}
_MUST_BE = {"model_type": "nemotron_h", "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
            "n_group": 1, "topk_group": 1, "n_shared_experts": 1, "tie_word_embeddings": False,
            "use_bias": False, "use_conv_bias": True, "attention_bias": False,
            "mamba_proj_bias": False, "mlp_bias": False, "sliding_window": None,
            "moe_shared_expert_overlap": False}
_INERT = ("expand", "intermediate_size", "norm_eps", "num_logits_to_keep", "partial_rotary_factor",
          "rope_theta", "rescale_prenorm_residual", "residual_in_fp32", "use_mamba_kernels")


@dataclass
class TowerSpec(RowTokens):
    hidden_size: int
    num_hidden_layers: int
    hybrid_override_pattern: str
    num_attention_heads: int        # this rank's query heads
    num_key_value_heads: int        # and their key-value heads
    head_dim: int
    mamba_num_heads: int            # this rank's Mamba heads
    mamba_head_dim: int
    n_groups: int                   # and their B/C groups
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    n_routed_experts: int           # the router's width: ALL experts
    experts_held: int               # this rank's
    expert_lo: int                  # its first
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_latent_size: int
    moe_shared_expert_intermediate_size: int
    vocab_size: int                 # this rank's slice
    max_position_embeddings: int
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    layer_norm_epsilon: float = 1e-5
    num_nextn_predict_layers: int = 0
    mtp_hybrid_override_pattern: str = ""
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    tensor_parallel_size: int = 1   # ranks that share each mixer's heads
    tensor_parallel_index: int = 0
    column_nums: List[int] = field(default_factory=list)
    column_bins: List[int] = field(default_factory=list)   # value bins a column
    feature_names: List[str] = field(default_factory=list)
    tower: str = "nemotron_h"
    kind: str = "tower"

    block_length = 1                # one token a column, then the tag

    @property
    def moe_layers(self) -> int:
        """LatentMoE layers of a step, the MTP module's among them."""
        return self.hybrid_override_pattern.count("E") + self.mtp_hybrid_override_pattern.count("E")


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
    for key in ("hybrid_override_pattern", "mtp_hybrid_override_pattern"):
        bad = sorted(set(str(p.get(key, ""))) - set("ME*"))
        if bad:
            problems.append(f"TowerParams.{key} holds {''.join(bad)!r}: a layer is one of "
                            "M (Mamba-2), E (LatentMoE), * (attention)")
    if problems:
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION, "; ".join(problems))
    p = {**_DEFAULTS, **p}
    held, size, index = (int(p[k]) for k in ("n_routed_experts", "expert_parallel_size",
                                             "expert_parallel_index"))
    ints = [k for k in _READ if k not in ("hybrid_override_pattern", "n_routed_experts")]
    spec = TowerSpec(
        **{k: int(p[k]) for k in ints}, hybrid_override_pattern=str(p["hybrid_override_pattern"]),
        n_routed_experts=held * size, experts_held=held, expert_lo=held * index,
        norm_topk_prob=bool(p["norm_topk_prob"]),
        routed_scaling_factor=float(p["routed_scaling_factor"]),
        layer_norm_epsilon=float(p["layer_norm_epsilon"]),
        num_nextn_predict_layers=int(p["num_nextn_predict_layers"]),
        mtp_hybrid_override_pattern=str(p["mtp_hybrid_override_pattern"]),
        **{k: float(p[k]) for k in ("time_step_min", "time_step_max", "time_step_floor")},
        tensor_parallel_size=int(p["tensor_parallel_size"]),
        tensor_parallel_index=int(p["tensor_parallel_index"]),
        column_nums=list(column_nums), column_bins=[int(b) for b in column_bins],
        feature_names=list(feature_names))
    if not 0 <= index < size:
        problems.append(f"expert_parallel_index {index} is not a rank of {size}")
    if not 0 <= spec.tensor_parallel_index < spec.tensor_parallel_size:
        problems.append(f"tensor_parallel_index {spec.tensor_parallel_index} is not a rank of "
                        f"{spec.tensor_parallel_size}")
    if len(spec.hybrid_override_pattern) != spec.num_hidden_layers:
        problems.append(f"hybrid_override_pattern has {len(spec.hybrid_override_pattern)} "
                        f"layers, num_hidden_layers is {spec.num_hidden_layers}")
    if spec.num_nextn_predict_layers not in (0, 1) or \
            bool(spec.num_nextn_predict_layers) != bool(spec.mtp_hybrid_override_pattern):
        problems.append("num_nextn_predict_layers is 0 or 1, and 1 with a non-empty "
                        "mtp_hybrid_override_pattern")
    if "M" in spec.mtp_hybrid_override_pattern:
        problems.append("mtp_hybrid_override_pattern holds no Mamba-2 layer here")
    if spec.num_experts_per_tok > spec.n_routed_experts:
        problems.append(f"num_experts_per_tok {spec.num_experts_per_tok} exceeds the "
                        f"router's {spec.n_routed_experts} experts")
    if spec.num_attention_heads % spec.num_key_value_heads:
        problems.append("num_attention_heads must be a multiple of num_key_value_heads")
    if spec.mamba_num_heads % spec.n_groups:
        problems.append("mamba_num_heads must be a multiple of n_groups")
    if "expand" in p and int(p["expand"]) * spec.hidden_size != \
            spec.mamba_num_heads * spec.mamba_head_dim * spec.tensor_parallel_size:
        problems.append(f"expand {p['expand']} x hidden_size is not mamba_num_heads x "
                        "mamba_head_dim x tensor_parallel_size")
    problems += spec.token_problems()
    if problems:
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION, "; ".join(problems))
    return spec


# ---------------------------------------------------------------- parameters
def _layer_shapes(kind: str, spec: TowerSpec) -> Dict[str, tuple]:
    d = spec.hidden_size
    if kind == "M":
        h, gn = spec.mamba_num_heads, spec.n_groups * spec.ssm_state_size
        di = h * spec.mamba_head_dim
        return {"norm": (d,), "w_in": (d, 2 * di + 2 * gn + h),
                "conv_w": (spec.conv_kernel, di + 2 * gn), "conv_b": (di + 2 * gn,),
                "dt_bias": (h,), "A_log": (h,), "D": (h,), "w_norm": (di,), "w_out": (di, d)}
    if kind == "*":
        h, kv, hd = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim
        return {"norm": (d,), "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
                "wo": (h * hd, d)}
    held, lat = spec.experts_held, spec.moe_latent_size
    f, fs = spec.moe_intermediate_size, spec.moe_shared_expert_intermediate_size
    return {"norm": (d,), "router": (d, spec.n_routed_experts), "bias": (spec.n_routed_experts,),
            "w_lat1": (d, lat), "w_lat2": (lat, d), "w_up": (held, lat, f),
            "w_down": (held, f, lat), "ws_up": (d, fs), "ws_down": (fs, d)}


def param_shapes(spec: TowerSpec) -> Dict[str, tuple]:
    """Flat name -> shape: ``blocks.<nn>.<array>`` a trunk layer, ``mtp.*`` the module."""
    d, v = spec.hidden_size, spec.vocab_size
    out = {"embed": (v, d), "head": (d, v), "norm_f": (d,)}
    for i, c in enumerate(spec.hybrid_override_pattern):
        out.update({f"blocks.{i:02d}.{k}": s for k, s in _layer_shapes(c, spec).items()})
    if spec.num_nextn_predict_layers:
        out.update({"mtp.norm_h": (d,), "mtp.norm_e": (d,), "mtp.w_eh": (2 * d, d),
                    "mtp.norm_f": (d,)})
        for i, c in enumerate(spec.mtp_hybrid_override_pattern):
            out.update({f"mtp.blocks.{i}.{k}": s for k, s in _layer_shapes(c, spec).items()})
    return out


def _draw(key, name: str, shape, spec: TowerSpec):
    leaf = name.rsplit(".", 1)[-1]
    uniform = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if leaf.startswith("norm") or leaf in ("w_norm", "D"):
        return jnp.ones(shape, jnp.float32)
    if leaf == "bias":
        return jnp.zeros(shape, jnp.float32)
    if leaf == "A_log":
        return jnp.log(uniform(1.0, 16.0))
    if leaf == "dt_bias":
        dt = jnp.maximum(jnp.exp(uniform(np.log(spec.time_step_min), np.log(spec.time_step_max))),
                         spec.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))                 # softplus^-1
    if leaf in ("conv_w", "conv_b"):
        bound = 1.0 / np.sqrt(spec.conv_kernel)
        return uniform(-bound, bound)
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


def init_params(key, spec: TowerSpec) -> Dict[str, Any]:
    """Array ``i`` of the flat names in sorted order is drawn from
    ``fold_in(key, i)``: normal(0, 0.02) matrices; unit norm weights and
    ``D``; a zero selection bias; ``A_log`` = log U[1, 16]; ``dt_bias`` =
    softplus^-1 of a log-uniform step in [time_step_min, time_step_max],
    floored; the conv's weight and bias U(-1/sqrt(k), 1/sqrt(k))."""
    shapes = param_shapes(spec)
    return nest_names({name: _draw(jax.random.fold_in(key, i), name, shapes[name], spec)
                       for i, name in enumerate(sorted(shapes))})


# ------------------------------------------------------------------- mixers
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """The selective state-space recurrence in chunks (Mamba-2's SSD form).

    x [n, T, H, P], dt [n, T, H] (after softplus), a [H] (negative), b / c
    [n, T, G, N] -> y [n, T, H, P] with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t
    (x) b_t`` and ``y_t = S_t c_t``, ``S = 0`` before position 0.  T is padded
    to whole chunks with dt = 0 (the state stands still, nothing enters).
    Inside a chunk: ``y_t = sum_{s<=t} exp(cs_t - cs_s) (c_t . b_s) dt_s x_s``
    with cs the inclusive cumulative sum of dt a — a masked chunk x chunk
    product; between chunks the state at a chunk's end is carried on."""
    n, t, h, p = x.shape
    g, ns = b.shape[2:]
    nc = -(-t // chunk)
    pad = nc * chunk - t
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    r = h // g                                                  # heads a B/C group
    xd = (x * dt[..., None]).reshape(n, nc, chunk, g, r, p)     # dt_s x_s
    b, c = b.reshape(n, nc, chunk, g, ns), c.reshape(n, nc, chunk, g, ns)
    cs = jnp.cumsum((dt * a).reshape(n, nc, chunk, g, r), axis=2)          # [n, c, Q, g, r] <= 0
    # inside a chunk
    seg = cs[:, :, :, None] - cs[:, :, None, :]                 # [n, c, Qt, Qs, g, r]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("nctgs,ncugs->nctug", c, b, preferred_element_type=jnp.float32)
    y = jnp.einsum("nctugr,ncugrp->nctgrp", decay * cb[..., None], xd,
                   preferred_element_type=jnp.float32)
    # a chunk's own contribution to the state at its end, and the carry
    to_end = jnp.exp(cs[:, :, -1:] - cs)                        # [n, c, Q, g, r]
    own = jnp.einsum("ncugs,ncugrp->ncgrps", b, xd * to_end[..., None],
                     preferred_element_type=jnp.float32)        # [n, c, g, r, P, N]
    total = jnp.exp(cs[:, :, -1])                               # [n, c, g, r]

    def carry(s, inp):
        own_c, total_c = inp
        return total_c[..., None, None] * s + own_c, s          # emits the state BEFORE the chunk

    _, before = jax.lax.scan(carry, jnp.zeros((n, g, r, p, ns), jnp.float32),
                             (own.swapaxes(0, 1), total.swapaxes(0, 1)))
    y = y + jnp.einsum("nctgs,ncgrps->nctgrp", c, before.swapaxes(0, 1),
                       preferred_element_type=jnp.float32) * jnp.exp(cs)[..., None]
    return y.reshape(n, nc * chunk, h, p)[:, :t]


def _mamba(p, x, spec: TowerSpec):
    n, t, _ = x.shape
    h, pd, g, ns = spec.mamba_num_heads, spec.mamba_head_dim, spec.n_groups, spec.ssm_state_size
    di = h * pd
    with jax.named_scope("tower/ssm/proj"):
        zxbcdt = x @ p["w_in"]
    with jax.named_scope("tower/ssm/scan"):
        z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * g * ns], zxbcdt[..., 2 * di + 2 * g * ns:]
        xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"]) + p["conv_b"])
        xs = xbc[..., :di].reshape(n, t, h, pd)
        b = xbc[..., di:di + g * ns].reshape(n, t, g, ns)
        c = xbc[..., di + g * ns:].reshape(n, t, g, ns)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        y = ssd_chunked(xs, dt, -jnp.exp(p["A_log"]), b, c, spec.chunk_size)
        y = (y + p["D"][:, None] * xs).reshape(n, t, di) * jax.nn.silu(z)
        y = _rms(y.reshape(n, t, g, di // g), 1.0, spec.layer_norm_epsilon).reshape(n, t, di)
        y = y * p["w_norm"]
    with jax.named_scope("tower/ssm/proj"):
        return y @ p["w_out"]


def _attention(p, x, spec: TowerSpec):
    n, t, _ = x.shape
    h, kv, hd = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim
    with jax.named_scope("tower/attn"):
        q = (x @ p["wq"]).reshape(n, t, kv, h // kv, hd)
        k = (x @ p["wk"]).reshape(n, t, kv, hd)
        v = (x @ p["wv"]).reshape(n, t, kv, hd)
        scores = jnp.einsum("nqgrd,nkgd->ngrqk", q, k,
                            preferred_element_type=jnp.float32) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, _NEG), axis=-1)
        out = jnp.einsum("ngrqk,nkgd->nqgrd", probs, v, preferred_element_type=jnp.float32)
        return out.reshape(n, t, h * hd) @ p["wo"]


def _latent_moe(p, x, spec: TowerSpec):
    n, t, d = x.shape
    x = x.reshape(n * t, d)
    with jax.named_scope("tower/moe/route"):
        weights, experts = moe.route(x, p["router"], spec.num_experts_per_tok, spec.norm_topk_prob,
                                     bias=p["bias"], scale=spec.routed_scaling_factor)
    with jax.named_scope("tower/moe/latent"):
        u = x @ p["w_lat1"]
    with jax.named_scope("tower/moe/experts"):
        y, counters = moe.held_experts_ffn(u, weights, experts, p["w_up"], p["w_down"],
                                           spec.expert_lo, act="relu2")
    with jax.named_scope("tower/moe/latent"):
        y = y @ p["w_lat2"]
    with jax.named_scope("tower/moe/shared"):
        y = y + jnp.square(jax.nn.relu(x @ p["ws_up"])) @ p["ws_down"]
    return y.reshape(n, t, d), counters


def _layers(blocks, pattern: str, h, spec: TowerSpec):
    """One mixer a layer, each layer recomputed in the backward pass.
    Returns (h, [the E layers' MoE counters])."""
    def layer(kind):
        def fn(h, p):
            x = _rms(h, p["norm"], spec.layer_norm_epsilon)
            if kind == "E":
                y, counters = _latent_moe(p, x, spec)
                return h + y, counters
            return h + (_mamba(p, x, spec) if kind == "M" else _attention(p, x, spec)), None
        return jax.checkpoint(fn)
    found = []
    for kind, name in zip(pattern, sorted(blocks)):
        h, counters = layer(kind)(h, blocks[name])
        if counters is not None:
            found.append(counters)
    return h, found


def trunk(params, spec: TowerSpec, ids):
    """ids [n, T] -> (the last layer's output [n, T, D] before ``norm_f``, counters)."""
    with jax.named_scope("tower/embed"):
        h = params["embed"][ids]
    with jax.named_scope("tower/trunk"):
        return _layers(params["blocks"], spec.hybrid_override_pattern, h, spec)


def _mtp_hidden(params, spec: TowerSpec, h, next_ids):
    m, eps = params["mtp"], spec.layer_norm_epsilon
    x = jnp.concatenate([_rms(h, m["norm_h"], eps),
                         _rms(params["embed"][next_ids], m["norm_e"], eps)], -1) @ m["w_eh"]
    h, found = _layers(m["blocks"], spec.mtp_hybrid_override_pattern, x, spec)
    return _rms(h, m["norm_f"], eps), found


def _ce(hidden, head, targets):
    logits = (hidden @ head).astype(jnp.float32)
    return jax.nn.logsumexp(logits, axis=-1) - \
        jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]


def causal_loss(params, spec: TowerSpec, ids, row_w):
    """The microbatch's loss.  ids [n, S] (features then the tag), row_w [n]
    row weights (0 = a padding row).  Returns (loss, aux): the rows'
    weighted mean of [mean_i CE(logits_i, id_{i+1}) + MTP_LOSS_SCALE x mean_i
    CE(mtp logits_i, id_{i+2})]."""
    n, s = ids.shape
    h, found = trunk(params, spec, ids[:, :-1])
    with jax.named_scope("tower/head"):
        main = jnp.sum(_ce(_rms(h, params["norm_f"], spec.layer_norm_epsilon), params["head"],
                           ids[:, 1:]) * row_w[:, None]) / (s - 1)
    mtp = jnp.float32(0.0)
    if spec.num_nextn_predict_layers:
        with jax.named_scope("tower/mtp"):
            hm, more = _mtp_hidden(params, spec, h[:, :-1], ids[:, 1:-1])
            found = found + more
            with jax.named_scope("tower/head"):
                mtp = jnp.sum(_ce(hm, params["head"], ids[:, 2:]) * row_w[:, None]) / (s - 2)
    with jax.named_scope("tower/head"):
        rows = jnp.sum(row_w)
        total = main + MTP_LOSS_SCALE * mtp
        chunks = -(-(s - 1) // spec.chunk_size) * spec.hybrid_override_pattern.count("M")
        aux = {"loss_sum": total * (s - 1), "positions": rows * (s - 1),
               "mtp_loss_sum": mtp * (s - 2), "ssm_chunks": jnp.sum(row_w > 0).astype(jnp.float32) * chunks,
               **{k: jnp.stack([c[k] for c in found]) for k in ("pairs", "rows", "dropped")}}
        return total / jnp.maximum(rows, 1.0), aux


def train_loss(params, spec: TowerSpec, ids, row_w, key, specials):
    """The trainer's loss of one microbatch; nothing is drawn: ``key`` goes unused."""
    return causal_loss(params, spec, ids, row_w)


def counter_shapes(spec: TowerSpec) -> Dict[str, tuple]:
    """``aux``'s counters beside ``loss_sum`` and ``positions``."""
    return {"mtp_loss_sum": (), "ssm_chunks": (), "pairs": (spec.moe_layers, spec.experts_held),
            "rows": (spec.moe_layers,), "dropped": (spec.moe_layers,)}


def tag_logits(params, spec: TowerSpec, feature_ids, tag0_id, mask_id):
    """One causal forward over the feature tokens.  feature_ids [n, C] ->
    [n, 2] logits of (TAG0, TAG1) at the last feature token."""
    h, _ = trunk(params, spec, feature_ids)
    with jax.named_scope("tower/head"):
        last = _rms(h[:, -1], params["norm_f"], spec.layer_norm_epsilon)
        two = jax.lax.dynamic_slice_in_dim(params["head"], tag0_id, 2, axis=1)
        return (last @ two).astype(jnp.float32)

