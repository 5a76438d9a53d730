"""Operations and bytes a training step of the ``nemotron_h`` tower needs, from
the configuration's shapes as the share has them: the same work whatever
implements it.

Model operations only: the matmuls of the parameters a position really uses
(the held experts count the pairs routed to them, not every position), the
recurrence at what the position-by-position form needs (the chunked form's
masked products are an implementation's, and not counted), attention over the
causal score pairs, forward once and backward twice (input and weight
gradients): 3 x forward.  Recomputation is not counted, nor the optimizer's
elementwise pass (it is bytes: :func:`opt_cost`).  ``seq`` is a row's
positions with the tag (433): the trunk runs over ``seq - 1`` of them, the MTP
module over ``seq - 2``.
"""

from __future__ import annotations

import math

from .reference.nemotron_h import param_shapes


def _mamba_dims(cfg: dict):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return h, p, h * p, gn


def _attn_weights(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def _routed(cfg: dict) -> int:
    return cfg["n_routed_experts"] * int(cfg.get("expert_parallel_size", 1))


def n_params(cfg: dict) -> int:
    """Every array of the share, as the reference lays them out."""
    return sum(math.prod(shape) for shape in param_shapes(cfg).values())


def opt_cost(cfg: dict) -> dict:
    """Adam over every parameter: read parameter, gradient, m, v; write
    parameter, m, v: 28 bytes a parameter."""
    return {"flops": 12.0 * n_params(cfg), "bytes_accessed": 28.0 * n_params(cfg)}


def scan_cost(cfg: dict, positions: float) -> dict:
    """One Mamba-2 layer's scan scope (conv, recurrence, gated norm) over
    ``positions``, forward + backward.  Per position and head the state
    update ``exp(dt a) S + dt x (x) B`` is 3 P N operations and ``S C`` 2 P N;
    the conv 2 k a channel, the gate and norm ~ 8 a channel.  Bytes: the
    projection's output in and the gated, normed y out, f32, once a pass (the
    state never has to leave the chip's fast memory)."""
    h, p, di, gn = _mamba_dims(cfg)
    per = 5.0 * h * p * cfg["ssm_state_size"] + 2.0 * cfg["conv_kernel"] * (di + 2 * gn) + 8.0 * di
    byts = 4.0 * positions * ((2 * di + 2 * gn + h) + di)
    return {"flops": 3.0 * per * positions, "bytes_accessed": 3.0 * byts}


def attn_cost(cfg: dict, rows: int, length: int) -> dict:
    """One attention layer over ``rows`` sequences of ``length``, forward +
    backward: the four projections on every position and QK^T / PV on the
    causal pairs.  Bytes: the weights and the hidden states in and out, f32,
    once a pass."""
    positions = rows * length
    proj = 2.0 * _attn_weights(cfg) * positions
    scores = 4.0 * cfg["head_dim"] * cfg["num_attention_heads"] * (length * (length + 1) // 2) * rows
    byts = 4.0 * (_attn_weights(cfg) + 2 * positions * cfg["hidden_size"])
    return {"flops": 3.0 * (proj + scores), "bytes_accessed": 3.0 * byts}


def experts_cost(cfg: dict, pairs: float) -> dict:
    """One layer's held experts over ``pairs`` (token, choice) pairs, forward
    + backward: up and down on each pair, in the latent space.  Bytes: the
    held experts' weights and each pair's latent row in and out, f32, once a
    pass."""
    lat, f, held = cfg["moe_latent_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    flops = 2.0 * 2 * lat * f * pairs
    byts = 4.0 * (held * 2 * lat * f + 2 * pairs * lat)
    return {"flops": 3.0 * flops, "bytes_accessed": 3.0 * byts}


def layer_flops(kind: str, cfg: dict, rows: int, length: int, pairs: float) -> float:
    """Forward + backward model operations of one layer over rows x length positions."""
    d, positions = cfg["hidden_size"], rows * length
    if kind == "M":
        h, _, di, gn = _mamba_dims(cfg)
        proj = 2.0 * (d * (2 * di + 2 * gn + h) + di * d) * positions
        return 3.0 * proj + scan_cost(cfg, positions)["flops"]
    if kind == "*":
        return attn_cost(cfg, rows, length)["flops"]
    dense = d * _routed(cfg) + 2 * d * cfg["moe_latent_size"] + \
        2 * d * cfg["moe_shared_expert_intermediate_size"]
    return 3.0 * 2.0 * dense * positions + experts_cost(cfg, pairs)["flops"]


def step_model_flops(cfg: dict, rows: int, seq: int, pairs_per_layer: float) -> float:
    """Model operations of one optimizer step on a microbatch of ``rows``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    total = sum(layer_flops(c, cfg, rows, seq - 1, pairs_per_layer)
                for c in cfg["hybrid_override_pattern"])
    total += 3.0 * 2.0 * d * v * rows * (seq - 1)
    if int(cfg.get("num_nextn_predict_layers", 0)):
        total += sum(layer_flops(c, cfg, rows, seq - 2, pairs_per_layer)
                     for c in cfg["mtp_hybrid_override_pattern"])
        total += 3.0 * 2.0 * (2 * d * d + d * v) * rows * (seq - 2)
    return total
