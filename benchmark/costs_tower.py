"""Operations and bytes a training step of the ``sdar_moe`` tower needs, from
the configuration's shapes: the same work whatever implements it.

Model operations only: the matmuls of the parameters a position really uses
(the held experts count the pairs routed to them, not every position),
attention over the allowed score pairs only (the block-diffusion mask lets a
query see about a quarter of ``[x_t ; x_0]``), forward once and backward twice
(input and weight gradients): 3 x forward.  Recomputation is not counted, nor
the optimizer's elementwise pass (it is bytes: :func:`opt_cost`).
"""

from __future__ import annotations


def allowed_pairs(seq: int, block: int) -> int:
    """Score pairs a row's ``[x_t ; x_0]`` may use: a noised query sees its
    own block's noised keys and the clean keys of earlier blocks, a clean
    query the clean keys of its own and earlier blocks."""
    blocks = seq // block
    noised = sum(block * (block + b * block) for b in range(blocks))
    clean = sum(block * ((b + 1) * block) for b in range(blocks))
    return noised + clean


def _attn_weights(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def attn_cost(cfg: dict, rows: int, seq: int, block: int) -> dict:
    """One layer's attention over a microbatch, forward + backward: the four
    projections on every position of ``[x_t ; x_0]`` and QK^T / PV on the
    allowed pairs.  Bytes: the weights and the hidden states in and out, f32,
    once a pass."""
    positions = rows * 2 * seq
    proj = 2.0 * _attn_weights(cfg) * positions
    scores = 4.0 * cfg["head_dim"] * cfg["num_attention_heads"] * allowed_pairs(seq, block) * rows
    byts = 4.0 * (_attn_weights(cfg) + 2 * positions * cfg["hidden_size"])
    return {"flops": 3.0 * (proj + scores), "bytes_accessed": 3.0 * byts}


def experts_cost(cfg: dict, pairs: float) -> dict:
    """One layer's held experts over ``pairs`` (token, choice) pairs, forward
    + backward: gate, up and down on each pair.  Bytes: the held experts'
    weights and each pair's row in and out, f32, once a pass."""
    d, f, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    flops = 2.0 * 3 * d * f * pairs
    byts = 4.0 * (held * 3 * d * f + 2 * pairs * d)
    return {"flops": 3.0 * flops, "bytes_accessed": 3.0 * byts}


def n_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    router = d * cfg["num_experts"] * int(cfg.get("expert_parallel_size", 1))
    layer = _attn_weights(cfg) + router + cfg["num_experts"] * 3 * d * f \
        + 2 * d + 2 * cfg["head_dim"]
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * d + d


def opt_cost(cfg: dict) -> dict:
    """Adam over every parameter: read parameter, gradient, m, v; write
    parameter, m, v: 28 bytes a parameter."""
    return {"flops": 12.0 * n_params(cfg), "bytes_accessed": 28.0 * n_params(cfg)}


def step_model_flops(cfg: dict, rows: int, seq: int, block: int, pairs_per_layer: float) -> float:
    """Model operations of one optimizer step on a microbatch of ``rows``."""
    positions = rows * 2 * seq
    router = 2.0 * cfg["hidden_size"] * cfg["num_experts"] * \
        int(cfg.get("expert_parallel_size", 1)) * positions
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * rows * seq      # the noised half
    layer = attn_cost(cfg, rows, seq, block)["flops"] + 3.0 * router + \
        experts_cost(cfg, pairs_per_layer)["flops"]
    return cfg["num_hidden_layers"] * layer + 3.0 * head
