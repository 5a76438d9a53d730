"""Operations and bytes a training step of the ``deepseek_v3`` tower needs,
from the configuration's shapes as the share has them: the same work whatever
implements it.

Model operations only: the matmuls of the parameters a position really uses
(the held experts count the pairs routed to them, not every position; the
shared experts every position), latent attention's kernels over the allowed
score pairs only (a full layer's lower triangle) at the real widths — q.k over
``qk_nope + qk_rope`` channels, p.v over ``v_head_dim`` — forward once and
backward twice (input and weight gradients): 3 x forward.  Recomputation is
not counted (each layer is run twice; the kernels' backward recomputes its
scores), nor the zero lanes the kernels give q and k (192 channels at 256
lanes on the chip), nor the score pairs a visited block holds beyond the
allowed ones, nor the balance loss's elementwise work, nor the optimizer's
pass: a share computed from these reads low, never over 100 %.  ``seq`` is a
packed sequence's positions, ``PAD`` included.
"""

from __future__ import annotations

import math

from .costs_afmoe import allowed_pairs
from .costs_tower import experts_cost as _experts_cost
from .reference.deepseek_v3 import param_shapes


def n_params(cfg: dict) -> int:
    """Every array of the share, as the reference lays them out."""
    return sum(math.prod(shape) for shape in param_shapes(cfg).values())


def _qk(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def attn_cost(cfg: dict, seqs: int, seq: int) -> dict:
    """One layer's attention kernels (no projection) over ``seqs``
    sequences, forward + backward: QK^T over the q/k width and PV over the
    value width on the allowed pairs, every head.  Bytes: q, k, v in and the
    output out at their real widths, f32, once a pass."""
    h, qk, dv = cfg["num_attention_heads"], _qk(cfg), cfg["v_head_dim"]
    flops = 2.0 * (qk + dv) * h * allowed_pairs(seq) * seqs
    byts = 4.0 * seqs * seq * h * (2 * qk + 2 * dv)
    return {"flops": 3.0 * flops, "bytes_accessed": 3.0 * byts}


def experts_cost(cfg: dict, pairs: float) -> dict:
    """One layer's held experts over ``pairs`` (token, choice) pairs."""
    return _experts_cost({**cfg, "num_experts": cfg["n_routed_experts"]}, pairs)


def _proj_weights(cfg: dict) -> int:
    """The latent attention's matrices a position multiplies by: W_Q, W_DKV,
    W_UKV (on the latent) and W_O."""
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    return d * h * _qk(cfg) + d * (r + cfg["qk_rope_head_dim"]) + \
        r * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) + h * cfg["v_head_dim"] * d


def layer_flops(layer: int, cfg: dict, seqs: int, seq: int, pairs: float) -> float:
    """Forward + backward model operations of one layer over seqs x seq positions."""
    d, positions = cfg["hidden_size"], seqs * seq
    total = 3.0 * 2.0 * _proj_weights(cfg) * positions + attn_cost(cfg, seqs, seq)["flops"]
    if layer < cfg["first_k_dense_replace"]:
        return total + 3.0 * 2.0 * 3 * d * cfg["intermediate_size"] * positions
    routed = cfg["n_routed_experts"] * int(cfg.get("expert_parallel_size", 1))
    shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return total + 3.0 * 2.0 * (d * routed + 3 * d * shared) * positions + \
        experts_cost(cfg, pairs)["flops"]


def step_model_flops(cfg: dict, seqs: int, seq: int, pairs_per_layer: float) -> float:
    """Model operations of one optimizer step on ``seqs`` packed sequences."""
    head = 3.0 * 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * seqs * seq
    return head + sum(layer_flops(i, cfg, seqs, seq, pairs_per_layer)
                      for i in range(cfg["num_hidden_layers"]))
