"""Operations and bytes a training step of the ``afmoe`` tower needs, from the
configuration's shapes as the share has them: the same work whatever
implements it.

Model operations only: the matmuls of the parameters a position really uses
(the held experts count the pairs routed to them, not every position),
attention over the allowed score pairs only — a window layer ``sum_i min(i +
1, window)`` of them, a full layer the lower triangle —, forward once and
backward twice (input and weight gradients): 3 x forward.  Recomputation is
not counted (the kernel's backward recomputes its scores; each layer is run
twice), nor the score pairs a visited block holds beyond the allowed ones, nor
the optimizer's elementwise pass (it is bytes: :func:`opt_cost`): a share
computed from these reads low, never over 100 %.  ``seq`` is a packed
sequence's positions, ``PAD`` included (they are computed like any other).
"""

from __future__ import annotations

import math

from .costs_tower import experts_cost  # noqa: F401  (SwiGLU experts over the routed pairs)
from .reference.afmoe import KINDS, param_shapes


def allowed_pairs(seq: int, window=None) -> int:
    """Score pairs one head may use over a sequence: key j for query i when
    j <= i and, with ``window``, i - j < window."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _attn_weights(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 3 * d * h * hd + 2 * d * kv * hd                 # Wq, Wg, Wo; Wk, Wv


def n_params(cfg: dict) -> int:
    """Every array of the share, as the reference lays them out."""
    return sum(math.prod(shape) for shape in param_shapes(cfg).values())


def opt_cost(cfg: dict) -> dict:
    """Adam over every parameter: read parameter, gradient, m, v; write
    parameter, m, v: 28 bytes a parameter."""
    return {"flops": 12.0 * n_params(cfg), "bytes_accessed": 28.0 * n_params(cfg)}


def _kernel_cost(cfg: dict, seqs: int, seq: int, window) -> dict:
    """One layer's attention kernel (no projection) over ``seqs`` sequences,
    forward + backward: QK^T and PV on the allowed pairs.  Bytes: q, k, v in
    and the output out, f32, once a pass."""
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    flops = 4.0 * hd * h * allowed_pairs(seq, window) * seqs
    byts = 4.0 * seqs * seq * hd * (2 * h + 2 * kv)
    return {"flops": 3.0 * flops, "bytes_accessed": 3.0 * byts}


def window_attn_cost(cfg: dict, seqs: int, seq: int) -> dict:
    return _kernel_cost(cfg, seqs, seq, cfg["sliding_window"])


def full_attn_cost(cfg: dict, seqs: int, seq: int) -> dict:
    return _kernel_cost(cfg, seqs, seq, None)


def layer_flops(layer: int, cfg: dict, seqs: int, seq: int, pairs: float) -> float:
    """Forward + backward model operations of one layer over seqs x seq positions."""
    d, positions = cfg["hidden_size"], seqs * seq
    kernel = window_attn_cost if cfg["layer_types"][layer] == KINDS[0] else full_attn_cost
    total = 3.0 * 2.0 * _attn_weights(cfg) * positions + kernel(cfg, seqs, seq)["flops"]
    if layer < cfg["num_dense_layers"]:
        return total + 3.0 * 2.0 * 3 * d * cfg["intermediate_size"] * positions
    routed = cfg["num_experts"] * int(cfg.get("expert_parallel_size", 1))
    dense = d * routed + 3 * d * cfg["moe_intermediate_size"]          # router, shared expert
    return total + 3.0 * 2.0 * dense * positions + experts_cost(cfg, pairs)["flops"]


def step_model_flops(cfg: dict, seqs: int, seq: int, pairs_per_layer: float) -> float:
    """Model operations of one optimizer step on ``seqs`` packed sequences."""
    head = 3.0 * 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * seqs * seq
    return head + sum(layer_flops(i, cfg, seqs, seq, pairs_per_layer)
                      for i in range(cfg["num_hidden_layers"]))
