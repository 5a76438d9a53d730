"""Operations and bytes a training step of the ``lfm2_moe`` tower needs, from
the configuration's shapes as the share has them: the same work whatever
implements it.

Model operations only: the matmuls of the parameters a position really uses
(the held experts count the pairs routed to them, not every position),
attention over the allowed score pairs only (a full layer's lower triangle),
forward once and backward twice (input and weight gradients): 3 x forward.
Recomputation is not counted (each layer is run twice; the kernel's backward
recomputes its scores), nor the score pairs a visited block holds beyond the
allowed ones, nor the optimizer's pass: a share computed from these reads low,
never over 100 %.  ``seq`` is a packed sequence's positions, ``PAD`` included.

The convolution's mix — the two gates and the taps between ``W_in`` and
``W_out`` — is elementwise: :func:`conv_mix_cost` counts its least bytes, f32,
one forward and one backward (the recomputed forward left out).
"""

from __future__ import annotations

import math

from .costs_afmoe import full_attn_cost as _afmoe_kernel_cost
from .costs_tower import experts_cost  # noqa: F401  (SwiGLU experts over the routed pairs)
from .reference.lfm2_moe import KINDS, param_shapes


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def n_params(cfg: dict) -> int:
    """Every array of the share, as the reference lays them out."""
    return sum(math.prod(shape) for shape in param_shapes(cfg).values())


def attn_cost(cfg: dict, seqs: int, seq: int) -> dict:
    """One full layer's attention kernel (no projection) over ``seqs``
    sequences, forward + backward: QK^T and PV on the allowed pairs at
    ``head_dim`` = hidden / heads.  Bytes: q, k, v in and the output out, f32,
    once a pass."""
    return _afmoe_kernel_cost({**cfg, "head_dim": head_dim(cfg)}, seqs, seq)


def conv_mix_cost(cfg: dict, seqs: int, seq: int) -> dict:
    """One conv layer's mix over ``seqs`` x ``seq`` positions, forward +
    backward.  Forward: read ``[B | C | x]`` (3D), write ``(C * c)`` (D); the
    taps' products and the gates: 2 x (L + 2) operations an element.
    Backward: read the output's cotangent (D) and ``[B | C | x]`` (3D), write
    their cotangent (3D); twice the forward's operations."""
    d, positions = cfg["hidden_size"], seqs * seq
    flops = 2.0 * (cfg["conv_L_cache"] + 2) * d * positions
    return {"flops": 3.0 * flops, "bytes_accessed": 4.0 * (4 + 7) * d * positions}


def layer_flops(layer: int, cfg: dict, seqs: int, seq: int, pairs: float) -> float:
    """Forward + backward model operations of one layer over seqs x seq positions."""
    d, positions = cfg["hidden_size"], seqs * seq
    if cfg["layer_types"][layer] == KINDS[0]:
        total = 3.0 * 2.0 * 4 * d * d * positions + conv_mix_cost(cfg, seqs, seq)["flops"]
    else:
        h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
        total = 3.0 * 2.0 * (2 * d * h * hd + 2 * d * kv * hd) * positions + \
            attn_cost(cfg, seqs, seq)["flops"]
    if layer < cfg["num_dense_layers"]:
        return total + 3.0 * 2.0 * 3 * d * cfg["intermediate_size"] * positions
    routed = cfg["num_experts"] * int(cfg.get("expert_parallel_size", 1))
    return total + 3.0 * 2.0 * d * routed * positions + experts_cost(cfg, pairs)["flops"]


def step_model_flops(cfg: dict, seqs: int, seq: int, pairs_per_layer: float) -> float:
    """Model operations of one optimizer step on ``seqs`` packed sequences."""
    head = 3.0 * 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * seqs * seq
    return head + sum(layer_flops(i, cfg, seqs, seq, pairs_per_layer)
                      for i in range(cfg["num_hidden_layers"]))
